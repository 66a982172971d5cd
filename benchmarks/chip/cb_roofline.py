"""Peaks of the chips the benchmark runs on, and the work of a GF(2^8) matmul.

The work is counted from the call's own (unpadded) shapes, so it reads the
same whatever implements the product: padding, bit planes and tiling are
the implementation's cost, not the algorithm's.
"""
from __future__ import annotations

#: keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
#: "TPU v5e" (published peaks of one chip): 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

#: one GF(2^8) multiply-add is 8 x 8 one-bit products summed by parity:
#: 64 binary multiply-adds, each one int8 multiply-add (two int8 ops)
BINARY_MACS_PER_GF_MAC = 64


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def gf_matmul_work(m: int, k: int, n: int) -> tuple[int, int]:
    """(int8 operations, HBM bytes) that C = A @ B over GF(2^8) needs.

    A is m x k, B is k x n, C is m x n, one byte per element.  Operations
    count the m*k*n GF multiply-adds at 64 binary multiply-adds each, two
    operations per multiply-add; bytes count reading A and B and writing C
    once.
    """
    ops = 2 * BINARY_MACS_PER_GF_MAC * m * k * n
    return ops, m * k + k * n + m * n


def least_time(shapes, peak: dict) -> tuple[float, str]:
    """Least seconds the chip could take for the calls of ``shapes``
    (m, k, n), one after the other, and which bound sets most of it
    ("compute" or "memory")."""
    by = {"compute": 0.0, "memory": 0.0}
    for m, k, n in shapes:
        o, b = gf_matmul_work(m, k, n)
        t_ops = o / peak["int8_ops_per_s"]
        t_mem = b / peak["hbm_bytes_per_s"]
        by["compute" if t_ops >= t_mem else "memory"] += max(t_ops, t_mem)
    total = by["compute"] + by["memory"]
    return total, max(by, key=by.get)
