"""Jitted public wrappers around the GF(2^8) matmul kernel.

``gf_matmul`` pads to block multiples, runs the Pallas kernel and slices
the result.  Padding with zeros is sound: 0 is the additive identity of
GF(2^8) and 0*x = 0.

The kernel compiles for the chip when JAX's default backend is ``tpu`` and
runs in Pallas interpret mode when it is ``cpu`` (how the tests run); any
other backend is an error.  There is no fallback: a kernel that the
backend's compiler rejects raises out of ``gf_matmul``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.spans import span

from .gf_matmul import gf_matmul_pallas
from .ref import gf_matmul_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_default() -> bool:
    """Compile on the TPU, interpret on the CPU, refuse anything else."""
    backend = jax.default_backend()
    if backend in ("tpu", "cpu"):
        return backend == "cpu"
    raise RuntimeError(
        f"the GF(2^8) Pallas kernel targets the TPU (and runs interpreted "
        f"on the CPU); backend {backend!r} has no path")


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _padded_call(a, b, bm, bn, bk, interpret):
    """The kernel on zero-padded operands; returns the padded product."""
    m, k = a.shape
    _, n = b.shape
    mp, kp, np_ = _ceil_to(m, bm), _ceil_to(k, bk), _ceil_to(n, bn)
    if (mp, kp, np_) != (m, k, n):
        # jnp.pad appends zero margins without materializing a full zero
        # buffer first; block multiples skip the copy altogether
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    return gf_matmul_pallas(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)


def gf_matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 512,
              interpret: bool | None = None) -> jnp.ndarray:
    """GF(2^8) matmul with automatic padding; compiled on TPU, interpreted
    on CPU (``interpret=None`` picks by ``jax.default_backend()``)."""
    a = jnp.asarray(a, jnp.uint8)
    b = jnp.asarray(b, jnp.uint8)
    if interpret is None:
        interpret = _interpret_default()
    out = _padded_call(a, b, bm, bn, bk, interpret)
    m, n = a.shape[0], b.shape[1]
    # sliced outside the jit: on the CPU backend (JAX 0.9.0), a slice
    # compiled into one program with the interpreted kernel sometimes reads
    # the kernel's output before the kernel has written it
    return out if out.shape == (m, n) else out[:m, :n]


def _host_bytes(x) -> int:
    """Bytes that putting ``x`` on the device as uint8 copies from the host."""
    return 0 if isinstance(x, jax.Array) else int(np.size(x))


def gf_matmul_numpy(a, b) -> np.ndarray:
    """Kernel-backed matmul with a numpy interface (pluggable into
    :class:`repro.coding.rlnc.RLNC` to run the coding plane through the
    kernel end-to-end).

    Spans: ``repro.gf_matmul`` around the call, and inside it
    ``repro.gf.h2d`` (the operands' copy to the device, ``bytes`` copied
    from the host), ``repro.gf.dispatch`` (the kernel call and the slice)
    and ``repro.gf.d2h`` (the product's copy back, its ``bytes``)."""
    with span("gf_matmul"):
        with span("gf.h2d", bytes=_host_bytes(a) + _host_bytes(b)):
            a, b = jnp.asarray(a, jnp.uint8), jnp.asarray(b, jnp.uint8)
        with span("gf.dispatch"):
            out = gf_matmul(a, b)
        with span("gf.d2h", bytes=out.size):
            return np.asarray(out)


def gf_matmul_reference(a, b) -> jnp.ndarray:
    """Pure-jnp oracle (no Pallas), exported for benchmarks/tests."""
    return gf_matmul_ref(jnp.asarray(a, jnp.uint8), jnp.asarray(b, jnp.uint8))
