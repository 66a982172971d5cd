"""Pallas TPU kernel: GF(2^8) matrix multiplication (RLNC encode/decode).

TPU adaptation of the paper's coding hot-spot (DESIGN.md §3).  GPU codes use
log/exp lookup tables in shared memory; VMEM gathers are slow on TPU, so we
decompose the field product into 8x8 = 64 one-bit-plane integer matmuls that
run on the MXU at int8 throughput, XOR being parity of the int32 count:

    C = reduce_mod_0x11D( planes[t] ),
    planes[t] = (sum_{i+j=t} A_i @ B_j) & 1,   A_i = (A >> i) & 1.

Blocking: grid (M/bm, N/bn, K/bk), K innermost.  Per grid step the kernel
issues 64 (bm,bk)x(bk,bn) int8 dots accumulated into a 15-plane int32 VMEM
scratch; the final K step takes parity, folds planes 14..8 (x^8 == 0x1D) and
writes bytes.  VMEM at the default bm=bn=128, bk=512: A 64K + B 64K + out
16K + scratch 15*128*128*4 = 983K — comfortably inside ~16 MB VMEM, with
MXU-aligned (128-multiple) dot shapes.

Roofline: one GF(2^8) MAC costs 64 int8-MXU MACs (2x bf16 rate), so the
kernel's compute ceiling is 197e12*2/64 ≈ 6.2e12 GF-MAC/s/chip; arithmetic
intensity matches a regular matmul, so blocks this size are compute-bound.
Validated against ``ref.gf_matmul_ref`` and the table-based numpy oracle in
interpret mode (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_FOLD = (0, 2, 3, 4)  # x^8 == x^4 + x^3 + x^2 + 1


def _gf_matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.int32)  # (bm, bk) bytes
    b = b_ref[...].astype(jnp.int32)  # (bk, bn) bytes
    abits = [((a >> i) & 1).astype(jnp.int8) for i in range(8)]
    bbits = [((b >> j) & 1).astype(jnp.int8) for j in range(8)]
    for t in range(15):
        acc = acc_ref[t]
        for i in range(max(0, t - 7), min(7, t) + 1):
            acc = acc + jax.lax.dot(abits[i], bbits[t - i],
                                    preferred_element_type=jnp.int32)
        acc_ref[t] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        planes = [acc_ref[t] & 1 for t in range(15)]
        for t in range(14, 7, -1):
            p = planes[t]
            for s in _FOLD:
                planes[t - 8 + s] = planes[t - 8 + s] ^ p
        out = planes[0]
        for t in range(1, 8):
            out = out | (planes[t] << t)
        o_ref[...] = out.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def gf_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
                     bn: int = 128, bk: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """C = A @ B over GF(2^8).  Shapes must be multiples of the block sizes
    (use :func:`repro.kernels.ops.gf_matmul` for automatic padding)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shapes {(m, k, n)} not multiples of blocks {(bm, bk, bn)}")
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_gf_matmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((15, bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
