"""Pallas GF(2^8) matmul kernel vs the pure-jnp ref and the table oracle.

Sweeps shapes (including non-block-multiples via the padding wrapper) and
block sizes; property tests over random matrices.  interpret=True executes
the kernel body on CPU (this container's only backend); the BlockSpecs are
the TPU deployment configuration.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.coding.gf import GF8
from repro.kernels.gf_matmul import gf_matmul_pallas
from repro.kernels.ops import gf_matmul, gf_matmul_reference

RNG = np.random.default_rng(1234)


def _rand(m, k, n):
    return (RNG.integers(0, 256, (m, k), dtype=np.uint8),
            RNG.integers(0, 256, (k, n), dtype=np.uint8))


@pytest.mark.parametrize("m,k,n", [
    (1, 1, 1), (3, 5, 2), (17, 33, 9), (128, 512, 128),
    (130, 700, 257), (256, 512, 384), (64, 1024, 64),
])
def test_matches_table_oracle(m, k, n):
    a, b = _rand(m, k, n)
    want = GF8.matmul(a, b)
    np.testing.assert_array_equal(np.asarray(gf_matmul(a, b)), want)
    np.testing.assert_array_equal(np.asarray(gf_matmul_reference(a, b)), want)


@pytest.mark.parametrize("bm,bn,bk", [(128, 128, 512), (128, 128, 128),
                                      (256, 128, 256), (128, 256, 128)])
def test_block_shape_sweep(bm, bn, bk):
    """The kernel result must be block-size invariant (same math, different
    VMEM tiling)."""
    a, b = _rand(2 * bm, 2 * bk, 2 * bn)
    want = GF8.matmul(a, b)
    got = gf_matmul_pallas(jnp.asarray(a), jnp.asarray(b), bm=bm, bn=bn,
                           bk=bk, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 64), st.integers(1, 96), st.integers(1, 64),
       st.integers(0, 2**31 - 1))
def test_property_random_shapes(m, k, n, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, (m, k), dtype=np.uint8)
    b = rng.integers(0, 256, (k, n), dtype=np.uint8)
    want = GF8.matmul(a, b)
    np.testing.assert_array_equal(np.asarray(gf_matmul(a, b)), want)


def test_linearity_and_identity():
    """Kernel respects GF structure: A@(B^C) == (A@B)^(A@C); A@I == A."""
    a, b = _rand(32, 48, 24)
    c = RNG.integers(0, 256, b.shape, dtype=np.uint8)
    left = np.asarray(gf_matmul(a, b ^ c))
    right = np.asarray(gf_matmul(a, b)) ^ np.asarray(gf_matmul(a, c))
    np.testing.assert_array_equal(left, right)
    eye = np.eye(48, dtype=np.uint8)
    np.testing.assert_array_equal(np.asarray(gf_matmul(a, eye)), a)


def test_zero_padding_soundness():
    """Padding with zeros must not perturb the visible result region."""
    a, b = _rand(100, 200, 50)
    np.testing.assert_array_equal(np.asarray(gf_matmul(a, b)), GF8.matmul(a, b))


def test_padded_result_is_read_after_the_kernel_writes_it():
    """Regression: with the slice compiled into the same program as the
    interpreted kernel, the CPU backend sometimes returned a few entries of
    the slice before the kernel had written them.  Many fresh calls at one
    unaligned shape catch that."""
    rng = np.random.default_rng(7)
    for _ in range(30):
        a = rng.integers(0, 256, (17, 33), dtype=np.uint8)
        b = rng.integers(0, 256, (33, 9), dtype=np.uint8)
        np.testing.assert_array_equal(np.asarray(gf_matmul(a, b)),
                                      GF8.matmul(a, b))
