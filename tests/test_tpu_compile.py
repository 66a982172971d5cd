"""Compile the chip's programs for a described TPU v5e, with no chip attached.

Each test lowers and compiles one program for one device of a ``v5e:2x2``
topology, so what the TPU's compiler refuses (an unaligned block, too much
fast memory, a program that does not fit) fails here at no chip time.  A
compile that passes says nothing about results or times.

The topology is described inside a fixture and never while a module is
imported: only one process at a time may load the TPU library, and every
test worker must collect the same tests.  The persistent compilation cache
is off around these compiles, because an entry compiled for the TPU cannot
be read back by a CPU process.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import CodeParams
from repro.core.jax_engine import _fr_kernel
from repro.core.regions import msr_region
from repro.kernels.gf_matmul import gf_matmul_pallas
from repro.kernels.ops import _padded_call

PAYLOAD = 1 << 20        # one 1 MiB HDFS striping cell per coded block


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this installation
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_gf_kernel_compiles_at_one_block(one_chip):
    a = _spec((128, 512), jnp.uint8, one_chip)
    b = _spec((512, 128), jnp.uint8, one_chip)
    compiled = gf_matmul_pallas.lower(a, b, interpret=False).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("m,k", [(2, 2), (2, 16)],
                         ids=["provider_encode", "newcomer_regenerate"])
def test_gf_kernel_compiles_at_repair_payload(one_chip, m, k):
    """The padded call a repair of the RS(6,3) store issues on 1 MiB cells:
    m and k pad to one (128, 512) block, the payload columns stay."""
    a = _spec((m, k), jnp.uint8, one_chip)
    b = _spec((k, PAYLOAD), jnp.uint8, one_chip)
    compiled = _padded_call.lower(a, b, 128, 128, 512, False).compile()
    assert _has_kernel(compiled)
    out = compiled.out_info
    assert out.shape == (128, PAYLOAD) and out.dtype == jnp.uint8


def test_fr_planner_compiles_under_x64(one_chip):
    """The jit FR planner at the RS(6,3) shape (d=8), batch of 4."""
    params = CodeParams.msr(n=9, k=6, d=8, M=12.0)
    x = np.asarray(msr_region(params).x, dtype=np.float64)
    with jax.enable_x64():
        direct = _spec((4, params.d), jnp.float64, one_chip)
        xs = _spec(x.shape, jnp.float64, one_chip)
        compiled = _fr_kernel.lower(direct, xs, float(params.alpha),
                                    float(params.M), is_msr=True,
                                    minimize_traffic=True).compile()
    times = compiled.out_info[0]
    assert times.shape == (4,) and times.dtype == jnp.float64
