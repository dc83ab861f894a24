"""Compile the device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, so what the chip's compiler would
refuse (tiling rules, fast-memory limits) fails here at no chip time.
Covered: the batched Monte-Carlo grid program under x64 at one small
bucket, and the five Pallas kernels at the cost model's full-size
calibration shapes with the real TPU lowering.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.costmodel.features import _kernel_cases

KERNELS = ("flash_attention", "decode_attention", "ssm_scan",
           "rwkv6_scan", "fused_rmsnorm")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def test_mc_grid_program_compiles_for_v5e(one_chip):
    """The smallest bucket: the rotation's batch-wide ``pmax`` and the
    ``cond`` around the sort lower for the chip, and the conditional
    survives as one."""
    from repro.mc.kernels import grid_kernel
    C, N, B = 4, 64, 3
    with jax.enable_x64(True):
        f64 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float64,
                                                  sharding=one_chip)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                  sharding=one_chip)
        compiled = grid_kernel(C, N).lower(
            f64(B, N), f64(B, N), i32(B), i32(B), f64(B)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    assert " conditional(" in compiled.as_text()


@pytest.mark.parametrize("name", KERNELS)
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    builder = dict(_kernel_cases(small=False))[name]
    fn, args = builder()
    specs = [_spec(a, one_chip) for a in args]
    compiled = fn.lower(*specs, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
