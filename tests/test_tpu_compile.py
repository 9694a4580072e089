"""The main path's kernels compiled for a described TPU v5e at real widths.

Nothing runs: the TPU compiler installed with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse (VMEM
overruns, misaligned tiles) where interpret mode lets it through. The
topology is described inside a fixture, never at import, so that only the
worker given this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_jacobi3d_kernel_compiles_at_512(one_chip):
    from repro.kernels.jacobi3d import jacobi3d
    n = 512
    c = _compile(functools.partial(jacobi3d, interpret=False), one_chip,
                 ((n + 2,) * 3, jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_matmul_kernel_compiles_at_4096_bf16(one_chip):
    from repro.kernels.matmul import matmul
    n = 4096
    c = _compile(functools.partial(matmul, interpret=False), one_chip,
                 ((n, n), jnp.bfloat16), ((n, n), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention
    qkv = ((24, 2048, 128), jnp.bfloat16)
    c = _compile(functools.partial(flash_attention, interpret=False),
                 one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in c.as_text()


def test_stencil_update_compiles_on_a_chunk(one_chip):
    """The jnp stencil ``run_tasked`` launches, on one chunk of a 512³ grid
    over-decomposed four ways."""
    from repro.apps.jacobi3d import stencil_update
    x, y, z = 128, 512, 512
    f32 = jnp.float32
    c = _compile(stencil_update, one_chip, ((x, y, z), f32),
                 ((y, z), f32), ((y, z), f32), ((x, z), f32), ((x, z), f32),
                 ((x, y), f32), ((x, y), f32))
    assert c.memory_analysis().output_size_in_bytes == x * y * z * 4
