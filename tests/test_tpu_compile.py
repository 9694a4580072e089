"""The main path's kernels compiled for a described TPU v5e at real widths.

Nothing runs: the TPU compiler installed with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse (VMEM
overruns, misaligned tiles) where interpret mode lets it through. The
topology is described inside a fixture, never at import, so that only the
worker given this file loads the TPU library.
"""
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
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
            # at the program's own matmul precision, whatever a test before
            # left set on this thread (the chip refuses f32 contraction of
            # bf16 operands)
            with jax.default_matmul_precision(None):
                yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _chunk_shapes(x, y, z):
    f32 = jnp.float32
    return (((x, y, z), f32), ((y, z), f32), ((y, z), f32), ((x, z), f32),
            ((x, z), f32), ((x, y), f32), ((x, y), f32))


def test_jacobi3d_kernel_compiles_at_512(one_chip):
    from repro.kernels.jacobi3d import jacobi3d
    c = _compile(functools.partial(jacobi3d, interpret=False), one_chip,
                 *_chunk_shapes(512, 512, 512))
    assert "tpu_custom_call" in c.as_text()


def test_spmd_step_compiles_on_four_chips(topo):
    """run_spmd's step on a 512³ grid over a 2x2 mesh: each chip's slab goes
    through the Pallas stencil inside shard_map."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS
    from repro.apps.jacobi3d import make_spmd_step
    mesh = Mesh(np.array(topo.devices), ("data",))
    grid = jax.ShapeDtypeStruct((512,) * 3, jnp.float32,
                                sharding=NamedSharding(mesh, PS("data")))
    c = make_spmd_step(mesh).lower(grid).compile()
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


@pytest.mark.parametrize("chunk", [(512, 512, 1024), (256, 256, 256)])
def test_stencil_update_compiles_on_a_chunk(one_chip, chunk):
    """The update ``run_tasked`` launches, on a chunk of a 1024³ grid
    over-decomposed 4 and 64 ways: one Pallas kernel, with no padded copy
    of the chunk and no halo written into one."""
    from repro.apps.jacobi3d import stencil_update
    c = _compile(stencil_update, one_chip, *_chunk_shapes(*chunk))
    hlo = c.as_text()
    assert "tpu_custom_call" in hlo
    assert not re.search(r"\b(pad|dynamic-update-slice)\(", hlo)
    m = c.memory_analysis()
    assert m.output_size_in_bytes == 4 * math.prod(chunk)
    # the padded form needs 1,231,836,160 B of temporaries at od4's chunk
    assert m.temp_size_in_bytes < 64 << 20


def test_expert_share_compiles_at_granite_widths(one_chip):
    """granite-4.0-h-small's 8-way expert share at a decode step of 64
    sequences: the router over 72 experts, 9 held, run as the chip's grouped
    matmul kernel."""
    from repro.configs import get_share
    from repro.models import moe
    mcfg = get_share("granite-4.0-h-small", "ep8").config.moe
    d, ff, e = 4096, mcfg.d_ff_expert, mcfg.n_held
    params = {"router": ((d, mcfg.num_experts), jnp.float32),
              "wi": ((e, d, ff), jnp.bfloat16), "wg": ((e, d, ff), jnp.bfloat16),
              "wo": ((e, ff, d), jnp.bfloat16)}
    args = {k: jax.ShapeDtypeStruct(s, t, sharding=one_chip)
            for k, (s, t) in params.items()}
    x = jax.ShapeDtypeStruct((64, 1, d), jnp.bfloat16, sharding=one_chip)
    no_shared = dataclasses.replace(mcfg, d_ff_shared=0)
    c = jax.jit(lambda p, h: moe.moe_share(p, h, no_shared, True)).lower(
        args, x).compile()
    assert "ragged-dot" in c.as_text()
