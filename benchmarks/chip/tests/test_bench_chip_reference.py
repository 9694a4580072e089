"""The benchmark's plain reference against the program's own single-array
Jacobi, on small grids, in whole and in blocks with halos."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_chip_util  # noqa: F401  (puts the harness on the path)

import reference
from repro.apps.jacobi3d import run_reference


@pytest.mark.parametrize("shape,iters,block_planes", [
    ((16, 12, 10), 3, 9),      # slabs of 2 planes, blocks of 8
    ((16, 8, 8), 5, 14),       # blocks shifted inward at both grid edges
    ((20, 6, 6), 4, 1000),     # one block holds the grid
    ((7, 6, 6), 4, 1),         # too thin for halos: one block
])
def test_blocks_match_the_single_array_jacobi(shape, iters, block_planes):
    u0 = np.random.default_rng(1).random(shape, dtype=np.float32)
    plane = u0[0].nbytes
    want = run_reference(u0, iters)
    got = reference.run(u0, iters, block_bytes=block_planes * plane,
                        devices=jax.devices())
    np.testing.assert_array_equal(got, want)
    assert reference.max_abs_gap(want, u0, iters,
                                 block_bytes=block_planes * plane) == 0.0


def test_plan_fits_the_block_budget():
    slab, size = reference.plan(1024, 4 << 20, 100)
    assert 1024 % slab == 0 and size == slab + 200
    assert size * (4 << 20) <= reference.BLOCK_BYTES


def test_bfloat16_reference_departs_from_float32():
    u0 = np.random.default_rng(2).random((16, 16, 16), dtype=np.float32)
    assert reference.control_gap(u0, 10, jnp.bfloat16) > 1e-3
