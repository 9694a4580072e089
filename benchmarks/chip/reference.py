"""Plain 7-point Jacobi, the reference that decides a Jacobi cell's `correct`.

Every interior point becomes the mean of its six neighbours; points outside
the grid read as 0 (fixed zero boundary). Nothing here comes from the
program under test.

The grid is swept in blocks of whole planes along axis 0, so that a grid far
larger than one chip's memory fits. After ``iters`` sweeps a block's planes
are exact wherever they lie more than ``iters`` planes from an edge of the
block that is not an edge of the grid: a wrong value at a cut travels one
plane per sweep. Each block is therefore the slab it answers for plus
``iters`` planes of halo on each side, shifted inward where the slab touches
the grid's own edge.
"""
from __future__ import annotations

import functools
from typing import Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# bytes of one block on the device; a sweep holds about three of them
BLOCK_BYTES = 2 << 30


def sweep(u: jax.Array) -> jax.Array:
    """One Jacobi sweep with a zero boundary."""
    p = jnp.pad(u, 1)
    return (p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1] +
            p[1:-1, :-2, 1:-1] + p[1:-1, 2:, 1:-1] +
            p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:]) / 6


@functools.partial(jax.jit, static_argnames=("iters", "dtype", "lo", "hi"))
def _block(u, *, iters, dtype, lo, hi):
    u = jax.lax.fori_loop(0, iters, lambda _, v: sweep(v), u.astype(dtype))
    return u[lo:hi].astype(jnp.float32)


def plan(n0: int, plane_bytes: int, iters: int,
         block_bytes: int = BLOCK_BYTES) -> Tuple[int, int]:
    """(slab, block): planes each block answers for and planes it holds.
    The slab is the largest divisor of ``n0`` whose block fits
    ``block_bytes``; a grid too thin for halos is one block."""
    if n0 <= 2 * iters + 1:
        return n0, n0
    fit = max(block_bytes // plane_bytes - 2 * iters, 1)
    slab = max(s for s in range(1, n0 + 1) if n0 % s == 0 and s <= fit)
    return slab, min(slab + 2 * iters, n0)


def blocks(u0: np.ndarray, iters: int, dtype=jnp.float32,
           devices: Optional[Sequence[jax.Device]] = None,
           block_bytes: int = BLOCK_BYTES
           ) -> Iterator[Tuple[int, int, jax.Array]]:
    """Yield ``(lo, hi, planes)``: the reference's planes ``lo:hi`` of the
    grid after ``iters`` sweeps, computed in ``dtype`` and returned as float32
    on a device. Blocks go round the devices one round at a time."""
    devices = list(devices or jax.devices()[:1])
    n0 = u0.shape[0]
    slab, size = plan(n0, u0[0].nbytes, iters, block_bytes)
    starts = list(range(0, n0, slab))
    with jax.default_matmul_precision("highest"):
        for r in range(0, len(starts), len(devices)):
            out = []
            for lo, dev in zip(starts[r:r + len(devices)], devices):
                b = min(max(lo - iters, 0), n0 - size)
                u = jax.device_put(u0[b:b + size], dev)
                out.append((lo, lo + slab, _block(
                    u, iters=iters, dtype=jnp.dtype(dtype),
                    lo=lo - b, hi=lo - b + slab)))
            yield from out


def run(u0: np.ndarray, iters: int, dtype=jnp.float32, **kw) -> np.ndarray:
    """The whole grid after ``iters`` sweeps, on the host."""
    out = np.empty(u0.shape, np.float32)
    for lo, hi, planes in blocks(u0, iters, dtype, **kw):
        out[lo:hi] = np.asarray(planes)
    return out


@jax.jit
def _gap(a, b):
    return jnp.max(jnp.abs(a - b))


def max_abs_gap(got: np.ndarray, u0: np.ndarray, iters: int, **kw) -> float:
    """Largest |got - reference| over the grid; ``got`` is on the host."""
    worst = 0.0
    for lo, hi, planes in blocks(u0, iters, **kw):
        other = jax.device_put(got[lo:hi], planes.devices().pop())
        worst = max(worst, float(_gap(planes, other)))
    return worst


def control_gap(u0: np.ndarray, iters: int, dtype, **kw) -> float:
    """The control: the largest gap of the reference computed in ``dtype``
    from the float32 reference, as ``max_abs_gap`` would read it had the
    lower precision run in the program's place."""
    worst = 0.0
    low = blocks(u0, iters, dtype, **kw)
    for _, _, planes in blocks(u0, iters, **kw):
        worst = max(worst, float(_gap(planes, next(low)[2])))
    return worst
