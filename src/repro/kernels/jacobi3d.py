"""Pallas TPU Jacobi-3D stencil (the proxy application's compute kernel).

Input is the halo-padded slab [X+2, Y+2, Z+2]; output the updated interior
[X, Y, Z]. The grid tiles x: program i reads the element window of padded
rows [i·bx, i·bx + bx + 2) — its own bx rows plus both x-neighbour rows —
with whole Y/Z planes, and sweeps it one output plane at a time, so the
live temporaries are a few planes whatever the tile. ``bx`` is capped so
that the double-buffered windows fit ``VMEM_BUDGET``; at 512³ f32 one
padded plane is 1.3 MB in VMEM (8×128 tiling), and the whole-plane window
of eight rows no longer fits the 16 MiB default scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scoped VMEM the kernel asks for: a quarter of a v5e/v6e core's 128 MiB
# and half of a v7x core's 64 MiB
VMEM_BUDGET = 32 << 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(bx: int, yp: int, zp: int, itemsize: int) -> int:
    """VMEM held by one grid step: the double-buffered input window and
    output tile, plus the planes one row of the sweep keeps live."""
    plane_in = _round_up(yp, 8) * _round_up(zp, 128) * itemsize
    plane_out = _round_up(yp - 2, 8) * _round_up(zp - 2, 128) * itemsize
    return 2 * (bx + 2) * plane_in + 2 * bx * plane_out + 4 * plane_in


def pick_bx(x: int, yp: int, zp: int, itemsize: int, bx: int) -> int:
    """Largest divisor of ``x`` at most ``bx`` whose tile fits the budget."""
    for b in range(min(bx, x), 0, -1):
        if x % b == 0 and _vmem_bytes(b, yp, zp, itemsize) <= VMEM_BUDGET:
            return b
    raise ValueError(f"one {yp}x{zp} plane does not fit {VMEM_BUDGET} B of "
                     "VMEM; the kernel does not tile Y or Z")


def _jacobi_kernel(u_ref, o_ref, *, bx: int):
    # window row k+1 is output row k; rows k and k+2 are its x neighbours
    def row(k, carry):
        lo, cur, hi = u_ref[k], u_ref[k + 1], u_ref[k + 2]   # [Y+2, Z+2]
        o_ref[k] = ((lo[1:-1, 1:-1] + hi[1:-1, 1:-1] +
                     cur[:-2, 1:-1] + cur[2:, 1:-1] +
                     cur[1:-1, :-2] + cur[1:-1, 2:]) / 6.0
                    ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bx, row, 0)


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def jacobi3d(u_pad: jax.Array, *, bx: int = 8,
             interpret: bool = False) -> jax.Array:
    """u_pad: [X+2, Y+2, Z+2] halo-padded slab → updated interior [X,Y,Z].
    ``bx`` caps the x rows per grid step (see ``pick_bx``)."""
    xp, yp, zp = u_pad.shape
    x = xp - 2
    bx = pick_bx(x, yp, zp, u_pad.dtype.itemsize, bx)
    window = pl.BlockSpec(
        (pl.Element(bx + 2), pl.Element(yp), pl.Element(zp)),
        lambda i: (i * bx, 0, 0))
    return pl.pallas_call(
        functools.partial(_jacobi_kernel, bx=bx),
        grid=(x // bx,),
        in_specs=[window],
        out_specs=pl.BlockSpec((bx, yp - 2, zp - 2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((x, yp - 2, zp - 2), u_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(u_pad)
