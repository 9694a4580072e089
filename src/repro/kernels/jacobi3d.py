"""Pallas TPU Jacobi-3D stencil fed by the chunk and its six faces.

Input is the unpadded chunk ``u[X, Y, Z]`` and its face halos ``lo0``/``hi0``
``[Y, Z]``, ``lo1``/``hi1`` ``[X, Z]`` and ``lo2``/``hi2`` ``[X, Y]`` (zeros at
a physical boundary); output the updated chunk: each point the sum of its six
neighbours in float32, over 6. No padded copy is built: every plane of the
chunk is read from HBM about once and every output plane written once.

The grid walks axis 0 in blocks of ``bx`` whole planes, in order, each block
fetched into VMEM a step ahead of its own. A plane's axis-0 neighbours are the
block's own planes, the last plane of the previous block (carried in scratch;
``lo0`` before the first block) and the first plane of the next block, already
fetched (``hi0`` after the last plane). So each plane is read from HBM once and
the output can take the chunk's place. Each plane is swept in tiles of whole
rows: the Y and Z neighbours come from ``pltpu.roll`` of the tile, and the row
or column that wraps round is replaced by the neighbouring tile's row, the
``lo1``/``hi1`` row of that plane, or that plane's ``lo2``/``hi2`` values
turned into a column (the block's ``[bx, Y]`` face rows are transposed once a
block; the plane's column is a one-hot lane reduction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scoped VMEM the kernel may ask for, of a v5e/v6e core's 128 MiB: the two
# chunk blocks and the double-buffered output block take most of it
VMEM_BUDGET = 100 << 20
# VMEM kept back for the sweep's spilled tiles and Mosaic's own scratch
VMEM_SLACK = 8 << 20
# vregs one row tile spans (8 sublanes x 128 lanes of float32 each): on a
# v5e 32 beat 16 and 8 at both 512x512x1024 and 256^3 chunks
TILE_VREGS = 32


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(bx: int, y: int, z: int) -> int:
    """VMEM held by one grid step, float32: the two chunk blocks (this one
    and the next, in flight), the double-buffered output block and face
    blocks, the carried plane, the hi0 plane and the two transposed z-face
    blocks."""
    plane = y * z * 4
    faces = 2 * bx * (z + _round_up(y, 128)) * 4
    return (4 * bx * plane + 2 * faces + 2 * plane + 2 * y * 128 * 4
            + VMEM_SLACK)


def supports(shape, dtype) -> bool:
    """Whether the kernel takes a chunk: float32, rows that fill whole
    (8, 128) tiles, and a step of 8 planes within ``VMEM_BUDGET`` (the
    kernel does not tile Y or Z)."""
    _, y, z = shape
    return (dtype == jnp.float32 and y % 8 == 0 and z % 128 == 0
            and _vmem_bytes(8, y, z) <= VMEM_BUDGET)


def pick_bx(x: int, y: int, z: int, bx: int) -> int:
    """Planes per grid step: the largest multiple of 8 up to ``bx`` (and no
    more than ``x`` rounds up to) whose step fits ``VMEM_BUDGET``."""
    b = min(_round_up(bx, 8), _round_up(x, 8))
    while b > 8 and _vmem_bytes(b, y, z) > VMEM_BUDGET:
        b -= 8
    return b


def pick_ty(y: int, z: int) -> int:
    """Rows per tile: ``TILE_VREGS`` vregs' worth, dividing ``y``."""
    ty = min(y, 8 * max(1, TILE_VREGS // (z // 128)))
    while y % ty:
        ty -= 8
    return ty


def _kernel(u_hbm, lo1_ref, hi1_ref, lo2_ref, hi2_ref, lo0_hbm, hi0_hbm,
            o_ref, buf, sem, prev_ref, hi_ref, lo2t_ref, hi2t_ref,
            *, x: int, bx: int, ty: int):
    i = pl.program_id(0)
    nb = pl.cdiv(x, bx)
    n_last = x - (nb - 1) * bx
    slot = i % 2
    _, _, y, z = buf.shape

    def block_dma(b, dst, op):
        # "start" or "wait" the copy of block b into buf[dst]: bx planes, or
        # the last block's n_last
        def dma(n):
            getattr(pltpu.make_async_copy(u_hbm.at[pl.ds(b * bx, n)],
                                          buf.at[dst, pl.ds(0, n)],
                                          sem.at[dst]), op)()

        if nb == 1 or n_last == bx:
            dma(n_last)
        else:
            pl.when(b < nb - 1)(lambda: dma(bx))
            pl.when(b == nb - 1)(lambda: dma(n_last))

    # the chunk's blocks come in one step ahead; the block a step works on
    # is only ever read, and lo0 (the plane before the first block) and hi0
    # (the one after the last) land in scratch of their own
    @pl.when(i == 0)
    def _():
        block_dma(0, 0, "start")
        pltpu.sync_copy(lo0_hbm, prev_ref)
        block_dma(0, 0, "wait")

    @pl.when(i + 1 < nb)
    def _():
        block_dma(i + 1, 1 - slot, "start")

    lo2t_ref[...] = lo2_ref[...].T
    hi2t_ref[...] = hi2_ref[...].T

    rows = jax.lax.broadcasted_iota(jnp.int32, (ty, z), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (ty, z), 1)
    face_lanes = jax.lax.broadcasted_iota(jnp.int32, (ty, bx), 1)
    face_rows = jax.lax.broadcasted_iota(jnp.int32, (bx, z), 0)
    n_tiles = y // ty

    def face_row(ref, k):
        # row k of a [bx, Z] face block, broadcast over a tile's rows
        r = jnp.sum(jnp.where(face_rows == k, ref[...], 0.0), axis=0,
                    keepdims=True)
        return jnp.broadcast_to(r, (ty, z))

    def face_col(ref, k, j0):
        # plane k's values of a transposed z-face, rows j0.., as a column
        t = ref[pl.ds(j0, ty), :]
        return jnp.sum(jnp.where(face_lanes == k, t, 0.0), axis=1,
                       keepdims=True)

    def plane(k, cur, lo, hi, save):
        # output plane k of the block from its axis-0 neighbours lo and hi
        below = face_row(hi1_ref, k)

        def after(j):
            # the rows after tile j: the next tile, or hi1's row past the end
            j1 = pl.multiple_of(jnp.minimum(j + 1, n_tiles - 1) * ty, ty)
            return jnp.where(j == n_tiles - 1, below, cur[pl.ds(j1, ty), :])

        def tile(j, carry):
            c, c_up, r_prev = carry
            j0 = pl.multiple_of(j * ty, ty)
            nxt = after(j)
            # Y neighbours: roll by one row each way; the wrapped row is the
            # neighbouring tile's edge row (or the lo1/hi1 face row)
            c_dn = pltpu.roll(c, 1, 0)
            n_up = pltpu.roll(nxt, ty - 1, 0)
            ym = jnp.where(rows == 0, r_prev, c_dn)
            yp = jnp.where(rows == ty - 1, n_up, c_up)
            # Z neighbours: roll by one lane each way; the wrapped column is
            # the plane's lo2/hi2 values
            zm = jnp.where(lanes == 0, face_col(lo2t_ref, k, j0),
                           pltpu.roll(c, 1, 1))
            zp = jnp.where(lanes == z - 1, face_col(hi2t_ref, k, j0),
                           pltpu.roll(c, z - 1, 1))
            xm = lo[pl.ds(j0, ty), :]
            xp = hi[pl.ds(j0, ty), :]
            o_ref[k, pl.ds(j0, ty), :] = (
                (xm + xp + ym + yp + zm + zp) / 6.0).astype(o_ref.dtype)
            if save:
                prev_ref[pl.ds(j0, ty), :] = c
            return nxt, n_up, c_dn

        c0 = after(-1)
        jax.lax.fori_loop(0, n_tiles, tile,
                          (c0, pltpu.roll(c0, ty - 1, 0),
                           face_row(lo1_ref, k)))

    def sweep(n, hi_last, last_ready=None):
        # planes 0..n-1 of this step's block; plane 0's lo neighbour is the
        # carried plane, plane n-1's hi neighbour is hi_last
        blk = buf.at[slot]
        if n == 1:
            plane(0, blk.at[0], prev_ref, hi_last, False)
            return
        plane(0, blk.at[0], prev_ref, blk.at[1], False)

        def middle(k, carry):
            plane(k, blk.at[k], blk.at[k - 1], blk.at[k + 1], False)
            return carry

        jax.lax.fori_loop(1, n - 1, middle, 0)
        if last_ready is not None:
            last_ready()
        # a whole block's last plane is the next block's lo neighbour
        plane(n - 1, blk.at[n - 1], blk.at[n - 2], hi_last, n == bx)

    if nb > 1:
        @pl.when(i < nb - 1)
        def _():
            sweep(bx, buf.at[1 - slot, 0],
                  lambda: block_dma(i + 1, 1 - slot, "wait"))

    @pl.when(i == nb - 1)
    def _():
        pltpu.sync_copy(hi0_hbm, hi_ref)
        sweep(n_last, hi_ref)


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def jacobi3d(u, lo0, hi0, lo1, hi1, lo2, hi2, *, bx: int = 16,
             interpret: bool = False) -> jax.Array:
    """One Jacobi sweep of the chunk ``u[X, Y, Z]`` given its face halos, for
    a chunk the kernel ``supports``; ``bx`` caps the planes per grid step
    (see ``pick_bx``)."""
    if not supports(u.shape, u.dtype):
        raise ValueError(f"no Pallas stencil for a {u.dtype} chunk {u.shape}")
    x, y, z = u.shape
    bx = pick_bx(x, y, z, bx)
    ty = pick_ty(y, z)
    grid = (pl.cdiv(x, bx),)
    rows = pl.BlockSpec((bx, z), lambda i: (i, 0))
    cols = pl.BlockSpec((bx, y), lambda i: (i, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # inside shard_map the kernel's values vary over u's mesh axes: so must
    # its faces (zero faces made in the body are not yet varying)
    vma = jax.typeof(u).vma

    def varying(f):
        missing = tuple(sorted(vma - jax.typeof(f).vma))
        return jax.lax.pcast(f, missing, to="varying") if missing else f

    lo0, hi0, lo1, hi1, lo2, hi2 = map(varying, (lo0, hi0, lo1, hi1, lo2, hi2))
    return pl.pallas_call(
        functools.partial(_kernel, x=x, bx=bx, ty=ty),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM), rows, rows, cols, cols,
                  hbm, hbm],
        out_specs=pl.BlockSpec((bx, y, z), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((2, bx, y, z), u.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((y, z), u.dtype),
                        pltpu.VMEM((y, z), u.dtype),
                        pltpu.VMEM((y, bx), u.dtype),
                        pltpu.VMEM((y, bx), u.dtype)],
        compiler_params=pltpu.CompilerParams(
            # a step carries a plane to the next and fetches its block
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(bx, y, z)),
        # in place: a block is fetched a step before the step that writes
        # it back, and the previous block's last plane is carried, so a
        # donated chunk needs no copy
        input_output_aliases={0: 0},
        interpret=interpret,
        name="jacobi_face_stencil",
    )(u, lo1, hi1, lo2, hi2, lo0, hi0)
