"""Pallas TPU fused Jacobi row-block sweeps (the paper's §3.3.1 hot loop).

x' = (b + up + down + left + right) / 4 on a 5-point Dirichlet stencil.

TPU layout: a (rows, g) row block is gridded over row tiles of ``tile``
rows, the last one padded past the block's end.  Each grid step loads its
tile plus a ghost band of ``halo`` rows from each neighbouring tile (the
same operand bound three times, with BlockSpec index maps in units of the
band), runs every sweep on that (tile + 2 * halo, g) window in VMEM and
writes back only the tile.  With ``halo >= sweeps`` the wrong values that
enter at the window's edges never reach the tile (temporal blocking), so
one HBM pass covers all sweeps.  The padding past the block's last row is
cut off by the frozen bottom halo row, and masked out of the norm.
Neighbours are ``pltpu.roll`` along sublanes and lanes, masked with iotas
for the block's frozen halo rows and the zero Dirichlet columns: no
concatenation or pad breaks the (8, 128) tiling.  Each tile writes its share
of the block-local squared residual to a lane-aligned (8, 128) block, which
the wrapper sums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM bytes of one row tile; the window, its rolled neighbours and the
#: double-buffered operands are small multiples of it.
_TILE_BYTES = 1 << 20
_VMEM_LIMIT = 64 << 20
#: Block-index literal: a Python 0 is an i64 under x64, which Mosaic's
#: index maps cannot return.
_0 = np.int32(0)


def row_tiling(rows: int, g: int, itemsize: int, sweeps: int):
    """``(tile, halo)`` for a (rows, g) block.

    ``halo`` is ``sweeps`` rounded up to the 8-row sublane tile, and
    ``tile`` is the largest multiple of it within ``_TILE_BYTES`` (one band
    at least).  The grid takes ``cdiv(rows, tile)`` steps.  A block no
    taller than one tile is a single tile with no ghost band
    (``halo == 0``).
    """
    halo = -(-sweeps // 8) * 8
    tile = max(halo, _TILE_BYTES // (g * itemsize) // halo * halo)
    if tile >= rows:
        return rows, 0
    return tile, halo


def _halo_kernel(*refs, sweeps: int, rows: int, tile: int, halo: int,
                 g: int):
    """``sweeps`` frozen-halo sweeps on one row tile (plus ghost bands)."""
    if halo:
        xp, xc, xn, bp, bc, bn, top_ref, bot_ref, o_ref, n_ref = refs
        x0 = jnp.concatenate([xp[...], xc[...], xn[...]], axis=0)
        bw = jnp.concatenate([bp[...], bc[...], bn[...]], axis=0)
    else:
        xc, bc, top_ref, bot_ref, o_ref, n_ref = refs
        x0, bw = xc[...], bc[...]
    win = tile + 2 * halo
    grow = (pl.program_id(0) * tile - halo
            + jax.lax.broadcasted_iota(jnp.int32, (win, g), 0))
    col = jax.lax.broadcasted_iota(jnp.int32, (win, g), 1)
    top, bot = top_ref[...], bot_ref[...]

    def roll(x, shift, axis):  # int32 shift: Mosaic rotates by no i64
        return pltpu.roll(x, jnp.int32(shift), axis)

    def one(_, x):
        # Same operand order as the reference: ((up + down) + left) + right.
        up = jnp.where(grow == 0, top, roll(x, 1, 0))
        dn = jnp.where(grow == rows - 1, bot, roll(x, win - 1, 0))
        lf = jnp.where(col == 0, 0.0, roll(x, 1, 1))
        rt = jnp.where(col == g - 1, 0.0, roll(x, g - 1, 1))
        return (bw + (((up + dn) + lf) + rt)) / 4.0

    new = jax.lax.fori_loop(0, sweeps, one, x0)[halo:halo + tile]
    o_ref[...] = new
    d = new - x0[halo:halo + tile]
    # The last tile's rows past the block are padding: out of the norm.
    trow = (pl.program_id(0) * tile
            + jax.lax.broadcasted_iota(jnp.int32, (tile, g), 0))
    d2 = jnp.where(trow < rows, d * d, 0.0)
    n_ref[...] = jnp.broadcast_to(jnp.sum(d2, keepdims=True), (8, 128))


@functools.partial(jax.jit, static_argnames=("sweeps", "interpret"))
def jacobi_halo_sweeps(xb: jax.Array, top: jax.Array, bot: jax.Array,
                       b: jax.Array, *, sweeps: int, interpret: bool):
    """``sweeps`` frozen-halo Jacobi sweeps on a (rows, g) row block.

    ``top``/``bot`` are the rows just outside the block (zeros at a grid
    edge), held fixed for the whole dispatch: the asynchronous block-update
    semantics.  Returns ``(new_block, local_sq_norm)`` where the second
    output is ``sum((new - old)**2)`` over the block.
    """
    rows, g = xb.shape
    tile, halo = row_tiling(rows, g, xb.dtype.itemsize, sweeps)
    ntiles = pl.cdiv(rows, tile)
    cur = pl.BlockSpec((tile, g), lambda i: (i, _0))
    row = pl.BlockSpec((1, g), lambda i: (_0, _0))
    operands = [xb, b]
    in_specs = [cur, cur]
    if halo:
        per = tile // halo  # halo bands per tile
        last = pl.cdiv(rows, halo) - 1
        prev = pl.BlockSpec((halo, g),
                            lambda i: (jnp.maximum(i * per - 1, 0), _0))
        nxt = pl.BlockSpec((halo, g),
                           lambda i: (jnp.minimum((i + 1) * per, last), _0))
        operands = [xb, xb, xb, b, b, b]
        in_specs = [prev, cur, nxt, prev, cur, nxt]
    out, norms = pl.pallas_call(
        functools.partial(_halo_kernel, sweeps=sweeps, rows=rows, tile=tile,
                          halo=halo, g=g),
        grid=(ntiles,),
        in_specs=in_specs + [row, row],
        out_specs=(cur, pl.BlockSpec((8, 128), lambda i: (i, _0))),
        out_shape=(jax.ShapeDtypeStruct((rows, g), xb.dtype),
                   jax.ShapeDtypeStruct((8 * ntiles, 128), xb.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*operands, top.reshape(1, g), bot.reshape(1, g))
    return out, jnp.sum(norms[::8, 0])


@functools.partial(jax.jit, static_argnames=("g", "interpret"))
def jacobi_sweep(x: jax.Array, b: jax.Array, g: int, *,
                 interpret: bool) -> jax.Array:
    """One global Jacobi sweep; x, b flat (g*g,).

    The whole grid is one row block whose frozen halo rows are the zero
    Dirichlet boundary.
    """
    zeros = jnp.zeros((g,), x.dtype)
    out, _ = jacobi_halo_sweeps(x.reshape(g, g), zeros, zeros,
                                b.reshape(g, g), sweeps=1,
                                interpret=interpret)
    return out.reshape(-1)
