"""Sub-tile shift of the block warp (Pallas TPU).

``repro.codec.motion.warp_blocks`` gathers, for every output block, the
four aligned 16×16 anchor tiles its source block straddles, as
row-major ``(N, 256)`` rows.  This kernel cuts the block out of the 32×32
square they make: rows ``fy .. fy+15``, columns ``fx .. fx+15``, each
offset in ``[0, 16)`` and its own per block (per sublane row here).

A block's 256 lanes are two 128-lane columns of eight rows each, so the
32-row strip of a tile pair (top over bottom) is four lane columns.
Output column ``o`` (rows ``8o .. 8o+7``) draws from two of them,
``o + fy // 8`` and the next, of the left pair and of the right.  One
dynamic lane gather permutes each of those four (lane ``16i + c`` takes
lane ``16 * ((fy + i) % 8) + (c + fx) % 16``), and each output lane
keeps the one its source row and column fall in.  Values move unchanged
(exact in any dtype).

One grid step handles ``BLOCK_ROWS`` blocks, ``GROUP`` at a time, so each
group's values stay in vector registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

LANES = 128
GROUP = 32           # blocks (sublane rows) per inner step
BLOCK_ROWS = 512     # blocks per grid step


def _kernel(f_ref, tl_ref, tr_ref, bl_ref, br_ref, out_ref, *, rows: int):
    lane = lax.broadcasted_iota(jnp.int32, (GROUP, LANES), 1)
    i, c = lane >> 4, lane & 15

    def body(g, carry):
        r = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        f = jnp.broadcast_to(f_ref[r, :], (GROUP, LANES))
        fy, fx = f >> 4, f & 15
        low = (fy & 8) == 0               # rows fy.. start in strip column o
        idx = ((fy + i) & 7) * 16 + ((c + fx) & 15)
        nxt = (fy & 7) + i >= 8           # source row in the next column
        right = c + fx >= 16              # source column in the right tile

        def strip(top, bottom):
            return [top[r, 0:LANES], top[r, LANES:2 * LANES],
                    bottom[r, 0:LANES], bottom[r, LANES:2 * LANES]]

        def cut(cols, o):
            a = jnp.where(low, cols[o], cols[o + 1])
            b = jnp.where(low, cols[o + 1], cols[o + 2])
            ga = jnp.take_along_axis(a, idx, axis=1,
                                     mode="promise_in_bounds")
            gb = jnp.take_along_axis(b, idx, axis=1,
                                     mode="promise_in_bounds")
            return jnp.where(nxt, gb, ga)

        left, rgt = strip(tl_ref, bl_ref), strip(tr_ref, br_ref)
        for o in range(2):
            out_ref[r, o * LANES:(o + 1) * LANES] = jnp.where(
                right, cut(rgt, o), cut(left, o))
        return carry

    lax.fori_loop(0, rows // GROUP, body, 0)


def tile_shift(tl, tr, bl, br, fy, fx, *, interpret: bool = False):
    """tl/tr/bl/br: (N, 256) row-major 16×16 tiles (top-left, top-right,
    bottom-left, bottom-right of each block's 32×32 square); fy, fx: (N,)
    int32 in [0, 16).  Returns (N, 256): each square's 16×16 block at
    (fy, fx)."""
    n = tl.shape[0]
    rows = min(BLOCK_ROWS, -(-n // GROUP) * GROUP)
    f = (fy * 16 + fx).astype(jnp.int32)[:, None]
    tile = pl.BlockSpec((rows, 2 * LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows),
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, 1), lambda i: (i, 0))] + [tile] * 4,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n, 2 * LANES), tl.dtype),
        interpret=interpret,
    )(f, tl, tr, bl, br)
