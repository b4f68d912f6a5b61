"""Block-motion SAD kernels (Pallas TPU): exhaustive full search and a
traced coarse-to-fine (diamond / three-step) search.

Layout.  Frames stay in their natural (rows, lanes) layout, padded on
the host side to whole 128-lane tiles (``shift.padded_width``) and to
whole grid steps.  One grid step owns ``rows`` macroblock rows: its
current-frame band is one (rows·16, Wp) block, and its reference band is
that block plus ``halo_blocks`` following blocks of the edge-padded
reference (several BlockSpecs on the same array), so the ±R halo arrives
through the ordinary pipeline with every DMA tile-aligned.  Candidate
windows are read with rotations (``pltpu.roll``) and selects, never with
an unaligned or dynamic-lane slice, which Mosaic refuses.

Per-block SADs.  ``|cur - cand|`` is summed over each macroblock's 16
rows (a tile-aligned sublane reshape), then over its 16 lanes with an
xor butterfly of lane rotations, which leaves every lane of a
macroblock column holding that block's SAD.  All selection state lives
in that (rows, Wp) lane layout; the wrapper reads lane 16·bx.

Exactness.  The in-kernel sums are used for SELECTION only.  On
integer-valued content ≤ 2²⁴ — real 8-bit video — every f32 summation
order is exact, so the selected candidate is provably the oracle's; on
continuous inputs a flip would need two distinct residual patterns whose
exact sums collide in one order but not the other.  The returned SAD is
recomputed outside the kernel for the winning offset only, with the
oracle's per-block reduction (``winner_sad``), so (mv, sad) are
bit-exact vs ``block_sad_scan`` / ``block_sad_diamond``.

Exhaustive search: a ``fori_loop`` over dy (one sublane rotation of the
band per dy) with the 2R+1 dx candidates unrolled inside (one lane
rotation each) — dy-major order, identical to
``repro.codec.motion._offsets``, with a strict ``<`` best-update, so
first-wins tie-breaking matches the scan oracle.

``search="diamond"``: the static step schedule of
``repro.codec.motion.diamond_steps`` probes a 3×3 neighbourhood of each
macroblock's running best offset, clipped to ±R.  Offsets differ per
block, so each probe reads its window with ``shift.lane_shift`` /
``shift.row_shift`` (per-lane amounts); the dx-shifted band is shared by
the three probes of a column.  Same probe order, clip and first-wins as
the fallback, so MVs are bit-exact vs ``block_sad_diamond``.

``dtype=jnp.bfloat16`` stages cur/ref in bf16 (half the DMA bytes and
VMEM); every SAD accumulates in f32 inside the kernel.  Band heights are
multiples of 16 rows, the bf16 minimum tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shift import (band, halo_blocks, lane_shift,
                                 padded_width, row_shift)

MB = 16
f32 = jnp.float32

# macroblock rows per grid step of the exhaustive search: a 4-row band of
# 1080p f32 is (64, 2048) = 512 KiB, well inside the scoped VMEM with
# double buffering.  The diamond kernel steps one row at a time, since
# its per-lane shifts scale with the band height.
_EXHAUSTIVE_ROWS = 4


def _block_sums(d, rows: int, lane):
    """(rows·16, Wp) per-pixel values -> (rows, Wp) per-macroblock sums,
    each broadcast over the block's 16 lanes (xor butterfly)."""
    width = d.shape[-1]
    s = d.reshape(rows, MB, width).sum(axis=1)
    for k in (1, 2, 4, 8):
        lower = pltpu.roll(s, k, 1)            # s[l - k]
        upper = pltpu.roll(s, width - k, 1)    # s[l + k]
        s = s + jnp.where((lane & k) != 0, lower, upper)
    return s


def _exhaustive_kernel(cur_ref, *refs, radius: int, rows: int):
    *band_refs, sad_ref, idx_ref = refs
    side = 2 * radius + 1
    bh = rows * MB
    cur = cur_ref[...].astype(f32)                         # (bh, Wp)
    ref = band(band_refs)                                  # (Hb, Wp)
    height, width = ref.shape
    lane = lax.broadcasted_iota(jnp.int32, (rows, width), 1)

    def body(dy, carry):
        best_sad, best_idx = carry
        rows_dy = pltpu.roll(ref, (height - dy) % height, 0)[:bh]
        for dx in range(side):
            win = rows_dy if dx == 0 else pltpu.roll(rows_dy, width - dx, 1)
            sad = _block_sums(jnp.abs(cur - win), rows, lane)
            better = sad < best_sad
            best_sad = jnp.where(better, sad, best_sad)
            best_idx = jnp.where(better, dy * side + dx, best_idx)
        return best_sad, best_idx

    init = (jnp.full((rows, width), jnp.inf, f32),
            jnp.zeros((rows, width), jnp.int32))
    best_sad, best_idx = lax.fori_loop(0, side, body, init)
    sad_ref[0] = best_sad
    idx_ref[0] = best_idx


def _diamond_kernel(cur_ref, *refs, radius: int, steps: tuple):
    *band_refs, sad_ref, mvy_ref, mvx_ref = refs
    n = 2 * radius + 1
    cur = cur_ref[...].astype(f32)                         # (16, Wp)
    ref = band(band_refs)                                  # (Hb, Wp)
    lane = lax.broadcasted_iota(jnp.int32, (1, ref.shape[1]), 1)

    def sad_at(cols, oy):
        cand = row_shift(cols, oy + radius, n, MB)
        return _block_sums(jnp.abs(cur - cand), 1, lane)

    zero = jnp.zeros((1, ref.shape[1]), jnp.int32)
    best_y, best_x = zero, zero
    best_sad = sad_at(lane_shift(ref, zero + radius, n), zero)
    # static unroll: len(steps) rounds of 9 probes, dy-major, first-wins
    for s in steps:
        cy, cx = best_y, best_x
        ox = [jnp.clip(cx + px, -radius, radius) for px in (-s, 0, s)]
        cols = [lane_shift(ref, o + radius, n) for o in ox]
        for py in (-s, 0, s):
            oy = jnp.clip(cy + py, -radius, radius)
            for j in range(3):
                sad = sad_at(cols[j], oy)
                better = sad < best_sad
                best_sad = jnp.where(better, sad, best_sad)
                best_y = jnp.where(better, oy, best_y)
                best_x = jnp.where(better, ox[j], best_x)
    sad_ref[0] = best_sad
    mvy_ref[0] = best_y
    mvx_ref[0] = best_x


def winner_sad(cur, ref, mv, radius: int, store=f32):
    """SAD of each macroblock at its chosen offset, in the oracle's
    per-block reduction order (``block_sad_scan`` / the fallbacks)."""
    H, W = cur.shape
    nby, nbx = H // MB, W // MB
    refp = jnp.pad(ref.astype(store), radius, mode="edge").astype(f32)
    curb = cur.astype(store).astype(f32).reshape(
        nby, MB, nbx, MB).transpose(0, 2, 1, 3)
    ar = jnp.arange(MB, dtype=jnp.int32)
    ys = (jnp.arange(nby, dtype=jnp.int32) * MB)[:, None] + mv[..., 0] \
        + radius
    xs = (jnp.arange(nbx, dtype=jnp.int32) * MB)[None, :] + mv[..., 1] \
        + radius
    cand = refp[(ys[..., None] + ar)[..., :, None],
                (xs[..., None] + ar)[..., None, :]]        # (nby, nbx, MB, MB)
    return jnp.abs(curb - cand).sum(axis=(2, 3))


def motion_sad_rows(cur, ref, *, radius: int = 8, interpret: bool = False,
                    dtype=None, search: str = "exhaustive"):
    """cur/ref: (H, W) with H, W multiples of 16.

    Returns (mv (nby, nbx, 2) int32, sad (nby, nbx) f32) — the codec
    convention pred(y) = ref(y + mv), matching ``repro.codec.motion``.
    ``dtype`` is the storage dtype of the staged operands (bf16 halves
    the DMA bytes); SADs accumulate in f32 regardless.  ``search`` picks
    exhaustive ±R full search (bit-exact vs the scan oracle) or the
    traced diamond search (subset of the candidate set, quality-contract
    semantics).
    """
    if search not in ("exhaustive", "diamond"):
        raise ValueError(f"unknown search strategy {search!r} "
                         "(expected 'exhaustive' or 'diamond')")
    store = dtype or f32
    H, W = cur.shape
    nby, nbx = H // MB, W // MB
    rows = min(_EXHAUSTIVE_ROWS, nby) if search == "exhaustive" else 1
    bh = rows * MB
    n_steps = -(-nby // rows)
    halo = halo_blocks(bh, 2 * radius)
    width = padded_width(W, 2 * radius)

    curp = jnp.pad(cur.astype(store),
                   ((0, n_steps * bh - H), (0, width - W)))
    refp = jnp.pad(ref.astype(store), radius, mode="edge")
    refp = jnp.pad(refp, ((0, (n_steps + halo) * bh - refp.shape[0]),
                          (0, width - refp.shape[1])))

    if search == "exhaustive":
        kernel = functools.partial(_exhaustive_kernel, radius=radius,
                                   rows=rows)
        out_dtypes = (f32, jnp.int32)
    else:
        from repro.codec.motion import diamond_steps
        kernel = functools.partial(_diamond_kernel, radius=radius,
                                   steps=diamond_steps(radius))
        out_dtypes = (f32, jnp.int32, jnp.int32)

    out_spec = pl.BlockSpec((1, rows, width), lambda i: (i, 0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((bh, width), lambda i: (i, 0))] + [
            pl.BlockSpec((bh, width), lambda i, j=j: (i + j, 0))
            for j in range(halo + 1)],
        out_specs=[out_spec] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((n_steps, rows, width), d)
                   for d in out_dtypes],
        interpret=interpret,
    )(curp, *([refp] * (halo + 1)))
    # every lane of a macroblock column holds the block's value
    outs = [o.reshape(n_steps * rows, width)[:nby, :W:MB] for o in outs]

    if search == "exhaustive":
        side = 2 * radius + 1
        idx = outs[1]
        mv = jnp.stack([idx // side - radius, idx % side - radius], axis=-1)
    else:
        mv = jnp.stack(outs[1:], axis=-1)
    mv = mv.astype(jnp.int32)
    return mv, winner_sad(cur, ref, mv, radius, store)
