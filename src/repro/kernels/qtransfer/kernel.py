"""Quality-transfer kernel (Pallas TPU) — paper Fig. 7 adapted to TPU.

One grid step produces one macroblock ROW of the output frame.  The HD
anchor is edge-padded by ``radius`` on every side and padded to whole
128-lane tiles; the step's anchor band (16 + 2R rows) arrives as its own
row block plus ``halo_blocks`` following blocks of the same array, so
every DMA is tile-aligned.  Each macroblock column reads its 16×16
source block at its own (dy, dx) offset with per-lane rotations and
selects (``repro.kernels.shift``): Mosaic has no gather and refuses a
vector load at a dynamic unaligned offset.  The decoded residual band is
added and the sum clipped to [0, 255].

Semantics: offsets are clipped to ±radius and blocks that reach past
the frame read edge-replicated pixels.  At radius 16 with |mv| <= 16 the
kernel and ``repro.codec.motion.warp_blocks`` plus the residual agree
exactly.  Past ±16 they differ: ``warp_blocks`` keeps the motion and
clamps the block's start inside the padded anchor, while the kernel
clips the motion, so an interior block with |mv| > 16 reads another
block.  ``repro.core.quality_transfer.transfer_frame(use_kernel=True)``
clips to ±16 before the call.

The MVs arrive expanded to one (dy, dx) pair per lane, so the offset of
every lane is a plain vector operand.  The bf16 variant
(``dtype=jnp.bfloat16`` on ``ops.qtransfer``) stages the anchor and
residual in bf16 — 16-row blocks satisfy the bf16 (16, 128) minimum
tile — while the gather + residual add runs in f32 before casting back
to the storage dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.shift import (band, halo_blocks, lane_shift,
                                 padded_width, row_shift)

MB = 16
f32 = jnp.float32


def _kernel(mv_ref, *refs, radius: int, width: int):
    *band_refs, resid_ref, out_ref = refs
    n = 2 * radius + 1
    anchor = band(band_refs)                     # (16 + halo rows, Wp)
    dy = mv_ref[0, 0:1, :]                       # (1, Wp), in [0, 2R]
    dx = mv_ref[0, 1:2, :]
    block = row_shift(lane_shift(anchor, dx, n), dy, n, MB)[:, :width]
    out = jnp.clip(block + resid_ref[...].astype(f32), 0.0, 255.0)
    out_ref[...] = out.astype(out_ref.dtype)


def qtransfer_rows(anchor, mv, resid, *, radius: int = 16,
                   interpret: bool = False):
    """anchor/resid: (H, W); mv: (nby, nbx, 2) int32 -> (H, W) in the
    anchor's dtype: warp_blocks(anchor, clip(mv, ±radius)) + resid,
    clipped to [0, 255]."""
    H, W = anchor.shape
    nby, nbx = H // MB, W // MB
    halo = halo_blocks(MB, 2 * radius)
    width = padded_width(W, 2 * radius)

    anchor_p = jnp.pad(anchor, radius, mode="edge")
    anchor_p = jnp.pad(anchor_p, ((0, (nby + halo) * MB - H - 2 * radius),
                                  (0, width - W - 2 * radius)))
    # one (dy, dx) pair per lane, offset by +radius into [0, 2R]
    lanes = jnp.repeat(jnp.clip(mv, -radius, radius) + radius, MB, axis=1)
    lanes = jnp.pad(lanes.transpose(0, 2, 1).astype(jnp.int32),
                    ((0, 0), (0, 0), (0, width - W)))

    kernel = functools.partial(_kernel, radius=radius, width=W)
    return pl.pallas_call(
        kernel,
        grid=(nby,),
        in_specs=[pl.BlockSpec((1, 2, width), lambda i: (i, 0, 0))] + [
            pl.BlockSpec((MB, width), lambda i, j=j: (i + j, 0))
            for j in range(halo + 1)] + [
            pl.BlockSpec((MB, W), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((MB, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), anchor.dtype),
        interpret=interpret,
    )(lanes, *([anchor_p] * (halo + 1)), resid)
