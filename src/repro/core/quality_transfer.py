"""Quality transfer (paper §IV-B, Fig. 7).

Enhances a non-anchor LR frame using high-quality content from the nearest
preceding HD anchor: 1) locate each macroblock's source block on the anchor
via the (accumulated) motion vectors, 2) gather the HD block, 3) add the
interpolated residual, 4) paste.  TPU adaptation: the whole operation is a
block-tiled gather+add over a (H/16 × W/16) grid — the Pallas kernel in
``repro.kernels.qtransfer`` executes it with anchor tiles staged in VMEM;
this module is the pure-jnp reference used on CPU and as the kernel oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.codec import blockdct as B
from repro.codec.motion import warp_blocks

f32 = jnp.float32


def residual_to_pixels(residual_q, qtab, H: int, W: int):
    """Dequantize + inverse-transform a frame's residual coefficients."""
    return B.unblockify(B.idct2(B.dequantize(residual_q, qtab)), H, W)


def transfer_frame(anchor_hd, mv_acc, residual_px, blend: float = 1.0,
                   use_kernel: bool = False):
    """One frame of quality transfer.

    anchor_hd: (H, W) the decoded HD anchor; mv_acc: (nby, nbx, 2)
    anchor-relative motion vectors; residual_px: (H, W) decoded residual.
    The default route (``warp_blocks``) takes any motion: each block's
    source start is clamped inside the anchor edge-padded by 16 px, so a
    block moved past the padding reads the edge it moved past.
    ``use_kernel`` routes through the Pallas TPU kernel (interpret mode on
    CPU), which clips the motion to ±16 first: the two agree only where
    |mv| <= 16.  Returns the enhanced frame.
    """
    if use_kernel:
        from repro.kernels.qtransfer.ops import qtransfer
        return qtransfer(anchor_hd, jnp.clip(mv_acc, -16, 16),
                         blend * residual_px, radius=16)
    warped = warp_blocks(anchor_hd, mv_acc)
    return jnp.clip(warped + blend * residual_px, 0.0, 255.0)


def transfer_chunk(frames_lr_up, anchor_hd, anchor_idx, mvs, residual_q,
                   qtab, types):
    """Apply quality transfer to every type-2 frame of a chunk.

    frames_lr_up: (T, H, W) decoder-upscaled LR frames (fallback content);
    anchor_hd: (T, H, W) per-frame nearest-anchor HD plane (gathered by the
    decoder); anchor_idx: (T,) index of that anchor; mvs: (T, nby, nbx, 2)
    frame-to-previous MVs; types: (T,) pipeline assignment.

    Returns (T, H, W) frames routed to pipeline ② (others pass through).
    """
    T, H, W = frames_lr_up.shape
    # accumulate MVs from each frame's anchor: cumsum minus cumsum at anchor
    cum = jnp.cumsum(mvs, axis=0)                       # (T, nby, nbx, 2)
    cum_at_anchor = cum[anchor_idx]                     # (T, nby, nbx, 2)
    mv_rel = cum - cum_at_anchor

    def one(i):
        resid = residual_to_pixels(residual_q[i], qtab, H, W)
        enhanced = transfer_frame(anchor_hd[i], mv_rel[i], resid)
        return jnp.where(types[i] == 2, enhanced, frames_lr_up[i])

    return jax.vmap(one)(jnp.arange(T))


def transfer_gain_psnr(raw, lr_up, enhanced):
    """PSNR gain of transfer vs plain upscale (paper Fig. 8a)."""
    def p(a, b):
        mse = jnp.mean(jnp.square(a.astype(f32) - b.astype(f32)))
        return 10.0 * jnp.log10(255.0 ** 2 / jnp.maximum(mse, 1e-9))
    return p(raw, enhanced) - p(raw, lr_up)
