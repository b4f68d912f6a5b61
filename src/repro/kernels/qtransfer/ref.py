"""Pure-jnp oracle for the qtransfer kernel.

``repro.codec.motion.warp_blocks`` + residual add with the kernel's
offset bound: offsets are clipped to ±radius and the anchor is
edge-padded, so a block reaching past the frame reads edge-replicated
pixels.  At radius 16 and |mv| <= 16 this is exactly
``clip(warp_blocks(anchor, mv) + resid, 0, 255)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

MB = 16
f32 = jnp.float32


def qtransfer_ref(anchor, mv, resid, *, radius: int = 16):
    H, W = anchor.shape
    nby, nbx = mv.shape[:2]
    ap = jnp.pad(anchor.astype(f32), radius, mode="edge")
    mv = jnp.clip(mv, -radius, radius)

    def one(by, bx):
        y0 = radius + by * MB + mv[by, bx, 0]
        x0 = radius + bx * MB + mv[by, bx, 1]
        return lax.dynamic_slice(ap, (y0, x0), (MB, MB))

    rows = jax.vmap(lambda by: jax.vmap(lambda bx: one(by, bx))(
        jnp.arange(nbx)))(jnp.arange(nby))          # (nby, nbx, MB, MB)
    warped = rows.transpose(0, 2, 1, 3).reshape(H, W)
    return jnp.clip(warped + resid.astype(f32), 0.0, 255.0).astype(anchor.dtype)
