"""8×8 block DCT + quantize + dequant/IDCT kernel (Pallas TPU).

The JPEG/codec transform core: y = D·x·Dᵀ, q = round(y / qtab),
recon = Dᵀ·(q·qtab)·D.  Each block is one row of 64 values (row-major
flatten), so the separable 2-D transform is one matmul with the 64×64
Kronecker matrix K = D ⊗ D: vec(D·x·Dᵀ) = K·vec(x), vec(Dᵀ·y·D) =
Kᵀ·vec(y).  A (TILE, 64) × (64, 64) contraction is MXU-shaped and needs
no 3-D reshape inside the kernel, which Mosaic cannot lay out.  The
matmuls run at HIGHEST precision: the default bf16 passes would move
quantized coefficients across rounding boundaries.

Grid: (nb / TILE,).  VMEM per step: TILE·64·4 bytes ·3 ≈ 196 KiB at
TILE = 256, plus the resident 64×64 matrix.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

f32 = jnp.float32


def _dot(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=f32)


def _kernel(x_ref, k_ref, qt_ref, q_ref, rec_ref):
    x = x_ref[...]                     # (TILE, 64) row-major blocks
    K = k_ref[...]                     # (64, 64) = D ⊗ D
    qt = qt_ref[...]                   # (1, 64)
    q = jnp.round(_dot(x, K.T) / qt)
    q_ref[...] = q.astype(q_ref.dtype)
    rec_ref[...] = _dot(q * qt, K).astype(rec_ref.dtype)


def blockdct_tiles(blocks, dmat, qtab, *, tile: int = 256,
                   interpret: bool = False):
    """blocks: (nb, 8, 8) f32 -> (quantized (nb, 8, 8), recon (nb, 8, 8))."""
    nb = blocks.shape[0]
    tile = min(tile, nb)
    pad = (-nb) % tile
    x = blocks.reshape(nb, 64)
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, 64), x.dtype)], axis=0)
    n = x.shape[0]
    kron = jnp.kron(dmat, dmat).astype(f32)

    q, rec = pl.pallas_call(
        _kernel,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile, 64), lambda i: (i, 0)),
            pl.BlockSpec((64, 64), lambda i: (0, 0)),
            pl.BlockSpec((1, 64), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, 64), lambda i: (i, 0)),
            pl.BlockSpec((tile, 64), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 64), blocks.dtype),
            jax.ShapeDtypeStruct((n, 64), blocks.dtype),
        ],
        interpret=interpret,
    )(x, kron, qtab.reshape(1, 64).astype(f32))
    return q[:nb].reshape(nb, 8, 8), rec[:nb].reshape(nb, 8, 8)
