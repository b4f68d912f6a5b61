"""Plain reference of what the edge returns for one chunk of one camera.

Written from the BiSwift description (paper section IV-B) and the packet
format, in straightforward ``jax.numpy``; it imports nothing of the
program.  Its inputs are the packet as the camera shipped it (the
decoded HD anchor plane, the LR reconstruction, motion vectors, quantized
residual coefficients and their quantization table, the frame types) and
the detector weights the benchmark made from the seed.

Per frame:

* type 1 (anchor): the detector runs on the HD anchor;
* type 2: the LR frame's residual is dequantized and inverse-transformed,
  upscaled (nearest) to HD and added to the nearest preceding anchor
  warped block by block by the motion accumulated since that anchor; the
  detector runs on that frame;
* type 3: no inference; the previous frame's boxes move by minus the mean
  HD motion vector of the macroblocks each box covers.

The detector is the module of ``bench/detectors/`` that the
configuration's ``detector.arch`` names (its docstring describes it).

``dtype`` is the precision of every floating array: float32 (at the
highest matmul precision) is the reference, bfloat16 is the control
that must come out as not correct.  Geometry (indices, motion vectors)
is integer and the same in both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.loader import detector

MB = 16          # macroblock side of the codec
WARP_PAD = 16    # edge padding of the anchor before the block gather


def _dct_matrix(n: int = 8) -> np.ndarray:
    k = np.arange(n)[:, None]
    i = np.arange(n)[None, :]
    d = np.cos((2 * i + 1) * k * math.pi / (2 * n)) * math.sqrt(2.0 / n)
    d[0] /= math.sqrt(2.0)
    return d.astype(np.float32)


def _precision(dt):
    return lax.Precision.HIGHEST if dt == jnp.float32 else None


def residual_pixels(residual_q, qtab, h: int, w: int, dt):
    """(T, nblocks, 8, 8) quantized coefficients -> (T, h, w) pixels."""
    T = residual_q.shape[0]
    coefs = residual_q.astype(dt) * qtab.astype(dt)
    D = jnp.asarray(_dct_matrix(), dt)
    px = jnp.einsum("ji,tnjk,kl->tnil", D, coefs, D,
                    precision=_precision(dt))
    return px.reshape(T, h // 8, w // 8, 8, 8).transpose(0, 1, 3, 2, 4) \
        .reshape(T, h, w)


def upscale_nearest(frames, H: int, W: int):
    h, w = frames.shape[1:]
    yi = np.minimum(np.arange(H) * h // H, h - 1)
    xi = np.minimum(np.arange(W) * w // W, w - 1)
    return frames[:, yi][:, :, xi]


def hd_motion(mv, H: int, W: int):
    """LR macroblock motion (T, nby_lr, nbx_lr, 2) -> the HD macroblock
    grid, scaled by the HD/LR size ratio and rounded (int32)."""
    nby, nbx = H // MB, W // MB
    nby_lr, nbx_lr = mv.shape[1:3]
    yi = np.minimum(np.arange(nby) * nby_lr // nby, nby_lr - 1)
    xi = np.minimum(np.arange(nbx) * nbx_lr // nbx, nbx_lr - 1)
    scale = np.array([np.float32(H) / (np.float32(nby_lr) * np.float32(MB)),
                      np.float32(W) / (np.float32(nbx_lr) * np.float32(MB))],
                     np.float32)
    up = mv[:, yi][:, :, xi].astype(jnp.float32)
    return jnp.round(up * scale).astype(jnp.int32)


def anchor_of(types: np.ndarray) -> np.ndarray:
    """Index of each frame's nearest preceding anchor (0 if none)."""
    out = np.zeros(len(types), np.int32)
    last = 0
    for i, t in enumerate(types):
        if t == 1:
            last = i
        out[i] = last
    return out


@jax.jit
def transfer(anchor, mv, resid):
    """A type-2 frame: the anchor moved block by block, plus the
    residual, clipped to [0, 255]."""
    return jnp.clip(warp(anchor, mv) + resid, 0, 255).astype(anchor.dtype)


def warp(anchor, mv):
    """Each 16x16 block of the output is the anchor's block displaced by
    its motion vector; the anchor is edge-padded by 16 px and a block that
    would still leave the padded plane is clamped inside it."""
    H, W = anchor.shape
    nby, nbx = mv.shape[:2]
    ap = jnp.pad(anchor, WARP_PAD, mode="edge")
    y0 = jnp.clip(jnp.arange(nby)[:, None] * MB + WARP_PAD + mv[..., 0],
                  0, H + 2 * WARP_PAD - MB)
    x0 = jnp.clip(jnp.arange(nbx)[None, :] * MB + WARP_PAD + mv[..., 1],
                  0, W + 2 * WARP_PAD - MB)
    rows = y0[:, :, None, None] + jnp.arange(MB)[None, None, :, None]
    cols = x0[:, :, None, None] + jnp.arange(MB)[None, None, None, :]
    blocks = ap[rows, cols]                               # (nby, nbx, 16, 16)
    return blocks.transpose(0, 2, 1, 3).reshape(H, W)


def detector_inputs(pk: dict, dt):
    """The frame the detector sees at each frame of the chunk (type-3
    frames get the upscaled LR frame; it is never inferred) and the HD
    motion vectors."""
    types = pk["types"]
    anchor_hd = jnp.asarray(pk["anchor_hd"], dt)
    T, H, W = anchor_hd.shape
    h, w = pk["recon"].shape[1:]
    lr_up = upscale_nearest(jnp.asarray(pk["recon"], dt), H, W)
    resid = upscale_nearest(residual_pixels(
        jnp.asarray(pk["residual_q"]), jnp.asarray(pk["qtab"]), h, w, dt),
        H, W)
    mv_hd = hd_motion(jnp.asarray(pk["mv"]), H, W)
    aidx = anchor_of(types)
    cum = jnp.cumsum(mv_hd, axis=0)
    frames = []
    for i in range(T):
        if types[i] == 1:
            frames.append(anchor_hd[i])
        elif types[i] == 2:
            frames.append(transfer(anchor_hd[aidx[i]], cum[i] - cum[aidx[i]],
                                   resid[i]))
        else:
            frames.append(lr_up[i])
    return jnp.stack(frames), mv_hd


@jax.jit
def shift(boxes, mv):
    """Move each box by minus the mean motion vector of the macroblocks
    whose centres lie within half a macroblock of it.  The sums are of
    integers and done as matmuls at the highest precision."""
    dt = boxes.dtype
    nby, nbx = mv.shape[:2]
    cy = (jnp.arange(nby, dtype=dt) + 0.5) * MB
    cx = (jnp.arange(nbx, dtype=dt) + 0.5) * MB
    iy = (jnp.abs(cy[None] - boxes[:, 0:1])
          <= boxes[:, 2:3] / 2 + MB / 2).astype(jnp.float32)
    ix = (jnp.abs(cx[None] - boxes[:, 1:2])
          <= boxes[:, 3:4] / 2 + MB / 2).astype(jnp.float32)
    hp = lax.Precision.HIGHEST
    n = jnp.maximum(iy.sum(1) * ix.sum(1), 1e-9)
    sy = jnp.einsum("bk,kl,bl->b", iy, mv[..., 0].astype(jnp.float32), ix,
                    precision=hp)
    sx = jnp.einsum("bk,kl,bl->b", iy, mv[..., 1].astype(jnp.float32), ix,
                    precision=hp)
    dy = (sy / n).astype(dt)
    dx = (sx / n).astype(dt)
    return boxes.at[:, 0].add(-dy).at[:, 1].add(-dx)


def reuse(types, boxes, scores, mv_hd):
    """Pipeline 3: carry the last inferred frame's detections through the
    type-3 frames, moving the boxes by the motion."""
    out_b, out_s = [boxes[0]], [scores[0]]
    for i in range(1, len(types)):
        if types[i] == 3:
            out_b.append(shift(out_b[-1], mv_hd[i]))
            out_s.append(out_s[-1])
        else:
            out_b.append(boxes[i])
            out_s.append(scores[i])
    return jnp.stack(out_b), jnp.stack(out_s)


def chunk(pk: dict, params: dict, det: dict, dt):
    """What the edge should return for one packet: per-frame (boxes,
    scores) as host arrays, in float32, and the HD motion."""
    types = np.asarray(pk["types"])
    with jax.default_matmul_precision("highest" if dt == jnp.float32
                                      else "default"):
        frames, mv_hd = detector_inputs(pk, dt)
        inferred = np.nonzero(types != 3)[0]
        b_inf, s_inf = detector(det["arch"]).reference(
            params, frames[inferred], det, dt)
        T, n_cells = frames.shape[0], b_inf.shape[1]
        boxes = jnp.zeros((T, n_cells, 4), dt).at[inferred].set(b_inf)
        scores = jnp.zeros((T, n_cells), dt).at[inferred].set(s_inf)
        boxes, scores = reuse(types, boxes, scores, mv_hd)
    return (np.asarray(boxes.astype(jnp.float32)),
            np.asarray(scores.astype(jnp.float32)), np.asarray(mv_hd))


def reuse_from(types, boxes: np.ndarray, mv_hd: np.ndarray) -> np.ndarray:
    """The reference's pipeline 3 run from the given boxes at the
    inferred frames (f32): what the reused frames should hold for the
    detections the program itself served at its inferred frames."""
    b = jnp.asarray(boxes, jnp.float32)
    out, _ = reuse(np.asarray(types), b, jnp.zeros(b.shape[:2]),
                   jnp.asarray(mv_hd))
    return np.asarray(out)
