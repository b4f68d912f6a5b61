"""Detector ``tiny``: the program's stand-in detector
(``repro.models.detection.TinyDetectorConfig``, an FCOS-style anchor-free
head on a small conv stack), the only detector the program serves today.

Architecture: ``len(channels)`` 3x3 convolutions with ReLU on one input
channel (the frame as ``x / 255 - 0.5``), the first ``log2(stride)`` of
them with stride 2, SAME padding; then a 1x1 head of 5 outputs per
output cell.  Each cell of the ``(H / stride, W / stride)`` grid decodes
into an objectness score ``sigmoid(o)`` and a box (cy, cx, h, w):
``cy = (row + 0.5 + tanh(dy)) * stride``, likewise ``cx``, and
``h = exp(clip(log h, -3, 3)) * stride``, likewise ``w``.

A module of ``bench/detectors/`` is found by the ``arch`` of a
configuration's ``detector`` block, and provides:

* ``weights(key, det)``: the seeded float32 weights, as one jitted call;
* ``program_config(det)``: the program's detector config handed to
  ``EdgeRuntime`` (the one place the benchmark imports model code);
* ``reference(params, frames, det, dt)``: the plain ``jax.numpy``
  forward pass and box decode, ``(boxes (B, N, 4) cxcywh, scores (B, N))``
  in ``dt``; the caller sets the matmul precision;
* ``frame_flops(det, h, w)`` and ``frame_bytes(det, h, w)``: the
  operations and bytes of one (h, w) frame, below.

Counts.  An operation is a multiply or an add (2 per multiply-
accumulate); bias, ReLU and the box decode are not counted.  Bytes are
those of a layer-by-layer schedule that keeps nothing between layers on
chip: the input frame is read once, each convolution's output is written
once and read once by the next layer, and the decoded boxes and scores
(5 float32 per output cell) are written once.  The head's output and the
weights are not counted, so a fused implementation can only move more
than this, never less: a share of the roofline computed from it stays
below 100%.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F32 = 4
OUTPUTS_PER_CELL = 5          # cy, cx, h, w, score


def weights(key, det: dict):
    """Fan-in normal kernels and small normal biases, float32, made on
    the device in one jitted call."""
    chans = tuple(det["channels"])

    @jax.jit
    def init(key):
        ks = jax.random.split(key, 2 * len(chans) + 2)
        p, cin = {}, 1
        for i, c in enumerate(chans):
            p[f"conv{i}"] = jax.random.normal(ks[2 * i], (3, 3, cin, c)) \
                / jnp.sqrt(9.0 * cin)
            p[f"bias{i}"] = 0.05 * jax.random.normal(ks[2 * i + 1], (c,))
            cin = c
        p["head"] = jax.random.normal(ks[-2], (1, 1, cin, 5)) / jnp.sqrt(cin)
        p["head_b"] = 0.05 * jax.random.normal(ks[-1], (5,))
        return p

    return init(key)


def program_config(det: dict):
    from repro.models.detection import TinyDetectorConfig
    return TinyDetectorConfig(channels=tuple(det["channels"]),
                              stride=det["stride"], dtype=det["dtype"])


def _precision(dt):
    return lax.Precision.HIGHEST if dt == jnp.float32 else None


@partial(jax.jit, static_argnames=("stride",))
def _raw(params, frames, *, stride: int):
    """(B, H, W) frames -> (B, H/stride, W/stride, 5) raw head output."""
    dt = frames.dtype
    x = (frames / 255.0 - 0.5).astype(dt)[..., None]
    n_down = int(round(math.log2(stride)))
    i = 0
    while f"conv{i}" in params:
        s = 2 if i < n_down else 1
        x = lax.conv_general_dilated(
            x, params[f"conv{i}"].astype(dt), (s, s), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=_precision(dt))
        x = jax.nn.relu(x + params[f"bias{i}"].astype(dt))
        i += 1
    return lax.conv_general_dilated(
        x, params["head"].astype(dt), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_precision(dt)) + params["head_b"].astype(dt)


@partial(jax.jit, static_argnames=("stride",))
def _decode(raw, *, stride: int):
    """Raw head output -> (B, cells, 4) cxcywh boxes and (B, cells)
    scores, in the raw output's dtype."""
    dt = raw.dtype
    B, hc, wc, _ = raw.shape
    score = jax.nn.sigmoid(raw[..., 0])
    cy = (jnp.arange(hc, dtype=dt)[None, :, None] + 0.5
          + jnp.tanh(raw[..., 1])) * stride
    cx = (jnp.arange(wc, dtype=dt)[None, None, :] + 0.5
          + jnp.tanh(raw[..., 2])) * stride
    bh = jnp.exp(jnp.clip(raw[..., 3], -3, 3)) * stride
    bw = jnp.exp(jnp.clip(raw[..., 4], -3, 3)) * stride
    boxes = jnp.stack([jnp.broadcast_to(cy, score.shape),
                       jnp.broadcast_to(cx, score.shape), bh, bw], -1)
    return boxes.reshape(B, -1, 4), score.reshape(B, -1)


def reference(params, frames, det: dict, dt):
    """(B, H, W) frames in ``dt`` -> (boxes (B, N, 4), scores (B, N)) in
    ``dt``, in one block."""
    p = {k: jnp.asarray(v, dt) for k, v in params.items()}
    return _decode(_raw(p, frames, stride=det["stride"]),
                   stride=det["stride"])


# ------------------------------------------------------------------ counts
def _layers(det: dict, h: int, w: int):
    """(out_h, out_w, c_in, c_out, k) of every layer, head last."""
    n_down = int(round(math.log2(det["stride"])))
    out, cin = [], 1
    for i, c in enumerate(det["channels"]):
        if i < n_down:
            h, w = -(-h // 2), -(-w // 2)
        out.append((h, w, cin, c, 3))
        cin = c
    out.append((h, w, cin, OUTPUTS_PER_CELL, 1))
    return out


def frame_flops(det: dict, h: int, w: int) -> float:
    """Operations of the detector on one (h, w) frame."""
    return float(sum(2 * oh * ow * ci * co * k * k
                     for oh, ow, ci, co, k in _layers(det, h, w)))


def frame_bytes(det: dict, h: int, w: int) -> float:
    """Bytes the layer-by-layer schedule moves for one (h, w) frame."""
    layers = _layers(det, h, w)
    convs = layers[:-1]
    oh, ow = layers[-1][:2]
    return float(F32 * (h * w
                        + sum(2 * a * b * co for a, b, _, co, _ in convs)
                        + OUTPUTS_PER_CELL * oh * ow))
