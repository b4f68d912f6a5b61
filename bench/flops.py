"""Operations and bytes of the detector, from the shapes that run.

The detector is a stack of 3x3 convolutions (the first ``log2(stride)``
with stride 2, SAME padding) and a 1x1 head of 5 outputs, on one input
channel, in float32.  An operation is a multiply or an add (2 per
multiply-accumulate); bias, ReLU and the box decode are not counted.

Bytes are those of a layer-by-layer schedule that keeps nothing between
layers on chip: the input frame is read once, each convolution's output
is written once and read once by the next layer, and the decoded boxes
and scores (5 float32 per output cell) are written once.  The head's
output and the weights are not counted, so a fused implementation can
only move more than this, never less: a share of the roofline computed
from it stays below 100%.
"""
from __future__ import annotations

import math

F32 = 4
OUTPUTS_PER_CELL = 5          # cy, cx, h, w, score


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


def dispatch_cost(det: dict, hd_hw, rows: int):
    """(operations, bytes) of one detector dispatch of ``rows`` rows, as
    it runs: padded rows included."""
    H, W = hd_hw
    return rows * frame_flops(det, H, W), rows * frame_bytes(det, H, W)
