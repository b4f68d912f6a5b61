"""Operations and bytes of a detector dispatch, from the shapes that run.

The counts of one frame are the detector module's own
(``detectors/<arch>.py``: ``frame_flops``, ``frame_bytes``, with what
they include); this module looks them up by the configuration's
``detector.arch`` and scales them to a dispatch.
"""
from __future__ import annotations

from bench.loader import detector


def frame_flops(det: dict, h: int, w: int) -> float:
    """Operations of the detector on one (h, w) frame."""
    return detector(det["arch"]).frame_flops(det, h, w)


def frame_bytes(det: dict, h: int, w: int) -> float:
    """Bytes the detector's layer-by-layer schedule moves for one (h, w)
    frame."""
    return detector(det["arch"]).frame_bytes(det, h, w)


def dispatch_cost(det: dict, hd_hw, rows: int):
    """(operations, bytes) of one detector dispatch of ``rows`` rows, as
    it runs: padded rows included."""
    H, W = hd_hw
    return rows * frame_flops(det, H, W), rows * frame_bytes(det, H, W)
