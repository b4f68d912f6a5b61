"""The comparison that decides ``correct``.

Each sampled chunk the window completed is compared with the plain
reference (``reference.chunk``) run on the same packet with the same
weights.  The numbers compared, each against the cell's limit in
``limits/<cell>.json`` (which also keeps the readings it was set from):

* ``type_mismatch``: frames whose pipeline differs from the packet's;
* ``nonfinite``: non-finite values among the served boxes and scores;
* ``center_gap_px``: the widest gap of a served box centre coordinate
  from the reference's, over the inferred (type 1 and 2) frames;
* ``score_mean``: the mean gap of the served scores from the
  reference's, over every cell of every frame (a NaN makes it NaN, and
  NaN passes no limit);
* ``reuse_off_share``: the share of boxes in the reused (type 3) frames
  that lie more than ``REUSE_TOL_PX`` from where the reference's
  pipeline 3 puts them when it starts from the boxes the program itself
  served at the inferred frames.  Pipeline 3 is a step function of the
  box geometry (a macroblock is in a box or not), so it is checked on the
  served inputs, as a served model is checked on its served tokens.

Box sizes are not compared on their own: on the chip the program's
relative size gap and the bfloat16 control's lie within 3x of each
other, so no limit separates them (PERF.md gives the readings); a wrong
detector moves the centres and scores as well.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REUSE_TOL_PX = 0.5


def limits(workload: str) -> dict:
    """The cell's limits, ``limits/<cell>.json``."""
    with open(os.path.join(HERE, "limits", workload + ".json")) as f:
        return {k: v["limit"] for k, v in json.load(f)["limits"].items()}


def numbers(served: list, reference: list, reuse_ref: list) -> dict:
    """``served``: per chunk (boxes, scores, types, packet types);
    ``reference``: per chunk (boxes, scores); ``reuse_ref``: per chunk
    the reference's pipeline 3 from the served inferred boxes."""
    out = dict(type_mismatch=0, nonfinite=0, center_gap_px=0.0)
    score_sum = score_n = off = reused = 0
    for (b, s, ty, pty), (rb, rs), ub in zip(served, reference, reuse_ref):
        pty = np.asarray(pty)
        out["type_mismatch"] += int(np.sum(np.asarray(ty) != pty))
        out["nonfinite"] += int(np.sum(~np.isfinite(b))
                                + np.sum(~np.isfinite(s)))
        ds = np.abs(s - rs)
        score_sum += float(np.sum(ds, dtype=np.float64))
        score_n += ds.size
        inf = pty != 3
        if inf.any():
            out["center_gap_px"] = max(out["center_gap_px"], float(
                np.max(np.abs(b[inf][..., :2] - rb[inf][..., :2]))))
        if (~inf).any():
            d = np.abs(b[~inf] - ub[~inf]).max(-1)
            off += int(np.sum(~(d <= REUSE_TOL_PX)))
            reused += d.size
    out["score_mean"] = score_sum / score_n if score_n else 0.0
    out["reuse_off_share"] = off / reused if reused else 0.0
    return out


def verdict(nums: dict, lim: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) -- correct when every number
    is at or under its limit."""
    checks = {k: {"value": nums[k], "limit": lim[k]} for k in lim}
    ok = all(c["value"] <= c["limit"] for c in checks.values())  # NaN fails
    return ok, checks
