"""Codec features of a traffic mix's pooled chunks, for choosing its
classification thresholds.  Runs on any backend; the numbers it reads
are counts and codec features, not times.

    PYTHONPATH=src python3 -m bench.tools.features --traffic paper_mix \
        --config biswift9_1080p --seeds 1 2 --tr1 0.02 --tr2 0.0

For each seed and camera it encodes the pooled chunks with the program's
codec at the camera's ladder rung, classifies the frames at the given
thresholds by paper Eq. 3 (a copy, below), and prints the type counts
and the quantiles of the per-frame features.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def classify(fd, rm, tr1: float, tr2: float) -> np.ndarray:
    """Paper Eq. 3: anchor when the accumulated frame difference passes
    tr1 (frame 0 always), else transfer when the accumulated residual
    passes tr2, else reuse; both accumulators reset at inferred frames."""
    types = np.zeros(len(fd), np.int32)
    ax = ar = 0.0
    for i, (x, r) in enumerate(zip(fd, rm)):
        ax, ar = ax + x, ar + r
        t = 1 if (ax > tr1 or i == 0) else (2 if ar > tr2 else 3)
        types[i] = t
        if t != 3:
            ax = ar = 0.0
    return types


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--tr1", type=float, required=True)
    ap.add_argument("--tr2", type=float, required=True)
    ap.add_argument("--cameras", type=int, default=None)
    args = ap.parse_args(argv)

    from bench import video
    from repro.codec.rate_model import (QUALITY_LADDER, downscale,
                                        ladder_for_bandwidth,
                                        video_bandwidth_share)
    from repro.codec.video_codec import VideoCodecConfig, encode_chunk

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", args.traffic + ".json")) as f:
        traffic = json.load(f)
    H, W, T = cfg["height"], cfg["width"], cfg["frames_per_chunk"]
    level = ladder_for_bandwidth(video_bandwidth_share(
        cfg["uplink_kbps"] / cfg["cameras"]))
    ql = QUALITY_LADDER[level]
    n_cam = args.cameras or cfg["cameras"]
    fds, rms, counts = [], [], []
    for seed in args.seeds:
        for c, cam in enumerate(video.cameras(traffic, n_cam)):
            sc = video.scene(video.seed_key(seed, video.CAMERAS, c), cam, H, W)
            for k in range(traffic["pool_chunks"]):
                raw = video.render(sc, float(k * T), n_frames=T,
                                   contrast=cam.texture_contrast,
                                   freq=cam.texture_freq)
                enc = encode_chunk(downscale(raw, ql.scale),
                                   VideoCodecConfig(quality=ql.quality))
                fd = np.asarray(enc.frame_diff) / 255.0
                rm = np.asarray(enc.residual_mag) / 255.0
                fds.append(fd[1:])
                rms.append(rm[1:])
                ty = classify(fd, rm, args.tr1, args.tr2)
                counts.append([int((ty == i).sum()) for i in (1, 2, 3)])
                print(f"seed {seed} camera {c} chunk {k}: types 1/2/3 "
                      f"{counts[-1]}", flush=True)
    fd, rm, counts = np.concatenate(fds), np.concatenate(rms), \
        np.asarray(counts)
    q = [0, 10, 50, 90, 99, 100]
    print("fd quantiles", dict(zip(q, np.percentile(fd, q).round(6))))
    print("rm quantiles", dict(zip(q, np.percentile(rm, q).round(6))))
    print("types 1/2/3 per chunk: mean", counts.mean(0).round(2).tolist(),
          "min", counts.min(0).tolist(), "max", counts.max(0).tolist())


if __name__ == "__main__":
    main()
