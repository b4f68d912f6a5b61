"""The arithmetic of the end-to-end numbers."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The q-th percentile (0..100) of all samples, linear between
    neighbours; None for no samples."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def streams_rt(chunks: int, window_s: float, chunk_s: float) -> float | None:
    """Cameras kept up with in real time: seconds of video completed per
    second of window."""
    if window_s <= 0 or chunks == 0:
        return None
    return chunks * chunk_s / window_s
