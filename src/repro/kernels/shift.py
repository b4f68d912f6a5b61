"""Per-lane shifted reads inside Pallas TPU kernels.

Mosaic has no gather, and a vector load may not start at a dynamic
offset that is not a multiple of the tile (8 sublanes, 128 lanes).  The
block-matching kernels (``motion_sad``, ``qtransfer``) need exactly that:
each 16-lane macroblock column reads its own reference window at its own
(dy, dx) offset.  These helpers express such reads with full-width
rotations and selects only, so every load and store stays tile-aligned:

* ``lane_shift(x, a, n)``: ``out[:, l] = x[:, l + a[l]]`` for ``a`` in
  ``[0, n)`` — one ``pltpu.roll`` per candidate shift, the destination
  lane's amount picking the right one;
* ``row_shift(x, a, n, rows)``: ``out[u, l] = x[u + a[l], l]`` for the
  first ``rows`` rows, the same on the sublane axis.

Both read lanes/rows past ``l + a`` by wrapping round, so callers pad the
operand (``halo_blocks``/``padded_width``) such that no valid output
reads a wrapped element.  A shifted read moves values unchanged, so the
helpers are exact in every dtype.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def padded_width(width: int, margin: int) -> int:
    """Lane extent of an operand of ``width`` columns read up to
    ``margin`` columns to the right, rounded up to whole 128-lane tiles."""
    return -(-(width + margin) // LANES) * LANES


def halo_blocks(block_rows: int, margin: int) -> int:
    """Extra row blocks of ``block_rows`` a band needs so that a grid step
    can read ``margin`` rows past its own block."""
    return -(-margin // block_rows)


def band(refs):
    """Concatenate consecutive row blocks (one ref each) into the f32
    band a grid step reads its shifted windows from."""
    return jnp.concatenate([r[...].astype(jnp.float32) for r in refs],
                           axis=0)


def lane_shift(x, amount, n: int):
    """``out[:, l] = x[:, l + amount[l]]``, ``amount`` in ``[0, n)``
    (int32, broadcastable to ``x``'s shape)."""
    width = x.shape[-1]

    def body(j, acc):
        return jnp.where(amount == j, pltpu.roll(x, width - j, 1), acc)

    return lax.fori_loop(1, n, body, x)


def row_shift(x, amount, n: int, rows: int):
    """``out[u, l] = x[u + amount[l], l]`` for ``u < rows``, ``amount``
    in ``[0, n)`` with ``rows + n - 1 <= x.shape[0]``."""
    height = x.shape[0]

    def body(k, acc):
        return jnp.where(amount == k,
                         pltpu.roll(x, height - k, 0)[:rows], acc)

    return lax.fori_loop(1, n, body, x[:rows])
