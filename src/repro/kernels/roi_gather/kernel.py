"""ROI patch-gather kernel (Pallas): pack top-K active regions densely.

One grid step per (frame, capacity-lane).  The lane's region indices
are scalar-prefetched (SMEM), and they drive the BlockSpec index maps:
the patch's rows [ry·R, ry·R + P), P = R + 2·halo, arrive as
``1 + halo_blocks`` consecutive R-row blocks of the halo-padded plane,
so only the rows a patch needs are DMA'd and every DMA is tile-aligned.
The column offset rx·R is applied in-kernel by one lane rotation
(``pltpu.roll``) of the full-width band, then the (P, P) patch is the
static top-left slice.  Every SHAPE is static — the packed batch always
has capacity-K lanes, so the detector trace downstream never changes
with scene content.

Invalid lanes (gate admitted fewer than K regions) still gather a patch
(the caller points them at region 0); their outputs are dropped at
scatter time.  A gather moves values unchanged, so the kernel is
bit-exact vs the pure-jnp ``dynamic_slice`` fallback — the parity
contract ``tests/test_roi.py`` holds, mirroring ``motion_sad``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shift import band, halo_blocks, padded_width


def _gather_kernel(ry_ref, rx_ref, *refs, region_px: int, halo: int):
    *blk_refs, o_ref = refs
    P = region_px + 2 * halo
    rows = band(blk_refs)[:P]                    # (P, Wp) f32
    width = rows.shape[1]
    # region (ry, rx) -> top-left corner in the halo-padded plane: the
    # padding shifts frame coords by +halo, so the patch spanning
    # [rx*R - halo, rx*R + R + halo) starts at padded column rx*R
    x0 = rx_ref[pl.program_id(0), pl.program_id(1)] * region_px
    patch = pltpu.roll(rows, (width - x0) % width, 1)[:, :P]
    o_ref[...] = patch.astype(o_ref.dtype)


def roi_gather_patches(planes, ry, rx, *, region_px: int, halo: int,
                       interpret: bool = False):
    """planes: (T, Hp, Wp) halo-padded planes; ry/rx: (T, K) int32 region
    indices -> (T, K, P, P) packed patches.  ``region_px`` must be a
    multiple of 8 (one row block per region row)."""
    T, Hp, Wp = planes.shape
    K = ry.shape[1]
    P = region_px + 2 * halo
    extra = halo_blocks(region_px, 2 * halo)
    n_blocks = -(-Hp // region_px) + extra
    width = padded_width(Wp, 0)
    planes = jnp.pad(planes, ((0, 0), (0, n_blocks * region_px - Hp),
                              (0, width - Wp)))
    kernel = functools.partial(_gather_kernel, region_px=region_px,
                               halo=halo)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, K),
        in_specs=[
            pl.BlockSpec((None, region_px, width),
                         lambda t, k, ry, rx, j=j: (t, ry[t, k] + j, 0))
            for j in range(extra + 1)],
        out_specs=pl.BlockSpec((None, None, P, P),
                               lambda t, k, ry, rx: (t, k, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, K, P, P), planes.dtype),
        interpret=interpret,
    )(ry.astype(jnp.int32), rx.astype(jnp.int32),
      *([planes] * (extra + 1)))
