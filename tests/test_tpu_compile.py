"""The video-path Pallas kernels compile for a TPU v5e at real frame sizes.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology (one chip of it) with ``JAX_PLATFORMS=cpu``.  What the chip's
compiler refuses — blocks that break the (8, 128) tiling rule, unaligned
dynamic loads, layouts Mosaic cannot infer — fails here, where interpret
mode would accept it.  Each compile must contain a ``tpu_custom_call``:
the kernel itself, not an XLA substitute.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles (a
program compiled for a described chip can be written to it but not read
back without one).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.blockdct.kernel import blockdct_tiles
from repro.kernels.motion_sad.kernel import motion_sad_rows
from repro.kernels.qtransfer.kernel import qtransfer_rows
from repro.kernels.roi_gather.kernel import roi_gather_patches
from repro.kernels.tile_shift import tile_shift

SIZES = [(1088, 1920), (720, 1280)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compile_for_chip(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("H,W", SIZES)
@pytest.mark.parametrize("search,dtype", [
    ("exhaustive", None), ("exhaustive", jnp.bfloat16),
    ("diamond", None), ("diamond", jnp.bfloat16)])
def test_motion_sad_compiles_for_v5e(one_chip, H, W, search, dtype):
    def fn(cur, ref):
        return motion_sad_rows(cur, ref, radius=8, dtype=dtype,
                               search=search, interpret=False)
    _compile_for_chip(fn, one_chip, ((H, W), jnp.float32),
                      ((H, W), jnp.float32))


@pytest.mark.parametrize("H,W", SIZES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qtransfer_compiles_for_v5e(one_chip, H, W, dtype):
    def fn(anchor, mv, resid):
        return qtransfer_rows(anchor, mv, resid, radius=16, interpret=False)
    _compile_for_chip(fn, one_chip, ((H, W), dtype),
                      ((H // 16, W // 16, 2), jnp.int32), ((H, W), dtype))


@pytest.mark.parametrize("H,W", SIZES)
def test_roi_gather_compiles_for_v5e(one_chip, H, W):
    region_px, halo, T, K = 32, 8, 4, 8       # RoiConfig defaults

    def fn(planes, ry, rx):
        return roi_gather_patches(planes, ry, rx, region_px=region_px,
                                  halo=halo, interpret=False)
    _compile_for_chip(fn, one_chip,
                      ((T, H + 2 * halo, W + 2 * halo), jnp.float32),
                      ((T, K), jnp.int32), ((T, K), jnp.int32))


@pytest.mark.parametrize("H,W", SIZES)
def test_blockdct_compiles_for_v5e(one_chip, H, W):
    def fn(blocks, dmat, qtab):
        return blockdct_tiles(blocks, dmat, qtab, interpret=False)
    _compile_for_chip(fn, one_chip, ((H * W // 64, 8, 8), jnp.float32),
                      ((8, 8), jnp.float32), ((8, 8), jnp.float32))


@pytest.mark.parametrize("H,W", SIZES)
def test_tile_shift_compiles_for_v5e(one_chip, H, W):
    n = (H // 16) * (W // 16)

    def fn(tl, tr, bl, br, fy, fx):
        return tile_shift(tl, tr, bl, br, fy, fx, interpret=False)
    _compile_for_chip(fn, one_chip, *[((n, 256), jnp.float32)] * 4,
                      ((n,), jnp.int32), ((n,), jnp.int32))


def test_stage_chunk_has_no_per_block_loop_on_v5e(one_chip, monkeypatch):
    """The staging jit at the 9 x 1080p cell's shapes (T = 30, LR
    352x640), on the route the chip takes, lowers the quality-transfer
    warp to whole-tile gathers and the Pallas shift: no ``while`` loop
    carries an array with one entry per 16x16 block of the chunk (the
    per-block slice form became one such loop, 244,800 trips a chunk)."""
    from repro.kernels.motion_sad import ops
    from repro.serving.runtime import _stage_chunk
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    H, W, T, h, w = 1088, 1920, 30, 352, 640
    blocks = T * (H // 16) * (W // 16)

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    stage = _stage_chunk.__wrapped__          # traced afresh, on this route
    text = jax.jit(lambda *a: stage(*a, hd_hw=(H, W))).lower(
        s((T,), jnp.int32), s((T, H, W)), s((T, h, w)),
        s((T, h // 16, w // 16, 2), jnp.int32),
        s((T, (h // 8) * (w // 8), 8, 8)), s((8, 8))).compile().as_text()
    assert "tpu_custom_call" in text
    carried = [line.split(" while(")[0] for line in text.splitlines()
               if " while(" in line]
    per_block = [c for c in carried if re.search(rf"\[{blocks}[,\]]", c)]
    assert not per_block, per_block
