"""Codec substrate: DCT round trips, rate-quality monotonicity, motion
estimation correctness (property-based where natural)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.codec import blockdct as B
from repro.codec.image_codec import jpeg_encode_decode, psnr
from repro.codec.motion import block_sad, warp_blocks
from repro.codec.rate_model import (QUALITY_LADDER, downscale,
                                    ladder_for_bandwidth, upscale_nearest)
from repro.codec.video_codec import VideoCodecConfig, encode_chunk, \
    chunk_psnr
from repro.sim.video_source import StreamConfig, generate_chunk

KEY = jax.random.PRNGKey(0)


def test_dct_orthonormal_roundtrip():
    blocks = jax.random.uniform(KEY, (16, 8, 8), jnp.float32) * 255 - 128
    rec = B.idct2(B.dct2(blocks))
    np.testing.assert_allclose(np.asarray(rec), np.asarray(blocks),
                               atol=1e-3)


def test_blockify_roundtrip():
    img = jax.random.uniform(KEY, (32, 48), jnp.float32)
    back = B.unblockify(B.blockify(img), 32, 48)
    np.testing.assert_allclose(np.asarray(back), np.asarray(img))


@pytest.mark.parametrize("q1,q2", [(20.0, 50.0), (50.0, 85.0)])
def test_jpeg_quality_monotone(q1, q2):
    img = np.asarray(generate_chunk(KEY, StreamConfig(height=64, width=96),
                                    0, 1)[0][0])
    r1, b1 = jpeg_encode_decode(jnp.asarray(img), q1)
    r2, b2 = jpeg_encode_decode(jnp.asarray(img), q2)
    assert float(b1) < float(b2)                       # more bits
    assert float(psnr(img, r1)) < float(psnr(img, r2)) # better quality


@settings(deadline=None, max_examples=8)
@given(dy=st.integers(-6, 6), dx=st.integers(-6, 6))
def test_motion_estimation_recovers_global_shift(dy, dx):
    """A globally shifted frame must be recovered by full-search ME."""
    frames, _, _ = generate_chunk(KEY, StreamConfig(height=64, width=96,
                                                    n_objects=5), 0, 1)
    ref = np.asarray(frames[0])
    cur = np.roll(np.roll(ref, dy, axis=0), dx, axis=1)
    mv, sad = block_sad(jnp.asarray(cur), jnp.asarray(ref), radius=8)
    mv = np.asarray(mv)
    # interior blocks (away from the wrap-around border) match exactly;
    # ME returns the *gather* offset: pred(y) = ref(y + mv) -> mv = -shift
    inner = mv[1:-1, 1:-1]
    assert (inner[..., 0] == -dy).all()
    assert (inner[..., 1] == -dx).all()


def test_warp_blocks_identity():
    frames, _, _ = generate_chunk(KEY, StreamConfig(height=48, width=64),
                                  0, 1)
    f = frames[0]
    mv = jnp.zeros((3, 4, 2), jnp.int32)
    np.testing.assert_allclose(np.asarray(warp_blocks(f, mv)),
                               np.asarray(f), atol=1e-4)


def _warp_oracle(ref, mv):
    """Plain loop: edge-pad the anchor by 16 px, clamp each block's start
    inside the padded plane, copy the block."""
    ref, mv = np.asarray(ref, np.float32), np.asarray(mv)
    H, W = ref.shape
    padded = np.pad(ref, 16, mode="edge")
    out = np.empty_like(ref)
    for by in range(H // 16):
        for bx in range(W // 16):
            y = min(max(by * 16 + 16 + int(mv[by, bx, 0]), 0), H + 16)
            x = min(max(bx * 16 + 16 + int(mv[by, bx, 1]), 0), W + 16)
            out[by * 16:by * 16 + 16, bx * 16:bx * 16 + 16] = \
                padded[y:y + 16, x:x + 16]
    return out


def _motion(kind, nby, nbx, H, W, rng):
    """(nby, nbx, 2) int32 motion of one class."""
    shape = (nby, nbx, 2)
    sign = rng.choice([-1, 1], shape)
    if kind == "zero":
        return np.zeros(shape, np.int32)
    if kind == "within_pad":                       # |mv| 1..16
        return (sign * rng.integers(1, 17, shape)).astype(np.int32)
    if kind == "past_pad":                         # |mv| 17..64
        return (sign * rng.integers(17, 65, shape)).astype(np.int32)
    if kind == "beyond_frame":                     # |mv| past H or W
        return (sign * rng.integers(max(H, W), 3 * max(H, W), shape)) \
            .astype(np.int32)
    # "edges": each edge block moves outward past the padding (its start
    # goes negative, or past the far edge); interior blocks stay small
    mv = rng.integers(-8, 9, shape).astype(np.int32)
    out = rng.integers(17, 41, shape).astype(np.int32)
    mv[0, :, 0], mv[-1, :, 0] = -out[0, :, 0], out[-1, :, 0]
    mv[:, 0, 1], mv[:, -1, 1] = -out[:, 0, 1], out[:, -1, 1]
    return mv


@pytest.mark.parametrize("H,W", [(64, 96), (1088, 1920)])
@pytest.mark.parametrize("kind", ["zero", "within_pad", "past_pad",
                                  "beyond_frame", "edges"])
def test_warp_blocks_matches_clamped_loop_oracle(H, W, kind):
    """Every block copies the padded anchor at its start clamped inside
    the padded plane, bit for bit: a block moved past the padding reads
    the edge it moved past, never the opposite one."""
    rng = np.random.default_rng([H, W, len(kind)])
    ref = rng.uniform(0, 255, (H, W)).astype(np.float32)
    mv = _motion(kind, H // 16, W // 16, H, W, rng)
    got = np.asarray(jax.jit(warp_blocks)(jnp.asarray(ref), jnp.asarray(mv)))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, _warp_oracle(ref, mv))


def _warp_slice_form(ref, mv):
    """The earlier per-block ``lax.dynamic_slice`` warp, kept as the
    oracle where no block start leaves the padded plane."""
    H, W = ref.shape
    refp = jnp.pad(ref.astype(jnp.float32), 16, mode="edge")
    by = jnp.arange(mv.shape[0]) * 16
    bx = jnp.arange(mv.shape[1]) * 16

    def block(y0, x0, d):
        return jax.lax.dynamic_slice(refp, (y0 + 16 + d[0], x0 + 16 + d[1]),
                                     (16, 16))

    rows = jax.vmap(lambda y0, m: jax.vmap(
        lambda x0, d: block(y0, x0, d))(bx, m))(by, mv)
    return rows.transpose(0, 2, 1, 3).reshape(H, W)


@pytest.mark.parametrize("H,W", [(64, 96), (352, 640)])
def test_warp_blocks_encoder_motion_bit_identical_to_slice_form(H, W):
    """The camera-side encoder warps at LR with |mv| <= search_radius (8):
    the clamp never engages, so its prediction is the slice form's."""
    radius = VideoCodecConfig().search_radius
    frames, _, _ = generate_chunk(KEY, StreamConfig(height=H, width=W,
                                                    n_objects=5), 0, 2)
    searched, _ = block_sad(frames[1], frames[0], radius=radius)
    rng = np.random.default_rng(H)
    drawn = jnp.asarray(rng.integers(-radius, radius + 1,
                                     searched.shape).astype(np.int32))
    for mv in (searched, drawn):
        assert int(jnp.abs(mv).max()) <= radius
        np.testing.assert_array_equal(
            np.asarray(jax.jit(warp_blocks)(frames[0], mv)),
            np.asarray(jax.jit(_warp_slice_form)(frames[0], mv)))


def test_video_codec_quality_and_bits_monotone():
    frames, _, _ = generate_chunk(KEY, StreamConfig(height=64, width=96),
                                  0, 3)
    lo = encode_chunk(frames, VideoCodecConfig(quality=25.0))
    hi = encode_chunk(frames, VideoCodecConfig(quality=75.0))
    assert float(lo.bits.sum()) < float(hi.bits.sum())
    assert float(chunk_psnr(frames, lo.recon).mean()) < \
        float(chunk_psnr(frames, hi.recon).mean())
    assert float(chunk_psnr(frames, hi.recon).min()) > 28.0


def test_qtab_computed_once_and_threaded(monkeypatch):
    """The encoder builds the quant table ONCE per chunk from cfg.quality
    and threads it through the I-frame and every P-frame — the legacy path
    rebuilt (and discarded) it per frame inside B.quantize."""
    import repro.codec.video_codec as VC
    calls = []
    orig = B.quant_table
    monkeypatch.setattr(B, "quant_table",
                        lambda q: (calls.append(1), orig(q))[1])
    cfg = VideoCodecConfig(quality=42.0)
    jax.eval_shape(lambda f: VC._encode_chunk(f, cfg),
                   jax.ShapeDtypeStruct((3, 32, 48), jnp.float32))
    assert len(calls) == 1, \
        f"quant_table built {len(calls)}x during one chunk trace"
    monkeypatch.undo()
    # the threaded table is the cfg-quality table (I-frame included)
    frames, _, _ = generate_chunk(KEY, StreamConfig(height=32, width=48),
                                  0, 2)
    enc = encode_chunk(frames, cfg)
    np.testing.assert_array_equal(np.asarray(enc.qtab),
                                  np.asarray(B.quant_table(42.0)))


def test_ladder_selection():
    assert ladder_for_bandwidth(400.0) == 0
    assert ladder_for_bandwidth(1200.0) >= 1
    assert ladder_for_bandwidth(20000.0) == len(QUALITY_LADDER) - 1
    # monotone in bandwidth
    lv = [ladder_for_bandwidth(b) for b in (300, 600, 1200, 2500, 9000)]
    assert lv == sorted(lv)


def test_down_up_scale_shapes():
    frames = jax.random.uniform(KEY, (2, 96, 160), jnp.float32)
    for ql in QUALITY_LADDER:
        small = downscale(frames, ql.scale)
        assert small.shape[1] % 16 == 0 and small.shape[2] % 16 == 0
        up = upscale_nearest(small, 96, 160)
        assert up.shape == (2, 96, 160)
