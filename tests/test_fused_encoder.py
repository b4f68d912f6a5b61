"""Fused encoder path: vmapped-fallback-vs-scan-oracle parity (property
based), warp/accumulate invariants, encoder edge cases (T=1, non-square,
GOP boundaries), batched/sharded encode parity, and the bf16 kernel
variants.

Like ``test_stream_sharding.py``, the mesh-parity matrix needs a real
multi-device platform: a driver test re-runs this file's ``forced``-named
tests in a subprocess with 4 fake CPU devices
(``conftest.forced_multidevice_run``).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax import lax

import conftest
from repro.codec.motion import (MB, accumulate_mv, block_sad, block_sad_scan,
                                diamond_num_evals, diamond_steps, warp_blocks)
from repro.kernels.motion_sad.ops import motion_sad
from repro.codec.rate_model import QUALITY_LADDER, downscale, ladder_lr_shape
from repro.codec.video_codec import (VideoCodecConfig, encode_chunk,
                                     encode_chunk_batched,
                                     encode_chunk_ladder_batched,
                                     pad_ladder_batch)
from repro.distributed.sharding import SINGLE_POD_RULES, SINGLE_POD_RULES_DP
from repro.distributed.stream_sharding import shard_encode, stream_shard_count
from repro.sim.video_source import (StreamConfig, generate_chunk,
                                    generate_chunk_batched)

_FORCED = int(os.environ.get(conftest.FORCED_MULTIDEVICE_ENV, "0"))

forced_only = pytest.mark.skipif(
    _FORCED < 4, reason="needs the forced multi-device child process")

CFG = VideoCodecConfig(quality=50.0, search_radius=4)


def _streams(S, T=3, H=32, W=48):
    cfgs = [StreamConfig(height=H, width=W, n_objects=2, seed=s)
            for s in range(S)]
    frames, _, _ = generate_chunk_batched(cfgs, 0, T)
    return frames


def _block_sads(cur, pred):
    d = jnp.abs(cur.astype(jnp.float32) - pred.astype(jnp.float32))
    nby, nbx = cur.shape[0] // MB, cur.shape[1] // MB
    return d.reshape(nby, MB, nbx, MB).sum(axis=(1, 3))


def _assert_enc_equal(a, b, err=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=err)


# ----------------------------------------- vmapped fallback vs scan oracle
@settings(deadline=None, max_examples=10)
@given(nby=st.integers(1, 4), nbx=st.integers(1, 5),
       radius=st.sampled_from([2, 4, 8]), seed=st.integers(0, 9999))
def test_block_sad_fallback_matches_scan_property(nby, nbx, radius, seed):
    """The per-macroblock-window fallback reproduces the legacy whole-frame
    scan over random grids/radii/contents: MVs bit-exact, SADs to fp
    tolerance."""
    H, W = nby * MB, nbx * MB
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cur = jax.random.uniform(k1, (H, W), jnp.float32) * 255
    ref = jnp.roll(cur, (seed % 3 - 1, -(seed % 5 - 2)), (0, 1)) \
        + jax.random.normal(k2, (H, W)) * 1.5
    mv_v, sad_v = block_sad(cur, ref, radius)
    mv_s, sad_s = block_sad_scan(cur, ref, radius)
    np.testing.assert_array_equal(np.asarray(mv_v), np.asarray(mv_s))
    np.testing.assert_allclose(np.asarray(sad_v), np.asarray(sad_s),
                               rtol=1e-6, atol=1e-4)


@settings(deadline=None, max_examples=8)
@given(nby=st.integers(1, 3), nbx=st.integers(1, 4),
       radius=st.sampled_from([2, 4]), period=st.integers(1, 7),
       vertical=st.booleans())
def test_block_sad_fallback_tie_breaking_property(nby, nbx, radius, period,
                                                  vertical):
    """Periodic stripes tie whole bands of candidates; the fallback must
    resolve them first-wins in dy-major order exactly like the scan."""
    H, W = nby * MB, nbx * MB
    ramp = (jnp.arange(H if vertical else W) % period).astype(jnp.float32)
    frame = jnp.tile(ramp[:, None], (1, W)) if vertical \
        else jnp.tile(ramp[None, :], (H, 1))
    mv_v, sad_v = block_sad(frame, frame, radius)
    mv_s, sad_s = block_sad_scan(frame, frame, radius)
    np.testing.assert_array_equal(np.asarray(mv_v), np.asarray(mv_s))
    np.testing.assert_allclose(np.asarray(sad_v), np.asarray(sad_s),
                               rtol=1e-6, atol=1e-4)


@settings(deadline=None, max_examples=8)
@given(radius=st.sampled_from([2, 4, 8]), seed=st.integers(0, 999))
def test_warp_prediction_error_never_exceeds_zero_mv(radius, seed):
    """warp_blocks∘block_sad: motion-compensated prediction error is
    per-block no worse than the zero-MV (no-motion) prediction — the
    (0, 0) candidate is always in the search set, so the argmin can only
    improve on it."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cur = jax.random.uniform(k1, (48, 64), jnp.float32) * 255
    ref = jnp.roll(cur, (seed % 5 - 2, -(seed % 7 - 3)), (0, 1)) \
        + jax.random.normal(k2, (48, 64)) * 3
    mv, best_sad = block_sad(cur, ref, radius)
    pred = warp_blocks(ref, mv)
    zero = warp_blocks(ref, jnp.zeros_like(mv))
    sad_pred = np.asarray(_block_sads(cur, pred))
    sad_zero = np.asarray(_block_sads(cur, zero))
    assert (sad_pred <= sad_zero + 1e-3).all()
    # the search's reported SAD is the SAD of the compensated prediction
    np.testing.assert_allclose(sad_pred, np.asarray(best_sad), atol=1e-2)


@settings(deadline=None, max_examples=8)
@given(T=st.integers(1, 6), split=st.integers(1, 5), seed=st.integers(0, 99))
def test_accumulate_mv_chaining_matches_sequential(T, split, seed):
    """cumsum chaining == sequential composition, and accumulating a
    concatenated MV stream == accumulating the parts with the carry."""
    split = min(split, T)
    mvs = jax.random.randint(jax.random.PRNGKey(seed), (T, 2, 3, 2),
                             -8, 9, jnp.int32)
    acc = np.asarray(accumulate_mv(mvs))
    seq = np.zeros_like(acc)
    run = np.zeros(acc.shape[1:], np.int32)
    for t in range(T):
        run = run + np.asarray(mvs[t])
        seq[t] = run
    np.testing.assert_array_equal(acc, seq)
    a, b = mvs[:split], mvs[split:]
    acc_a = accumulate_mv(a)
    chained = jnp.concatenate([acc_a, acc_a[-1][None] + accumulate_mv(b)]
                              if b.shape[0] else [acc_a], axis=0)
    np.testing.assert_array_equal(np.asarray(chained), acc)


# ------------------------------------------- diamond search (quality contract)
def _translated_pair(field, dy, dx, H, W, margin=MB):
    """cur and an EXACT (dy, dx)-translated ref cut from one oversized
    field — no wraparound, so interior macroblocks have a true zero-SAD
    candidate at (dy, dx).  Border blocks still see edge-replicated
    padding instead of the real field, hence the interior restriction in
    the assertions below."""
    cur = lax.dynamic_slice(field, (margin, margin), (H, W))
    ref = lax.dynamic_slice(field, (margin - dy, margin - dx), (H, W))
    return cur, ref


def _interior(a):
    return np.asarray(a)[1:-1, 1:-1]


@settings(deadline=None, max_examples=10)
@given(nby=st.integers(1, 4), nbx=st.integers(1, 5),
       radius=st.sampled_from([2, 4, 8]), seed=st.integers(0, 9999))
def test_block_sad_diamond_never_beats_exhaustive_property(nby, nbx, radius,
                                                           seed):
    """Diamond probes a SUBSET of the exhaustive candidate set with the
    identical per-candidate SAD expression, so its found SAD is ≥ the
    exhaustive minimum EXACTLY (no fp tolerance), and ≤ its own (0, 0)
    starting point (strict-< updates only improve) — on any content,
    including adversarial noise where the greedy descent traps."""
    H, W = nby * MB, nbx * MB
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cur = jax.random.uniform(k1, (H, W), jnp.float32) * 255
    ref = jnp.roll(cur, (seed % 5 - 2, -(seed % 7 - 3)), (0, 1)) \
        + jax.random.normal(k2, (H, W)) * 4
    _, sad_e = block_sad(cur, ref, radius)
    _, sad_d = block_sad(cur, ref, radius, search="diamond")
    assert (np.asarray(sad_d) >= np.asarray(sad_e)).all()
    sad_zero = np.asarray(_block_sads(cur, ref))   # the (0, 0) candidate
    assert (np.asarray(sad_d) <= sad_zero + 1e-3).all()


@settings(deadline=None, max_examples=12)
@given(radius=st.sampled_from([2, 4, 8]), ringy=st.integers(-1, 1),
       ringx=st.integers(-1, 1), seed=st.integers(0, 9999))
def test_block_sad_diamond_first_ring_translation_exact_property(
        radius, ringy, ringx, seed):
    """A translation on the first diamond ring ({-s0, 0, s0}², s0 the
    largest power of two ≤ R) is found EXACTLY on any non-periodic
    content: the zero-SAD candidate is probed in round one and strict-<
    makes it absorbing.  MVs and SADs equal the exhaustive search
    bit-for-bit (integer-valued frames keep every summation order exact)."""
    s0 = diamond_steps(radius)[0]
    dy, dx = ringy * s0, ringx * s0
    H, W = 64, 96
    field = jnp.round(jax.random.uniform(jax.random.PRNGKey(seed),
                                         (H + 2 * MB, W + 2 * MB)) * 255)
    cur, ref = _translated_pair(field, dy, dx, H, W)
    mv_d, sad_d = block_sad(cur, ref, radius, search="diamond")
    mv_e, sad_e = block_sad(cur, ref, radius)
    assert (_interior(mv_d) == (dy, dx)).all()
    assert (_interior(sad_d) == 0).all()
    np.testing.assert_array_equal(_interior(mv_d), _interior(mv_e))
    np.testing.assert_array_equal(_interior(sad_d), _interior(sad_e))


def test_diamond_candidate_budget():
    """The acceptance contract: ≤ ¼ of the exhaustive candidate count at
    the production radius (37 vs 289 at ±8), and the static schedule halves
    down to a final 1-pel refinement ring at every radius."""
    assert diamond_num_evals(8) * 4 <= 17 * 17
    for radius in (2, 4, 8, 16):
        steps = diamond_steps(radius)
        assert steps[0] * 2 > radius and steps[-1] == 1
        assert all(a == 2 * b for a, b in zip(steps, steps[1:]))
        assert diamond_num_evals(radius) == 1 + 9 * len(steps)


@settings(deadline=None, max_examples=10)
@given(nby=st.integers(1, 3), nbx=st.integers(1, 4),
       radius=st.sampled_from([2, 4, 8]), seed=st.integers(0, 9999),
       bf16=st.booleans())
def test_motion_sad_diamond_kernel_matches_fallback_property(nby, nbx,
                                                             radius, seed,
                                                             bf16):
    """The Pallas diamond kernel replays the fallback's probe schedule
    (same order, same clip, same first-wins) — MVs and SADs bit-exact on
    integer content in BOTH storage dtypes."""
    H, W = nby * MB, nbx * MB
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cur = jnp.round(jax.random.uniform(k1, (H, W)) * 255)
    ref = jnp.round(jnp.clip(jnp.roll(cur, (seed % 3 - 1, seed % 5 - 2),
                                      (0, 1))
                             + jax.random.normal(k2, (H, W)) * 2, 0, 255))
    dt = jnp.bfloat16 if bf16 else None
    mv_f, sad_f = block_sad(cur, ref, radius, search="diamond", dtype=dt)
    # call the kernel entry directly: block_sad's static dispatch routes
    # small/interpret-mode canvases to the traced descent, which would
    # make this parity check compare the fallback with itself
    mv_k, sad_k = motion_sad(cur, ref, radius=radius, dtype=dt,
                             search="diamond")
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_f))
    np.testing.assert_array_equal(np.asarray(sad_k), np.asarray(sad_f))


def test_block_sad_rejects_unknown_search():
    cur = jnp.zeros((32, 32), jnp.float32)
    with pytest.raises(ValueError, match="unknown search strategy"):
        block_sad(cur, cur, 4, search="hexagon")


# ----------------------------------- retiled exhaustive kernel bit-exactness
@settings(deadline=None, max_examples=12)
@given(nby=st.integers(1, 4), nbx=st.integers(1, 5),
       radius=st.sampled_from([2, 4, 8]), seed=st.integers(0, 9999),
       bf16=st.booleans())
def test_motion_sad_kernel_bit_exact_vs_scan_property(nby, nbx, radius,
                                                      seed, bf16):
    """The kernel (multi-row grid steps, rotation-based selection +
    oracle-order winner recompute) reproduces ``block_sad_scan``
    bit-for-bit — MVs including tie-breaks AND SADs — on integer-valued
    (real-video-domain) frames, where every f32 summation order is exact.
    bf16 storage is lossless for 0..255 integers, so even the bf16 kernel
    must match the f32 scan oracle exactly."""
    H, W = nby * MB, nbx * MB
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cur = jnp.round(jax.random.uniform(k1, (H, W)) * 255)
    ref = jnp.round(jnp.clip(jnp.roll(cur, (seed % 5 - 2, seed % 7 - 3),
                                      (0, 1))
                             + jax.random.normal(k2, (H, W)) * 3, 0, 255))
    mv_s, sad_s = block_sad_scan(cur, ref, radius)
    mv_k, sad_k = motion_sad(cur, ref, radius=radius, interpret=True,
                             dtype=jnp.bfloat16 if bf16 else None)
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_s))
    np.testing.assert_array_equal(np.asarray(sad_k), np.asarray(sad_s))


@settings(deadline=None, max_examples=8)
@given(nby=st.integers(1, 3), nbx=st.integers(1, 4),
       radius=st.sampled_from([2, 4]), period=st.integers(1, 7),
       vertical=st.booleans())
def test_motion_sad_kernel_tie_breaking_property(nby, nbx, radius, period,
                                                 vertical):
    """Periodic stripes tie whole bands of candidates; the kernel's
    selection loop must resolve them first-wins in dy-major order exactly
    like the scan oracle — the case a fast-but-reordered reduce would
    silently break."""
    H, W = nby * MB, nbx * MB
    ramp = (jnp.arange(H if vertical else W) % period).astype(jnp.float32)
    frame = jnp.tile(ramp[:, None], (1, W)) if vertical \
        else jnp.tile(ramp[None, :], (H, 1))
    mv_k, sad_k = motion_sad(frame, frame, radius=radius, interpret=True)
    mv_s, sad_s = block_sad_scan(frame, frame, radius)
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_s))
    np.testing.assert_array_equal(np.asarray(sad_k), np.asarray(sad_s))


@pytest.mark.parametrize("H,W,radius", [(64, 96, 8), (80, 112, 4),
                                        (32, 48, 2)])
def test_motion_sad_kernel_matches_scan_continuous(H, W, radius):
    """Deterministic continuous-f32 fixtures: MVs bit-exact, SADs to fp
    tolerance (the winner recompute replays the oracle's per-block reduce
    order, so in practice these are bit-equal too)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    cur = jax.random.uniform(k1, (H, W), jnp.float32) * 255
    ref = jnp.roll(cur, (3, -2), (0, 1)) + jax.random.normal(k2, (H, W)) * 2
    mv_s, sad_s = block_sad_scan(cur, ref, radius)
    mv_k, sad_k = motion_sad(cur, ref, radius=radius, interpret=True)
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_s))
    np.testing.assert_allclose(np.asarray(sad_k), np.asarray(sad_s),
                               rtol=1e-6, atol=1e-4)


# ------------------------------------------------------ encoder edge cases
def test_encode_single_frame_chunk_is_iframe_only():
    frames = _streams(1, T=1)[0]
    enc = encode_chunk(frames, CFG)
    assert enc.recon.shape == frames.shape
    assert enc.mv.shape == (1, 2, 3, 2) and (np.asarray(enc.mv) == 0).all()
    assert enc.bits.shape == (1,) and float(enc.bits[0]) > 0
    assert float(enc.frame_diff[0]) == 0.0
    # batched T=1 stays consistent
    encb = encode_chunk_batched(_streams(2, T=1), CFG)
    assert encb.recon.shape == (2, 1, 32, 48)


@pytest.mark.parametrize("H,W", [(48, 80), (128, 64), (32, 144)])
def test_encode_non_square_frames(H, W):
    sc = StreamConfig(height=H, width=W, n_objects=3, seed=1)
    frames, _, _ = generate_chunk(None, sc, 0, 3)
    enc = encode_chunk(frames, CFG)
    enc_k = encode_chunk(frames, VideoCodecConfig(
        quality=50.0, search_radius=4, use_kernel=True))
    assert enc.mv.shape == (3, H // MB, W // MB, 2)
    _assert_enc_equal(enc, enc_k, err=f"kernel parity at {H}x{W}")


def test_gop_boundary_alignment():
    """Chunks cut at GOP boundaries are self-contained: the tail chunk of
    a continuous scene encodes identically whether its frames come from a
    long render or a t0-offset render (producer continuity), and frame 0
    of every chunk is an I-frame (zero MV row)."""
    sc = StreamConfig(height=32, width=48, n_objects=2, seed=5)
    T = 4
    long, _, _ = generate_chunk(None, sc, 0, 2 * T)
    tail, _, _ = generate_chunk(None, sc, T, T)
    np.testing.assert_array_equal(np.asarray(long[T:]), np.asarray(tail))
    _assert_enc_equal(encode_chunk(long[T:], CFG), encode_chunk(tail, CFG),
                      err="GOP-aligned tail chunk diverged")
    for chunk in (long[:T], tail):
        assert (np.asarray(encode_chunk(chunk, CFG).mv[0]) == 0).all()


@pytest.mark.parametrize("S", [1, 3, 4, 8])
def test_encode_batched_matches_per_stream(S):
    frames = _streams(S)
    enc = encode_chunk_batched(frames, CFG)
    for s in range(S):
        _assert_enc_equal(jax.tree.map(lambda x: x[s], enc),
                          encode_chunk(frames[s], CFG),
                          err=f"stream {s} of {S}")


def test_shard_encode_single_device_matches_oracle():
    """The sharded wrapper degrades to the vmap oracle on a 1-extent mesh
    — parity here guards the zero-padding/unpadding plumbing."""
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    frames = _streams(3)
    run = shard_encode(mesh, SINGLE_POD_RULES, cfg=CFG)
    _assert_enc_equal(run(frames), encode_chunk_batched(frames, CFG))


# --------------------------------------------- heterogeneous ladder batching
def _mixed_ladder_lrs(levels=(4, 3, 2), H=96, W=160, T=4):
    """Per-stream LR chunks at MIXED ladder rungs from one HD source shape
    (the 1080p/720p/480p analogue at sim scale)."""
    lrs, quals = [], []
    for s, level in enumerate(levels):
        raw, _, _ = generate_chunk(None, StreamConfig(
            height=H, width=W, n_objects=3, seed=s), 0, T)
        lrs.append(downscale(raw, QUALITY_LADDER[level].scale))
        quals.append(QUALITY_LADDER[level].quality)
        assert lrs[-1].shape[1:] == ladder_lr_shape(level, H, W)
    return lrs, jnp.asarray(quals, jnp.float32)


def _assert_ladder_lane_equal(lane, single, h, w, err=""):
    """Valid-extent bit-exactness of one padded lane vs the unpadded
    single-stream encode (padded blocks are zeroed / edge-replicated)."""
    Hp, Wp = lane.recon.shape[1:]
    np.testing.assert_array_equal(np.asarray(lane.recon[:, :h, :w]),
                                  np.asarray(single.recon), err_msg=err)
    np.testing.assert_array_equal(
        np.asarray(lane.mv[:, :h // MB, :w // MB]), np.asarray(single.mv),
        err_msg=err)
    bm = ((np.arange(Hp // 8)[:, None] < h // 8)
          & (np.arange(Wp // 8)[None, :] < w // 8)).reshape(-1)
    np.testing.assert_array_equal(np.asarray(lane.residual_q)[:, bm],
                                  np.asarray(single.residual_q), err_msg=err)
    for field in ("qtab", "bits", "residual_mag", "frame_diff"):
        np.testing.assert_array_equal(np.asarray(getattr(lane, field)),
                                      np.asarray(getattr(single, field)),
                                      err_msg=f"{err}: {field}")


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["vmapped_fallback", "kernel"])
def test_encode_ladder_batched_mixed_rungs_bit_exact(use_kernel):
    """One padded dispatch over a 3-rung mixed batch is lane-for-lane
    bit-exact vs sequentially encoding each stream unpadded at its own
    rung — rate model (bits), codec features and recon included."""
    lrs, quals = _mixed_ladder_lrs()
    frames, extents = pad_ladder_batch(lrs)
    cfg = VideoCodecConfig(quality=50.0, search_radius=4,
                           use_kernel=use_kernel)
    enc = encode_chunk_ladder_batched(frames, extents, quals, cfg)
    for s, lr in enumerate(lrs):
        single = encode_chunk(lr, VideoCodecConfig(
            quality=float(quals[s]), search_radius=4, use_kernel=use_kernel))
        lane = jax.tree.map(lambda x: x[s], enc)
        _assert_ladder_lane_equal(lane, single, *lr.shape[1:],
                                  err=f"mixed-rung lane {s}")


def test_encode_ladder_batched_padding_content_irrelevant():
    """Garbage in the padded margin must not leak into any output: the
    masked encode re-edge-replicates the canvas in-trace."""
    lrs, quals = _mixed_ladder_lrs(levels=(4, 2))
    frames, extents = pad_ladder_batch(lrs)
    noise = jax.random.uniform(jax.random.PRNGKey(9), frames.shape) * 255
    h, w = lrs[1].shape[1:]
    poisoned = frames.at[1, :, h:, :].set(noise[1, :, h:, :])
    poisoned = poisoned.at[1, :, :, w:].set(noise[1, :, :, w:])
    cfg = VideoCodecConfig(quality=50.0, search_radius=4)
    a = encode_chunk_ladder_batched(frames, extents, quals, cfg)
    b = encode_chunk_ladder_batched(poisoned, extents, quals, cfg)
    _assert_enc_equal(a, b, err="padding content leaked into the encode")


def test_encode_ladder_batched_full_extent_matches_batched():
    """Uniform rungs through the ladder path == the homogeneous vmap
    (full-extent masking is the identity transformation)."""
    frames = _streams(3)
    S = frames.shape[0]
    extents = jnp.tile(jnp.asarray(frames.shape[2:], jnp.int32), (S, 1))
    quals = jnp.full((S,), CFG.quality, jnp.float32)
    enc = encode_chunk_ladder_batched(frames, extents, quals, CFG)
    _assert_enc_equal(enc, encode_chunk_batched(frames, CFG),
                      err="full-extent ladder encode diverged from vmap")


def test_encode_ladder_batched_padded_outputs_deterministic():
    """Padded MVs/coefficients are zero and the padded recon margin is the
    edge replication of the valid region — downstream consumers can rely
    on the canvas contract."""
    lrs, quals = _mixed_ladder_lrs(levels=(4, 2))
    frames, extents = pad_ladder_batch(lrs)
    enc = encode_chunk_ladder_batched(
        frames, extents, quals, VideoCodecConfig(quality=50.0,
                                                 search_radius=4))
    h, w = lrs[1].shape[1:]
    mv = np.asarray(enc.mv[1])
    assert (mv[:, h // MB:, :] == 0).all() and (mv[:, :, w // MB:] == 0).all()
    recon = np.asarray(enc.recon[1])
    np.testing.assert_array_equal(recon[:, h:, :],
                                  np.broadcast_to(recon[:, h - 1:h, :],
                                                  recon[:, h:, :].shape))
    np.testing.assert_array_equal(recon[:, :, w:],
                                  np.broadcast_to(recon[:, :, w - 1:w],
                                                  recon[:, :, w:].shape))


# ------------------------------------------------------------ bf16 variants
def test_motion_sad_bf16_kernel_matches_bf16_fallback():
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    cur = jax.random.uniform(k1, (64, 96), jnp.float32) * 255
    ref = jnp.roll(cur, (2, -1), (0, 1)) + jax.random.normal(k2, (64, 96))
    mv_f, sad_f = block_sad(cur, ref, 4, dtype=jnp.bfloat16)
    mv_k, sad_k = block_sad(cur, ref, 4, use_kernel=True,
                            dtype=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(mv_f), np.asarray(mv_k))
    np.testing.assert_allclose(np.asarray(sad_f), np.asarray(sad_k),
                               rtol=1e-6, atol=1e-3)


def test_motion_sad_bf16_close_to_f32():
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    cur = jax.random.uniform(k1, (64, 96), jnp.float32) * 255
    ref = jnp.roll(cur, (3, -2), (0, 1)) + jax.random.normal(k2, (64, 96))
    mv32, _ = block_sad(cur, ref, 8)
    mvbf, _ = block_sad(cur, ref, 8, dtype=jnp.bfloat16)
    # bf16 rounding may move near-tied candidates, but the dominant
    # motion must survive quantization
    agree = (np.asarray(mv32) == np.asarray(mvbf)).all(axis=-1).mean()
    assert agree >= 0.9, f"bf16 search agrees on only {agree:.0%} of blocks"


def test_qtransfer_bf16_within_tolerance():
    from repro.kernels.qtransfer.ops import qtransfer
    from repro.kernels.qtransfer.ref import qtransfer_ref
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    anchor = jax.random.uniform(ks[0], (64, 96), jnp.float32) * 255
    mv = jax.random.randint(ks[1], (4, 6, 2), -8, 9, jnp.int32)
    resid = jax.random.normal(ks[2], (64, 96), jnp.float32) * 8
    o = qtransfer(anchor, mv, resid, interpret=True, dtype=jnp.bfloat16)
    assert o.dtype == jnp.bfloat16
    r = qtransfer_ref(anchor, mv, resid)
    # bf16 has ~8 bits of mantissa: |err| <= ~1 grey level at 255 scale
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(r),
                               atol=1.5)


def test_video_codec_config_dtype_policy():
    assert VideoCodecConfig().search_dtype is None
    assert VideoCodecConfig(dtype="bfloat16").search_dtype == jnp.bfloat16
    assert VideoCodecConfig(dtype="bf16").search_dtype == jnp.bfloat16
    hash(VideoCodecConfig(use_kernel=True, dtype="bfloat16"))  # stays static


# --------------------------------------------------- forced 4-device child
def test_spawns_multidevice_encoder_child():
    """Driver: re-run ONLY this file's ``forced``-named tests under 4
    forced CPU devices (mirrors test_stream_sharding.py)."""
    if _FORCED:
        pytest.skip("already inside the forced multi-device child")
    r = conftest.forced_multidevice_run(
        "tests/test_fused_encoder.py", extra_args=["-k", "forced"])
    assert r.returncode == 0, (
        f"forced multi-device encoder child failed\n--- stdout ---\n"
        f"{r.stdout}\n--- stderr ---\n{r.stderr}")
    assert "passed" in r.stdout


@forced_only
@pytest.mark.parametrize("S", [1, 3, 4, 8])
def test_forced_encode_bit_exact_vs_vmap_oracle(S):
    """Mesh-sharded batched encode equals the single-device vmap oracle
    bit-for-bit — including S=1 and S=3, which zero-pad the stream axis up
    to the mesh extent and drop the padded lanes on exit."""
    mesh = jax.make_mesh((4,), ("data",))
    assert stream_shard_count(mesh, SINGLE_POD_RULES) == 4
    frames = _streams(S)
    run = shard_encode(mesh, SINGLE_POD_RULES, cfg=CFG)
    sharded = run(frames)
    assert sharded.recon.shape[0] == S
    _assert_enc_equal(sharded, encode_chunk_batched(frames, CFG),
                      err=f"sharded encode diverged at S={S}")


@forced_only
def test_forced_encode_two_dimensional_mesh_parity():
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    assert stream_shard_count(mesh, SINGLE_POD_RULES_DP) == 4
    frames = _streams(6)
    run = shard_encode(mesh, SINGLE_POD_RULES_DP, cfg=CFG)
    _assert_enc_equal(run(frames), encode_chunk_batched(frames, CFG),
                      err="2-D mesh encode diverged")
