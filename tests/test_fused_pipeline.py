"""Fused analytics path: motion-SAD kernel parity vs the scan oracle,
single-jit chunk execution parity vs the legacy host-orchestrated path,
and the batched runtime's dispatch/carry invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.codec.motion import block_sad
from repro.kernels.motion_sad.ops import motion_sad
from repro.kernels.motion_sad.ref import motion_sad_ref

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------- motion_sad
@pytest.mark.parametrize("H,W,radius", [
    (64, 96, 4), (64, 96, 8), (64, 96, 16),
    (48, 160, 8), (128, 64, 8), (32, 32, 4),
])
def test_motion_sad_matches_scan_oracle(H, W, radius):
    ks = jax.random.split(KEY, 2)
    cur = jax.random.uniform(ks[0], (H, W), jnp.float32) * 255
    ref = jnp.roll(cur, (3, -2), (0, 1)) \
        + jax.random.normal(ks[1], (H, W)) * 2
    mv_k, sad_k = motion_sad(cur, ref, radius=radius, interpret=True)
    mv_o, sad_o = motion_sad_ref(cur, ref, radius)
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_o))
    np.testing.assert_allclose(np.asarray(sad_k), np.asarray(sad_o),
                               rtol=1e-6)


@pytest.mark.parametrize("radius", [4, 8])
def test_motion_sad_tie_breaking_matches_oracle(radius):
    # constant frame: every candidate SAD is identical — both paths must
    # pick the FIRST candidate in dy-major order, i.e. (-R, -R)
    cur = jnp.full((32, 48), 9.0, jnp.float32)
    mv_k, _ = motion_sad(cur, cur, radius=radius, interpret=True)
    mv_o, _ = motion_sad_ref(cur, cur, radius)
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_o))
    assert (np.asarray(mv_k) == -radius).all()
    # horizontal stripes: exact ties along dx at fixed dy
    stripes = jnp.tile((jnp.arange(32) % 7).astype(jnp.float32)[:, None],
                       (1, 48))
    mv_k, _ = motion_sad(stripes, stripes, radius=radius, interpret=True)
    mv_o, _ = motion_sad_ref(stripes, stripes, radius)
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_o))


def test_motion_sad_recovers_known_shift():
    """pred(y) = ref(y + mv): for ref = roll(cur, s), interior MVs = s."""
    cur = jax.random.uniform(KEY, (64, 96), jnp.float32) * 255
    s = (3, -2)
    ref = jnp.roll(cur, s, (0, 1))
    mv, sad = motion_sad(cur, ref, radius=8, interpret=True)
    mv = np.asarray(mv)
    assert (mv[1:-1, 1:-1, 0] == s[0]).all()
    assert (mv[1:-1, 1:-1, 1] == s[1]).all()
    assert float(jnp.max(sad[1:-1, 1:-1])) < 1e-3


def test_block_sad_use_kernel_flag_dispatches():
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    cur = jax.random.uniform(ks[0], (48, 64), jnp.float32) * 255
    ref = jax.random.uniform(ks[1], (48, 64), jnp.float32) * 255
    mv_a, sad_a = block_sad(cur, ref, 4)
    mv_b, sad_b = block_sad(cur, ref, 4, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(mv_a), np.asarray(mv_b))
    np.testing.assert_allclose(np.asarray(sad_a), np.asarray(sad_b),
                               rtol=1e-6)


@settings(deadline=None, max_examples=10)
@given(nby=st.integers(1, 4), nbx=st.integers(1, 5),
       radius=st.sampled_from([2, 4, 8]), seed=st.integers(0, 9999))
def test_motion_sad_property_random_shapes(nby, nbx, radius, seed):
    """Kernel-vs-oracle parity over random macroblock grids, search radii
    and contents: MVs bit-exact, SADs to fp tolerance.  Runs under the
    real hypothesis when installed, else the deterministic shim."""
    H, W = nby * 16, nbx * 16
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cur = jax.random.uniform(k1, (H, W), jnp.float32) * 255
    ref = jnp.roll(cur, (seed % 3 - 1, -(seed % 5 - 2)), (0, 1)) \
        + jax.random.normal(k2, (H, W)) * 1.5
    mv_k, sad_k = motion_sad(cur, ref, radius=radius, interpret=True)
    mv_o, sad_o = motion_sad_ref(cur, ref, radius)
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_o))
    np.testing.assert_allclose(np.asarray(sad_k), np.asarray(sad_o),
                               rtol=1e-6, atol=1e-4)


@settings(deadline=None, max_examples=8)
@given(nby=st.integers(1, 3), nbx=st.integers(1, 4),
       radius=st.sampled_from([2, 4]), period=st.integers(1, 7),
       vertical=st.booleans())
def test_motion_sad_property_tie_breaking(nby, nbx, radius, period,
                                          vertical):
    """Periodic stripe patterns produce exact SAD ties along whole bands
    of candidate offsets; both paths must resolve them first-wins in
    dy-major order (period=1 is the all-ties constant frame)."""
    H, W = nby * 16, nbx * 16
    ramp = (jnp.arange(H if vertical else W) % period).astype(jnp.float32)
    frame = jnp.tile(ramp[:, None], (1, W)) if vertical \
        else jnp.tile(ramp[None, :], (H, 1))
    mv_k, sad_k = motion_sad(frame, frame, radius=radius, interpret=True)
    mv_o, sad_o = motion_sad_ref(frame, frame, radius)
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_o))
    np.testing.assert_allclose(np.asarray(sad_k), np.asarray(sad_o),
                               rtol=1e-6, atol=1e-4)


def test_motion_sad_batched_entry():
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    cur = jax.random.uniform(ks[0], (3, 32, 32), jnp.float32) * 255
    ref = jax.random.uniform(ks[1], (3, 32, 32), jnp.float32) * 255
    mv, sad = motion_sad(cur, ref, radius=4, interpret=True)
    assert mv.shape == (3, 2, 2, 2) and sad.shape == (3, 2, 2)


# ----------------------------------------------------- fused chunk pipeline
def _setup_chunk(seed=0, T=4):
    from repro.core.hybrid_encoder import encode_hybrid
    from repro.models import detection as D
    from repro.sim.video_source import StreamConfig, generate_chunk
    frames, gtb, gtv = generate_chunk(
        jax.random.PRNGKey(seed),
        StreamConfig(height=64, width=96, n_objects=3), 0, T)
    det_cfg = D.TinyDetectorConfig()
    params = D.init(jax.random.PRNGKey(1), det_cfg)
    packet = encode_hybrid(np.asarray(frames), 8000.0, 0.05, 0.1)
    return packet, params, det_cfg, gtb, gtv


def test_anchor_index_matches_python_loop():
    from repro.core.hybrid_decoder import anchor_index
    rng = np.random.default_rng(0)
    for _ in range(5):
        types = rng.choice([1, 2, 3], size=12)
        ref = np.zeros(12, np.int64)
        last = 0
        for i in range(12):
            if types[i] == 1:
                last = i
            ref[i] = last
        got = np.asarray(anchor_index(jnp.asarray(types)))
        np.testing.assert_array_equal(got, ref)


def test_decode_execute_chunk_matches_legacy():
    from repro.core.hybrid_decoder import (decode_and_execute,
                                           decode_and_execute_fused)
    packet, params, det_cfg, gtb, gtv = _setup_chunk()
    a = decode_and_execute(packet, params, det_cfg, gtb, gtv,
                           bw_kbps=8000.0, queue_delay=0.01)
    b = decode_and_execute_fused(packet, params, det_cfg, gtb, gtv,
                                 bw_kbps=8000.0, queue_delay=0.01)
    np.testing.assert_allclose(a.boxes, b.boxes, atol=1e-2)
    np.testing.assert_allclose(a.scores, b.scores, atol=1e-4)
    np.testing.assert_allclose(a.f1, b.f1, atol=1e-5)
    assert a.latency == pytest.approx(b.latency, rel=1e-5)
    assert a.t_comp == pytest.approx(b.t_comp, rel=1e-5)


def test_decode_execute_chunk_is_one_jit_boundary():
    from repro.core import hybrid_decoder as HD
    # the public callable IS the jit wrapper (lower/trace API present) …
    assert hasattr(HD.decode_execute_chunk, "lower")
    # … and its traced body never leaves jax: no np.asarray / Python
    # per-frame loops inside (they would fail under tracing)
    packet, params, det_cfg, gtb, gtv = _setup_chunk()
    out = HD.decode_execute_chunk(
        packet.video, jnp.asarray(packet.types),
        jnp.asarray(packet.anchor_hd), jnp.asarray(gtb), jnp.asarray(gtv),
        params, det_cfg, bw_kbps=8000.0, total_bits=packet.total_bits)
    assert all(isinstance(v, jax.Array) for v in out.values())


def test_decode_execute_batched_matches_per_stream():
    from repro.core.hybrid_decoder import (decode_execute_batched,
                                           decode_execute_chunk)
    p0, params, det_cfg, gtb0, gtv0 = _setup_chunk(seed=0)
    p1, _, _, gtb1, gtv1 = _setup_chunk(seed=5)
    stack = lambda a, b: jnp.stack([jnp.asarray(a), jnp.asarray(b)])
    enc = jax.tree.map(lambda *xs: jnp.stack(xs), p0.video, p1.video)
    out = decode_execute_batched(
        enc, stack(p0.types, p1.types), stack(p0.anchor_hd, p1.anchor_hd),
        stack(gtb0, gtb1), stack(gtv0, gtv1), params, det_cfg,
        bw_kbps=jnp.asarray([8000.0, 8000.0]),
        queue_delay=jnp.zeros(2),
        total_bits=jnp.asarray([p0.total_bits, p1.total_bits]))
    for i, (p, gb, gv) in enumerate([(p0, gtb0, gtv0), (p1, gtb1, gtv1)]):
        one = decode_execute_chunk(
            p.video, jnp.asarray(p.types), jnp.asarray(p.anchor_hd),
            jnp.asarray(gb), jnp.asarray(gv), params, det_cfg,
            bw_kbps=8000.0, total_bits=p.total_bits)
        np.testing.assert_allclose(np.asarray(out["boxes"][i]),
                                   np.asarray(one["boxes"]), atol=1e-3)
        np.testing.assert_allclose(np.asarray(out["f1"][i]),
                                   np.asarray(one["f1"]), atol=1e-5)


def test_video_codec_config_stays_hashable():
    """encode_chunk is jitted with the config as a static argument at its
    production call site (hybrid_encoder) and in benchmarks; an unhashable
    config would fail there with an opaque jit TypeError."""
    from repro.codec.video_codec import VideoCodecConfig
    hash(VideoCodecConfig())


# ------------------------------------------------------------ reuse carry
def test_reuse_chunk_init_carry():
    from repro.core.reuse import reuse_chunk, shift_boxes
    T, N = 3, 2
    types = jnp.full((T,), 3, jnp.int32)
    mvs = jnp.zeros((T, 4, 6, 2), jnp.int32).at[..., 0].set(2)
    infer_b = jnp.zeros((T, N, 4), jnp.float32)
    infer_s = jnp.zeros((T, N), jnp.float32)
    init_b = jnp.asarray([[32.0, 48.0, 16.0, 16.0]] * N)
    init_s = jnp.asarray([0.9] * N)
    boxes, scores = reuse_chunk(types, mvs, infer_b, infer_s,
                                init_boxes=init_b, init_scores=init_s)
    exp0, _ = shift_boxes(init_b, init_s, mvs[0])
    np.testing.assert_allclose(np.asarray(boxes[0]), np.asarray(exp0),
                               atol=1e-5)
    # codec mv dy=+2 => object shifts -2 per frame
    np.testing.assert_allclose(np.asarray(boxes[:, 0, 0]),
                               [30.0, 28.0, 26.0], atol=1e-4)
    assert float(scores[0, 0]) == pytest.approx(0.9)
    # default (no carry) preserves the legacy within-chunk behavior:
    # the carry seeds from infer_boxes[0], shifted by mvs[0]
    b2, _ = reuse_chunk(types, mvs, infer_b, infer_s)
    legacy0, _ = shift_boxes(infer_b[0], infer_s[0], mvs[0])
    np.testing.assert_allclose(np.asarray(b2[0]), np.asarray(legacy0),
                               atol=1e-5)


# -------------------------------------------------------------- runtime
def test_runtime_one_padded_dispatch_per_chunk():
    from repro.serving.runtime import EdgeRuntime
    from repro.serving.scheduler import ServingConfig
    packet, params, det_cfg, _, _ = _setup_chunk()
    cfg = ServingConfig(n_streams=1, batch_size=8)
    rt = EdgeRuntime(cfg, params, det_cfg)
    calls = []
    inner = rt._infer
    rt._infer = lambda frames: (calls.append(frames.shape), inner(frames))[1]
    rt.process_chunk(0, 0, packet)
    n_infer = int((packet.types != 3).sum())
    if n_infer:
        assert len(calls) == 1                    # one dispatch per chunk
        assert calls[0][0] % cfg.batch_size == 0  # padded, fixed shape set


def test_runtime_pipelines_match_decode_execute_chunk():
    """The runtime serves the reference decode's pipelines: a chunk with
    anchor, quality-transfer and reuse frames comes back with the
    packet's types and the detections of ``decode_execute_chunk`` —
    pipeline-② rows are quality-transferred from their anchor, not the
    plain upscaled LR frame."""
    from repro.core.hybrid_decoder import decode_execute_chunk
    from repro.serving.runtime import EdgeRuntime
    from repro.serving.scheduler import ServingConfig
    packet, params, det_cfg, gtb, gtv = _setup_chunk(T=6)
    pk = dataclasses.replace(
        packet, types=np.asarray([1, 2, 2, 3, 2, 3], packet.types.dtype))
    rt = EdgeRuntime(ServingConfig(n_streams=1), params, det_cfg)
    boxes, scores, types = rt.process_chunk(0, 0, pk)
    ref = decode_execute_chunk(
        pk.video, jnp.asarray(pk.types), jnp.asarray(pk.anchor_hd), gtb,
        gtv, params, det_cfg, bw_kbps=8000.0, total_bits=pk.total_bits)
    np.testing.assert_array_equal(types, pk.types)
    # the detector sees the two routes' frames in different batches, and
    # XLA:CPU conv numerics depend on the batch size
    np.testing.assert_allclose(boxes, np.asarray(ref["boxes"]), atol=1e-3)
    np.testing.assert_allclose(scores, np.asarray(ref["scores"]), atol=1e-5)


def test_runtime_deep_overload_falls_back_to_full_reuse():
    """When even anchors-only would blow the latency budget and a carry
    exists, the whole chunk runs on pipeline ③ through the REAL admission
    path (no hand-built packet)."""
    from repro.serving.runtime import EdgeRuntime
    from repro.serving.scheduler import ServingConfig
    packet, params, det_cfg, _, _ = _setup_chunk()
    cfg = ServingConfig(n_streams=1, gpu_capacity_fps=0.5,
                        latency_budget=1.0)   # admits nothing
    rt = EdgeRuntime(cfg, params, det_cfg)
    # chunk 0: no carry yet -> anchors are kept even under overload
    _, _, t0 = rt.process_chunk(0, 0, packet)
    assert (t0 == np.where(packet.types == 2, 3, packet.types)).all()
    assert (t0 == 1).sum() >= 1
    # chunk 1: carry exists -> full fallback to reuse, zero dispatches
    calls = []
    inner = rt._infer
    rt._infer = lambda f: (calls.append(1), inner(f))[1]
    _, _, t1 = rt.process_chunk(0, 1, packet)
    assert (t1 == 3).all()
    assert calls == []
    assert rt.deferred == 2


def test_runtime_carries_boxes_across_chunks():
    from repro.core.reuse import shift_boxes
    from repro.core.hybrid_decoder import _upscale_mvs
    from repro.serving.runtime import EdgeRuntime
    from repro.serving.scheduler import ServingConfig
    packet, params, det_cfg, _, _ = _setup_chunk()
    rt = EdgeRuntime(ServingConfig(n_streams=1), params, det_cfg)
    rt.process_chunk(0, 0, packet)
    prev = rt.streams[0]
    assert prev.last_boxes is not None
    # second chunk forced to all-reuse: no inference happens, so frame 0
    # must be the previous chunk's last boxes shifted by mv[0]
    p2 = dataclasses.replace(packet, types=np.full_like(packet.types, 3))
    H, W = packet.anchor_hd.shape[1:]
    mvs_hd = np.asarray(_upscale_mvs(packet.video.mv, (H, W)))
    exp0, _ = shift_boxes(jnp.asarray(prev.last_boxes),
                          jnp.asarray(prev.last_scores),
                          jnp.asarray(mvs_hd[0]))
    boxes, scores, types = rt.process_chunk(0, 1, p2)
    assert (types == 3).all()
    np.testing.assert_allclose(boxes[0], np.asarray(exp0), atol=1e-4)
    # stream state advanced to the new chunk's last frame
    np.testing.assert_allclose(rt.streams[0].last_boxes, boxes[-1],
                               atol=1e-6)
