"""Fused encode->decode round-trip: one chunk, one jit (ISSUE 4 tentpole).

BiSwift's end-to-end claim is that the adaptive hybrid codec plus the
multi-level pipelines keep 9+ concurrent streams real-time on one edge
GPU.  In this reproduction that means the whole camera->edge loop —
ladder downscale, video encode, Eq. 3 frame classification, JPEG anchor
encode, the rate/latency model, and the 3-pipeline decode-execute with
the detector backend — should trace as ONE program instead of two
separately-jitted halves stitched together by host Python:

  * ``roundtrip_chunk``         — one stream, one chunk, one module-level
    jit: source HD frames in, HD detections + accuracy + latency out.
  * ``roundtrip_batched``       — vmap over a homogeneous-signature
    stream set (same HD shape, same ladder rung).
  * ``roundtrip_ladder_batched``— MIXED ladder rungs in one dispatch: the
    per-stream static rungs fix each LR shape, streams pad onto a common
    LR canvas, and the heterogeneous-ladder masked encode plus the
    extent-aware decode keep every lane bit-exact vs its own
    single-stream round trip.
  * ``roundtrip_oracle``        — the compose-the-two-jits reference
    (module-level ``encode_chunk`` jit + host glue + ``decode_execute_chunk``
    jit).  ``tests/test_roundtrip.py`` holds the f32 bit-exactness
    contract between the oracle and all three fused forms; the
    mesh-sharded twin is ``repro.distributed.stream_sharding.shard_roundtrip``.

Static vs traced: the ladder rung (it fixes the LR shapes) lives in
``RoundtripConfig`` and is a static jit argument; thresholds (tr1, tr2),
bandwidth and queue delay are traced scalars, so the controller can
sweep them without recompiling.  The anchor JPEG quality is EITHER
static (``anchor_search=False``: pinned to ``cfg.anchor_quality``,
byte-identical to the pre-search trace) OR traced
(``anchor_search=True``: every frame is encoded at every rung of
``ANCHOR_QUALITY_LADDER`` in one masked sweep with static shapes, bits
are charged per rung through ``entropy_bits``, and a traced argmax picks
the highest rung whose per-anchor share of the chunk's bandwidth budget
fits — so ``bw_kbps`` can vary chunk-to-chunk without retracing).

Semantics note vs ``hybrid_encoder.encode_hybrid``: the legacy host
encoder searches the JPEG quality ladder and demotes anchors when the
budget runs out — both data-dependent host decisions.  The fused round
trip keeps the pure Eq. 3 classification inside the trace; with
``anchor_search`` on, the quality search moves inside too (same budget
arithmetic as ``encode_hybrid``: ``bw_kbps * 1000 * T/fps`` minus video
bits, split evenly across anchors), leaving anchor demotion as the one
remaining host-side decision.  Anchor bits are charged through the same
``entropy_bits`` rate model either way.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.codec import blockdct as B
from repro.codec.image_codec import (ANCHOR_QUALITY_LADDER, budget_rung,
                                     jpeg_encode_decode, ladder_sweep,
                                     quality_for_budget)
from repro.codec.rate_model import QUALITY_LADDER, downscale, ladder_lr_shape
from repro.codec.video_codec import (VideoCodecConfig, _encode_chunk,
                                     _encode_ladder_batch, encode_chunk)
from repro.core.classification import classify_frames
from repro.core.hybrid_decoder import (PipelineCosts, _execute_chunk,
                                       decode_execute_chunk)
from repro.models.detection import TinyDetectorConfig

f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class RoundtripConfig:
    """Static (hashable) half of the round-trip signature.

    ``level`` is the bitrate-ladder rung (§VI-A): it decides the LR shape
    and the codec quality, so it must be static.  ``codec.quality`` is
    overridden by the rung's quality — set ``use_kernel``/``dtype`` there
    to pick the search variant.  ``roi`` (a ``repro.core.roi.RoiConfig``)
    turns on ROI-gated inference inside the fused trace: the detector
    runs only on the top-K packed region patches.  ``anchor_search``
    switches the anchor quality from the static ``anchor_quality`` pin to
    the in-trace budget search over ``ANCHOR_QUALITY_LADDER`` (the flag
    itself is static — off-mode traces are byte-identical to pre-search
    builds; ``anchor_quality`` remains as the off-mode pin)."""
    level: int = 2
    codec: VideoCodecConfig = VideoCodecConfig()
    anchor_quality: float = 70.0
    det_cfg: TinyDetectorConfig = TinyDetectorConfig()
    costs: PipelineCosts = PipelineCosts()
    fps: float = 30.0
    roi: object | None = None
    anchor_search: bool = False

    def codec_for(self, level: int | None = None) -> VideoCodecConfig:
        ql = QUALITY_LADDER[self.level if level is None else level]
        return dataclasses.replace(self.codec, quality=ql.quality)


def anchor_budget_bits(bw_kbps, video_bits, n_anchors, n_frames: int,
                       fps: float):
    """Per-anchor bit budget: the chunk's bandwidth allowance
    (``bw_kbps * 1000 * T/fps``, the ``encode_hybrid`` arithmetic) minus
    the video-layer bits, split evenly across the chunk's anchors.  All
    of bw_kbps / video_bits / n_anchors may be traced — this is the
    shared budget expression of the fused search and the host oracle, so
    the two agree bit-for-bit by construction."""
    chunk_bits = jnp.asarray(bw_kbps, f32) * 1000.0 * (n_frames / fps)
    spare = jnp.maximum(chunk_bits - jnp.asarray(video_bits, f32), 0.0)
    return spare / jnp.maximum(jnp.asarray(n_anchors, f32), 1.0)


def _roundtrip_execute(raw, enc, lr_extent, gt_boxes, gt_valid,
                       detector_params, tr1, tr2, bw_kbps, queue_delay,
                       cfg: RoundtripConfig) -> dict:
    """Post-encode half of the trace: classification, anchors, rate model,
    3-pipeline execution.  Shared by every fused form (``lr_extent`` is
    the valid LR extent for heterogeneous-ladder lanes, None otherwise).
    """
    # seq_sum everywhere a variable-length total feeds the rate model: the
    # oracle accumulates the same terms in the same left-to-right order,
    # so the fused and composed paths agree bit-for-bit
    video_bits = B.seq_sum(enc.bits)
    types, _, _ = classify_frames(enc.frame_diff / 255.0,
                                  enc.residual_mag / 255.0, tr1, tr2)
    is1 = types == 1
    T = raw.shape[0]
    if cfg.anchor_search:
        # masked ladder sweep: encode EVERY frame at EVERY rung (static
        # shapes — neither content nor budget retraces), charge bits per
        # rung, then a traced argmax picks each frame's highest rung that
        # fits its even share of the chunk's spare bandwidth
        sweep_rec, sweep_bits = jax.vmap(ladder_sweep)(raw)  # (T,Q,H,W),(T,Q)
        n_anchors = B.seq_sum(jnp.where(is1, 1.0, 0.0))
        per_anchor = anchor_budget_bits(bw_kbps, video_bits, n_anchors,
                                        T, cfg.fps)
        rung = budget_rung(sweep_bits, per_anchor)           # (T,)
        jrec = jnp.take_along_axis(
            sweep_rec, rung[:, None, None, None], axis=1)[:, 0]
        jbits = jnp.take_along_axis(sweep_bits, rung[:, None], axis=1)[:, 0]
        frame_q = jnp.asarray(ANCHOR_QUALITY_LADDER, f32)[rung]
    else:
        # JPEG-encode EVERY frame at the pinned anchor quality and mask to
        # the type-1 plane: data-independent shapes keep the anchor
        # pipeline inside the trace (the host path only encodes anchors)
        jrec, jbits = jax.vmap(
            lambda fr: jpeg_encode_decode(fr, cfg.anchor_quality))(raw)
        frame_q = jnp.full((T,), cfg.anchor_quality, f32)
    anchor_hd = jnp.where(is1[:, None, None], jrec, 0.0)
    anchor_bits = B.seq_sum(jnp.where(is1, jbits, 0.0))
    anchor_q = jnp.where(is1, frame_q, 0.0)
    total_bits = video_bits + anchor_bits

    out = _execute_chunk(enc, types, anchor_hd, gt_boxes, gt_valid,
                         detector_params, cfg.det_cfg, bw_kbps, queue_delay,
                         total_bits, cfg.costs, lr_extent=lr_extent,
                         roi=cfg.roi)
    out.update(types=types, video_bits=video_bits, anchor_bits=anchor_bits,
               total_bits=total_bits, anchor_q=anchor_q)
    return out


def _roundtrip_chunk(raw, gt_boxes, gt_valid, detector_params, tr1, tr2,
                     bw_kbps, queue_delay, cfg: RoundtripConfig) -> dict:
    """Traced single-stream body: raw (T, H, W) HD frames -> detections."""
    ql = QUALITY_LADDER[cfg.level]
    lr = downscale(jnp.asarray(raw, f32), ql.scale)
    enc = _encode_chunk(lr, cfg.codec_for())
    return _roundtrip_execute(raw, enc, None, gt_boxes, gt_valid,
                              detector_params, tr1, tr2, bw_kbps,
                              queue_delay, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def roundtrip_chunk(raw, gt_boxes, gt_valid, detector_params, *, tr1, tr2,
                    bw_kbps, queue_delay=0.0,
                    cfg: RoundtripConfig = RoundtripConfig()) -> dict:
    """One chunk of one stream, source frames -> HD detections, ONE jit.

    raw: (T, H, W) [0..255]; gt_boxes/gt_valid: (T, N, 4)/(T, N);
    tr1/tr2/bw_kbps/queue_delay: traced scalars; cfg static.  Returns the
    ``decode_execute_chunk`` result dict plus types/video_bits/
    anchor_bits/total_bits.
    """
    return _roundtrip_chunk(raw, gt_boxes, gt_valid, detector_params,
                            tr1, tr2, bw_kbps, queue_delay, cfg)


def _roundtrip_batch(raw, gt_boxes, gt_valid, detector_params, tr1, tr2,
                     bw_kbps, queue_delay, cfg: RoundtripConfig) -> dict:
    """vmap-over-streams traced body (homogeneous signature + rung)."""
    return jax.vmap(
        lambda r, gb, gv, t1, t2, bw, qd: _roundtrip_chunk(
            r, gb, gv, detector_params, t1, t2, bw, qd, cfg)
    )(raw, gt_boxes, gt_valid, tr1, tr2, bw_kbps, queue_delay)


@partial(jax.jit, static_argnames=("cfg",))
def roundtrip_batched(raw, gt_boxes, gt_valid, detector_params, *, tr1, tr2,
                      bw_kbps, queue_delay,
                      cfg: RoundtripConfig = RoundtripConfig()) -> dict:
    """S streams of one signature group, one device dispatch.

    raw: (S, T, H, W); per-stream scalars are (S,) arrays; detector
    params shared.  Same stream-axis shape discipline as
    ``decode_execute_batched`` — the mesh-sharded twin is
    ``stream_sharding.shard_roundtrip``.
    """
    return _roundtrip_batch(raw, gt_boxes, gt_valid, detector_params,
                            tr1, tr2, bw_kbps, queue_delay, cfg)


def _roundtrip_ladder_body(raw, lr_pad, extents, qualities, gt_boxes,
                           gt_valid, detector_params, tr1, tr2, bw_kbps,
                           queue_delay, cfg: RoundtripConfig) -> dict:
    """Post-downscale mixed-ladder traced body: lr_pad (S, T, Hp, Wp) is
    the padded LR canvas, extents (S, 2) the per-stream valid (h, w),
    qualities (S,) the per-stream QP.  Shared by the single-device jit
    and ``shard_roundtrip`` (the shape-changing per-rung downscale happens
    OUTSIDE the shard_map region; everything here is uniform-shape)."""
    enc = _encode_ladder_batch(lr_pad, extents, qualities, cfg.codec)
    return jax.vmap(
        lambda r, e, ext, gb, gv, t1, t2, bw, qd: _roundtrip_execute(
            r, e, (ext[0], ext[1]), gb, gv, detector_params, t1, t2, bw,
            qd, cfg)
    )(raw, enc, extents, gt_boxes, gt_valid, tr1, tr2, bw_kbps, queue_delay)


def ladder_batch_arrays(levels, H: int, W: int):
    """Static per-rung LR shapes -> (extents (S, 2) int32, qualities (S,))
    for a mixed-ladder batch over an (H, W) HD source."""
    shapes = [ladder_lr_shape(level, H, W) for level in levels]
    extents = jnp.asarray(shapes, jnp.int32)
    qualities = jnp.asarray([QUALITY_LADDER[level].quality
                             for level in levels], f32)
    return extents, qualities


def _downscale_pad(raw, levels):
    """Per-stream static-rung downscale, padded onto one LR canvas."""
    S, T, H, W = raw.shape
    shapes = [ladder_lr_shape(level, H, W) for level in levels]
    hp = max(h for h, _ in shapes)
    wp = max(w for _, w in shapes)
    lanes = []
    for s, level in enumerate(levels):
        lr = downscale(jnp.asarray(raw[s], f32), QUALITY_LADDER[level].scale)
        h, w = shapes[s]
        lanes.append(jnp.pad(lr, ((0, 0), (0, hp - h), (0, wp - w))))
    return jnp.stack(lanes)


def full_lr_canvas(H: int, W: int) -> tuple[int, int]:
    """The largest LR shape any ladder rung can produce for an (H, W)
    source — the fixed canvas of the shape-stable dispatch below."""
    from repro.codec.rate_model import lr_shape_for_scale
    return lr_shape_for_scale(1.0, H, W)


@partial(jax.jit, static_argnames=("cfg",))
def roundtrip_padded_batched(raw, lr_pad, extents, qualities, gt_boxes,
                             gt_valid, detector_params, *, tr1, tr2,
                             bw_kbps, queue_delay,
                             cfg: RoundtripConfig = RoundtripConfig()
                             ) -> dict:
    """Shape-stable mixed-ladder round trip: rungs travel as DATA.

    The caller downscales each stream to its rung eagerly and pads onto
    one fixed canvas (``full_lr_canvas``), passing extents (S, 2) and
    qualities (S,) as arrays — so a stream set of fixed size compiles ONE
    trace no matter how per-step bandwidth reallocation reshuffles the
    rungs.  (``roundtrip_ladder_batched`` below, with its static rung
    tuple, sizes the canvas to the batch's largest rung — less masked
    margin to encode, but one retrace per rung combination; the sim env
    uses THIS entry to bound compile churn at one trace per signature.)
    ``cfg.level`` is ignored.
    """
    return _roundtrip_ladder_body(jnp.asarray(raw, f32), lr_pad, extents,
                                  qualities, gt_boxes, gt_valid,
                                  detector_params, tr1, tr2, bw_kbps,
                                  queue_delay, cfg)


@partial(jax.jit, static_argnames=("levels", "cfg"))
def roundtrip_ladder_batched(raw, gt_boxes, gt_valid, detector_params, *,
                             tr1, tr2, bw_kbps, queue_delay,
                             levels: tuple,
                             cfg: RoundtripConfig = RoundtripConfig()
                             ) -> dict:
    """Mixed bitrate-ladder rungs, ONE padded dispatch, still one jit.

    ``levels`` (static tuple, one rung per stream) fixes each stream's LR
    shape; streams downscale to their own rung, pad onto the common LR
    canvas, and run the masked heterogeneous encode + extent-aware
    decode.  Lane s is bit-exact (f32) vs
    ``roundtrip_chunk(raw[s], ..., cfg=replace(cfg, level=levels[s]))``.
    ``cfg.level`` is ignored (the per-stream rungs win).
    """
    raw = jnp.asarray(raw, f32)
    S, T, H, W = raw.shape
    lr_pad = _downscale_pad(raw, levels)
    extents, qualities = ladder_batch_arrays(levels, H, W)
    return _roundtrip_ladder_body(raw, lr_pad, extents, qualities, gt_boxes,
                                  gt_valid, detector_params, tr1, tr2,
                                  bw_kbps, queue_delay, cfg)


# --------------------------------------------------------------------------
# Compose-the-two-jits oracle (host glue between the PR-3 jits)
# --------------------------------------------------------------------------
# module-level jit: re-wrapping per call would retrace the JPEG encode
# inside every oracle invocation and inflate the two-jit bench baseline
_jpeg = jax.jit(jpeg_encode_decode)
# static qualities so the probe's per-rung loop unrolls over the same
# constants the fused sweep bakes in
_q_for_budget = jax.jit(quality_for_budget, static_argnames=("qualities",))


def roundtrip_oracle(raw, gt_boxes, gt_valid, detector_params, *, tr1, tr2,
                     bw_kbps, queue_delay=0.0,
                     cfg: RoundtripConfig = RoundtripConfig()) -> dict:
    """The pre-tentpole execution: ``encode_chunk`` (jit #1), host-side
    classification + per-anchor JPEG loop + rate model, then
    ``decode_execute_chunk`` (jit #2).  The fused forms must reproduce
    this bit-for-bit in f32 — it is the parity baseline for
    ``tests/test_roundtrip.py`` and the "sequential two-jit" side of
    ``benchmarks/roundtrip.py``.
    """
    raw = jnp.asarray(raw, f32)
    ql = QUALITY_LADDER[cfg.level]
    lr = downscale(raw, ql.scale)
    enc = encode_chunk(lr, cfg.codec_for())                    # jit #1
    video_bits = B.seq_sum(enc.bits)
    types, _, _ = classify_frames(enc.frame_diff / 255.0,
                                  enc.residual_mag / 255.0, tr1, tr2)
    types_host = jax.device_get(types)
    anchors = np.flatnonzero(types_host == 1)
    T = raw.shape[0]
    anchor_hd = jnp.zeros_like(raw)
    anchor_bits = jnp.asarray(0.0, f32)
    anchor_q = jnp.zeros((T,), f32)
    if cfg.anchor_search:
        # host-side twin of the traced search: probe the ladder per anchor
        # with quality_for_budget against the same per-anchor budget share
        per_anchor = anchor_budget_bits(bw_kbps, video_bits,
                                        float(len(anchors)), T, cfg.fps)
        for i in anchors:
            q_i, _ = _q_for_budget(raw[i], per_anchor)
            rec, bits = _jpeg(raw[i], q_i)
            anchor_hd = anchor_hd.at[i].set(rec)
            anchor_bits = anchor_bits + bits
            anchor_q = anchor_q.at[i].set(q_i)
    else:
        for i in anchors:
            rec, bits = _jpeg(raw[i], cfg.anchor_quality)
            anchor_hd = anchor_hd.at[i].set(rec)
            anchor_bits = anchor_bits + bits
            anchor_q = anchor_q.at[i].set(cfg.anchor_quality)
    total_bits = video_bits + anchor_bits
    out = decode_execute_chunk(                                # jit #2
        enc, types, anchor_hd, gt_boxes, gt_valid, detector_params,
        cfg.det_cfg, bw_kbps=bw_kbps, queue_delay=queue_delay,
        total_bits=total_bits, costs=cfg.costs, roi=cfg.roi)
    out = dict(out)
    out.update(types=types, video_bits=video_bits, anchor_bits=anchor_bits,
               total_bits=total_bits, anchor_q=anchor_q)
    return out
