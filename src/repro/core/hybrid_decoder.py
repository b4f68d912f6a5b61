"""Hybrid decoder + 3 execution pipelines (paper §IV-B, Fig. 6) — edge side.

Pipeline ①: decoded HD anchors -> DNN inference (results cached)
Pipeline ②: LR frame -> quality transfer from anchors -> DNN inference
Pipeline ③: no decode — cached detections shifted by mean MV (reuse)

Latency model (paper Fig. 13b): transmission = bits / allocated bandwidth,
queueing from the serving queues, compute from per-pipeline costs.

Two execution paths:

* ``decode_and_execute`` — the legacy host-orchestrated path: per-frame
  Python loops, eager op dispatch, ``np.asarray`` round trips.  Kept as the
  oracle for the fused path.
* ``decode_execute_chunk`` — ONE ``jax.jit`` end to end: vectorized
  anchor-index computation (``lax.cummax`` instead of the Python loop),
  fused upscale + quality transfer + detector forward + reuse + F1, and
  the latency model as traced scalar math.  ``decode_execute_batched`` is
  its vmap-over-streams entry point (one device dispatch for N streams).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.codec.rate_model import upscale_nearest
from repro.core.hybrid_encoder import HybridPacket
from repro.core.reuse import reuse_chunk
from repro.models import detection as D

f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class PipelineCosts:
    """Per-frame edge compute costs (seconds) — calibrated to the paper's
    RTX-3070 numbers: full inference ~33 ms, transfer+infer ~43 ms, reuse
    ~6 ms, DRL <10 ms.  Used by the latency model (wall-clock cannot be
    measured on this CPU-only container; DESIGN.md §2)."""
    infer: float = 0.033
    transfer: float = 0.010     # on top of infer for pipeline ②
    reuse: float = 0.006
    decode_hd: float = 0.004
    decode_video: float = 0.002


def pipeline_cost(n1, n2, n3, costs: PipelineCosts = PipelineCosts()):
    """Per-chunk edge compute time for n1/n2/n3 frames on pipelines ①/②/③.

    The single source of truth for the per-pipeline cost formula — shared
    by the legacy path, the fused traced path, and the serving runtime.
    Works for host ints and traced scalars alike.
    """
    return (n1 * (costs.infer + costs.decode_hd)
            + n2 * (costs.infer + costs.transfer + costs.decode_video)
            + n3 * costs.reuse)


@dataclasses.dataclass
class ChunkResult:
    boxes: np.ndarray           # (T, N, 4)
    scores: np.ndarray          # (T, N)
    types: np.ndarray           # (T,)
    f1: np.ndarray              # (T,) accuracy vs GT
    mean_f1: float
    latency: float              # end-to-end chunk latency (s)
    t_trans: float
    t_queue: float
    t_comp: float


def _detect(detector_params, det_cfg, frames):
    raw = D.forward(detector_params, det_cfg, frames)
    boxes, scores = D.decode_boxes(raw, det_cfg)
    return boxes, scores


def decode_and_execute(packet: HybridPacket, detector_params, det_cfg,
                       gt_boxes, gt_valid, *, bw_kbps: float,
                       queue_delay: float = 0.0,
                       costs: PipelineCosts = PipelineCosts(),
                       fps: float = 30.0) -> ChunkResult:
    """Run the 3 pipelines for one chunk of one stream (host orchestration,
    jitted compute)."""
    enc = packet.video
    T = packet.types.shape[0]
    H, W = packet.anchor_hd.shape[1:]
    types = jnp.asarray(packet.types)

    # decode + upscale the LR video to analytics resolution
    lr_up = upscale_nearest(enc.recon, H, W)

    # per-frame nearest preceding anchor plane
    anchor_idx = np.zeros(T, np.int64)
    last = 0
    for i in range(T):
        if packet.types[i] == 1:
            last = i
        anchor_idx[i] = last
    anchor_plane = jnp.asarray(packet.anchor_hd[anchor_idx])

    # scale LR MVs/residuals up to analytics resolution
    mvs_hd = _upscale_mvs(enc.mv, (H, W))

    # pipeline ②: quality transfer (type-2 frames)
    residual_up = jax.vmap(lambda r: upscale_nearest(r[None], H, W)[0])(
        _residual_px(enc.recon, enc.residual_q, enc.qtab))
    frames_exec = jnp.where((types == 1)[:, None, None],
                            jnp.asarray(packet.anchor_hd), lr_up)
    qt = _transfer(anchor_plane, jnp.asarray(anchor_idx, jnp.int32),
                   mvs_hd, residual_up, frames_exec, types)

    # pipelines ① + ②: DNN inference on type-1/2 frames
    boxes_i, scores_i = _detect(detector_params, det_cfg, qt)

    # pipeline ③: reuse with MV shift
    boxes, scores = reuse_chunk(types, mvs_hd, boxes_i, scores_i)

    f1 = jax.vmap(lambda b, s, g, v: D.f1_score(b, s, g, v))(
        boxes, scores, jnp.asarray(gt_boxes), jnp.asarray(gt_valid))

    n1 = int((packet.types == 1).sum())
    n2 = int((packet.types == 2).sum())
    n3 = int((packet.types == 3).sum())
    t_comp = pipeline_cost(n1, n2, n3, costs)
    t_trans = packet.total_bits / max(bw_kbps * 1000.0, 1e-6)
    latency = t_trans + queue_delay + t_comp
    return ChunkResult(boxes=np.asarray(boxes), scores=np.asarray(scores),
                       types=packet.types, f1=np.asarray(f1),
                       mean_f1=float(f1.mean()), latency=float(latency),
                       t_trans=float(t_trans), t_queue=float(queue_delay),
                       t_comp=float(t_comp))


# --------------------------------------------------------------------------
# Fused path: the whole chunk as one jitted computation
# --------------------------------------------------------------------------
def anchor_index(types):
    """Vectorized nearest-preceding-anchor index: for each frame i, the
    largest j <= i with types[j] == 1 (frame 0 if none).  Replaces the
    legacy per-frame Python loop with a cumulative max over marked indices.
    """
    idx = jnp.arange(types.shape[0], dtype=jnp.int32)
    marked = jnp.where(types == 1, idx, -1)
    return jnp.maximum(lax.cummax(marked), 0)


def execution_planes(recon, mv, residual_q, qtab, types, anchor_hd,
                     lr_extent=None):
    """The frame each pipeline-①/② row runs the detector on, plus the
    HD-grid motion vectors pipeline ③ shifts boxes with.

    Type-1 frames take the decoded HD anchor; type-2 frames take the
    upscaled LR frame quality-transferred from their nearest preceding
    anchor (paper Fig. 7); type-3 frames carry the upscaled LR frame (not
    inferred).  Shared by the fused decode (``_execute_chunk``) and the
    serving runtime's stage step, so both serve the same pipelines.
    Returns ((T, H, W) frames, (T, nby, nbx, 2) int32 MVs)."""
    H, W = anchor_hd.shape[1:]
    lr_up = upscale_nearest(recon, H, W, src_hw=lr_extent)
    aidx = anchor_index(types)
    mvs_hd = _upscale_mvs(mv, (H, W), lr_hw=lr_extent)
    residual_up = jax.vmap(
        lambda r: upscale_nearest(r[None], H, W, src_hw=lr_extent)[0])(
        _residual_px(recon, residual_q, qtab))
    frames_exec = jnp.where((types == 1)[:, None, None], anchor_hd, lr_up)
    qt = _transfer(anchor_hd[aidx], aidx, mvs_hd, residual_up, frames_exec,
                   types)
    return qt, mvs_hd


def _execute_chunk(enc, types, anchor_hd, gt_boxes, gt_valid,
                   detector_params, det_cfg, bw_kbps, queue_delay,
                   total_bits, costs: PipelineCosts, lr_extent=None,
                   roi=None):
    """Traced body shared by ``decode_execute_chunk`` (single stream) and
    ``decode_execute_batched`` (vmap over streams).  Pure jnp: no host
    transfers, no Python loops over frames.

    ``lr_extent`` ((h, w), traced ints) is the valid LR extent when
    ``enc`` came out of the heterogeneous-ladder padded encode: the
    upscale/MV index maps then read only the valid region of the padded
    canvas, making the result bit-identical to decoding the stream's
    unpadded encode (the fused round-trip relies on this).

    ``roi`` (a static ``repro.core.roi.RoiConfig``) gates the detector:
    instead of the full-frame forward, a relevance head over the codec's
    macroblock statistics picks top-K regions, only their packed patches
    run the convs, and a scatter with a temporal carry covers gated-off
    regions (bit-exact vs the ungated path when the gate admits every
    region — ``tests/test_roi.py``)."""
    qt, mvs_hd = execution_planes(enc.recon, enc.mv, enc.residual_q,
                                  enc.qtab, types, anchor_hd,
                                  lr_extent=lr_extent)

    # pipelines ① + ② fused into one detector forward over the whole chunk
    # (ROI-gated onto the top-K packed patch batch when cfg carries a roi)
    if roi is not None:
        from repro.core.roi import roi_detect
        boxes_i, scores_i = roi_detect(
            detector_params, det_cfg, roi, qt, enc.mv, enc.residual_q,
            enc.recon.shape[1:], lr_extent=lr_extent)
    else:
        boxes_i, scores_i = _detect(detector_params, det_cfg, qt)
    boxes, scores = reuse_chunk(types, mvs_hd, boxes_i, scores_i)

    f1 = jax.vmap(D.f1_score)(boxes, scores, gt_boxes, gt_valid)

    # latency model as traced scalar math (no host round trip)
    n1 = jnp.sum(types == 1).astype(f32)
    n2 = jnp.sum(types == 2).astype(f32)
    n3 = jnp.sum(types == 3).astype(f32)
    t_comp = pipeline_cost(n1, n2, n3, costs)
    t_trans = total_bits / jnp.maximum(bw_kbps * 1000.0, 1e-6)
    latency = t_trans + queue_delay + t_comp
    return {"boxes": boxes, "scores": scores, "f1": f1,
            "mean_f1": f1.mean(), "latency": latency, "t_trans": t_trans,
            "t_queue": queue_delay, "t_comp": t_comp}


@partial(jax.jit, static_argnames=("det_cfg", "costs", "roi"))
def decode_execute_chunk(enc, types, anchor_hd, gt_boxes, gt_valid,
                         detector_params, det_cfg, *, bw_kbps,
                         queue_delay=0.0, total_bits=0.0,
                         costs: PipelineCosts = PipelineCosts(),
                         roi=None):
    """One chunk of one stream as a SINGLE jitted computation.

    enc: EncodedChunk (pytree); types: (T,) int; anchor_hd: (T, H, W);
    gt_boxes/gt_valid: (T, N, 4)/(T, N); bw_kbps/queue_delay/total_bits:
    traced scalars; roi: optional static RoiConfig (detector gate).
    Returns a dict of device arrays (boxes, scores, f1, mean_f1, latency,
    t_trans, t_queue, t_comp).
    """
    return _execute_chunk(enc, types, anchor_hd, gt_boxes, gt_valid,
                          detector_params, det_cfg, bw_kbps, queue_delay,
                          total_bits, costs, roi=roi)


def _execute_batch(enc, types, anchor_hd, gt_boxes, gt_valid,
                   detector_params, det_cfg, bw_kbps, queue_delay,
                   total_bits, costs: PipelineCosts, roi=None):
    """vmap-over-streams traced body: every leading axis is the stream axis
    (S, ...); detector params are shared.  Shared by the single-device jit
    below and the mesh-sharded wrapper in
    ``repro.distributed.stream_sharding.shard_streams`` (which calls it
    inside a ``shard_map`` region with per-shard stream slices)."""
    fn = lambda e, ty, ah, gb, gv, bw, qd, tb: _execute_chunk(
        e, ty, ah, gb, gv, detector_params, det_cfg, bw, qd, tb, costs,
        roi=roi)
    return jax.vmap(fn)(enc, types, anchor_hd, gt_boxes, gt_valid,
                        bw_kbps, queue_delay, total_bits)


@partial(jax.jit, static_argnames=("det_cfg", "costs", "roi"))
def decode_execute_batched(enc, types, anchor_hd, gt_boxes, gt_valid,
                           detector_params, det_cfg, *, bw_kbps,
                           queue_delay, total_bits,
                           costs: PipelineCosts = PipelineCosts(),
                           roi=None):
    """vmap-over-streams fused execution — one device dispatch for the
    whole batch of chunks.  Single-device oracle for the sharded path."""
    return _execute_batch(enc, types, anchor_hd, gt_boxes, gt_valid,
                          detector_params, det_cfg, bw_kbps, queue_delay,
                          total_bits, costs, roi=roi)


def decode_and_execute_fused(packet: HybridPacket, detector_params, det_cfg,
                             gt_boxes, gt_valid, *, bw_kbps: float,
                             queue_delay: float = 0.0,
                             costs: PipelineCosts = PipelineCosts()
                             ) -> ChunkResult:
    """Host convenience wrapper: ``decode_execute_chunk`` with the same
    packet-in / ChunkResult-out contract as ``decode_and_execute``."""
    out = decode_execute_chunk(
        packet.video, jnp.asarray(packet.types), jnp.asarray(packet.anchor_hd),
        jnp.asarray(gt_boxes), jnp.asarray(gt_valid), detector_params,
        det_cfg, bw_kbps=bw_kbps, queue_delay=queue_delay,
        total_bits=packet.total_bits, costs=costs)
    return ChunkResult(boxes=np.asarray(out["boxes"]),
                       scores=np.asarray(out["scores"]), types=packet.types,
                       f1=np.asarray(out["f1"]),
                       mean_f1=float(out["mean_f1"]),
                       latency=float(out["latency"]),
                       t_trans=float(out["t_trans"]),
                       t_queue=float(out["t_queue"]),
                       t_comp=float(out["t_comp"]))


def _residual_px(recon, residual_q, qtab):
    from repro.core.quality_transfer import residual_to_pixels
    h, w = recon.shape[1:]
    return jax.vmap(lambda q: residual_to_pixels(q, qtab, h, w))(residual_q)


def _upscale_mvs(mv, hw, lr_hw=None):
    """LR MVs -> HD block grid + magnitude rescale (Fig. 7 step 2).

    ``lr_hw`` ((h, w), traced ints) overrides the LR extent when ``mv``
    carries padded macroblock rows/cols from the heterogeneous-ladder
    encode.  The scale factors are computed with f32 jnp ops in BOTH
    forms (constant-folded when static) so the padded path stays
    bit-identical to the unpadded one."""
    H, W = hw
    nby, nbx = H // 16, W // 16
    T, nby_p, nbx_p, _ = mv.shape
    nby_lr, nbx_lr = (nby_p, nbx_p) if lr_hw is None \
        else (lr_hw[0] // 16, lr_hw[1] // 16)
    yi = jnp.clip(jnp.arange(nby) * nby_lr // nby, 0, nby_lr - 1)
    xi = jnp.clip(jnp.arange(nbx) * nbx_lr // nbx, 0, nbx_lr - 1)
    mvu = mv[:, yi][:, :, xi].astype(f32)
    sy = jnp.asarray(H, f32) / (jnp.asarray(nby_lr, f32) * 16.0)
    sx = jnp.asarray(W, f32) / (jnp.asarray(nbx_lr, f32) * 16.0)
    return jnp.round(mvu * jnp.stack([sy, sx])).astype(jnp.int32)


def _transfer(anchor_plane, anchor_idx, mvs_hd, residual_up, frames, types):
    from repro.core.quality_transfer import transfer_frame
    cum = jnp.cumsum(mvs_hd, axis=0)
    cum_at_anchor = cum[anchor_idx]               # (T, nby, nbx, 2)
    mv_rel = (cum - cum_at_anchor).astype(jnp.int32)

    def one(i):
        enhanced = transfer_frame(anchor_plane[i], mv_rel[i], residual_up[i])
        return jnp.where(types[i] == 2, enhanced, frames[i])

    return jax.vmap(one)(jnp.arange(frames.shape[0]))
