"""BiSwift edge serving runtime: decoder -> pipelines -> results.

Binds the hybrid decoder's three pipelines to the scheduler's queues and a
(pjit-able) detector, per chunk per stream.  This is the deployable analog
of the paper's Fig. 4 right half; benchmarks/run.py drives it with
1..N concurrent streams to reproduce Fig. 11(a).

Async continuous-batching plane (ISSUE 7): the runtime is a
submit/flush/poll dispatcher in the style of LLM serving —

  * ``submit_chunk`` runs ONLY host-side control (delivery ladder,
    admission, demotion, queue accounting) and stages the chunk's frames
    / motion vectors on device with a single jit (``_stage_chunk``); it
    returns a :class:`ChunkTicket` immediately, without waiting for any
    device work.
  * ``flush`` groups pending tickets by (shard, T, H, W) batch signature,
    gathers each group's pipeline-①/② frames into one padded detector
    batch (power-of-two bucketed so the jit cache stays warm), dispatches
    it asynchronously, and finishes every ticket with one fused
    scatter+reuse jit (``_finish_chunk``).  At most
    ``ServingConfig.max_inflight`` dispatched batches are outstanding per
    shard (double-buffered chunk slots): dispatching past the cap first
    retires the oldest with ``block_until_ready``, so host scheduling of
    the NEXT batch overlaps the device computing the current one.
  * ``poll`` materializes a ticket's results with a single device->host
    transfer at the poll boundary — no intermediate ``np.asarray`` syncs
    anywhere on the chunk path.

``process_chunk`` is now literally ``poll(submit_chunk(...))``, so every
legacy call site keeps its synchronous semantics (admission sees drained
queues, one dispatch per chunk) while sharing the async machinery.

Robustness plane (chaos PR): when constructed with ``faults=`` (a
``repro.serving.faults.FaultSchedule``) the runtime additionally runs

  * a per-stream deadline-driven **degradation ladder** replacing silent
    deferral — lost/corrupt chunks retry with exponential backoff; streams
    that keep missing their deadline are demoted down the bitrate ladder
    (``suggest_level``), then forced onto pipeline-③ reuse, then
    frame-skipped with explicit accounting (types == 0).  Every decision
    lands in ``stats[stream]`` (a :class:`StreamStats`).
  * **straggler eviction + elastic recovery** — per-dispatch shard
    timings feed a ``StragglerDetector``; ``poll_faults`` evicts flagged
    shards from ``active_shards`` (re-homing queued requests AND pending
    tickets onto survivors via ``PipelineQueues.remap_shards``) and
    re-admits them when the schedule says the device is healthy again.
    Dispatches hedge across active shards through a ``HedgedExecutor``;
    already-dispatched batches complete on their original device.

The accounting invariant every chaos test asserts —
``frames_in == frames_inferred + frames_reused + frames_skipped`` — is
established at SUBMIT time (types are decided by host control), so it
holds for every stream even while its chunk is still in flight.

Instrumentation (docs/serving_async.md, "Tracing and counters"): host
spans ``edge.*`` (``jax.profiler.TraceAnnotation``, on the profiler's
clock, carrying ``stream``/``chunk``/``shard`` ids), a
``jax.named_scope`` per serving program, always-on host counters
(``EdgeRuntime.counters``) and a process-wide trace count per program
(``TRACE_COUNTS``).  None of them reads a device value.
"""
from __future__ import annotations

import contextlib
import dataclasses
from collections import Counter, defaultdict, deque
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.hybrid_encoder import HybridPacket
from repro.core.hybrid_decoder import (PipelineCosts, execution_planes,
                                       pipeline_cost)
from repro.codec.rate_model import QUALITY_LADDER
from repro.core.reuse import reuse_chunk
from repro.models import detection as D
from repro.serving.elastic import ElasticPool
from repro.serving.scheduler import (AdmissionController, InferRequest,
                                     PipelineQueues, ServingConfig)
from repro.serving.straggler import (DetectorConfig, HedgeConfig,
                                     HedgedExecutor, StragglerDetector)

f32 = np.float32


# ---------------------------------------------------------------------------
# module-level jits — one trace per batch signature, shared by every runtime
# ---------------------------------------------------------------------------
PROGRAMS = ("stage", "gather", "detect", "finish", "hold")

# process-wide: how often JAX traced each serving program (its Python body
# runs only while tracing), so a retrace names the program that caused it
TRACE_COUNTS: Counter = Counter()


@contextlib.contextmanager
def _program(name: str):
    """Body of a serving jit: count the trace and name the device ops
    (``jax.named_scope`` is op metadata; the XLA module keeps the jit's
    own name)."""
    TRACE_COUNTS[name] += 1
    with jax.named_scope(name):
        yield


@partial(jax.jit, static_argnames=("hd_hw", "roi", "anchor_search"))
def _stage_chunk(types, anchor_hd, recon, mv, residual_q, qtab, *, hd_hw,
                 roi=None, anchor_search=False):
    """Stage one chunk on device: build each frame's execution plane
    (decoded HD anchor for type-1, the upscaled LR frame quality-transferred
    from its anchor for type-2 — ``hybrid_decoder.execution_planes``, the
    same planes the fused decode runs) and upscale the motion vectors —
    one async dispatch, nothing touches the host.  With ``roi`` set (a
    static ``repro.core.roi.RoiConfig``) the relevance head also scores
    each HD region from the codec's macroblock statistics; the (T, R)
    flat scores ride the ticket so the detector dispatch can gate its
    rows without re-deriving anything.  With ``anchor_search`` on the
    per-rung anchor bit planes (``ladder_bits`` over the HD anchor plane,
    (T, Q)) ride along too — staged in this same async dispatch so a
    downstream in-trace budget pick costs no extra host round trip."""
    with _program("stage"):
        frames, mvs = execution_planes(recon, mv, residual_q, qtab, types,
                                       anchor_hd)
        rung_bits = None
        if anchor_search:
            from repro.codec.image_codec import ladder_bits
            rung_bits = jax.vmap(ladder_bits)(anchor_hd)
        if roi is None:
            return frames, mvs, None, rung_bits
        from repro.core.roi import region_grid, region_scores
        nry, nrx = region_grid(hd_hw, roi)
        scores = region_scores(mv, residual_q, recon.shape[1:], hd_hw, roi)
        return (frames, mvs, scores.reshape(types.shape[0], nry * nrx),
                rung_bits)


@jax.jit
def _gather_batch(frames_seq, flat_idx, valid):
    """Pack per-ticket staged frames into one padded detector batch.

    ``frames_seq``: tuple of (T, H, W) staged planes (one per ticket slot,
    padded to a power-of-two count so the trace cache stays bounded);
    ``flat_idx``: (n_pad,) row ``slot * T + frame_idx`` per batch entry;
    ``valid``: (n_pad,) mask — padding rows come out exactly zero, matching
    the legacy ``np.zeros_like`` padding semantics bit-for-bit."""
    with _program("gather"):
        stacked = jnp.stack(frames_seq)
        flat = stacked.reshape((-1,) + stacked.shape[2:])
        batch = jnp.take(flat, jnp.clip(flat_idx, 0, flat.shape[0] - 1),
                         axis=0)
        return jnp.where(valid[:, None, None], batch, 0.0)


@jax.jit
def _gather_rows(rows_seq, flat_idx, valid):
    """ROI-mode companion to ``_gather_batch``: pack per-ticket staged
    (T, R) region-score rows into the batch order.  Padding rows score 0
    everywhere — their gated patches run on the zero frames
    ``_gather_batch`` produced and are dropped at scatter time."""
    with _program("gather"):
        stacked = jnp.stack(rows_seq)
        flat = stacked.reshape((-1,) + stacked.shape[2:])
        rows = jnp.take(flat, jnp.clip(flat_idx, 0, flat.shape[0] - 1),
                        axis=0)
        return jnp.where(valid[:, None], rows, 0.0)


@partial(jax.jit, static_argnames=("has_init",))
def _finish_chunk(types, pos, mvs, batch_boxes, batch_scores,
                  init_b, init_s, *, has_init):
    """Scatter one ticket's rows out of the batched detector output and
    run pipeline-③ reuse — fused, so the carry slice (``boxes[-1]``)
    never leaves the device between chunks."""
    with _program("finish"):
        mask = pos >= 0
        idx = jnp.clip(pos, 0, batch_boxes.shape[0] - 1)
        boxes_t = jnp.where(mask[:, None, None], batch_boxes[idx], 0.0)
        scores_t = jnp.where(mask[:, None], batch_scores[idx], 0.0)
        boxes, scores = reuse_chunk(
            types, mvs, boxes_t, scores_t,
            init_boxes=init_b if has_init else None,
            init_scores=init_s if has_init else None)
        return boxes, scores, boxes[-1], scores[-1]


@partial(jax.jit, static_argnames=("T",))
def _hold_chunk(last_b, last_s, *, T):
    """Zero-motion pipeline-③ hold for an undeliverable chunk with a
    carry: the previous detections repeated across the chunk."""
    with _program("hold"):
        return (jnp.broadcast_to(last_b[None], (T,) + last_b.shape),
                jnp.broadcast_to(last_s[None], (T,) + last_s.shape))


def _pad_bucket(n: int, base: int) -> int:
    """Smallest ``base * 2**k >= n`` — power-of-two bucketed padding keeps
    the detector's jit cache small while capping padding waste at 2x."""
    m = max(int(base), 1)
    while m < n:
        m *= 2
    return m


@dataclasses.dataclass
class StreamState:
    """Pipeline-③ carry across chunks.  DEVICE arrays: the carry feeds the
    next chunk's ``_finish_chunk`` without a host round trip."""
    last_boxes: jax.Array
    last_scores: jax.Array


@dataclasses.dataclass
class ChunkTicket:
    """Handle for one submitted chunk.  ``done`` flips when the device
    graph is built (dispatch + finish); ``poll`` materializes the result
    with one transfer and caches it."""
    stream: int
    chunk_t: int
    shard: int
    types: np.ndarray
    hw: tuple
    reqs: list = dataclasses.field(default_factory=list)
    frames_dev: jax.Array | None = None
    mvs_dev: jax.Array | None = None
    rscores_dev: jax.Array | None = None   # (T, R) ROI scores (roi mode)
    # (T, Q) per-rung anchor bit planes (anchor_search mode) — small, so
    # kept past dispatch for budget audits after poll
    rung_bits_dev: jax.Array | None = None
    init_b: jax.Array | None = None
    init_s: jax.Array | None = None
    n_cells: int = 0
    done: bool = False
    _dev_out: tuple | None = None      # (boxes, scores) on device
    _host: tuple | None = None         # cached poll result


@dataclasses.dataclass(frozen=True)
class DegradeConfig:
    """Deadline ladder knobs (rungs in escalation order).

    1. retry-with-backoff — a lost/corrupt chunk is retransmitted up to
       ``max_retries`` times, backoff doubling from ``retry_backoff_s``,
       while the accumulated penalty still fits ``deadline_s``;
    2. rung demotion — ``demote_patience`` consecutive deadline misses
       drop the stream one bitrate-ladder rung (down to ``max_demotion``
       below its bandwidth-derived rung);
    3. pipeline-③ fallback — misses at the bottom rung force whole chunks
       onto motion-vector reuse (no inference);
    4. frame-skip — an undeliverable chunk with no carried detections is
       dropped with explicit accounting (types == 0).

    ``promote_patience`` consecutive on-deadline chunks walk the stream
    back up one step (reuse → inference, then rung by rung).
    """
    deadline_s: float = 1.0
    max_retries: int = 3
    retry_backoff_s: float = 0.05
    demote_patience: int = 2
    promote_patience: int = 3
    max_demotion: int = len(QUALITY_LADDER) - 1


@dataclasses.dataclass
class StreamStats:
    """Per-stream degradation accounting — every ladder decision is
    surfaced here, nothing is silent."""
    stream: int
    frames_in: int = 0
    frames_inferred: int = 0          # pipelines ① and ② (through the DNN)
    frames_reused: int = 0            # pipeline ③
    frames_skipped: int = 0           # rung 4: explicitly dropped
    chunks: int = 0
    chunks_lost: int = 0
    chunks_corrupt: int = 0
    chunks_stalled: int = 0
    retries: int = 0
    deadline_misses: int = 0
    rung_demotion: int = 0            # current ladder demotion (0 = none)
    demote_events: int = 0
    promote_events: int = 0
    reuse_fallback_chunks: int = 0
    force_reuse: bool = False         # rung 3 engaged
    events: list = dataclasses.field(default_factory=list)
    # transient per-chunk fields (the soak reads them right after a chunk)
    last_penalty_s: float = 0.0
    last_transmitted: bool = True
    last_delivered: int = 0
    last_inferred: int = 0
    last_skipped: int = 0
    _miss_streak: int = 0
    _ok_streak: int = 0

    def note(self, t: int, action: str, detail: str = ""):
        self.events.append((int(t), action, detail))

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["events"] = [list(e) for e in d["events"]]
        return {k: v for k, v in d.items() if not k.startswith("_")}


@dataclasses.dataclass
class DispatchCounters:
    """What the runtime handed the device, as plain host ints, always on.
    Each is taken from a host value already at hand (a shape, a count,
    the ``nbytes`` of a host array), never from a device array.

    ``dispatches``: calls of each serving program (``PROGRAMS``);
    ``detector_rows``: padded detector rows as dispatched (the useful
    ones are ``StreamStats.frames_inferred``); ``h2d_bytes``: packet
    arrays copied host -> device at submit; ``d2h_bytes``: results
    copied device -> host; ``inflight_waits``: batches retired by a
    blocking wait under ``max_inflight``."""
    dispatches: dict = dataclasses.field(
        default_factory=lambda: dict.fromkeys(PROGRAMS, 0))
    detector_rows: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    inflight_waits: int = 0

    def as_dict(self) -> dict:
        """A snapshot (copies ``dispatches``)."""
        return dataclasses.asdict(self)


class EdgeRuntime:
    def __init__(self, cfg: ServingConfig, detector_params, det_cfg,
                 costs: PipelineCosts = PipelineCosts(), *,
                 mesh=None, rules=None, faults=None,
                 degrade: DegradeConfig | None = None,
                 hedge: HedgeConfig | None = None,
                 straggler_cfg: DetectorConfig | None = None):
        """``mesh``/``rules`` (jax Mesh + AxisRules with a "stream" entry)
        switch the runtime to sharded mode: n_shards is derived from the
        mesh's stream extent, streams map to shards round-robin, each
        chunk's detector dispatch drains only its own shard's queues, and
        shard i's detector (params replicated per shard) is COMMITTED to
        mesh device i — the per-shard capacity slice corresponds to a real
        device, not an accounting fiction.

        ``faults`` (a ``FaultSchedule``) arms the chaos plane: the
        degradation ladder (``degrade``), hedged dispatch (``hedge``) and
        straggler eviction (``straggler_cfg``) all activate; without it
        the runtime behaves exactly as before (stats still collected)."""
        if (mesh is None) != (rules is None):
            raise ValueError("sharded mode needs BOTH mesh= and rules= "
                             "(got only one)")
        self._shard_infer = None
        if mesh is not None:
            from repro.distributed.stream_sharding import stream_shard_count
            cfg = dataclasses.replace(
                cfg, n_shards=stream_shard_count(mesh, rules))
        self.cfg = cfg
        self.n_shards = max(cfg.n_shards, 1)
        self.det_cfg = det_cfg
        self.costs = costs

        # params enter the jit as an ARGUMENT (closure capture would embed
        # them as constants and the computation would ignore their device)
        # In ROI mode the dispatch payload is (frames, region_scores) and
        # each row runs only its top-K gated region patches.
        roi = getattr(cfg, "roi", None)
        if roi is None:
            def detect(p, frames):
                with _program("detect"):
                    return D.decode_boxes(D.forward(p, det_cfg, frames),
                                          det_cfg)
        else:
            from repro.core.roi import roi_infer

            def detect(p, payload):
                with _program("detect"):
                    return roi_infer(p, det_cfg, roi, payload[0], payload[1])
        # jitted as a lambda: the XLA module keeps the name jit__lambda,
        # which the benchmark's module table (bench/modules.json) matches
        infer_jit = jax.jit(lambda p, x: detect(p, x))
        self.roi = roi
        self.anchor_search = bool(getattr(cfg, "anchor_search", False))

        def make_infer(params, dev=None):
            # staged batches are COMMITTED (jit outputs); an explicit
            # device_put routes them onto this replica's device — required
            # for both the per-shard dispatch and the hedge backup, whose
            # input may sit on the primary's device
            if dev is None:
                return lambda frames: infer_jit(params, frames)
            return lambda frames: infer_jit(params,
                                            jax.device_put(frames, dev))

        self._infer = make_infer(detector_params)
        if mesh is not None and self.n_shards > 1:
            devs = list(mesh.devices.flat)
            self._shard_infer = [
                make_infer(jax.device_put(detector_params,
                                          devs[i % len(devs)]),
                           devs[i % len(devs)])
                for i in range(self.n_shards)]
        self.queues = PipelineQueues(cfg, self._infer_batch)
        self.admission = AdmissionController(cfg)
        self.streams: dict[int, StreamState] = {}
        self.deferred = 0
        self.deferred_by_shard = np.zeros(self.n_shards, np.int64)
        # pipeline-③ fallback accounting: frames demoted ②->③ under
        # overload, and whole chunks forced onto reuse (deep overload)
        self.demoted_frames = np.zeros(self.n_shards, np.int64)
        self.reuse_fallback_chunks = np.zeros(self.n_shards, np.int64)
        self.counters = DispatchCounters()

        # ------------------------------------------ async dispatch plane
        self.max_inflight = max(int(getattr(cfg, "max_inflight", 2)), 1)
        self._pending: list[ChunkTicket] = []     # submitted, undispatched
        self._open: dict[int, ChunkTicket] = {}   # stream -> pending ticket
        self._inflight: dict[int, deque] = defaultdict(deque)

        # ---------------------------------------------- robustness plane
        self.faults = faults
        self.degrade = degrade or DegradeConfig(
            deadline_s=cfg.latency_budget)
        self.stats: dict[int, StreamStats] = {}
        self.active_shards: list[int] = list(range(self.n_shards))
        self.pool = ElasticPool(self.n_shards)
        self.straggler = StragglerDetector(
            straggler_cfg or DetectorConfig(), self.n_shards)
        self._hedge_cfg = hedge or HedgeConfig()
        self._hedge: HedgedExecutor | None = None
        if self.n_shards > 1 and (faults is not None or hedge is not None):
            self._rebuild_hedge()
        self.fault_log: list[tuple[int, str, str]] = []
        self._t = 0

    # ------------------------------------------------------------------
    def stream_shard(self, stream: int) -> int:
        """Owning shard for a stream — round-robin over the CURRENTLY
        active shards, so eviction re-homes streams onto survivors."""
        return self.active_shards[stream % len(self.active_shards)]

    def _shard_fn(self, shard: int):
        return self._infer if self._shard_infer is None \
            else self._shard_infer[shard]

    def _rebuild_hedge(self):
        old = self._hedge
        self._hedge = HedgedExecutor(
            self._hedge_cfg,
            [self._shard_fn(s) for s in self.active_shards])
        if old is not None:
            self._hedge.lat.extend(old.lat)
            self._hedge.hedges = old.hedges
            old.close()

    @property
    def hedged_dispatches(self) -> int:
        return 0 if self._hedge is None else self._hedge.hedges

    def _infer_batch_dev(self, frames, shard=None):
        """Shard-aware detector dispatch returning DEVICE arrays
        ``(boxes, scores)`` — nothing here blocks on the computation.
        In sharded mode the batch runs on the shard's own committed
        device (jit follows the committed params); otherwise on the
        single default-device detector.  With a fault schedule armed, the
        dispatch's simulated step time (base cost × the schedule's shard
        slowdown) feeds the straggler detector, and the call hedges
        across active shards when the primary would blow the
        latency-quantile deadline."""
        n_rows = frames[0].shape[0] if isinstance(frames, tuple) \
            else frames.shape[0]
        self.counters.dispatches["detect"] += 1
        self.counters.detector_rows += int(n_rows)
        if shard is not None and self.faults is not None:
            base = n_rows / max(self.cfg.shard_capacity_fps, 1e-6)
            slow = self.faults.shard_slowdown(shard, self._t)
            self.straggler.record(shard, base * slow)
            if self._hedge is not None and len(self.active_shards) > 1 \
                    and shard in self.active_shards:
                idx = self.active_shards.index(shard)

                def sim(i):
                    return base * self.faults.shard_slowdown(
                        self.active_shards[i], self._t)

                out, _ = self._hedge.run(frames,
                                         simulate_latency=sim, primary=idx)
                return out
        fn = self._infer if (shard is None or self._shard_infer is None) \
            else self._shard_infer[shard]
        return fn(frames)

    def _infer_batch(self, frames, shard=None):
        """Legacy host-facing executor (``PipelineQueues.drain_fused``):
        the device dispatch plus an immediate transfer per row."""
        if self.roi is not None:
            raise RuntimeError(
                "the legacy frame-payload drain cannot run in ROI mode — "
                "region scores are staged per ticket; use "
                "submit_chunk/flush/poll (process_chunk)")
        boxes, scores = self._infer_batch_dev(jnp.asarray(frames), shard)
        out = np.asarray(boxes), np.asarray(scores)
        self.counters.d2h_bytes += out[0].nbytes + out[1].nbytes
        return list(zip(*out))

    # ------------------------------------------------- degradation ladder
    def _stats(self, stream: int) -> StreamStats:
        if stream not in self.stats:
            self.stats[stream] = StreamStats(stream)
        return self.stats[stream]

    def suggest_level(self, stream: int, base_level: int) -> int:
        """Ladder rung the stream should encode at: its bandwidth-derived
        rung minus any deadline-driven demotion (rung 2)."""
        st = self._stats(stream)
        return max(int(base_level) - st.rung_demotion, 0)

    def note_stall(self, stream: int, t: int):
        st = self._stats(stream)
        st.chunks_stalled += 1
        st.note(t, "stall", "camera produced no chunk")

    def note_chunk_latency(self, stream: int, t: int, latency_s: float):
        """Feed one chunk's end-to-end latency into the ladder controller:
        consecutive deadline misses demote (rung 2) then force reuse
        (rung 3); consecutive on-deadline chunks walk back up."""
        st = self._stats(stream)
        d = self.degrade
        if latency_s > d.deadline_s:
            st.deadline_misses += 1
            st._miss_streak += 1
            st._ok_streak = 0
            if st._miss_streak >= d.demote_patience:
                st._miss_streak = 0
                if st.rung_demotion < d.max_demotion:
                    st.rung_demotion += 1
                    st.demote_events += 1
                    st.note(t, "demote",
                            f"latency {latency_s:.3f}s > deadline; "
                            f"rung -{st.rung_demotion}")
                elif not st.force_reuse:
                    st.force_reuse = True
                    st.note(t, "force_reuse",
                            "bottom rung still missing deadline")
        else:
            st._ok_streak += 1
            st._miss_streak = 0
            if st._ok_streak >= d.promote_patience:
                st._ok_streak = 0
                if st.force_reuse:
                    st.force_reuse = False
                    st.note(t, "resume_infer", "deadline met; leaving "
                            "pipeline-3 fallback")
                elif st.rung_demotion > 0:
                    st.rung_demotion -= 1
                    st.promote_events += 1
                    st.note(t, "promote", f"rung -{st.rung_demotion}")

    def _deliver(self, stream: int, t: int) -> bool:
        """Rung 1: was the chunk's payload delivered (possibly after
        retries)?  Retransmissions traverse the same degraded link and
        each backoff eats deadline budget; accumulated backoff is charged
        to the chunk via ``last_penalty_s``."""
        st = self.stats[stream]
        f, d = self.faults, self.degrade
        lost = f.chunk_lost(stream, t)
        corrupt = f.chunk_corrupt(stream, t)
        if not (lost or corrupt):
            return True
        if lost:
            st.chunks_lost += 1
        if corrupt:
            st.chunks_corrupt += 1
        penalty = 0.0
        for attempt in range(d.max_retries):
            backoff = d.retry_backoff_s * (2 ** attempt)
            if penalty + backoff > d.deadline_s:
                break
            penalty += backoff
            st.retries += 1
            if f.retry_succeeds(stream, t, attempt):
                st.last_penalty_s = penalty
                st.note(t, "retry_ok",
                        f"attempt {attempt + 1}, +{penalty:.3f}s")
                return True
        st.last_penalty_s = penalty
        st.note(t, "retry_exhausted",
                f"{'lost' if lost else 'corrupt'} chunk undeliverable")
        return False

    def _skip_chunk(self, stream: int, t: int,
                    packet: HybridPacket) -> ChunkTicket:
        """Rungs 3/4 for an undeliverable chunk: hold the previous
        detections (zero-motion pipeline-③) when a carry exists, else
        drop the chunk with explicit accounting (types == 0).  The carry
        stays on device; the hold is a broadcast, not a transfer."""
        st = self.stats[stream]
        T = packet.types.shape[0]
        H, W = packet.anchor_hd.shape[1:]
        n_cells = (H // self.det_cfg.stride) * (W // self.det_cfg.stride)
        prev = self.streams.get(stream)
        tk = ChunkTicket(stream, t, self.stream_shard(stream),
                         np.zeros(T, packet.types.dtype), (H, W),
                         n_cells=n_cells, done=True)
        if prev is not None and prev.last_boxes.shape[0] == n_cells:
            tk.types = np.full(T, 3, packet.types.dtype)
            tk._dev_out = _hold_chunk(prev.last_boxes, prev.last_scores,
                                      T=T)
            self.counters.dispatches["hold"] += 1
            st.frames_reused += T
            st.reuse_fallback_chunks += 1
            st.last_delivered = T
            st.note(t, "reuse_hold",
                    f"{T} frames held on carried detections")
            return tk
        st.frames_skipped += T
        st.last_skipped = T
        st.note(t, "frame_skip", f"{T} frames dropped (no carry)")
        tk._host = (np.zeros((T, n_cells, 4), f32),
                    np.zeros((T, n_cells), f32), tk.types)
        return tk

    def hold_chunk(self, stream: int, t: int,
                   packet: HybridPacket) -> ChunkTicket:
        """Predictive admission: withhold a chunk the forecast says the
        link cannot deliver inside the deadline, BEFORE transmitting it.
        Same degradation semantics as an undeliverable chunk (pipeline-③
        hold on the carried detections, frame-skip without a carry), but
        entered proactively by the caller's bandwidth forecast rather
        than reactively after a miss — no bits are charged and no
        deadline penalty accrues.  Accounting mirrors ``submit_chunk``
        (frames_in grows; the invariant frames_in == inferred + reused +
        skipped holds)."""
        self._t = t
        st = self._stats(stream)
        T = packet.types.shape[0]
        st.chunks += 1
        st.frames_in += T
        st.last_penalty_s = 0.0
        st.last_transmitted = False
        st.last_delivered = st.last_inferred = st.last_skipped = 0
        st.note(t, "forecast_hold",
                "predicted bandwidth below deadline; chunk withheld")
        return self._skip_chunk(stream, t, packet)

    # --------------------------------------------------- submit/flush/poll
    def submit_chunk(self, stream: int, t: int,
                     packet: HybridPacket) -> ChunkTicket:
        """Non-blocking admission of one chunk: run the host-side control
        ladder (delivery retries, forced reuse, admission/demotion), stage
        the chunk's execution planes on device, enqueue its pipeline-①/②
        requests, and return a :class:`ChunkTicket`.  No device work is
        waited on; the detector dispatch happens at ``flush`` and results
        cross to the host only at ``poll``.

        Per-stream ordering: submitting a stream's next chunk while its
        previous ticket is still pending first flushes the pipeline, so
        the pipeline-③ carry chain stays ordered (on device)."""
        with TraceAnnotation("edge.submit", stream=stream, chunk=t,
                             shard=self.stream_shard(stream)):
            return self._submit_chunk(stream, t, packet)

    def _submit_chunk(self, stream: int, t: int,
                      packet: HybridPacket) -> ChunkTicket:
        self._t = t
        prev_tk = self._open.get(stream)
        if prev_tk is not None and not prev_tk.done:
            self.flush()

        st = self._stats(stream)
        T = packet.types.shape[0]
        st.chunks += 1
        st.frames_in += T
        st.last_penalty_s = 0.0
        st.last_transmitted = True
        st.last_delivered = st.last_inferred = st.last_skipped = 0

        if self.faults is not None and not self._deliver(stream, t):
            st.last_transmitted = False
            return self._skip_chunk(stream, t, packet)

        enc = packet.video
        H, W = packet.anchor_hd.shape[1:]
        types = packet.types.copy()
        prev = self.streams.get(stream)
        shard = self.stream_shard(stream)

        if st.force_reuse and prev is not None:
            # rung 3: ladder floor exhausted — whole chunk on pipeline ③
            # with the packet's REAL motion vectors (payload did arrive)
            types = np.full_like(types, 3)
            st.reuse_fallback_chunks += 1
            self.reuse_fallback_chunks[shard] += 1
            st.note(t, "reuse_chunk", "forced pipeline-3 chunk")

        n_infer = int((types != 3).sum())
        if n_infer and not self.admission.admit_shard(
                self.queues.shard_depths, shard, n_infer):
            # overload: demote transfer frames to reuse, keep chunk anchors
            self.demoted_frames[shard] += int((types == 2).sum())
            types = np.where(types == 2, 3, types)
            self.deferred += 1
            self.deferred_by_shard[shard] += 1
            st.note(t, "defer", "shard overloaded; type-2 frames demoted")
            # deep overload: if even anchors-only blows the budget AND we
            # have carried detections to reuse, the whole chunk runs on
            # pipeline ③ (the previous chunk's boxes keep tracking via MVs)
            if prev is not None and \
                    not self.admission.admit_shard(self.queues.shard_depths,
                                                   shard,
                                                   int((types != 3).sum())):
                self.demoted_frames[shard] += int((types != 3).sum())
                types = np.full_like(types, 3)
                self.reuse_fallback_chunks[shard] += 1
                st.reuse_fallback_chunks += 1
                st.note(t, "reuse_chunk", "deep overload")

        # one async dispatch stages the whole chunk on device; values stay
        # there until the poll boundary (anchor_search additionally stages
        # the per-rung bit planes in the SAME dispatch)
        host = (types, packet.anchor_hd, enc.recon, enc.mv, enc.residual_q,
                enc.qtab)
        nbytes = sum(a.nbytes for a in host if isinstance(a, np.ndarray))
        with TraceAnnotation("edge.h2d", stream=stream, chunk=t,
                             bytes=nbytes):
            dev = [jnp.asarray(a) for a in host]
        self.counters.h2d_bytes += nbytes
        with TraceAnnotation("edge.stage", stream=stream, chunk=t):
            frames_dev, mvs_dev, rscores_dev, rung_bits_dev = _stage_chunk(
                *dev, hd_hw=(H, W), roi=self.roi,
                anchor_search=self.anchor_search)
        self.counters.dispatches["stage"] += 1

        n_cells = (H // self.det_cfg.stride) * (W // self.det_cfg.stride)
        tk = ChunkTicket(stream, t, shard, types, (H, W),
                         frames_dev=frames_dev, mvs_dev=mvs_dev,
                         rscores_dev=rscores_dev,
                         rung_bits_dev=rung_bits_dev,
                         init_b=None if prev is None else prev.last_boxes,
                         init_s=None if prev is None else prev.last_scores,
                         n_cells=n_cells)
        for i in range(T):
            if types[i] in (1, 2):
                req = InferRequest(stream, t, int(i), int(types[i]),
                                   None, shard=shard)
                self.queues.submit(req)
                tk.reqs.append(req)

        n_inf = int(((types == 1) | (types == 2)).sum())
        st.frames_inferred += n_inf
        st.frames_reused += int((types == 3).sum())
        st.last_inferred = n_inf
        st.last_delivered = T
        self._pending.append(tk)
        self._open[stream] = tk
        return tk

    def _dispatch_group(self, shard: int, tickets: list[ChunkTicket]):
        """Dispatch one (shard, T, H, W) signature group: gather every
        ticket's pipeline-①/② frames into one padded batch (① rows before
        ②, submit order within each, matching the legacy drain), run the
        detector asynchronously under the double-buffer cap, and finish
        each ticket's scatter+reuse on device."""
        T = int(tickets[0].types.shape[0])
        reqs = [r for tk in tickets for r in tk.reqs if r.pipeline == 1] \
            + [r for tk in tickets for r in tk.reqs if r.pipeline == 2]
        self.queues.take(reqs)

        n = len(reqs)
        n_pad = _pad_bucket(n, self.cfg.batch_size) if n else 0
        with TraceAnnotation("edge.dispatch", shard=shard, rows=n_pad,
                             valid=n):
            bb, bs = self._dispatch_detector(shard, tickets, reqs, T, n_pad)
            for tk in tickets:
                with TraceAnnotation("edge.finish", stream=tk.stream,
                                     chunk=tk.chunk_t):
                    self._finish_ticket(tk, reqs, T, bb, bs)

    def _dispatch_detector(self, shard: int, tickets: list[ChunkTicket],
                           reqs: list, T: int, n_pad: int):
        """Gather the group's rows into one padded batch and dispatch the
        detector under the ``max_inflight`` cap; (None, None) without
        rows."""
        if not reqs:
            return None, None
        by_stream = {tk.stream: tk for tk in tickets}
        slot = {id(tk): i for i, tk in enumerate(tickets)}
        flat_idx = np.zeros(n_pad, np.int32)
        valid = np.zeros(n_pad, bool)
        for j, r in enumerate(reqs):
            flat_idx[j] = slot[id(by_stream[r.stream])] * T + r.frame_idx
            valid[j] = True
        k_pad = _pad_bucket(len(tickets), 1)
        with TraceAnnotation("edge.gather"):
            planes = tuple(tk.frames_dev for tk in tickets) \
                + (tickets[0].frames_dev,) * (k_pad - len(tickets))
            batch = _gather_batch(planes, jnp.asarray(flat_idx),
                                  jnp.asarray(valid))
            if self.roi is not None:
                rows = tuple(tk.rscores_dev for tk in tickets) \
                    + (tickets[0].rscores_dev,) * (k_pad - len(tickets))
                batch = (batch, _gather_rows(rows, jnp.asarray(flat_idx),
                                             jnp.asarray(valid)))
        self.counters.dispatches["gather"] += 1
        q = self._inflight[shard]
        if len(q) >= self.max_inflight:
            with TraceAnnotation("edge.inflight_wait"):
                while len(q) >= self.max_inflight:
                    jax.block_until_ready(q.popleft())
                    self.counters.inflight_waits += 1
        with TraceAnnotation("edge.detect"):
            bb, bs = self._infer_batch_dev(batch, shard=shard)
            if self._shard_infer is not None:
                # finish on the staging device: the carry must live on ONE
                # device regardless of which shard ran the batch
                home = next(iter(tickets[0].mvs_dev.devices()))
                bb, bs = jax.device_put((bb, bs), home)
        q.append((bb, bs))
        return bb, bs

    def _finish_ticket(self, tk: ChunkTicket, reqs: list, T: int, bb, bs):
        """Scatter the ticket's rows out of the batch and run pipeline-③
        reuse on device (``_finish_chunk``); the ticket is then done."""
        pos = np.full(T, -1, np.int32)
        for j, r in enumerate(reqs):
            if r.stream == tk.stream:
                pos[r.frame_idx] = j
        if bb is None:
            dbb = jnp.zeros((1, tk.n_cells, 4), jnp.float32)
            dbs = jnp.zeros((1, tk.n_cells), jnp.float32)
        else:
            dbb, dbs = bb, bs
        has_init = tk.init_b is not None
        zb = jnp.zeros((tk.n_cells, 4), jnp.float32)
        zs = jnp.zeros((tk.n_cells,), jnp.float32)
        boxes, scores, last_b, last_s = _finish_chunk(
            jnp.asarray(tk.types), jnp.asarray(pos), tk.mvs_dev,
            dbb, dbs,
            tk.init_b if has_init else zb,
            tk.init_s if has_init else zs, has_init=has_init)
        self.counters.dispatches["finish"] += 1
        self.streams[tk.stream] = StreamState(last_b, last_s)
        tk._dev_out = (boxes, scores)
        tk.done = True
        tk.frames_dev = tk.mvs_dev = tk.init_b = tk.init_s = None
        tk.rscores_dev = None
        if self._open.get(tk.stream) is tk:
            del self._open[tk.stream]

    def flush(self, shard: int | None = None):
        """Dispatch every pending ticket (optionally one shard's) —
        continuous batching: tickets submitted since the last flush form
        the NEXT padded batch-signature groups while earlier batches are
        still computing on device."""
        todo = [tk for tk in self._pending
                if not tk.done and (shard is None or tk.shard == shard)]
        with TraceAnnotation("edge.flush", tickets=len(todo)):
            groups: dict[tuple, list[ChunkTicket]] = {}
            for tk in todo:
                key = (tk.shard, int(tk.types.shape[0]), *tk.hw)
                groups.setdefault(key, []).append(tk)
            for key in sorted(groups):
                self._dispatch_group(key[0], groups[key])
            self._pending = [tk for tk in self._pending if not tk.done]

    def poll(self, ticket: ChunkTicket):
        """Block until the ticket's chunk is finished and return per-frame
        ``(boxes, scores, types)`` as host arrays — the ONE device->host
        transfer on the chunk path."""
        with TraceAnnotation("edge.poll", stream=ticket.stream,
                             chunk=ticket.chunk_t):
            if ticket._host is None:
                if not ticket.done:
                    self.flush()
                boxes, scores = ticket._dev_out
                # the wait, apart from the copy (np.asarray would block)
                with TraceAnnotation("edge.poll_wait", stream=ticket.stream,
                                     chunk=ticket.chunk_t):
                    jax.block_until_ready(ticket._dev_out)
                nbytes = boxes.nbytes + scores.nbytes
                with TraceAnnotation("edge.d2h", stream=ticket.stream,
                                     chunk=ticket.chunk_t, bytes=nbytes):
                    ticket._host = (np.asarray(boxes), np.asarray(scores),
                                    ticket.types)
                self.counters.d2h_bytes += nbytes
                ticket._dev_out = None
            return ticket._host

    def poll_all(self, tickets):
        """Flush once, then materialize every ticket."""
        self.flush()
        return [self.poll(tk) for tk in tickets]

    # ------------------------------------------------------------------
    def process_chunk(self, stream: int, t: int, packet: HybridPacket):
        """Synchronous convenience wrapper: submit + flush + poll one
        chunk.  Returns per-frame (boxes, scores, types).

        All pipeline-①/② frames of the chunk go through ONE padded detector
        invocation on the stream's OWN mesh shard instead of one dispatch
        per frame; admission reads that shard's queue depths before the
        chunk is enqueued (a hot shard defers its streams to pipeline-③
        reuse without stalling the other shards), and pipeline ③ carries
        the previous chunk's last detections across the chunk boundary.

        With a fault schedule armed, the chunk first runs the delivery
        ladder (loss/corruption → retries → reuse-hold/frame-skip) and a
        stream in forced-reuse state routes the whole delivered chunk to
        pipeline ③.  Returned ``types`` may then contain 0 (explicitly
        skipped frames) alongside the usual 1/2/3.
        """
        return self.poll(self.submit_chunk(stream, t, packet))

    def close(self):
        """Tear down the dispatch plane: retire in-flight batches and shut
        the hedge executor's thread pool.  Idempotent."""
        for q in self._inflight.values():
            while q:
                jax.block_until_ready(q.popleft())
        if self._hedge is not None:
            self._hedge.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -------------------------------------------- eviction and recovery
    def evict_shard(self, shard: int, t: int, reason: str = "straggler"):
        """Remove a shard from service: queued requests AND pending
        (undispatched) tickets re-home onto survivor shards; future
        ``stream_shard`` routing skips it.  Batches already dispatched to
        the evicted device are kept — their results are in flight and
        identical, so admitted streams never lose work.  The LAST shard is
        never evicted (the plane degrades, it does not abandon admitted
        streams)."""
        if shard not in self.active_shards or len(self.active_shards) <= 1:
            return False
        self.pool.fail(shard)
        self.active_shards.remove(shard)
        moved = self.queues.remap_shards(self.stream_shard)
        for tk in self._pending:
            if not tk.done:
                tk.shard = self.stream_shard(tk.stream)
        self.straggler.reset(shard)
        if self._hedge is not None:
            self._rebuild_hedge()
        self.fault_log.append(
            (int(t), "evict",
             f"shard {shard} ({reason}); {moved} queued requests re-homed; "
             f"survivors {self.active_shards}"))
        return True

    def recover_shard(self, shard: int, t: int):
        if shard in self.active_shards or not 0 <= shard < self.n_shards:
            return False
        self.pool.recover(shard)
        self.active_shards = sorted(self.active_shards + [shard])
        self.straggler.reset(shard)
        if self._hedge is not None:
            self._rebuild_hedge()
        self.fault_log.append(
            (int(t), "recover",
             f"shard {shard} re-admitted; active {self.active_shards}"))
        return True

    def poll_faults(self, t: int):
        """Once-per-chunk control step: evict shards the straggler
        detector flags; re-admit evicted shards once the fault schedule
        reports them healthy (slowdown back to 1.0)."""
        self._t = t
        for shard in self.straggler.flagged():
            self.evict_shard(shard, t)
        if self.faults is not None:
            for g in range(self.n_shards):
                if g not in self.active_shards and \
                        self.faults.shard_slowdown(g, t) <= 1.0:
                    self.recover_shard(g, t)

    # ------------------------------------------------------------------
    def compute_latency(self, types: np.ndarray, bits: float,
                        bw_kbps: float, stream: int | None = None) -> dict:
        """Latency model for one chunk.  With ``stream`` given, queueing
        delay comes from that stream's shard backlog against the shard's
        capacity slice (identical to the global estimate at n_shards=1)."""
        n1 = int((types == 1).sum())
        n2 = int((types == 2).sum())
        n3 = int((types == 3).sum())
        t_comp = pipeline_cost(n1, n2, n3, self.costs)
        if stream is None:
            t_queue = float(self.queues.depths.sum()) \
                / self.cfg.gpu_capacity_fps
        else:
            shard = self.stream_shard(stream)
            t_queue = float(self.queues.shard_depths[shard].sum()) \
                / self.cfg.shard_capacity_fps
        t_trans = bits / max(bw_kbps * 1000.0, 1e-6)
        return {"t_trans": t_trans, "t_queue": t_queue, "t_comp": t_comp,
                "total": t_trans + t_queue + t_comp}
