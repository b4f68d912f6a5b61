"""The serving runtime's own instrumentation, on the CPU at a small size:
the ``edge.*`` host spans in the profiler's trace (names, nesting, ids),
``EdgeRuntime.counters`` against what the benchmark counts from outside,
the per-program trace counts, results unchanged by the profiler, the
XLA module names the benchmark's module table matches, and the
launcher's latency, read from the host clock."""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

import jax
import numpy as np
import pytest

from repro.serving import runtime as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# span -> the span it sits in (edge.flush also runs inside submit or poll)
PARENT = {
    "edge.submit": None,
    "edge.h2d": "edge.submit",
    "edge.stage": "edge.submit",
    "edge.flush": None,
    "edge.dispatch": "edge.flush",
    "edge.gather": "edge.dispatch",
    "edge.inflight_wait": "edge.dispatch",
    "edge.detect": "edge.dispatch",
    "edge.finish": "edge.dispatch",
    "edge.poll": None,
    "edge.poll_wait": "edge.poll",
    "edge.d2h": "edge.poll",
}


def _runtime(n_streams=2, **cfg_kw):
    from repro.models import detection as D
    from repro.serving.scheduler import ServingConfig
    det_cfg = D.TinyDetectorConfig()
    params = D.init(jax.random.PRNGKey(1), det_cfg)
    cfg = ServingConfig(n_streams=n_streams, batch_size=2, **cfg_kw)
    return R.EdgeRuntime(cfg, params, det_cfg)


def _host_packet(seed, T=3, height=32, width=48):
    """A packet as the edge receives it: every array in host memory."""
    from repro.core.hybrid_encoder import encode_hybrid
    from repro.sim.video_source import StreamConfig, generate_chunk
    frames, _, _ = generate_chunk(
        None, StreamConfig(height=height, width=width, seed=seed), 0, T)
    pk = encode_hybrid(np.asarray(frames), 8000.0, 0.05, 0.1)
    return dataclasses.replace(pk, video=jax.tree.map(np.asarray, pk.video))


def _round(rt, t, packets):
    """The benchmark's round: submit and flush each stream, then poll."""
    tickets = []
    for s, pk in enumerate(packets):
        tickets.append(rt.submit_chunk(s, t, pk))
        rt.flush()
    return [rt.poll(tk) for tk in tickets]


def _edge_spans(log_dir) -> list:
    """(name, start, end, args, thread line) of every ``edge.*`` host
    event in the profile written under ``log_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("edge."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats),
                                (plane.name, i)))
    return out


def _innermost_parent(span, spans):
    name, s, e, _, line = span
    best = None
    for o in spans:
        if o is span or o[4] != line or not (o[1] <= s and e <= o[2]):
            continue
        if best is None or o[1] >= best[1]:
            best = o
    return best


@pytest.fixture(scope="module")
def packets():
    return [_host_packet(seed) for seed in range(2)]


def test_spans_are_recorded_nested_and_carry_the_chunk_ids(packets,
                                                           tmp_path):
    # max_inflight 1: the second dispatch of a round retires the first
    rt = _runtime(max_inflight=1)
    _round(rt, 0, packets)
    with jax.profiler.trace(str(tmp_path)):
        _round(rt, 1, packets)
        rt.poll(rt.submit_chunk(0, 2, packets[0]))   # flush inside poll
    rt.close()
    spans = _edge_spans(tmp_path)
    assert {sp[0] for sp in spans} == set(PARENT)
    for sp in spans:
        parent = _innermost_parent(sp, spans)
        want = PARENT[sp[0]]
        if want is not None:
            assert parent is not None and parent[0] == want, sp
        elif sp[0] == "edge.flush" and parent is not None:
            assert parent[0] in ("edge.submit", "edge.poll"), sp
        else:
            assert parent is None, (sp, parent)
    submits = [sp[3] for sp in spans if sp[0] == "edge.submit"]
    assert [(a["stream"], a["chunk"]) for a in submits] == \
        [(0, 1), (1, 1), (0, 2)]
    assert all(a["shard"] == 0 for a in submits)
    polls = [sp[3] for sp in spans if sp[0] == "edge.poll"]
    assert [(a["stream"], a["chunk"]) for a in polls] == \
        [(0, 1), (1, 1), (0, 2)]
    # a stream-chunk's spans share its ids
    for name in ("edge.h2d", "edge.stage", "edge.finish", "edge.poll_wait",
                 "edge.d2h"):
        got = [(sp[3]["stream"], sp[3]["chunk"]) for sp in spans
               if sp[0] == name]
        assert got == [(0, 1), (1, 1), (0, 2)], name
    for sp in spans:
        if sp[0] == "edge.dispatch":
            a = sp[3]
            assert a["shard"] == 0 and 0 < a["valid"] <= a["rows"]
            assert a["rows"] % 2 == 0                 # batch_size buckets
        if sp[0] == "edge.flush":
            assert sp[3]["tickets"] == 1
    h2d = [sp[3]["bytes"] for sp in spans if sp[0] == "edge.h2d"]
    assert h2d == [_crossing_bytes(packets[s]) for s in (0, 1, 0)]
    d2h = [sp[3]["bytes"] for sp in spans if sp[0] == "edge.d2h"]
    T, H, W = packets[0].anchor_hd.shape
    cells = (H // 8) * (W // 8)
    assert d2h == [T * cells * 5 * 4] * 3              # f32 boxes + scores


def _crossing_bytes(pk):
    """The benchmark's packet bytes less the codec's per-frame features
    (bits, residual magnitude, frame difference): those stay on the
    host."""
    from bench import harness
    v = pk.video
    return harness.packet_bytes(pk) - sum(
        np.asarray(x).nbytes for x in (v.bits, v.residual_mag, v.frame_diff))


def test_counters_match_what_the_benchmark_counts_from_outside(packets):
    from bench import harness
    rt = _runtime()
    counter = harness._RowCounter(rt._infer)
    rt._infer = counter
    counter.on = True
    for t in range(3):
        outs = _round(rt, t, packets)
    c = rt.counters
    assert c.detector_rows == sum(counter.rows) > 0
    assert c.dispatches["detect"] == len(counter.rows)
    assert c.h2d_bytes == 3 * sum(_crossing_bytes(pk) for pk in packets)
    assert c.d2h_bytes == 3 * sum(b.nbytes + s.nbytes for b, s, _ in outs)
    assert c.dispatches["stage"] == c.dispatches["finish"] == 6
    assert c.dispatches["gather"] == 6 and c.dispatches["hold"] == 0
    assert c.inflight_waits == 6 - rt.max_inflight
    inferred = sum(st.frames_inferred for st in rt.stats.values())
    assert 0 < inferred <= c.detector_rows
    # a held chunk: one hold dispatch, its result copied at poll
    before = c.as_dict()
    rt.poll(rt.hold_chunk(0, 3, packets[0]))
    assert c.dispatches["hold"] == 1
    assert c.d2h_bytes - before["d2h_bytes"] == outs[0][0].nbytes \
        + outs[0][1].nbytes
    assert before["dispatches"]["hold"] == 0          # a snapshot, not a view
    rt.close()


def test_a_second_identical_round_traces_nothing(packets):
    rt = _runtime()
    _round(rt, 0, packets)
    _round(rt, 1, packets)                  # the carry's first use
    traces = dict(R.TRACE_COUNTS)
    assert {"stage", "gather", "detect", "finish"} <= set(traces)
    dispatched = rt.counters.as_dict()["dispatches"]
    _round(rt, 2, packets)
    assert dict(R.TRACE_COUNTS) == traces
    assert rt.counters.dispatches["stage"] == dispatched["stage"] + 2
    rt.close()


def test_results_are_bit_identical_with_the_profiler_on(packets, tmp_path):
    def serve(trace_dir=None):
        rt = _runtime()
        outs = []
        for t in range(2):
            if trace_dir is None:
                outs += _round(rt, t, packets)
            else:
                with jax.profiler.trace(str(trace_dir / str(t))):
                    outs += _round(rt, t, packets)
        rt.close()
        return outs
    for a, b in zip(serve(), serve(tmp_path)):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def test_lowered_module_names_are_those_the_benchmark_matches():
    """Each serving jit lowers to the module name ``bench/modules.json``
    lists: a named scope changes op metadata, not the module's name."""
    with open(os.path.join(ROOT, "bench", "modules.json")) as f:
        table = json.load(f)
    listed = {n for names in table["layers"].values() for n in names}
    lowered = []

    def cb(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            # JAX's module name: "jit(_stage_chunk)" -> "jit__stage_chunk"
            lowered.append(re.sub(r"[^\w.-]", "_", kw["fun_name"])
                           .rstrip("_"))

    jax.monitoring.register_event_duration_secs_listener(cb)
    try:
        rt = _runtime()                       # a fresh shape: all lower
        _round(rt, 0, [_host_packet(s, height=48, width=64)
                       for s in range(2)])
        rt.close()
        x = jax.numpy.zeros((3, 4))               # (T, R) region scores
        text = R._gather_rows.lower(
            (x, x), jax.numpy.zeros(4, np.int32),
            jax.numpy.ones(4, bool)).as_text()
    finally:
        jax.monitoring.unregister_event_duration_listener(cb)
    assert "module @jit__gather_rows" in text and \
        lowered[-1] == "jit__gather_rows"
    assert table["per_chunk"] in lowered
    assert listed <= set(lowered), (listed, lowered)


def test_serve_reports_the_measured_process_chunk_time(monkeypatch):
    """The launcher's ``lat=`` and ``mean_latency_s`` are the host clock
    around ``process_chunk``, not the cost model ``compute_latency``."""
    import time

    from repro.launch import serve

    def model(*a, **kw):
        raise AssertionError("the launcher read the cost model")

    monkeypatch.setattr(R.EdgeRuntime, "compute_latency", model)
    calls = []
    orig = R.EdgeRuntime.process_chunk

    def timed(self, *a):
        t0 = time.perf_counter()
        out = orig(self, *a)
        calls.append(time.perf_counter() - t0)
        return out

    monkeypatch.setattr(R.EdgeRuntime, "process_chunk", timed)
    summary = serve.main(["--streams", "2", "--chunks", "2",
                          "--quick-train", "0"])
    assert len(calls) == 4
    # the launcher's clock brackets the wrapper's: a few us more
    assert np.mean(calls) <= summary["mean_latency_s"] \
        < np.mean(calls) + 0.05
