"""One run of one cell: set-up, the measured window, the correctness
check and the metrics.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``configs/<name>.json``, the detector that
configuration's ``detector.arch`` names in ``detectors/<arch>.py``, its
traffic mix in ``traffic/<name>.json`` and each metric's reader in
``metrics/<name>.py``.  The program under test is the edge runtime
(``repro.serving.runtime.EdgeRuntime``) driven through ``submit_chunk``,
``flush`` and ``poll``; the camera side (``encode_hybrid``) makes its
input packets during set-up.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
import types as pytypes

import numpy as np

from bench.loader import detector, reader

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SAMPLE_CHUNKS = 8        # chunks of the window compared with the reference
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_spec(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_cell(workload: str, overrides: dict | None = None):
    """(benchmark, cell, configuration, traffic) with test overrides
    merged into the configuration and the traffic."""
    bench = benchmark()
    cell = cell_spec(bench, workload)
    cfg = _json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    traffic = _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))
    return bench, cell, cfg, traffic


def metrics_of(bench: dict, kind: str, workload: str) -> list:
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# ------------------------------------------------------------ set-up parts
def make_weights(seed: int, det: dict):
    """The detector's weights, made on the device from the seed."""
    from bench.video import WEIGHTS, seed_key
    return detector(det["arch"]).weights(seed_key(seed, WEIGHTS), det)


def make_packets(seed: int, cfg: dict, traffic: dict) -> list:
    """Per camera, ``pool_chunks`` consecutive packets as the edge
    receives them: rendered from the seed, encoded by the program's
    camera side at the camera's share of the uplink, every array moved
    to host memory."""
    import jax

    from bench import video
    from repro.core.hybrid_encoder import encode_hybrid

    H, W, T = cfg["height"], cfg["width"], cfg["frames_per_chunk"]
    bw = cfg["uplink_kbps"] / cfg["cameras"]
    pool = []
    for c, cam in enumerate(video.cameras(traffic, cfg["cameras"])):
        sc = video.scene(video.seed_key(seed, video.CAMERAS, c), cam, H, W)
        row = []
        for k in range(traffic["pool_chunks"]):
            raw = video.render(sc, float(k * T), n_frames=T,
                               contrast=cam.texture_contrast,
                               freq=cam.texture_freq)
            pk = encode_hybrid(raw, bw, traffic["tr1"], traffic["tr2"],
                               fps=cfg["fps"])
            row.append(dataclasses.replace(
                pk, video=jax.tree.map(np.asarray, pk.video)))
        pool.append(row)
    return pool


def packet_bytes(pk) -> int:
    import jax
    return int(pk.anchor_hd.nbytes + pk.types.nbytes + sum(
        np.asarray(x).nbytes for x in jax.tree.leaves(pk.video)))


def packet_arrays(pk) -> dict:
    v = pk.video
    return dict(types=np.asarray(pk.types), anchor_hd=pk.anchor_hd,
                recon=v.recon, mv=v.mv, residual_q=v.residual_q,
                qtab=v.qtab)


def make_runtime(cfg: dict, params):
    """The program's edge runtime for the configuration, with the
    detector that ``detector.arch`` names."""
    from repro.serving.runtime import EdgeRuntime
    from repro.serving.scheduler import ServingConfig

    det = cfg["detector"]
    s = cfg["serving"]
    scfg = ServingConfig(n_streams=cfg["cameras"],
                         batch_size=s["batch_size"],
                         max_inflight=s["max_inflight"],
                         gpu_capacity_fps=s["gpu_capacity_fps"],
                         latency_budget=cfg["latency_budget_s"])
    return EdgeRuntime(scfg, params,
                       detector(det["arch"]).program_config(det))


# ------------------------------------------------------------- the window
@dataclasses.dataclass
class Window:
    latencies_ms: list = dataclasses.field(default_factory=list)
    spans: dict = dataclasses.field(default_factory=lambda: {
        "submit": [], "flush": [], "poll": []})
    chunks: int = 0
    attempted: int = 0
    failed: int = 0
    packet_bytes: int = 0
    detector_rows: list = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    lowerings: int = 0
    sample: list = dataclasses.field(default_factory=list)


class _RowCounter:
    """Wraps the runtime's detector callable to count the rows of each
    dispatch (the padded batch as it runs)."""

    def __init__(self, fn):
        self.fn, self.rows, self.on = fn, [], False

    def __call__(self, batch):
        if self.on:
            x = batch[0] if isinstance(batch, tuple) else batch
            self.rows.append(int(x.shape[0]))
        return self.fn(batch)


def one_round(rt, t: int, pool: list, win: Window | None, annotate, clock,
              keep=None):
    """Submit every camera's chunk ``t`` (flushing after each), then poll
    every ticket.  A chunk's latency runs from the start of its submit to
    the return of its poll."""
    n_pool = len(pool[0])
    started, tickets = [], []
    for c, row in enumerate(pool):
        pk = row[t % n_pool]
        t0 = clock()
        with annotate("bench.submit"):
            tk = rt.submit_chunk(c, t, pk)
        t1 = clock()
        with annotate("bench.flush"):
            rt.flush()
        t2 = clock()
        started.append(t0)
        tickets.append(tk)
        if win is not None:
            win.attempted += 1
            win.spans["submit"].append(t1 - t0)
            win.spans["flush"].append(t2 - t1)
    for c, tk in enumerate(tickets):
        t0 = clock()
        with annotate("bench.poll"):
            boxes, scores, types = rt.poll(tk)
        t1 = clock()
        if win is None:
            continue
        win.spans["poll"].append(t1 - t0)
        win.latencies_ms.append((t1 - started[c]) * 1e3)
        win.chunks += 1
        if not (np.isfinite(boxes.sum()) and np.isfinite(scores.sum())):
            win.failed += 1
        if keep is not None:
            keep(c, t % n_pool, (boxes, scores, types))


def reservoir(rng, k: int, sample: list):
    """Keep a uniform sample of ``k`` of all the items offered, drawn
    from ``rng``."""
    seen = [0]

    def offer(*item):
        i = seen[0]
        seen[0] += 1
        if i < k:
            sample.append(item)
        else:
            j = int(rng.integers(0, i + 1))
            if j < k:
                sample[j] = item
    return offer


@contextlib.contextmanager
def _lowering_counter(win: Window):
    import jax.monitoring

    def cb(event, duration, **kw):
        if event == LOWERING_EVENT:
            win.lowerings += 1

    jax.monitoring.register_event_duration_secs_listener(cb)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(cb)


# ------------------------------------------------------------------- a run
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, require_chip: bool = True,
        overrides: dict | None = None, peaks: dict | None = None,
        served_fn=None) -> dict:
    """Run one cell once and return the result line as a dict.

    ``require_chip=False``, ``overrides``, ``peaks`` and ``served_fn``
    exist for the benchmark's own tests and control: they drive a run on
    the CPU at a small size, or replace what the program served by
    another function's output (``served_fn(packet, params, det)``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench, cell, cfg, traffic = load_cell(workload, overrides)

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        raise NoAccelerator(
            f"need {cell['chips']} TPU chip(s), JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
    dev = devices[0]
    peak = peaks if peaks is not None else peaks_for(dev.device_kind)

    from repro.launch.compile_cache import configure_compile_cache
    if configure_compile_cache() is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import compare, reference

    det = cfg["detector"]
    params = make_weights(seed, det)
    pool = make_packets(seed, cfg, traffic)
    rt = make_runtime(cfg, params)
    counter = _RowCounter(rt._infer)
    rt._infer = counter
    clock = time.perf_counter
    annotate = jax.profiler.TraceAnnotation
    n_pool = traffic["pool_chunks"]
    for t in range(n_pool):                 # every shape the window uses
        one_round(rt, t, pool, None, annotate, clock)
    setup_s = clock() - t_start
    log(f"set-up {setup_s:.1f} s; packets: types per chunk "
        f"{[[int((pk.types != 3).sum()) for pk in row] for row in pool]} "
        "inferred")

    win = Window()
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    keep = reservoir(rng, SAMPLE_CHUNKS, win.sample)
    inferred0 = sum(st.frames_inferred for st in rt.stats.values())
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(log_dir)
    counter.on = True
    t = n_pool
    with _lowering_counter(win):
        with annotate("bench.window"):
            w0 = clock()
            while clock() - w0 < seconds:
                one_round(rt, t, pool, win, annotate, clock, keep)
                t += 1
            win.window_s = clock() - w0
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    win.detector_rows = counter.rows
    rounds = t - n_pool
    win.packet_bytes = sum(packet_bytes(row[r % n_pool])
                           for r in range(rounds) for row in pool)
    frames_inferred = sum(st.frames_inferred for st in rt.stats.values()) \
        - inferred0
    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    deferred = rt.deferred
    rt.close()
    del rt, counter
    log(f"window {win.window_s:.2f} s: {win.chunks} chunks, "
        f"{win.lowerings} lowerings (compiles) inside the window, "
        f"{deferred} chunks deferred by admission")

    # ----------------------------------------------- correctness check
    served, refs, reuse_refs = [], [], []
    ref_cache = {}
    t_ref = time.perf_counter()
    for cam, k, out in win.sample:
        pk = packet_arrays(pool[cam][k])
        if (cam, k) not in ref_cache:
            ref_cache[(cam, k)] = reference.chunk(pk, params, det,
                                                  jnp.float32)
        rb, rs, mv_hd = ref_cache[(cam, k)]
        boxes, scores, types = out if served_fn is None \
            else served_fn(pk, params, det)
        served.append((boxes, scores, types, pk["types"]))
        refs.append((rb, rs))
        reuse_refs.append(reference.reuse_from(pk["types"], boxes, mv_hd))
    nums = compare.numbers(served, refs, reuse_refs)
    log(f"reference: {len(served)} chunks ({len(ref_cache)} packets) "
        f"compared in {time.perf_counter() - t_ref:.1f} s")
    correct, checks = compare.verdict(nums, compare.limits(workload))
    # every chunk served in full: none demoted by admission, none failed
    correct = correct and win.chunks > 0 and deferred == 0 \
        and win.failed == 0

    # ------------------------------------------------------- metrics
    tr = None
    if trace:
        from bench import trace as tracemod
        tr = tracemod.load(tracemod.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        staged = tr.count(tracemod.module_table()["per_chunk"])
        log(f"trace: {tr.window_s():.2f} s of the {win.window_s:.2f}-s "
            f"window analysed, {staged} of its {win.chunks} chunks staged "
            f"there (devices that dropped events: "
            f"{sorted(tr.dropped) or 'none'})")
    ctx = pytypes.SimpleNamespace(
        setup_s=setup_s, window=win, frames_inferred=frames_inferred,
        det=det, hd=(cfg["height"], cfg["width"]),
        chunk_s=cfg["frames_per_chunk"] / cfg["fps"], trace=tr, peak=peak)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, kind, workload):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        mods = sorted(tr.module_seconds().items(), key=lambda x: -x[1])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in mods[:10]],
            "idle_gaps": [[n, s] for n, s in tr.idle_gaps()[:10]]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result
