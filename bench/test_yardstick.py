"""Tests of the benchmark's yardstick, on the CPU: the trace reduction,
the operation and byte counts, the latency and rate arithmetic, the
layout that finds a cell's files by name, the refusal to run without a
TPU, and the correctness check -- which passes the program at a small
size and fails each fault a cell can have and the bfloat16 control."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from functools import partial

import numpy as np
import pytest

from bench import compare, flops, harness, loader, stats, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = harness.benchmark()
DET = {"arch": "tiny", "channels": [16, 32, 64], "stride": 8}


# ----------------------------------------------------------- trace reduction
def _trace(dropped=None):
    """Three chunk cycles (staging, gather, detector, finish) of one
    device, in milliseconds; the third staging run is the last of the
    window span."""
    ms = 1_000_000
    modules = {"/device:TPU:0": [(n, s * ms, e * ms) for n, s, e in [
        ("jit__stage_chunk(3)", 10, 20), ("jit__gather_batch", 20, 21),
        ("jit__lambda.7", 21, 31), ("jit__finish_chunk(9)", 30, 35),
        ("jit__stage_chunk(3)", 45, 60), ("jit__lambda.7", 62, 72),
        ("jit__finish_chunk(9)", 72, 75), ("jit__stage_chunk(3)", 90, 99)]]}
    spans = [(n, s * ms, e * ms) for n, s, e in [
        ("bench.window", 0, 120), ("bench.submit", 0, 12),
        ("bench.flush", 12, 40), ("bench.poll", 40, 50),
        ("bench.submit", 50, 52), ("bench.flush", 52, 80),
        ("bench.poll", 80, 120)]]
    return trace.Trace(modules, {"/device:TPU:0": dropped * ms}
                       if dropped else {}, spans)


def test_trace_window_ends_where_a_device_dropped_events():
    # the drop at 99 ms cuts the third staging run short: the window
    # still ends where that run starts, and it is not counted
    tr = _trace(dropped=99)
    assert tr.window_s() == pytest.approx(0.080)
    assert tr.count("jit__stage_chunk") == 2
    # a drop at 50 ms leaves one whole cycle, 10-45 ms
    tr = _trace(dropped=50)
    assert tr.window_s() == pytest.approx(0.035)
    assert tr.busy_s() == pytest.approx(0.025)
    assert tr.count("jit__stage_chunk") == 1
    # with no whole cycle before the drop, the span cut at the drop
    tr = _trace(dropped=40)
    assert tr.window() == (0, 40_000_000)


def test_per_chunk_device_time_divides_by_the_per_chunk_module_runs():
    table = trace.module_table()
    tr = _trace()
    # whole cycles: staging runs start at 10 and 45 ms; the one at 90 ms
    # closes the window
    assert tr.window() == (10_000_000, 90_000_000)
    assert tr.count(table["per_chunk"]) == 2
    assert tr.per_chunk_ms("stage", table) == pytest.approx(25.0 / 2)
    assert tr.per_chunk_ms("detector", table) == pytest.approx(20.0 / 2)
    assert tr.per_chunk_ms("finish", table) == pytest.approx(9.0 / 2)
    assert tr.per_chunk_ms("nothing", table) is None


def test_trace_busy_union_and_idle_share():
    tr = _trace()
    # busy: 10-35 (overlaps merged), 45-60, 62-75 -> 53 ms of 80
    assert tr.window_s() == pytest.approx(0.080)
    assert tr.busy_s() == pytest.approx(0.053)
    ctx = type("C", (), {"trace": tr})
    assert harness.reader("device.idle_share")(ctx) == pytest.approx(
        100.0 * 27 / 80)


def test_trace_modules_map_to_layers_by_base_name():
    tr = _trace()
    table = trace.module_table()
    assert trace.base_name("jit__stage_chunk(12)") == "jit__stage_chunk"
    assert trace.base_name("jit__lambda.3") == "jit__lambda"
    secs = tr.module_seconds()
    assert secs["jit__stage_chunk"] == pytest.approx(0.025)
    assert secs["jit__lambda"] == pytest.approx(0.020)
    assert [n for n, _, _ in tr.layer_runs("finish", table)] == [
        "jit__gather_batch", "jit__finish_chunk", "jit__finish_chunk"]


def test_trace_gaps_are_labelled_by_the_open_host_span():
    # gaps 75-90 (midpoint 82.5: poll), 35-45 (40: poll), 60-62 (flush)
    gaps = _trace().idle_gaps()
    assert [g[0] for g in gaps] == ["poll", "poll", "flush"]
    assert [g[1] for g in gaps] == pytest.approx([0.015, 0.010, 0.002])


# ----------------------------------------------------------- flops / bytes
def test_detector_flops_per_1080p_frame():
    f = flops.frame_flops(DET, 1088, 1920)
    assert f == pytest.approx(2.577e9, rel=1e-3)
    # three convs and the head: 75 M + 602 M + 602 M + 10 M MACs
    assert f == 2 * (544 * 960 * 16 * 9 + 272 * 480 * 32 * 16 * 9
                     + 136 * 240 * 64 * 32 * 9 + 136 * 240 * 64 * 5)


def test_dispatch_cost_counts_the_padded_rows_as_they_run():
    ops, nbytes = flops.dispatch_cost(DET, (1088, 1920), 32)
    assert ops == 32 * flops.frame_flops(DET, 1088, 1920)
    assert nbytes == 32 * flops.frame_bytes(DET, 1088, 1920)
    # 4.5 inferred frames pad to an 8-row batch: the roofline counts 8
    assert flops.dispatch_cost(DET, (1088, 1920), 8)[0] == pytest.approx(
        8 * 2.577e9, rel=1e-3)


def test_tiny_counts_and_the_dispatch_lookup():
    tiny = loader.detector("tiny")
    assert tiny.frame_flops(DET, 1088, 1920) == 2_577_776_640
    # dispatch_cost looks the counts up by arch: the formula it had
    # before the detector modules, for any row count
    for rows in (8, 16, 32):
        assert flops.dispatch_cost(DET, (1088, 1920), rows) == (
            rows * 2 * (544 * 960 * 16 * 9 + 272 * 480 * 32 * 16 * 9
                        + 136 * 240 * 64 * 32 * 9 + 136 * 240 * 64 * 5),
            rows * 4 * (1088 * 1920 + 2 * (544 * 960 * 16 + 272 * 480 * 32
                                           + 136 * 240 * 64)
                        + 5 * 136 * 240))


def test_frame_bytes_layer_by_layer():
    b = flops.frame_bytes(DET, 1088, 1920)
    want = 4 * (1088 * 1920 + 2 * (544 * 960 * 16 + 272 * 480 * 32
                                   + 136 * 240 * 64) + 5 * 136 * 240)
    assert b == want


# ---------------------------------------------------- latency and rate
def test_percentiles_and_streams_rt():
    lat = list(range(1, 101))                # 1..100 ms
    assert stats.percentile(lat, 50) == pytest.approx(50.5)
    assert stats.percentile(lat, 90) == pytest.approx(90.1)
    assert stats.percentile([], 90) is None
    assert stats.streams_rt(90, 10.0, 1.0) == pytest.approx(9.0)
    assert stats.streams_rt(0, 10.0, 1.0) is None


def test_a_stall_moves_the_tail_and_not_the_median():
    lat = [100.0] * 95 + [100.0] * 5
    stalled = [100.0] * 88 + [2000.0] * 12
    assert stats.percentile(stalled, 50) == stats.percentile(lat, 50)
    assert stats.percentile(stalled, 90) > 10 * stats.percentile(lat, 90)


# ------------------------------------------------------- data-driven layout
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] \
        + [w["name"] for w in BENCH["workloads"]] \
        + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_every_cell_and_metric_is_found_by_name():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert "assumed" in cfg and "source" in cfg
    for w in BENCH["workloads"]:
        _, _, cfg, traffic = harness.load_cell(w["name"])
        assert traffic["tr1"] is not None and traffic["tr2"] is not None
        assert traffic["type_counts"], w["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


# ------------------------------------------------- no accelerator, no result
def _no_result(cmd, cwd, env):
    p = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    _no_result([sys.executable, "bench/run.py", "--workload",
                "full.paper_mix", "--seed", "4294967301", "--seconds", "1",
                "--trace", "0"], ROOT, env)


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    _no_result([sys.executable, "bench/run.py", "--workload",
                "full.paper_mix", "--seed", "3", "--seconds", "1",
                "--trace", "0"], str(tmp_path), env)


# ------------------------------------------------- the correctness check
SMALL = {"n_objects": 2, "min_size": 24.0, "max_size": 40.0, "speed": 6.0,
         "texture_contrast": 90.0, "background_level": 110.0,
         "texture_freq": 0.4}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _small(width=192, **traffic):
    return {"config": {"cameras": 2, "height": 96, "width": width,
                       "frames_per_chunk": 6},
            "traffic": dict({"cameras": [SMALL]}, **traffic)}


def _run(workload, seed=2**33 + 5, **kw):
    kw.setdefault("overrides", _small())
    return harness.run(workload, seed, 0.3, False, require_chip=False,
                       peaks=PEAKS, **kw)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_program_is_correct_at_a_small_size(workload):
    r = _run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}


def _rt():
    from repro.serving import runtime
    return runtime


def _altered_answer():
    import jax
    orig = _rt()._finish_chunk

    @partial(jax.jit, static_argnames=("has_init",))
    def fault(*a, has_init):
        # the first frame's detections: every box 2 px down, scores +0.05
        b, s, lb, ls = orig(*a, has_init=has_init)
        return b.at[0, :, 0].add(2.0), s.at[0].add(0.05), lb, ls
    return "_finish_chunk", fault


def _half_batch():
    import jax
    import jax.numpy as jnp
    orig = _rt()._gather_batch

    @jax.jit
    def fault(frames_seq, flat_idx, valid):
        n = valid.sum()
        keep = valid & (jnp.arange(valid.shape[0]) < n // 2)
        return orig(frames_seq, flat_idx, keep)
    return "_gather_batch", fault


def _reuse_state_unchanged():
    import jax
    import jax.numpy as jnp
    orig = _rt()._finish_chunk

    @partial(jax.jit, static_argnames=("has_init",))
    def fault(types, pos, mvs, *a, has_init):
        return orig(types, pos, jnp.zeros_like(mvs), *a, has_init=has_init)
    return "_finish_chunk", fault


@pytest.mark.parametrize("fault", [_altered_answer, _half_batch,
                                   _reuse_state_unchanged])
def test_each_fault_makes_the_run_incorrect(fault, monkeypatch):
    name, fn = fault()
    monkeypatch.setattr(_rt(), name, fn)
    # launcher-like thresholds far above the features: frame 0 is the only
    # anchor and the rest is reused, so every fault has frames to break
    r = _run("full.paper_mix", overrides=_small(tr1=1.0, tr2=1.0))
    assert not r["correct"], r["checks"]


def test_bfloat16_control_is_incorrect():
    from bench import control
    # 1152 px wide: box coordinates pass 1024, where bfloat16 keeps
    # 8-px steps, as they do in the 1920-px cells
    r = control.control_run("full.paper_mix", 11, 0.3, require_chip=False,
                            peaks=PEAKS, overrides=_small(width=1152))
    assert not r["correct"], r["checks"]


def test_a_detector_found_by_an_unseen_name_runs_end_to_end(tmp_path,
                                                           monkeypatch):
    """A new detector needs only new files: a copy of ``tiny`` under a
    name no file of the benchmark mentions, with other channels, runs
    through the harness and the reference."""
    shutil.copy(os.path.join(ROOT, "bench", "detectors", "tiny.py"),
                tmp_path / "probe_arch7.py")
    monkeypatch.setattr(loader, "DETECTORS", str(tmp_path))
    ov = _small()
    ov["config"]["detector"] = {"arch": "probe_arch7",
                                "channels": [8, 16, 24, 24], "stride": 8,
                                "dtype": "float32"}
    r = _run("full.dense_infer", overrides=ov)
    assert r["correct"], r["checks"]
    mod = loader.detector("probe_arch7")
    assert mod.__file__ == str(tmp_path / "probe_arch7.py")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_limits_every_number_compared(workload):
    nums = compare.numbers([], [], [])
    lim = compare.limits(workload)
    assert set(lim) == set(nums)
    ok, checks = compare.verdict(nums, lim)
    assert ok and list(checks) == list(lim)


def test_reference_warp_clamps_a_source_beyond_the_padding_to_the_edge():
    """A block whose motion points past the 16-px padding reads the edge
    it moved past, never the opposite edge of the anchor."""
    import jax.numpy as jnp

    from bench import reference
    anchor = jnp.arange(64, dtype=jnp.float32)[:, None] * jnp.ones((1, 64))
    mv = np.zeros((4, 4, 2), np.int32)
    mv[0, 0] = (-40, 0)                        # 40 px above the frame
    mv[3, 3] = (40, 0)                         # 40 px below it
    out = np.asarray(reference.warp(anchor, jnp.asarray(mv)))
    assert (out[:16, :16] == 0).all()          # top row, not the bottom
    assert (out[48:, 48:] == 63).all()         # bottom row, not the top
    np.testing.assert_array_equal(out[16:48], np.asarray(anchor)[16:48])


def test_reservoir_keeps_a_seeded_sample_of_what_was_offered():
    def draw(seed, n):
        got = []
        offer = harness.reservoir(np.random.default_rng(seed), 8, got)
        for i in range(n):
            offer(i)
        return got
    assert draw(5, 3) == [(0,), (1,), (2,)]
    a = draw(5, 100)
    assert len(a) == 8 and len(set(a)) == 8 and a == draw(5, 100)
    assert a != draw(6, 100)
    late = sum(x[0] >= 50 for s in range(200) for x in draw(s, 100))
    assert 0.4 < late / 1600 < 0.6            # every item equally likely


def test_compare_reads_centres_at_inferred_frames_and_moved_reuse():
    types = np.array([1, 3, 3])
    rb = np.zeros((3, 4, 4), np.float32)
    rs = np.full((3, 4), 0.5, np.float32)
    b = rb.copy()
    b[0, :, 1] += 0.25                         # inferred: centre 0.25 px off
    b[2, 0, 0] += 1.0                          # one reused box 1 px off
    nums = compare.numbers([(b, rs + 0.01, types, types)], [(rb, rs)], [rb])
    assert nums["center_gap_px"] == pytest.approx(0.25)
    assert nums["score_mean"] == pytest.approx(0.01)
    assert nums["reuse_off_share"] == pytest.approx(1 / 8)
    assert nums["type_mismatch"] == 0 and nums["nonfinite"] == 0
