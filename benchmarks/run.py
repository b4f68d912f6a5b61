"""Benchmark harness: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure (benchmarks/figures.py) + kernel
micro-benchmarks (toy and 720p-shaped) + the fused-vs-legacy chunk
pipeline comparison + fused round-trip rows + multi-stream runtime
throughput + the roofline summary from the dry-run artifacts.  Prints
``name,us_per_call,derived`` CSV rows and mirrors every row into
``BENCH_pipeline.json`` so the perf trajectory is machine-readable across
PRs.

``--smoke`` (CI bench-smoke job): tiny shapes, 1 rep, no warmup — every
bench still imports, traces and executes, so import/trace breakage in
bench code is caught without timing noise.  Timings from a smoke run are
meaningless; the JSON payload is tagged ``"smoke": true``.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BENCH_JSON = os.environ.get("BENCH_JSON", "BENCH_pipeline.json")

# set by --smoke (or inherited by subprocess children via the env var):
# 1 rep, no warmup, tiny shapes in the shape-parameterized benches
SMOKE = os.environ.get("BISWIFT_BENCH_SMOKE") == "1"


def bench_row(name, us, derived) -> dict:
    """Serialize one (name, us-or-label, derived) bench tuple as a
    schema-v2 row: ``us_per_call`` stays numeric (or null), string labels
    move to ``params``."""
    if isinstance(us, (int, float)) and not isinstance(us, bool):
        return {"name": name, "us_per_call": float(us), "params": None,
                "derived": str(derived)}
    return {"name": name, "us_per_call": None, "params": str(us),
            "derived": str(derived)}


def migrate_rows_v2(rows: list[dict]) -> list[dict]:
    """Upgrade v1 row dicts to v2 (see ``bench_row``).  v1 rows abused
    ``us_per_call`` for label strings; v2 rows pass through unchanged."""
    out = []
    for r in rows:
        us = r.get("us_per_call")
        if us is None and r.get("params") is not None:
            us = r["params"]          # already v2
        out.append(bench_row(r["name"], us, r.get("derived", "")))
    return out


def _timeit(fn, *args, n=3, warmup=1):
    if SMOKE:
        n, warmup = 1, 0
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def _kernel_tag() -> str:
    """Row-name tag of the Pallas kernel rows: the ops wrappers interpret
    on CPU and compile the kernel on TPU (no row forces interpret mode
    on a chip)."""
    return "tpu" if jax.default_backend() == "tpu" else "interp"


def kernel_microbench():
    rows = []
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.qtransfer.ops import qtransfer
    from repro.kernels.blockdct.ops import blockdct_quantize
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    us = _timeit(lambda: flash_attention(q, k, v), n=2)
    rows.append((f"kernel_flash_attention_{_kernel_tag()}", us,
                 "B1S256H4D64"))
    anchor = jax.random.uniform(ks[0], (64, 96), jnp.float32) * 255
    mv = jax.random.randint(ks[1], (4, 6, 2), -8, 9, jnp.int32)
    resid = jnp.zeros((64, 96), jnp.float32)
    us = _timeit(lambda: qtransfer(anchor, mv, resid), n=2)
    rows.append((f"kernel_qtransfer_{_kernel_tag()}", us, "64x96"))
    blocks = jax.random.uniform(ks[2], (256, 8, 8), jnp.float32) * 255 - 128
    us = _timeit(lambda: blockdct_quantize(blocks, 50.0),
                 n=2)
    rows.append((f"kernel_blockdct_{_kernel_tag()}", us, "256blocks"))
    from repro.codec.motion import block_sad, block_sad_scan
    from repro.kernels.motion_sad.ops import motion_sad
    cur = jax.random.uniform(ks[0], (64, 96), jnp.float32) * 255
    ref = jnp.roll(cur, (2, -3), (0, 1))
    # oracle-relative columns: the kernel-trajectory CI summary tracks
    # vs_scan / vs_fallback per PR so kernel regressions can't hide
    scan = jax.jit(lambda c, r: block_sad_scan(c, r, 8))
    us_scan = _timeit(lambda: scan(cur, ref), n=2)
    fb = jax.jit(lambda c, r: block_sad(c, r, 8))
    us_fb = _timeit(lambda: fb(cur, ref), n=2)
    us = _timeit(lambda: motion_sad(cur, ref, radius=8), n=2)
    rows.append((f"kernel_motion_sad_{_kernel_tag()}", us,
                 f"64x96r8;vs_scan:{us_scan / max(us, 1e-9):.2f}x;"
                 f"vs_fallback:{us_fb / max(us, 1e-9):.2f}x"))
    us_d = _timeit(lambda: motion_sad(cur, ref, radius=8,
                                      search="diamond"), n=2)
    rows.append((f"kernel_motion_sad_diamond_{_kernel_tag()}", us_d,
                 f"64x96r8;evals:37/289;"
                 f"vs_exhaustive_kernel:{us / max(us_d, 1e-9):.2f}x"))
    return rows


def realistic_shape_bench():
    """720p-shaped kernel rows — the resolution the paper's edge actually
    serves, so regressions on real tile counts (45×80 macroblocks) show up
    even though CI runs interpret mode on CPU.  (--smoke shrinks to 144p:
    same code paths, tiny tile counts.)"""
    from repro.codec.motion import block_sad_scan
    from repro.kernels.motion_sad.ops import motion_sad
    from repro.kernels.qtransfer.ops import qtransfer
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    H, W = (144, 256) if SMOKE else (720, 1280)
    tag = "144p" if SMOKE else "720p"
    cur = jax.random.uniform(ks[0], (H, W), jnp.float32) * 255
    ref = jnp.roll(cur, (3, -2), (0, 1))
    rows = []
    scan = jax.jit(lambda c, r: block_sad_scan(c, r, 8))
    us_scan = _timeit(lambda: scan(cur, ref), n=2)
    rows.append((f"motion_sad_scan_{tag}", us_scan, "r8scan289cand"))
    us = _timeit(lambda: motion_sad(cur, ref, radius=8), n=2)
    rows.append((f"kernel_motion_sad_{_kernel_tag()}_{tag}", us,
                 f"r8band;vs_scan:{us_scan / max(us, 1e-9):.2f}x"))
    us_d = _timeit(lambda: motion_sad(cur, ref, radius=8,
                                      search="diamond"), n=2)
    rows.append((f"kernel_motion_sad_diamond_{_kernel_tag()}_{tag}",
                 us_d,
                 f"r8;evals:37/289;"
                 f"vs_exhaustive_kernel:{us / max(us_d, 1e-9):.2f}x"))
    # static diamond dispatch at a realistic block count (720p = 3600
    # macroblocks): on CPU CI this routes to the traced descent (interpret
    # mode loses at every shape), on TPU to the kernel — either way the
    # row must track the fallback row (vs_fallback ~1.0x or better).  The
    # small-canvas twin is encoder_block_sad_diamond_dispatch_64x96.
    from repro.codec.motion import (block_sad, block_sad_diamond,
                                    diamond_kernel_profitable)
    routed = "kernel" if diamond_kernel_profitable(H, W) else "fallback"
    fb_dia = jax.jit(lambda c, r: block_sad_diamond(c, r, 8))
    us_fbd = _timeit(lambda: fb_dia(cur, ref), n=2)
    disp = jax.jit(lambda c, r: block_sad(c, r, 8, use_kernel=True,
                                          search="diamond"))
    us_disp = _timeit(lambda: disp(cur, ref), n=2)
    rows.append((f"motion_sad_diamond_dispatch_{tag}", us_disp,
                 f"routed:{routed};"
                 f"vs_fallback:{us_fbd / max(us_disp, 1e-9):.2f}x"))
    mv = jax.random.randint(ks[1], (H // 16, W // 16, 2), -8, 9, jnp.int32)
    resid = jnp.zeros((H, W), jnp.float32)
    us = _timeit(lambda: qtransfer(cur, mv, resid), n=2)
    rows.append((f"kernel_qtransfer_{_kernel_tag()}_{tag}", us,
                 f"{H // 16}x{W // 16}blocks"))
    return rows


def pipeline_bench():
    """Fused single-jit chunk execution vs the legacy host-orchestrated
    path on the SAME 4-frame 64×96 chunk, plus 1..N-stream EdgeRuntime
    throughput (one padded detector dispatch per chunk)."""
    from repro.core.hybrid_decoder import (decode_and_execute,
                                           decode_execute_chunk)
    from repro.core.hybrid_encoder import encode_hybrid
    from repro.models import detection as D
    from repro.serving.runtime import EdgeRuntime
    from repro.serving.scheduler import ServingConfig
    from repro.sim.video_source import StreamConfig, generate_chunk

    frames, gtb, gtv = generate_chunk(
        jax.random.PRNGKey(0), StreamConfig(height=64, width=96,
                                            n_objects=3), 0, 4)
    det_cfg = D.TinyDetectorConfig()
    params = D.init(jax.random.PRNGKey(1), det_cfg)
    packet = encode_hybrid(np.asarray(frames), 8000.0, 0.05, 0.1)

    us_legacy = _timeit(
        lambda: decode_and_execute(packet, params, det_cfg, gtb, gtv,
                                   bw_kbps=8000.0), n=5)
    types = jnp.asarray(packet.types)
    ahd = jnp.asarray(packet.anchor_hd)
    gb, gv = jnp.asarray(gtb), jnp.asarray(gtv)
    us_fused = _timeit(
        lambda: decode_execute_chunk(packet.video, types, ahd, gb, gv,
                                     params, det_cfg, bw_kbps=8000.0,
                                     total_bits=packet.total_bits)["boxes"],
        n=5)
    rows = [
        ("pipeline_legacy_per_frame_4f_64x96", us_legacy, "host-orchestrated"),
        ("pipeline_fused_jit_4f_64x96", us_fused,
         f"speedup:{us_legacy / max(us_fused, 1e-9):.1f}x"),
    ]

    for n_streams in (1, 2, 4):
        rt = EdgeRuntime(ServingConfig(n_streams=n_streams), params, det_cfg)

        def run_all():
            for s in range(n_streams):
                rt.process_chunk(s, 0, packet)

        us = _timeit(run_all, n=3)
        fps = n_streams * packet.types.shape[0] / (us / 1e6)
        rows.append((f"runtime_process_chunk_{n_streams}stream", us,
                     f"fps:{fps:.0f}"))
    return rows


def codec_bench():
    from repro.codec.video_codec import VideoCodecConfig, encode_chunk
    from repro.sim.video_source import StreamConfig, generate_chunk
    frames, _, _ = generate_chunk(jax.random.PRNGKey(0),
                                  StreamConfig(height=64, width=96), 0, 4)
    cfg = VideoCodecConfig()
    try:
        hash(cfg)
    except TypeError as e:
        # encode_chunk is jitted with the config as a static argument; an
        # unhashable config would otherwise surface as an opaque jit
        # TypeError deep inside tracing.
        raise TypeError(
            "codec_bench jits encode_chunk with static_argnums=1, which "
            f"requires VideoCodecConfig to stay hashable; got {cfg!r}. "
            "Keep it a frozen dataclass with hashable fields (or switch "
            "this bench to static_argnames/jax.tree_util registration)."
        ) from e
    fn = jax.jit(encode_chunk, static_argnums=1)
    us = _timeit(lambda: fn(frames, cfg), n=3)
    return [("codec_encode_chunk_4f_64x96", us, "mv+dct+bits")]


def _forced_cpu_env(n_devices: int = 4) -> dict:
    """os.environ copy forcing an n-device CPU platform in a CHILD process
    (append, not clobber, so caller XLA flags survive; XLA takes the last
    occurrence on conflict).  Mirrors tests/conftest.forced_multidevice_env
    — benchmarks must stay importable without the test tree."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _multidevice_rows(module: str, fallback_name: str):
    """Rows of a multi-device bench module (its ``sharded_rows``).

    Where this process already sees >= 4 devices (a 4-chip host, or a
    forced CPU platform) the bench runs in-process: a child started now
    could not get a chip this process holds.  Otherwise it runs in a
    child pinned to a forced 4-device CPU platform (XLA only honours the
    device-count flag before the first jax import), which never needs a
    chip; its JSON rows come back on the last stdout line."""
    if len(jax.devices()) >= 4:
        import importlib
        return [tuple(r) for r in
                importlib.import_module(module).sharded_rows()]
    import subprocess
    r = subprocess.run([sys.executable, "-m", module],
                       capture_output=True, text=True, env=_forced_cpu_env(),
                       timeout=900)
    if r.returncode != 0:
        tail = (r.stderr or r.stdout).strip().replace("\n", ";")[-160:]
        return [(fallback_name, -1.0, f"ERROR:{tail}")]
    return [tuple(row) for row in json.loads(r.stdout.strip().splitlines()[-1])]


def stream_sharding_bench():
    """Sharded-vs-single-device stream throughput (ROADMAP multi-host
    sharding item)."""
    return _multidevice_rows("benchmarks.stream_shard",
                                 "stream_sharding_bench")


def roundtrip_sharding_bench():
    """Mesh-sharded fused round trip vs the single-device batched jit
    (``benchmarks.roundtrip`` main, forced multi-device child)."""
    return _multidevice_rows("benchmarks.roundtrip",
                                 "roundtrip_sharding_bench")


def roofline_summary():
    from benchmarks.roofline import load_cells
    rows = []
    for mesh in ("single", "multi"):
        cells = load_cells("experiments/dryrun", mesh)
        runnable = [c for c in cells if "skipped" not in c]
        if not runnable:
            continue
        dom = {k: sum(c["dominant"] == k for c in runnable)
               for k in ("compute", "memory", "collective")}
        rows.append((f"roofline_{mesh}_cells", len(runnable) * 1.0,
                     f"dominant:{dom}".replace(",", ";")))
    return rows


def main() -> None:
    global SMOKE
    if "--smoke" in sys.argv:
        # export so subprocess children (stream_shard / roundtrip
        # multi-device benches, --multidevice re-exec) smoke too
        SMOKE = True
        os.environ["BISWIFT_BENCH_SMOKE"] = "1"
    # --multidevice: re-run the whole harness on a forced 4-device CPU
    # platform.  Nothing above has started a jax backend, so this process
    # holds no chip, and the child is pinned to the CPU anyway.
    if "--multidevice" in sys.argv \
            and os.environ.get("BISWIFT_MULTIDEVICE_CHILD") != "1":
        import subprocess
        env = _forced_cpu_env()
        env["BISWIFT_MULTIDEVICE_CHILD"] = "1"
        sys.exit(subprocess.run(
            [sys.executable, "-m", "benchmarks.run"], env=env).returncode)

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    print("name,us_per_call,derived")
    all_rows = []
    t0 = time.time()
    from benchmarks.figures import ALL
    from benchmarks.bilevel import bilevel_bench
    from benchmarks.encoder import encoder_bench
    from benchmarks.roundtrip import roundtrip_bench, roundtrip_roi_bench
    benches = list(ALL.items()) + [
        (fn.__name__, fn)
        for fn in (kernel_microbench, realistic_shape_bench, pipeline_bench,
                   codec_bench, encoder_bench, roundtrip_bench,
                   roundtrip_roi_bench, bilevel_bench, stream_sharding_bench,
                   roundtrip_sharding_bench, roofline_summary)]
    for name, fn in benches:
        try:
            all_rows.extend(fn())
        except Exception as e:  # keep the harness robust
            all_rows.append((name, -1.0, f"ERROR:{type(e).__name__}:{e}"))
    for name, us, derived in all_rows:
        if isinstance(us, float):
            print(f"{name},{us:.1f},{derived}")
        else:
            print(f"{name},{us},{derived}")
    print(f"# total wall: {time.time() - t0:.1f}s")
    errors = [n for n, _, d in all_rows if str(d).startswith("ERROR")]
    payload = {
        # v2: ``us_per_call`` is numeric-or-null, always.  Figure rows
        # whose middle slot is a parameter label (e.g. "scale=0.25") land
        # in ``params`` instead of corrupting the numeric field — numeric
        # trajectory tooling can trust every us_per_call it reads.
        "schema": "biswift-bench-v2",
        "backend": jax.default_backend(),
        "smoke": SMOKE,
        "wall_s": round(time.time() - t0, 2),
        "rows": [bench_row(n, u, d) for n, u, d in all_rows],
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {BENCH_JSON} ({len(all_rows)} rows)")
    if errors:
        # every row is still written above; an ERROR row swallowed into a
        # green exit would hide a broken bench, in smoke and full runs alike
        sys.exit(f"# {'smoke ' if SMOKE else ''}FAILED: {len(errors)} "
                 f"bench(es) errored: {', '.join(errors)}")


if __name__ == "__main__":
    main()
