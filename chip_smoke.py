"""Run BiSwift's serving path once on a TPU and check what comes out.

    python chip_smoke.py            # one chip: serving phase + kernel phase
    python chip_smoke.py --chips 4  # four chips: sharded stream plane only

The deployment is the paper's base case: 9 cameras with 1080p sources
(coded on the 1088-row macroblock grid, as H.264 does: the codec's 16×16
macroblocks and the 32-px ROI regions need multiples of 16 and 32),
T = 30 frames per 1-s chunk at 30 fps, one shared 16 Mbps uplink, the
``TinyDetectorConfig`` detector with weights made from ``--seed``.

Phases (all in this one process; any failure raises and exits non-zero):

* serving — ``repro.launch.serve.main`` for 3 chunks: synthetic cameras,
  ``encode_hybrid``, ``allocate`` over a ``generate_trace`` uplink, then
  ``EdgeRuntime.process_chunk``.  Every result must be finite, every
  stream must keep ``frames_in == inferred + reused + skipped``, and each
  stream's first chunk must match the plain reference
  ``decode_execute_chunk`` run on the same packet on the same chip.  One
  packet re-typed to mix pipelines ①, ② and ③ is checked the same way,
  so quality transfer is covered whatever the thresholds select.
* kernels — every Pallas route at 1088×1920 against its jnp route, with
  ``tpu_custom_call`` required in each compiled kernel route.
* sharded (``--chips 4``) — ``shard_roundtrip`` over a 4-device stream
  mesh (9 streams padded to 12) against its single-device twin, and one
  chunk per stream through a 4-shard ``EdgeRuntime`` against the 1-shard
  runtime.

Lines before the last are progress and smoke numbers (wall times, peak
device memory), not benchmark results.  The last line is the JSON
verdict.  With no TPU (for example under ``JAX_PLATFORMS=cpu``) the
script exits 2 and prints no verdict.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# the deployment (nothing is cut: these are the paper's sizes)
STREAMS, T, H, W, CHUNKS = 9, 30, 1088, 1920, 3
BW_MEAN_KBPS = 16000.0
TR1, TR2 = 0.05, 0.10            # serve.py's classification thresholds

# Detections of two routes that should compute the same thing.  On TPU an
# f32 conv runs with bf16 input passes and the routes batch frames
# differently (the runtime pads its detector batch to 32 rows), so their
# accumulation order can differ: a logit moves by ~1e-4, a score by less,
# and a box side exp(clip(raw, ±3))·8 px by up to ~1e-3 of its size.  A
# wrong frame or a wrong pipeline moves scores by ~0.1 and boxes by whole
# pixels, far outside these bounds.
SCORE_ATOL = 2e-3
BOX_ATOL_PX, BOX_RTOL = 0.1, 2e-3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def compiled_kernel(fn, *args, **kwargs):
    """Compile ``fn`` and require a Pallas TPU kernel in it: proof that
    the kernel itself runs, not a substitute."""
    import jax
    compiled = jax.jit(partial(fn, **kwargs)).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"no tpu_custom_call in the compiled {getattr(fn, '__name__', fn)}"
          f" route {kwargs}")
    return compiled


def detections_close(b, s, rb, rs, what: str) -> tuple[float, float]:
    import numpy as np
    b, s, rb, rs = (np.asarray(x, np.float32) for x in (b, s, rb, rs))
    check(b.shape == rb.shape and s.shape == rs.shape,
          f"{what}: shapes {b.shape}/{s.shape} vs {rb.shape}/{rs.shape}")
    db = float(np.max(np.abs(b - rb) - BOX_RTOL * np.abs(rb)))
    ds = float(np.max(np.abs(s - rs)))
    check(db <= BOX_ATOL_PX, f"{what}: boxes differ by {db:.4g} px "
          f"beyond rtol {BOX_RTOL} (bound {BOX_ATOL_PX})")
    check(ds <= SCORE_ATOL, f"{what}: scores differ by {ds:.4g} "
          f"(bound {SCORE_ATOL})")
    return db, ds


def all_finite(*xs) -> bool:
    import numpy as np
    return all(bool(np.all(np.isfinite(np.asarray(x)))) for x in xs)


# ------------------------------------------------------------------ serving
def serving_phase(seed: int, streams=STREAMS, frames=T, height=H, width=W,
                  chunks=CHUNKS, quick_train=150) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.hybrid_decoder import decode_execute_chunk
    from repro.launch import serve
    from repro.serving.runtime import EdgeRuntime
    from repro.serving.scheduler import ServingConfig

    compared, first = [], {}

    def against_reference(info, pk, boxes, scores, types, what):
        ref = decode_execute_chunk(
            pk.video, jnp.asarray(pk.types), jnp.asarray(pk.anchor_hd),
            jnp.asarray(info["gt_boxes"]), jnp.asarray(info["gt_valid"]),
            info["params"], info["det_cfg"], bw_kbps=info["bw_kbps"],
            queue_delay=0.0, total_bits=pk.total_bits)
        check(np.array_equal(types, pk.types),
              f"{what}: runtime types {types} != packet types {pk.types}")
        return detections_close(boxes, scores, ref["boxes"], ref["scores"],
                                f"{what} vs reference")

    def on_chunk(info):
        pk = info["packet"]
        check(all_finite(info["boxes"], info["scores"]),
              f"non-finite detections, chunk {info['t']} stream "
              f"{info['stream']}")
        if info["t"] != 0:
            return
        first.setdefault("info", info)
        db, ds = against_reference(info, pk, info["boxes"], info["scores"],
                                   info["types"], f"stream {info['stream']}")
        compared.append((info["stream"], db, ds,
                         int((pk.types == 2).sum())))

    argv = ["--streams", str(streams), "--chunks", str(chunks),
            "--chunk-frames", str(frames), "--height", str(height),
            "--width", str(width), "--bw-mean-kbps", str(BW_MEAN_KBPS),
            "--quick-train", str(quick_train), "--seed", str(seed)]
    log(f"serving: python -m repro.launch.serve {' '.join(argv)}")
    summary = serve.main(argv, on_chunk=on_chunk)

    check(len(compared) == streams,
          f"{len(compared)} of {streams} streams compared")
    check(np.isfinite(summary["mean_latency_s"]), "non-finite latency")
    stats = summary["stream_stats"]
    check(sorted(stats) == list(range(streams)), f"streams {sorted(stats)}")
    for s, st in stats.items():
        check(st["frames_in"] == chunks * frames,
              f"stream {s}: frames_in {st['frames_in']}")
        check(st["frames_in"] == st["frames_inferred"] + st["frames_reused"]
              + st["frames_skipped"], f"stream {s}: accounting {st}")
    worst_b = max(c[1] for c in compared)
    worst_s = max(c[2] for c in compared)
    log(f"serving: {streams} streams x {chunks} chunks OK; first chunk vs "
        f"decode_execute_chunk: worst box excess {worst_b:.3g} px, worst "
        f"score diff {worst_s:.3g}; type-2 frames compared "
        f"{sum(c[3] for c in compared)}")

    # serve.py's thresholds may send no frame to pipeline ② (quality
    # transfer), so it is checked on its own: stream 0's first packet with
    # every other non-anchor frame re-typed to 2, through a fresh runtime
    info = first["info"]
    pk = info["packet"]
    odd = np.arange(pk.types.shape[0]) % 2 == 1
    pk = dataclasses.replace(pk, types=np.where(
        pk.types == 1, 1, np.where(odd, 2, 3)).astype(pk.types.dtype))
    with EdgeRuntime(ServingConfig(n_streams=1), info["params"],
                     info["det_cfg"]) as rt:
        b, s, ty = rt.process_chunk(0, 0, pk)
    check(all_finite(b, s), "pipeline-2 check: non-finite detections")
    db, ds = against_reference(info, pk, b, s, ty, "pipeline-2 check")
    log(f"serving: re-typed chunk ({int((pk.types == 2).sum())} type-2, "
        f"{int((pk.types == 3).sum())} type-3 frames) vs "
        f"decode_execute_chunk: box excess {db:.3g} px, score diff "
        f"{ds:.3g}")
    log("smoke numbers (not a benchmark): wall per chunk of "
        f"{streams} streams [s] = "
        f"{[round(x, 2) for x in summary['chunk_wall_s']]} (chunk 0 "
        f"includes compiles); peak device memory "
        f"{peak_gib(jax.devices()[0])}")


# ------------------------------------------------------------------ kernels
def kernel_phase(seed: int, height=H, width=W, require_kernel=True) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.codec import blockdct as B
    from repro.codec.motion import block_sad
    from repro.codec.video_codec import VideoCodecConfig, encode_chunk
    from repro.core.quality_transfer import transfer_frame
    from repro.core.roi import RoiConfig
    from repro.kernels.blockdct.ops import blockdct_quantize
    from repro.kernels.roi_gather.ops import roi_gather, roi_gather_ref
    from repro.sim.video_source import StreamConfig, generate_chunk

    kernel = compiled_kernel if require_kernel else \
        (lambda fn, *a, **kw: jax.jit(partial(fn, **kw)).lower(*a).compile())
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 5)
    # camera-like content rounded to 8-bit values, as real pixels are: on
    # such frames every f32 summation order of a SAD is exact
    video, _, _ = generate_chunk(ks[0], StreamConfig(
        height=height, width=width, n_objects=8, min_size=40, max_size=120,
        speed=3.0, seed=seed), 0, 4)
    video = jnp.round(jnp.clip(video, 0.0, 255.0))
    cur, ref = video[1], video[0]

    t0 = time.time()
    for search in ("exhaustive", "diamond"):
        for dtype in (None, jnp.bfloat16):
            kw = dict(radius=8, search=search, dtype=dtype)
            run = kernel(block_sad, cur, ref, use_kernel=True, **kw)
            mv_k, sad_k = run(cur, ref)
            mv_j, sad_j = jax.jit(partial(block_sad, **kw))(cur, ref)
            name = f"motion_sad {search} {jnp.dtype(dtype or 'float32')}"
            check(np.array_equal(np.asarray(mv_k), np.asarray(mv_j)),
                  f"{name}: MVs differ on "
                  f"{int((np.asarray(mv_k) != np.asarray(mv_j)).any(-1).sum())}"
                  " blocks")
            check(np.array_equal(np.asarray(sad_k), np.asarray(sad_j)),
                  f"{name}: SADs differ")
            log(f"kernels: {name} == jnp route ({mv_k.shape[0]}x"
                f"{mv_k.shape[1]} blocks)")

    # the kernel route inside the codec: same MVs as the default jnp route
    run = kernel(encode_chunk, video, cfg=VideoCodecConfig(use_kernel=True))
    enc_k = run(video)
    enc_j = encode_chunk(video, VideoCodecConfig())
    check(np.array_equal(np.asarray(enc_k.mv), np.asarray(enc_j.mv)),
          "encode_chunk(use_kernel=True) MVs differ from the jnp route")
    check(all_finite(enc_k.recon, enc_k.bits), "encode_chunk non-finite")
    log("kernels: encode_chunk(use_kernel=True) MVs == jnp route")

    # quality transfer: the kernel route equals warp_blocks + residual
    nby, nbx = height // 16, width // 16
    mv = jax.random.randint(ks[1], (nby, nbx, 2), -16, 17, jnp.int32)
    resid = jax.random.normal(ks[2], (height, width), jnp.float32) * 8.0
    run = kernel(transfer_frame, cur, mv, resid, use_kernel=True)
    out_k = run(cur, mv, resid)
    out_j = jax.jit(transfer_frame)(cur, mv, resid)
    check(np.array_equal(np.asarray(out_k), np.asarray(out_j)),
          "qtransfer != warp_blocks + residual")
    log("kernels: qtransfer == warp_blocks + residual (bit-exact)")

    # ROI gather at the runtime's RoiConfig: a gather is exact
    roi = RoiConfig()
    xp = jnp.pad(video / 255.0 - 0.5, ((0, 0), (roi.halo,) * 2,
                                       (roi.halo,) * 2))
    ry = jax.random.randint(ks[3], (4, roi.capacity), 0,
                            height // roi.region_px)
    rx = jax.random.randint(ks[4], (4, roi.capacity), 0,
                            width // roi.region_px)
    kw = dict(region_px=roi.region_px, halo=roi.halo)
    run = kernel(roi_gather, xp, ry, rx, **kw)
    check(np.array_equal(np.asarray(run(xp, ry, rx)),
                         np.asarray(roi_gather_ref(xp, ry, rx, **kw))),
          "roi_gather != roi_gather_ref")
    log("kernels: roi_gather == roi_gather_ref (bit-exact)")

    # block DCT + quantize.  Forward: the kernel and the f32 jnp DCT may
    # disagree only where y/qtab sits on a rounding boundary (one step, in
    # a tiny fraction of coefficients).  Inverse: checked against the jnp
    # IDCT of the kernel's own coefficients, so boundary flips cancel;
    # the bound covers f32 rounding of 64-term sums of values <= ~1000.
    blocks = B.blockify(cur - 128.0)
    run = kernel(blockdct_quantize, blocks, 50.0)
    q_k, rec_k = run(blocks, 50.0)
    with jax.default_matmul_precision("highest"):
        q_r, qtab = B.quantize(B.dct2(blocks), 50.0)
        rec_r = B.idct2(B.dequantize(q_k, qtab))
    dq = np.abs(np.asarray(q_k) - np.asarray(q_r))
    drec = float(np.max(np.abs(np.asarray(rec_k) - np.asarray(rec_r))))
    check(dq.max() <= 1.0 and (dq > 0).mean() <= 1e-4,
          f"blockdct coefficients: max diff {dq.max()}, "
          f"{(dq > 0).mean():.2e} of them differ")
    check(drec <= 1e-2, f"blockdct recon differs by {drec}")
    log(f"kernels: blockdct ~ jnp DCT ({int((dq > 0).sum())} of {dq.size} "
        f"coefficients on a rounding boundary; recon max diff {drec:.3g})")
    log(f"smoke numbers (not a benchmark): kernel phase {time.time() - t0:.1f}"
        f" s including compiles")


# ------------------------------------------------------------------ sharded
def _deployment_chunk(seed: int, streams: int, frames: int, height: int,
                      width: int):
    """Host arrays for one chunk of the stream mix: raw (S, T, H, W) and
    ground truth padded to the mix's largest object count."""
    import jax
    import numpy as np

    from repro.sim.video_source import generate_chunk, paper_stream_mix

    mix = paper_stream_mix(streams, height, width)
    n = max(sc.max_objects for sc in mix)
    raw = np.zeros((streams, frames, height, width), np.float32)
    gtb = np.zeros((streams, frames, n, 4), np.float32)
    gtv = np.zeros((streams, frames, n), bool)
    key = jax.random.PRNGKey(seed)
    for s, sc in enumerate(mix):
        fr, b, v = generate_chunk(key, sc, 0, frames)
        raw[s] = np.asarray(fr)
        gtb[s, :, :b.shape[1]] = np.asarray(b)
        gtv[s, :, :v.shape[1]] = np.asarray(v)
    return mix, raw, gtb, gtv


def sharded_phase(seed: int, streams=STREAMS, frames=T, height=H, width=W,
                  n_devices=4) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.codec.rate_model import (ladder_for_bandwidth,
                                        video_bandwidth_share)
    from repro.core.hybrid_encoder import encode_hybrid
    from repro.core.roundtrip import RoundtripConfig, roundtrip_ladder_batched
    from repro.distributed.sharding import SINGLE_POD_RULES
    from repro.distributed.stream_sharding import (shard_roundtrip,
                                                   stream_shard_count)
    from repro.models import detection as D
    from repro.serving.runtime import EdgeRuntime
    from repro.serving.scheduler import ServingConfig

    devs = jax.devices()
    check(len(devs) >= n_devices, f"{len(devs)} devices, need {n_devices}")
    mesh = jax.make_mesh((n_devices,), ("data",), devices=devs[:n_devices])
    n_shards = stream_shard_count(mesh, SINGLE_POD_RULES)
    check(n_shards == n_devices, f"stream mesh has {n_shards} shards")
    det_cfg = D.TinyDetectorConfig()
    params = D.init(jax.random.PRNGKey(seed + 1), det_cfg)
    bw = BW_MEAN_KBPS / streams
    level = ladder_for_bandwidth(video_bandwidth_share(bw))
    mix, raw, gtb, gtv = _deployment_chunk(seed, streams, frames, height,
                                           width)

    # 1) fused round trip, sharded over the stream mesh vs its single-
    # device twin (same traced body).  The twin runs in groups of
    # streams // shards-per-device-sized batches on one device: all 9
    # streams at once would need ~3x one chip's memory, and each group is
    # exactly one device's share of the sharded program.
    cfg = RoundtripConfig(level=level)
    scal = dict(tr1=TR1, tr2=TR2, bw_kbps=bw, queue_delay=0.0)
    per_dev = -(-streams // n_shards)
    t0 = time.time()
    out = shard_roundtrip(mesh, SINGLE_POD_RULES, cfg=cfg)(
        raw, gtb, gtv, params, levels=(level,) * streams, **scal)
    out = jax.tree.map(np.asarray, out)
    log(f"sharded: shard_roundtrip {streams} streams (padded to "
        f"{per_dev * n_shards}) over {n_shards} chips, level {level}: "
        f"{time.time() - t0:.1f} s incl. compile")
    for g in range(0, streams, per_dev):
        sl = slice(g, min(g + per_dev, streams))
        n = sl.stop - sl.start
        twin = roundtrip_ladder_batched(
            jax.device_put(raw[sl], devs[0]), gtb[sl], gtv[sl], params,
            levels=(level,) * n, cfg=cfg,
            **{k: jnp.full((n,), v, jnp.float32) for k, v in scal.items()})
        twin = jax.tree.map(np.asarray, twin)
        for k in ("types", "video_bits", "anchor_bits", "total_bits",
                  "anchor_q"):
            check(np.array_equal(out[k][sl], twin[k]),
                  f"shard_roundtrip {k} differs for streams {sl}")
        detections_close(out["boxes"][sl], out["scores"][sl], twin["boxes"],
                         twin["scores"], f"shard_roundtrip streams {sl}")
        check(all_finite(out["boxes"][sl], out["scores"][sl],
                         out["latency"][sl]), "non-finite round trip")
    log("sharded: shard_roundtrip == single-device twin (types, bits "
        "exact; detections within tolerance)")

    # 2) one chunk per stream through a 4-shard runtime vs the 1-shard one
    packets = [encode_hybrid(raw[s], bw, TR1, TR2) for s in range(streams)]
    sharded_rt = EdgeRuntime(ServingConfig(n_streams=streams), params,
                             det_cfg, mesh=mesh, rules=SINGLE_POD_RULES)
    single_rt = EdgeRuntime(ServingConfig(n_streams=streams), params,
                            det_cfg)
    for s, pk in enumerate(packets):
        b, sc, ty = sharded_rt.process_chunk(s, 0, pk)
        rb, rsc, rty = single_rt.process_chunk(s, 0, pk)
        check(np.array_equal(ty, rty), f"runtime stream {s}: types differ")
        detections_close(b, sc, rb, rsc, f"runtime stream {s}")
        check(all_finite(b, sc), f"runtime stream {s}: non-finite")
    for rt in (sharded_rt, single_rt):
        for s, st in rt.stats.items():
            check(st.frames_in == st.frames_inferred + st.frames_reused
                  + st.frames_skipped, f"runtime stream {s}: accounting")
        rt.close()
    log(f"sharded: EdgeRuntime {n_shards} shards == 1 shard for "
        f"{streams} streams (types exact; detections within tolerance)")
    log("smoke numbers (not a benchmark): peak device memory "
        + ", ".join(peak_gib(d) for d in devs[:n_devices]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded stream-plane phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import configure_compile_cache
    log(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
        f"{configure_compile_cache()}")
    log(f"deployment: {STREAMS} streams x T={T} x {H}x{W} (1080p on the "
        f"1088-row macroblock grid), {BW_MEAN_KBPS:.0f} kbps shared uplink;"
        f" size cuts: none")
    t0 = time.time()
    if args.chips == 4:
        sharded_phase(args.seed)
        count = 4
    else:
        serving_phase(args.seed)
        kernel_phase(args.seed)
        count = len(devs)
    log(f"all phases passed in {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
