"""Serving launcher: the BiSwift multi-stream edge runtime.

``python -m repro.launch.serve --streams 4 --chunks 10`` runs the full
loop: synthetic cameras -> hybrid encoder -> (simulated) shared uplink ->
edge runtime (3 pipelines, batched detector, admission control) ->
bandwidth controller feedback.  This is deliverable (b)'s end-to-end
serving driver; benchmarks/ reuse the same plumbing.

``main`` returns a summary dict, and an ``on_chunk`` callback sees every
served chunk (its packet, ground truth and results) — ``chip_smoke.py``
drives the paper deployment through here and checks results that way.
Weights and video are made from ``--seed``.  ``lat=`` and the summary's
``mean_latency_s`` are measured: the host clock around each
``process_chunk`` call (edge serving only, not the camera encode).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.bandwidth_controller import BandwidthController, \
    even_proportions
from repro.core.hybrid_encoder import encode_hybrid
from repro.launch.compile_cache import configure_compile_cache
from repro.models import detection as D
from repro.serving.runtime import EdgeRuntime
from repro.serving.scheduler import ServingConfig
from repro.sim.env import EnvConfig, high_state_dim, MultiStreamEnv
from repro.sim.network import TraceConfig, allocate, generate_trace
from repro.sim.video_source import paper_stream_mix, generate_chunk


@jax.jit
def _chunk_f1(boxes, scores, gt_boxes, gt_valid):
    """(T,) per-frame F1 of NMS'd detections — one dispatch per chunk."""
    def one(b, s, g, v):
        return D.f1_score(*D.greedy_nms(b, s, iou_thresh=0.4, top_k=16),
                          g, v)
    return jax.vmap(one)(boxes, scores, gt_boxes, gt_valid)


def main(argv=None, on_chunk=None) -> dict:
    """Run the serving loop; returns the summary (also printed).

    ``on_chunk(info)`` is called after each served chunk with a dict of
    ``t``, ``stream``, ``packet``, ``gt_boxes``, ``gt_valid``,
    ``bw_kbps``, the runtime's ``boxes``/``scores``/``types`` and the
    detector ``params``/``det_cfg``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--chunks", type=int, default=6)
    ap.add_argument("--chunk-frames", type=int, default=4)
    ap.add_argument("--height", type=int, default=64)
    ap.add_argument("--width", type=int, default=96)
    ap.add_argument("--bw-mean-kbps", type=float, default=16000.0)
    ap.add_argument("--controller", choices=["even", "sac"], default="even")
    ap.add_argument("--detector-ckpt", default=None)
    ap.add_argument("--quick-train", type=int, default=150,
                    help="inline detector fit steps when no ckpt (0=off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds detector weights, quick-fit and video")
    args = ap.parse_args(argv)
    configure_compile_cache()

    streams = paper_stream_mix(args.streams, args.height, args.width)
    det_cfg = D.TinyDetectorConfig()
    params = D.init(jax.random.PRNGKey(args.seed + 1), det_cfg)
    if args.detector_ckpt:
        from repro.train import checkpoint as CKPT
        step = CKPT.latest_step(args.detector_ckpt)
        params = CKPT.restore(args.detector_ckpt, step, params)
    elif args.quick_train:
        # make the demo self-sufficient: a short detector fit on the
        # stream mix (use examples/train_detector.py + --detector-ckpt
        # for a properly trained model)
        from repro.train.optimizer import AdamWConfig, apply_updates, \
            init_state
        opt = init_state(params)
        ocfg = AdamWConfig(lr=3e-3, weight_decay=0.0, warmup_steps=10,
                           total_steps=args.quick_train)

        @jax.jit
        def _fit(params, opt, frames, boxes, valid):
            loss, g = jax.value_and_grad(lambda p: D.loss_fn(
                p, det_cfg, frames, boxes, valid))(params)
            params, opt, _ = apply_updates(params, g, opt, ocfg)
            return params, opt, loss

        print(f"quick-training detector ({args.quick_train} steps)...")
        kq = jax.random.PRNGKey(args.seed + 3)
        for i in range(args.quick_train):
            sc = streams[i % len(streams)]
            fr, bx, vl = generate_chunk(kq, sc, i * 4, 4)
            params, opt, loss = _fit(params, opt, fr, bx, vl)
        print(f"  final det loss {float(loss):.3f}")

    runtime = EdgeRuntime(ServingConfig(n_streams=args.streams), params,
                          det_cfg)
    trace = generate_trace(TraceConfig(mean_kbps=args.bw_mean_kbps),
                           args.chunks)
    env_cfg = EnvConfig(streams=tuple(streams),
                        chunk_frames=args.chunk_frames)
    controller = None
    env = MultiStreamEnv(env_cfg)
    if args.controller == "sac":
        controller = BandwidthController.create(
            jax.random.PRNGKey(args.seed + 2), high_state_dim(env_cfg),
            args.streams)

    key = jax.random.PRNGKey(args.seed)
    f1_all, lat_all, chunk_wall = [], [], []
    t_start = time.time()
    for t in range(args.chunks):
        t_chunk = time.time()
        env.t = t
        if controller is not None:
            props = controller.proportions(key, env.observe_high(), t,
                                           explore=False)
        else:
            props = even_proportions(args.streams)
        alloc = allocate(trace[t], props)
        for c, sc in enumerate(streams):
            frames, boxes, valid = generate_chunk(
                key, sc, t * args.chunk_frames, args.chunk_frames)
            packet = encode_hybrid(np.asarray(frames), alloc[c],
                                   tr1=0.05, tr2=0.10)
            # host clock around the call: it returns host arrays, so the
            # device work is done
            t_call = time.perf_counter()
            b, s, types = runtime.process_chunk(c, t, packet)
            lat = time.perf_counter() - t_call
            f1 = float(np.mean(np.asarray(_chunk_f1(b, s, boxes, valid))))
            f1_all.append(f1)
            lat_all.append(lat)
            print(f"chunk {t} stream {c}: bw={alloc[c]:7.0f}kbps "
                  f"types={types.tolist()} f1={f1:.3f} "
                  f"lat={lat * 1e3:6.1f}ms")
            if on_chunk is not None:
                on_chunk(dict(t=t, stream=c, packet=packet, gt_boxes=boxes,
                              gt_valid=valid, bw_kbps=float(alloc[c]),
                              boxes=b, scores=s, types=types,
                              params=params, det_cfg=det_cfg))
        chunk_wall.append(time.time() - t_chunk)
    wall = time.time() - t_start
    runtime.close()
    fps = args.streams * args.chunks * args.chunk_frames / wall
    print(f"\nmean F1 {np.mean(f1_all):.3f} | mean process_chunk "
          f"{np.mean(lat_all) * 1e3:.1f} ms | deferred chunks "
          f"{runtime.deferred} | wall {wall:.1f}s ({fps:.1f} fps incl. "
          f"encode sim)")
    return {"mean_f1": float(np.mean(f1_all)),
            "mean_latency_s": float(np.mean(lat_all)),
            "deferred": int(runtime.deferred), "wall_s": wall, "fps": fps,
            "chunk_wall_s": chunk_wall,
            "stream_stats": {s: st.as_dict()
                             for s, st in sorted(runtime.stats.items())}}

if __name__ == "__main__":
    main()
