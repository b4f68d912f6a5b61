"""Fused encode->decode round-trip benchmark rows (``roundtrip_*`` in
BENCH_pipeline.json).

Single-device (invoked from ``benchmarks.run``): the sequential two-jit
path (per-stream ``roundtrip_oracle`` — ``encode_chunk`` jit + host glue +
``decode_execute_chunk`` jit) against the fused ``roundtrip_batched`` jit
at 1..8 streams, plus a mixed-bitrate-ladder row through the padded
heterogeneous dispatch.

Multi-device: run this module directly under a forced multi-device CPU
platform (``benchmarks.run`` spawns it the same way as
``benchmarks.stream_shard``); it prints a JSON payload of
``roundtrip_sharded_*`` rows as the LAST stdout line, comparing the
single-device batched jit to ``shard_roundtrip`` over the mesh.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp


def _inputs(S, H=64, W=96, T=4):
    from repro.models import detection as D
    from repro.sim.video_source import StreamConfig, generate_chunk

    data = [generate_chunk(None, StreamConfig(height=H, width=W,
                                              n_objects=3, seed=s), 0, T)
            for s in range(S)]
    raw = jnp.stack([d[0] for d in data])
    gtb = jnp.stack([d[1] for d in data])
    gtv = jnp.stack([d[2] for d in data])
    det_cfg = D.TinyDetectorConfig()
    params = D.init(jax.random.PRNGKey(1), det_cfg)
    scalars = dict(tr1=jnp.full((S,), 0.05), tr2=jnp.full((S,), 0.1),
                   bw_kbps=jnp.full((S,), 4000.0),
                   queue_delay=jnp.zeros((S,)))
    return raw, gtb, gtv, params, det_cfg, scalars


def roundtrip_bench():
    """Sequential two-jit vs fused round-trip, 1..8 streams + mixed
    ladder (single device)."""
    from benchmarks.run import SMOKE, _timeit
    from repro.core.roundtrip import (RoundtripConfig, roundtrip_batched,
                                      roundtrip_ladder_batched,
                                      roundtrip_oracle)

    rows = []
    stream_counts = (1, 2) if SMOKE else (1, 2, 4, 8)
    levels = (4, 3, 2)               # the mixed-ladder row's rungs
    S_max = max(*stream_counts, len(levels))
    raw, gtb, gtv, params, det_cfg, sc = _inputs(S_max)
    cfg = RoundtripConfig(level=3, det_cfg=det_cfg)
    T = raw.shape[1]

    for S in stream_counts:
        def seq():
            return [roundtrip_oracle(
                raw[s], gtb[s], gtv[s], params, tr1=0.05, tr2=0.1,
                bw_kbps=4000.0, cfg=cfg) for s in range(S)]

        us_seq = _timeit(seq, n=3)
        rows.append((f"roundtrip_seq_twojit_{S}stream", us_seq,
                     "encode-jit+host-glue+decode-jit"))

        def fused():
            return roundtrip_batched(
                raw[:S], gtb[:S], gtv[:S], params, tr1=sc["tr1"][:S],
                tr2=sc["tr2"][:S], bw_kbps=sc["bw_kbps"][:S],
                queue_delay=sc["queue_delay"][:S], cfg=cfg)

        us_fused = _timeit(fused, n=3)
        fps = S * T / (us_fused / 1e6)
        rows.append((f"roundtrip_fused_{S}stream", us_fused,
                     f"fps:{fps:.0f};speedup_vs_twojit:"
                     f"{us_seq / max(us_fused, 1e-9):.2f}x"))

    # ---- kernel + bf16 codec configs on the TIMED fused path (these were
    # dead flags before: every headline row above ran the f32 fallback
    # search).  Same batched jit, only RoundtripConfig.codec changes.
    from repro.codec.video_codec import VideoCodecConfig
    variant_counts = (1,) if SMOKE else (1, 4)
    cfg_bf16 = RoundtripConfig(
        level=3, det_cfg=det_cfg,
        codec=VideoCodecConfig(use_kernel=True, dtype="bfloat16"))
    cfg_diamond = RoundtripConfig(
        level=3, det_cfg=det_cfg,
        codec=VideoCodecConfig(use_kernel=True, search="diamond"))

    def fused_with(cfg_v, S):
        return roundtrip_batched(
            raw[:S], gtb[:S], gtv[:S], params, tr1=sc["tr1"][:S],
            tr2=sc["tr2"][:S], bw_kbps=sc["bw_kbps"][:S],
            queue_delay=sc["queue_delay"][:S], cfg=cfg_v)

    f32_us = {int(n.split("_")[2][:-6]): u for n, u, _ in rows
              if n.startswith("roundtrip_fused_") and n.endswith("stream")}
    for S in variant_counts:
        us_bf = _timeit(lambda: fused_with(cfg_bf16, S), n=3)
        ref = f32_us.get(S)
        derived = "use_kernel+bf16"
        if ref:
            derived += f";vs_f32:{ref / max(us_bf, 1e-9):.2f}x"
        rows.append((f"roundtrip_fused_{S}stream_bf16", us_bf, derived))
    S_d = variant_counts[-1]
    us_dia = _timeit(lambda: fused_with(cfg_diamond, S_d), n=3)
    ref = f32_us.get(S_d)
    derived = "use_kernel+diamond-search"
    if ref:
        derived += f";vs_f32_exhaustive:{ref / max(us_dia, 1e-9):.2f}x"
    rows.append((f"roundtrip_fused_{S_d}stream_diamond", us_dia, derived))

    # ---- in-trace anchor-quality budget search (bench-adaptive): the
    # masked ladder sweep + traced argmax vs the pinned-quality trace —
    # the cost of making anchor quality adapt per chunk without retracing
    import dataclasses
    cfg_qs = dataclasses.replace(cfg, anchor_search=True)
    S_q = variant_counts[-1]
    us_qs = _timeit(lambda: fused_with(cfg_qs, S_q), n=3)
    ref = f32_us.get(S_q)
    derived = "in-trace-anchor-budget-search"
    if ref:
        derived += f";vs_pinned:{ref / max(us_qs, 1e-9):.2f}x"
    rows.append((f"roundtrip_fused_{S_q}stream_qsearch", us_qs, derived))

    S = len(levels)

    def ladder():
        return roundtrip_ladder_batched(
            raw[:S], gtb[:S], gtv[:S], params, tr1=sc["tr1"][:S],
            tr2=sc["tr2"][:S], bw_kbps=sc["bw_kbps"][:S],
            queue_delay=sc["queue_delay"][:S], levels=levels, cfg=cfg)

    us_lad = _timeit(ladder, n=3)
    rungs = "/".join(str(lv) for lv in levels)
    rows.append((f"roundtrip_fused_mixed_ladder_{S}stream", us_lad,
                 f"rungs:{rungs};one-padded-jit"))
    return rows


def roundtrip_roi_bench():
    """ROI-gated vs full-frame fused round trip on the fig.14-style
    scenarios (``roundtrip_roi_*`` rows).

    Both regimes run the SAME fused ``roundtrip_batched`` jit; only
    ``RoundtripConfig.roi`` differs.  Gating is capacity-only
    (threshold=0.0, K < n_regions), so the per-chunk detector work is
    deterministic — K packed patches instead of the full frame — and the
    speedup column measures the gate, not scene luck.  The sparse row is
    the acceptance gate (>= 1.5x); the dense row documents where the gate
    saturates (larger K, smaller win).  ``f1`` rides the derived column
    as accuracy evidence."""
    import dataclasses

    from benchmarks.run import SMOKE, _timeit
    from repro.core.roi import RoiConfig, region_grid
    from repro.core.roundtrip import RoundtripConfig, roundtrip_batched
    from repro.models import detection as D
    from repro.sim.video_source import generate_chunk, scenario_streams

    H, W = (96, 128) if SMOKE else (192, 256)
    T = 4 if SMOKE else 8
    det_cfg = D.TinyDetectorConfig()
    params = D.init(jax.random.PRNGKey(1), det_cfg)
    cfg0 = RoundtripConfig(level=3, det_cfg=det_cfg)
    nry, nrx = region_grid((H, W), RoiConfig())
    nreg = nry * nrx

    rows = []
    for label, scenario, cap in (
            ("sparse", "sparse-highway", max(nreg // 16, 2)),
            ("dense", "crowded-crossroad", max(nreg // 4, 4))):
        sc = scenario_streams(scenario, 1, height=H, width=W)[0]
        frames, gtb, gtv = generate_chunk(None, sc, 0, T)
        raw = frames[None]
        args = (raw, gtb[None], gtv[None], params)
        kw = dict(tr1=jnp.full((1,), 0.05), tr2=jnp.full((1,), 0.1),
                  bw_kbps=jnp.full((1,), 4000.0),
                  queue_delay=jnp.zeros((1,)))

        def off():
            return roundtrip_batched(*args, **kw, cfg=cfg0)

        # n=10/warmup=2: at n=3 run-to-run noise on a loaded CPU swamps
        # the ~2x gating effect these rows exist to witness
        us_off = _timeit(off, n=10, warmup=2)
        f1_off = float(off()["mean_f1"].mean())
        rows.append((f"roundtrip_roi_{label}_off", us_off,
                     f"full-frame;regions:{nreg};f1:{f1_off:.3f}"))

        # ref gather: the Pallas kernel only runs interpret-mode on CPU,
        # whose per-step overhead would mask the gating win this row is
        # measuring (kernel parity + timing have their own rows/tests)
        roi = RoiConfig(capacity=cap, threshold=0.0, use_kernel=False)
        cfg1 = dataclasses.replace(cfg0, roi=roi)

        def on():
            return roundtrip_batched(*args, **kw, cfg=cfg1)

        us_on = _timeit(on, n=10, warmup=2)
        f1_on = float(on()["mean_f1"].mean())
        rows.append((f"roundtrip_roi_{label}_on", us_on,
                     f"capacity:{cap}/{nreg};vs_off:"
                     f"{us_off / max(us_on, 1e-9):.2f}x;f1:{f1_on:.3f}"))
    return rows


def sharded_rows():
    """Sharded vs single-device round-trip rows on this process's
    devices."""
    from benchmarks.run import SMOKE, _timeit
    from repro.core.roundtrip import RoundtripConfig, roundtrip_batched
    from repro.distributed.sharding import SINGLE_POD_RULES
    from repro.distributed.stream_sharding import (shard_roundtrip,
                                                   stream_shard_count)

    n_dev = len(jax.devices())
    S = 4 if SMOKE else 8
    raw, gtb, gtv, params, det_cfg, sc = _inputs(S)
    cfg = RoundtripConfig(level=3, det_cfg=det_cfg)
    T = raw.shape[1]

    def single():
        return roundtrip_batched(raw, gtb, gtv, params, cfg=cfg, **sc)

    us_single = _timeit(single)

    mesh = jax.make_mesh((n_dev,), ("data",))
    run = shard_roundtrip(mesh, SINGLE_POD_RULES, cfg=cfg)
    n_shards = stream_shard_count(mesh, SINGLE_POD_RULES)

    def sharded():
        return run(raw, gtb, gtv, params, **sc)

    us_sharded = _timeit(sharded)
    fps = S * T / (us_sharded / 1e6)
    rows = [
        [f"roundtrip_batched_single_dev_{S}streams", us_single,
         f"oracle_{n_dev}devhost"],
        [f"roundtrip_sharded_{n_shards}shard_{S}streams", us_sharded,
         f"fps:{fps:.0f};vs_single:"
         f"{us_single / max(us_sharded, 1e-9):.2f}x"],
    ]
    return rows


def main():
    print(json.dumps(sharded_rows()))


if __name__ == "__main__":
    main()
