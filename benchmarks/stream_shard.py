"""Sharded-vs-single-device stream throughput rows.

Run directly under a forced multi-device CPU platform:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.stream_shard

Prints a single JSON payload (list of [name, us_per_call, derived] rows)
as the LAST stdout line.  ``benchmarks/run.py`` invokes this module as a
subprocess — its own process has already committed jax to the real
1-device platform, and XLA only honours the device-count flag before the
first jax import.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.run import _timeit

N_STREAMS = 8


def build_inputs(n_streams):
    from repro.core.hybrid_encoder import encode_hybrid
    from repro.models import detection as D
    from repro.sim.video_source import StreamConfig, generate_chunk

    packs = []
    for s in range(n_streams):
        frames, _, _ = generate_chunk(
            jax.random.PRNGKey(s),
            StreamConfig(height=64, width=96, n_objects=3), 0, 4)
        packs.append(encode_hybrid(np.asarray(frames), 8000.0, 0.05, 0.1))
    det_cfg = D.TinyDetectorConfig()
    params = D.init(jax.random.PRNGKey(1), det_cfg)
    T = packs[0].types.shape[0]
    n_cells_gt = 8
    args = dict(
        enc=jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[p.video for p in packs]),
        types=jnp.stack([jnp.asarray(p.types) for p in packs]),
        anchor_hd=jnp.stack([jnp.asarray(p.anchor_hd) for p in packs]),
        gt_boxes=jnp.zeros((n_streams, T, n_cells_gt, 4), jnp.float32),
        gt_valid=jnp.zeros((n_streams, T, n_cells_gt), jnp.bool_),
        bw_kbps=jnp.full((n_streams,), 8000.0, jnp.float32),
        queue_delay=jnp.zeros((n_streams,), jnp.float32),
        total_bits=jnp.asarray([p.total_bits for p in packs], jnp.float32),
    )
    return args, params, det_cfg, T


def sharded_rows():
    """Sharded-vs-single-device rows on this process's devices."""
    from repro.core.hybrid_decoder import decode_execute_batched
    from repro.distributed.sharding import SINGLE_POD_RULES
    from repro.distributed.stream_sharding import (shard_streams,
                                                   stream_shard_count)

    n_dev = len(jax.devices())
    args, params, det_cfg, T = build_inputs(N_STREAMS)
    a = args

    def single():
        return decode_execute_batched(
            a["enc"], a["types"], a["anchor_hd"], a["gt_boxes"],
            a["gt_valid"], params, det_cfg, bw_kbps=a["bw_kbps"],
            queue_delay=a["queue_delay"], total_bits=a["total_bits"])["f1"]

    us_single = _timeit(single)

    mesh = jax.make_mesh((n_dev,), ("data",))
    run = shard_streams(mesh, SINGLE_POD_RULES, det_cfg=det_cfg)
    n_shards = stream_shard_count(mesh, SINGLE_POD_RULES)

    def sharded():
        return run(a["enc"], a["types"], a["anchor_hd"], a["gt_boxes"],
                   a["gt_valid"], params, bw_kbps=a["bw_kbps"],
                   queue_delay=a["queue_delay"],
                   total_bits=a["total_bits"])["f1"]

    us_sharded = _timeit(sharded)
    fps = N_STREAMS * T / (us_sharded / 1e6)
    rows = [
        [f"stream_batched_single_dev_{N_STREAMS}streams", us_single,
         f"oracle_{n_dev}devhost"],
        [f"stream_sharded_{n_shards}shard_{N_STREAMS}streams", us_sharded,
         f"fps:{fps:.0f};vs_single:{us_single / max(us_sharded, 1e-9):.2f}x"],
    ]
    return rows


def main():
    print(json.dumps(sharded_rows()))


if __name__ == "__main__":
    main()
