"""Compile the serving window's device programs for a described TPU v5e
(no chip needed) at a cell's sizes, and print each one's memory.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m bench.tools.compile_v5e \
        --config biswift9_1080p

Programs: the staging jit (``_stage_chunk``), the batch gather, the
full-frame detector dispatch at ``--rows`` rows as the runtime builds it
(with the detector that the configuration's ``detector.arch`` names),
and the finish jit.  A compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--rows", type=int, default=32)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.codec.rate_model import (ladder_for_bandwidth,
                                        ladder_lr_shape,
                                        video_bandwidth_share)
    from repro.serving import runtime as R

    from bench import harness
    from bench.loader import detector

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    H, W, T = cfg["height"], cfg["width"], cfg["frames_per_chunk"]
    level = ladder_for_bandwidth(video_bandwidth_share(
        cfg["uplink_kbps"] / cfg["cameras"]))
    h, w = ladder_lr_shape(level, H, W)
    det = cfg["detector"]
    arch = detector(det["arch"])

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    params = jax.tree.map(lambda x: s(x.shape, x.dtype), jax.eval_shape(
        lambda k: arch.weights(k, det), jax.random.PRNGKey(0)))

    def detect(p, x):
        # the runtime built on the traced weights: its own dispatch
        return harness.make_runtime(cfg, p)._infer(x)

    dispatch = jax.jit(detect).lower(params, s((args.rows, H, W)))
    n_cells = dispatch.out_info[1].shape[1]
    progs = {
        "stage": (jax.jit(lambda *a: R._stage_chunk(
            *a, hd_hw=(H, W))),
            (s((T,), jnp.int32), s((T, H, W)), s((T, h, w)),
             s((T, h // 16, w // 16, 2), jnp.int32),
             s((T, (h // 8) * (w // 8), 8, 8)), s((8, 8)))),
        "gather": (R._gather_batch, ((s((T, H, W)),), s((args.rows,),
                                                          jnp.int32),
                                     s((args.rows,), jnp.bool_))),
        "finish": (jax.jit(lambda *a: R._finish_chunk(*a, has_init=True)),
                   (s((T,), jnp.int32), s((T,), jnp.int32),
                    s((T, H // 16, W // 16, 2), jnp.int32),
                    s((args.rows, n_cells, 4)), s((args.rows, n_cells)),
                    s((n_cells, 4)), s((n_cells,)))),
    }
    lowered = {name: fn.lower(*a) for name, (fn, a) in progs.items()}
    lowered["detector"] = dispatch
    for name, low in lowered.items():
        m = low.compile().memory_analysis()
        print(f"{name}: compiled for v5e; temp "
              f"{m.temp_size_in_bytes / 2**30:.2f} GiB, args "
              f"{m.argument_size_in_bytes / 2**30:.2f} GiB, out "
              f"{m.output_size_in_bytes / 2**30:.2f} GiB", flush=True)


if __name__ == "__main__":
    main()
