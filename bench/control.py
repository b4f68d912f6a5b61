"""The control of the correctness check: the plain reference computed in
bfloat16 (the precision below the configuration's float32), put in the
program's place.  Every number it reads comes out of the same harness
run, sampling and comparison as a benchmark run; only what is compared
with the float32 reference is the bfloat16 reference's output instead of
what the program served.  The control has to come out as not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 2]

Runs every seed in this one process (on the TPU; it exits 2 without
one) and prints one JSON line per seed with the numbers compared.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def served_in_bf16(pk, params, det):
    """What the bfloat16 reference would serve for this packet."""
    import jax.numpy as jnp

    from bench import reference
    boxes, scores, _ = reference.chunk(pk, params, det, jnp.bfloat16)
    return boxes, scores, pk["types"]


def control_run(workload: str, seed: int, seconds: float, **kw) -> dict:
    from bench import harness
    return harness.run(workload, seed, seconds, False,
                       served_fn=served_in_bf16, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from bench import harness
    for seed in args.seeds:
        try:
            r = control_run(args.workload, seed, args.seconds)
        except harness.NoAccelerator as e:
            print(f"[control] {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": r["correct"], "checks": r["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
