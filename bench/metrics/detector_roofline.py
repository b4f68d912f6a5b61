"""The detector's share of its roofline: the least time the chip could
take for the detector runs in the analysed window (the larger of their
operations over peak bf16 FLOP/s and their bytes over HBM bandwidth,
counted by bench.flops from the rows each dispatch ran, padding
included) over those runs' device time.  The i-th detector run of the
window is the window's i-th dispatch: the warm pass drained before it."""
from bench import flops
from bench.trace import module_table


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.layer_runs("detector", module_table())
    rows = ctx.window.detector_rows[:len(runs)]
    if not runs or len(rows) < len(runs):
        return None
    ops = nbytes = 0.0
    for r in rows:
        o, b = flops.dispatch_cost(ctx.det, ctx.hd, r)
        ops += o
        nbytes += b
    least = max(ops / ctx.peak["bf16_flops_per_s"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (sum(e - s for _, s, e in runs) * 1e-9)
