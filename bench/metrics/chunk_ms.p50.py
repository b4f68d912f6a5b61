"""Median latency of every stream-chunk completed in the window, from
the start of its submit to the return of its poll."""
from bench.stats import percentile


def read(ctx):
    return percentile(ctx.window.latencies_ms, 50)
