"""90th percentile of the same latencies as chunk_ms.p50."""
from bench.stats import percentile


def read(ctx):
    return percentile(ctx.window.latencies_ms, 90)
