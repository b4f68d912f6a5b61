"""Device milliseconds per stream-chunk of the detector dispatch.
Over the analysed part of the traced window (bench.trace)."""
from bench.trace import module_table


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.per_chunk_ms("detector", module_table())
