"""Device milliseconds per stream-chunk of the staging program
(upscale, quality transfer, motion upscale).
Over the analysed part of the traced window (bench.trace)."""
from bench.trace import module_table


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.per_chunk_ms("stage", module_table())
