"""Device milliseconds per stream-chunk of the batch gather and of the
finish program (scatter of the detector rows and pipeline-3 reuse).
Over the analysed part of the traced window (bench.trace)."""
from bench.trace import module_table


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.per_chunk_ms("finish", module_table())
