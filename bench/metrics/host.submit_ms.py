"""Host milliseconds per stream-chunk inside EdgeRuntime.submit_chunk
(the benchmark's span around each call): admission, the host-to-device
copy of the packet and the staging dispatch."""


def read(ctx):
    w = ctx.window
    return sum(w.spans["submit"]) / w.chunks * 1e3 if w.chunks else None
