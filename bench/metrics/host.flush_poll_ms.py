"""Host milliseconds per stream-chunk inside EdgeRuntime.flush and
EdgeRuntime.poll (the benchmark's spans): batch gather and detector
dispatch, then the wait for the device and the device-to-host copy."""


def read(ctx):
    w = ctx.window
    if not w.chunks:
        return None
    return (sum(w.spans["flush"]) + sum(w.spans["poll"])) / w.chunks * 1e3
