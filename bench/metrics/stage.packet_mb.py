"""Megabytes (1e6 bytes) of all arrays of the packets handed to
submit_chunk, per stream-chunk: what crosses from host to device."""


def read(ctx):
    w = ctx.window
    return w.packet_bytes / w.chunks / 1e6 if w.chunks else None
