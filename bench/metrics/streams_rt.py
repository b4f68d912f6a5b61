"""Cameras this chip keeps up with in real time: stream-chunks completed
in the window times the video seconds of a chunk, over the window."""
from bench.stats import streams_rt


def read(ctx):
    return streams_rt(ctx.window.chunks, ctx.window.window_s, ctx.chunk_s)
