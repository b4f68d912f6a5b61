"""Frames per stream-chunk that went to pipelines 1 and 2 (the
detector), from the runtime's frames_inferred counter."""


def read(ctx):
    w = ctx.window
    return ctx.frames_inferred / w.chunks if w.chunks else None
