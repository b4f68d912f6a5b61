"""The whole serving step's share of the chip's bf16 peak: the
detector operations the window needed (the inferred frames, unpadded)
over the window times the peak."""
from bench import flops


def read(ctx):
    w = ctx.window
    if w.window_s <= 0 or not w.chunks:
        return None
    ops = ctx.frames_inferred * flops.frame_flops(ctx.det, *ctx.hd)
    return 100.0 * ops / (w.window_s * ctx.peak["bf16_flops_per_s"])
