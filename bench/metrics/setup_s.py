"""Process start to the first timed round: imports, weights, rendering
and encoding the packet pool, and the warm pass that compiles (or loads
from the compile cache) every program the window runs."""


def read(ctx):
    return ctx.setup_s
