"""Share of the traced window in which no operation ran on the device, on
the chip that idled most, in percent (image cells)."""
from perfbench import trace


def read(ctx):
    if ctx.items != "images" or ctx.trace is None:
        return None
    return trace.idle_pct(ctx.trace)
