"""Images trained per second per chip: every image of every step in the
window, over the window's host-clock seconds and the chips."""


def read(ctx):
    if ctx.items != "images" or ctx.trace is not None:
        return None
    return ctx.steps * ctx.items_per_step / ctx.window_s / ctx.chips
