"""Model FLOP/s utilisation of the traced window, in percent: the
training FLOPs of every image traced (``counts``), over the window's
seconds, the chips and the chip's peak bf16 FLOP/s. Recomputation is not
counted."""


def read(ctx):
    if ctx.items != "images" or ctx.trace is None or ctx.steps == 0:
        return None
    return (100 * ctx.steps * ctx.items_per_step * ctx.flops_per_item
            / ctx.window_s / ctx.chips / ctx.peaks["bf16_flops_per_s"])
