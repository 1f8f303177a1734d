"""Model FLOP/s utilisation of the traced window, in percent: the
training FLOPs of every token traced (the configuration module's
``flops_per_item``, the chip's share of the model), over the window's
seconds, the chips and the chip's peak bf16 FLOP/s. Recomputation is not
counted."""


def read(ctx):
    if ctx.items != "tokens" or ctx.trace is None or ctx.steps == 0:
        return None
    return (100 * ctx.steps * ctx.items_per_step * ctx.flops_per_item
            / ctx.window_s / ctx.chips / ctx.peaks["bf16_flops_per_s"])
