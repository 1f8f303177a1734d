"""Median duration, in milliseconds, of the training loop's
``loop.dispatch`` spans in the traced window: the host's call of the
jitted step until it returns. Also says how the device's idle time in
the window splits over the loop's spans (``scopes.idle_split``)."""
import statistics

from perfbench import scopes


def read(ctx):
    spans = scopes.host_spans(ctx, "loop.dispatch")
    if not spans:
        return None
    split = scopes.idle_split(ctx.trace)
    if split is not None:
        ctx.say("device idle ms per step by host span: " + ", ".join(
            f"{k} {1e3 * v / max(ctx.steps, 1)!r}"
            for k, v in split.items()))
    return 1e-6 * statistics.median(e - s for s, e in spans)
