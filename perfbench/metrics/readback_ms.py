"""Milliseconds per step the training loop spends reading the step's
metrics back to the host and logging them: the summed ``loop.readback``
spans of the traced window over the window's steps."""
from perfbench import scopes


def read(ctx):
    spans = scopes.host_spans(ctx, "loop.readback")
    if not spans or ctx.steps == 0:
        return None
    return 1e-6 * sum(e - s for s, e in spans) / ctx.steps
