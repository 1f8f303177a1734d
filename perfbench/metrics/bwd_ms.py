"""Device milliseconds per traced step of the backward pass: the union of
the intervals of the ops under ``transpose(jvp(forward))`` (``scopes``;
rematerialised recompute included, a bucket's exchange scope and the
fusions that hold update work, ``mixed_ms``, excluded), per chip, the
mean over chips."""
from perfbench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "backward")
