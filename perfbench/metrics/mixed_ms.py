"""Device milliseconds per traced step of the fusions that hold the
optimizer's work together with the forward's or the backward's, as XLA
builds them when it fuses LARS's gradient norms into the weight-gradient
fusions (``scopes``): the union of their intervals, per chip, the mean
over chips. Counted in neither ``bwd_ms`` nor ``update_ms``."""
from perfbench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "mixed")
