"""Device milliseconds per traced step of multi-head latent attention:
the union of the intervals of the operations under the model's
``attention`` scope, forward, backward and recompute, per chip, the mean
over chips (``model_scopes``)."""
from perfbench import model_scopes


def read(ctx):
    return model_scopes.scope_ms(ctx, "attention")
