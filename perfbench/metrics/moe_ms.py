"""Device milliseconds per traced step of the MoE layers: the union of the
intervals of the operations under the model's ``moe`` scope (router,
dispatch, held and shared experts, combine), forward, backward and
recompute, per chip, the mean over chips (``model_scopes``)."""
from perfbench import model_scopes


def read(ctx):
    value = model_scopes.scope_ms(ctx, "moe")
    if value is not None:
        parts = {p: model_scopes.scope_ms(ctx, "moe", p)
                 for p in ("router", "dispatch", "experts", "combine")}
        ctx.say("device ms per step under moe: " + ", ".join(
            f"{p} {v!r}" for p, v in parts.items()))
    return value
