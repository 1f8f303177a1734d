"""Device milliseconds per traced step of the forward pass: the union of
the intervals of the ops under the program's ``forward`` scope and not
under a ``transpose(...)`` (``scopes``), per chip, the mean over chips.
Also says the whole split: each phase, and the busy time outside every
phase with its largest operations."""
from perfbench import scopes


def read(ctx):
    value = scopes.phase_ms(ctx, "forward")
    if value is None:
        return None
    split = {p: scopes.phase_ms(ctx, p) for p in scopes.PHASES}
    rest = scopes.unscoped(ctx)
    ctx.say("device ms per step by scope: " + ", ".join(
        f"{p} {v!r}" for p, v in split.items()) + f"; unscoped {rest[0]!r}, "
        f"its largest ops {rest[1]!r}")
    return value
