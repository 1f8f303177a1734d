"""Device milliseconds per traced step of the optimizer's update: the
union of the intervals of the ops under the program's ``update`` scope
(``scopes``; the fusions that also hold forward or backward work,
``mixed_ms``, excluded), per chip, the mean over chips."""
from perfbench import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "update")
