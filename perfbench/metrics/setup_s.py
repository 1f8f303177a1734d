"""Seconds from the start of the process to the first timed step:
imports, the program's build and initial state, compilation or loading
from the cache, the batch pool, the first steps and the warm-up steps."""


def read(ctx):
    return ctx.setup_s
