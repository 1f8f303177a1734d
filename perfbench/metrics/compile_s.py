"""Seconds of set-up spent compiling or loading compiled programs from the
persistent cache: every ``jax.monitoring`` compile and cache-retrieval
duration before the window."""


def read(ctx):
    return ctx.compile_s
