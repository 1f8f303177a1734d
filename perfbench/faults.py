"""Faults planted in the program's timed path, for the tests and the
calibration of the limits: each must make ``correct`` come out false.
Each is a context manager that patches the program while a step is built
and compiled inside it; none is used by a benchmark run.

- ``state_unchanged``: the step returns its state as it got it (only the
  step counter moves);
- ``half_batch``: the loss leaves out the second half of the batch (of
  each chip's rows) and takes the mean over the rest.

The cells run on one chip, so no exchange between chips can be left out.
"""
from __future__ import annotations

import contextlib
import functools

import jax.numpy as jnp


@contextlib.contextmanager
def state_unchanged():
    from repro.train import step as step_mod
    real = step_mod.make_train_step

    @functools.wraps(real)
    def make(*a, **kw):
        ts = real(*a, **kw)

        def stuck(state, batch):
            _, metrics = ts(state, batch)
            return state._replace(step=state.step + 1), metrics

        stuck.__dict__.update(ts.__dict__)
        return stuck

    step_mod.make_train_step = make
    try:
        yield
    finally:
        step_mod.make_train_step = real


@contextlib.contextmanager
def half_batch():
    from repro.core.label_smoothing import IGNORE
    from repro.train import step as step_mod
    real = step_mod.smoothed_xent

    def half(logits, labels, *, smoothing=0.1):
        keep = jnp.arange(labels.shape[0]) < labels.shape[0] // 2
        keep = keep.reshape((-1,) + (1,) * (labels.ndim - 1))
        return real(logits, jnp.where(keep, labels, IGNORE),
                    smoothing=smoothing)

    step_mod.smoothed_xent = half
    try:
        yield
    finally:
        step_mod.smoothed_xent = real


def planted(name: str):
    return {"state_unchanged": state_unchanged,
            "half_batch": half_batch}[name]()
