"""Observability layer (docs/observability.md).

* ``obs.metrics`` — typed counter/gauge/event registry with pluggable
  sinks (stdout in the MLPerf-v0.5.0 tag format, JSONL file, in-memory);
  the structured replacement for the loop's ad-hoc ``print`` logging.

Timing lives in the JAX profiler's own trace: the train step names its
phases with ``jax.named_scope`` (``forward``, ``update``, each bucket's
``ar_b<k>``/``rs_b<k>``/``ag_b<k>``/``ag_g<k>``) and the training loop
wraps each step in ``jax.profiler`` host annotations (``train_step``,
``loop.*``); ``launch.train --trace DIR`` records both.
"""
from repro.obs.metrics import (JsonlSink, MemorySink, Registry,  # noqa: F401
                               StdoutSink, default_registry)
