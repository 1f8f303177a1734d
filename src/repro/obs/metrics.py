"""Structured metrics registry with pluggable sinks.

The training stack used to log through two ad-hoc channels: the loop's
``mlperf_log`` (the paper's Appendix-1 ``:::MLPv0.5.0`` tag stream) and
bare ``print(..., flush=True)`` calls sprinkled over the loop, the fault
injector, and the launcher. This module replaces both with one typed
event stream fanned out to pluggable sinks:

* :class:`StdoutSink` — the exact ``:::MLPv0.5.0`` line format the old
  ``mlperf_log`` printed (``flush=True`` preserved), so every existing
  log parser keeps working;
* :class:`JsonlSink` — one JSON object per line, the machine-readable
  artifact CI uploads per PR (``launch.train --metrics out.jsonl``);
* :class:`MemorySink` — in-memory capture for tests.

Three event kinds:

=========  ==============================================================
kind       meaning
=========  ==============================================================
event      a tagged occurrence (``run_start``, ``train_step``, ...) with
           an optional structured value — the MLPerf tag stream.
counter    monotonically accumulating count; the emitted value is the
           running total (``obs.retry_total`` etc.).
gauge      a point-in-time measurement (``obs.guard.gnorm``).
=========  ==============================================================

The module-level :func:`default_registry` carries a single
:class:`StdoutSink`, so ``metrics.event(...)`` is a drop-in for the old
prints; callers that need a private stream construct their own
:class:`Registry`. The metric name catalogue lives in
docs/observability.md.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time
from typing import Any, List, Optional, Tuple

#: tag-stream version prefix — the paper's Appendix-1 MLPerf log format
MLPERF_VERSION = "MLPv0.5.0"

KINDS = ("event", "counter", "gauge")


@dataclasses.dataclass(frozen=True)
class Event:
    """One emitted metric row. ``value`` must be JSON-serializable."""
    name: str
    kind: str                       # one of KINDS
    value: Any = None
    ts: float = 0.0                 # unix seconds (time.time)
    where: str = "repro"            # source tag, e.g. 'repro/train/loop.py'
    step: Optional[int] = None


class Sink:
    """Sink interface: receives every :class:`Event` the registry emits."""

    def emit(self, ev: Event) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class StdoutSink(Sink):
    """The legacy ``mlperf_log`` line format, byte-for-byte:

    ``:::MLPv0.5.0 repro <ts:.9f> (<where>) <tag>[: <value>]``

    printed with ``flush=True`` — unbuffered even under a SIGKILL fault,
    which is what the elastic subprocess tests grep for."""

    def emit(self, ev: Event) -> None:
        suffix = "" if ev.value is None else f": {ev.value}"
        print(f":::{MLPERF_VERSION} repro {ev.ts:.9f} ({ev.where}) "
              f"{ev.name}{suffix}", flush=True)


class JsonlSink(Sink):
    """One JSON object per line, flushed per event (a killed process keeps
    every fully-written row). The per-PR metrics artifact format."""

    def __init__(self, path: str):
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self.path = path
        self._f = open(path, "a")
        self._lock = threading.Lock()

    def emit(self, ev: Event) -> None:
        row = {"name": ev.name, "kind": ev.kind, "value": ev.value,
               "ts": ev.ts, "where": ev.where}
        if ev.step is not None:
            row["step"] = ev.step
        line = json.dumps(row, sort_keys=True, default=str)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()


class MemorySink(Sink):
    """Test sink: keeps every event in order."""

    def __init__(self):
        self.events: List[Event] = []

    def emit(self, ev: Event) -> None:
        self.events.append(ev)

    def find(self, name: str) -> List[Event]:
        return [e for e in self.events if e.name == name]


class Registry:
    """Fan-out point: every ``event``/``counter``/``gauge`` call builds one
    :class:`Event` and hands it to every attached sink. Thread-safe — the
    watchdog worker thread and the SIGTERM handler both log through it."""

    def __init__(self, sinks: Tuple[Sink, ...] = ()):
        self._sinks: List[Sink] = list(sinks)
        self._counters = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------- sinks

    def add_sink(self, sink: Sink) -> Sink:
        with self._lock:
            self._sinks.append(sink)
        return sink

    def remove_sink(self, sink: Sink) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    @contextlib.contextmanager
    def use_sink(self, sink: Sink):
        """Attach ``sink`` for the scope of the with-block, then detach and
        close it — the launcher's ``--metrics`` lifetime and the test idiom."""
        self.add_sink(sink)
        try:
            yield sink
        finally:
            self.remove_sink(sink)
            sink.close()

    # ------------------------------------------------------------- emits

    def _emit(self, name: str, kind: str, value, where: str,
              step: Optional[int]) -> Event:
        ev = Event(name=name, kind=kind, value=value, ts=time.time(),
                   where=where, step=step)
        with self._lock:
            sinks = tuple(self._sinks)
        for s in sinks:
            s.emit(ev)
        return ev

    def event(self, name: str, value=None, *, where: str = "repro",
              step: Optional[int] = None) -> Event:
        return self._emit(name, "event", value, where, step)

    def counter(self, name: str, inc: int = 1, *, where: str = "repro",
                step: Optional[int] = None) -> int:
        """Accumulate and emit the running total (the emitted value)."""
        with self._lock:
            total = self._counters.get(name, 0) + inc
            self._counters[name] = total
        self._emit(name, "counter", total, where, step)
        return total

    def gauge(self, name: str, value: float, *, where: str = "repro",
              step: Optional[int] = None) -> Event:
        return self._emit(name, "gauge", value, where, step)


_DEFAULT = Registry((StdoutSink(),))


def default_registry() -> Registry:
    """The process-wide registry the loop/faults/launcher log through; born
    with one :class:`StdoutSink` so the tag stream is on by default."""
    return _DEFAULT


def event(name: str, value=None, *, where: str = "repro",
          step: Optional[int] = None) -> Event:
    return _DEFAULT.event(name, value, where=where, step=step)


def counter(name: str, inc: int = 1, *, where: str = "repro",
            step: Optional[int] = None) -> int:
    return _DEFAULT.counter(name, inc, where=where, step=step)


def gauge(name: str, value: float, *, where: str = "repro",
          step: Optional[int] = None) -> Event:
    return _DEFAULT.gauge(name, value, where=where, step=step)
