"""Set-up time and compilations.

``process_start()`` is when this process began, from ``/proc`` where
Linux gives it, so that interpreter start-up and imports count as set-up.
``CompileLog`` counts and times compilations from ``jax.monitoring``'s
events: every ``/jax/core/compile/*`` duration (tracing, lowering, the
backend's compile) and every retrieval from the persistent cache.
"""
from __future__ import annotations

import os
import time

_IMPORTED = time.time()


def process_start() -> float:
    """Wall-clock time at which this process started (``time.time()``
    base)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        boot = time.time() - uptime
        return min(boot + int(fields[19]) / ticks, _IMPORTED)
    except (OSError, ValueError, IndexError):
        return _IMPORTED


class CompileLog:
    """Compile and cache-retrieval events, from the moment it is made."""

    def __init__(self):
        import jax
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, seconds, **kw):
        if event.startswith(("/jax/core/compile/",
                             "/jax/compilation_cache/cache_retrieval")):
            self.events.append((event, seconds))

    def mark(self) -> int:
        return len(self.events)

    def seconds(self, since: int = 0, until=None) -> float:
        return sum(s for _, s in self.events[since:until])

    def compiles(self, since: int = 0, until=None) -> int:
        """Backend compilations and persistent-cache retrievals (a program
        taken from the cache may give one of each)."""
        return sum(1 for e, _ in self.events[since:until]
                   if e.endswith("backend_compile_duration")
                   or e.startswith("/jax/compilation_cache/"))
