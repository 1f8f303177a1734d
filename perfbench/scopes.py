"""The program's own names for its work, read from a trace.

Device side: the train step names its phases with ``jax.named_scope``,
which the compiled HLO keeps in each instruction's ``op_name`` metadata
(a fusion carries its root's). ``scope_of`` maps an ``op_name`` to the
phase it belongs to, the innermost named scope deciding:

- ``exchange``: a bucket's collective, ``ar_b<k>``, ``rs_b<k>``,
  ``ag_b<k>`` or ``ag_g<k>`` (inside a backward JAX writes it
  ``transpose(jvp(ar_b3))``);
- ``update``: the optimizer's application, ``update``;
- ``backward``: ``forward`` under a ``transpose(...)``, which is how JAX
  names the differentiated forward, rematerialised recompute included;
- ``forward``: ``forward`` with no ``transpose(...)`` around it;
- None: no such scope (the step's glue, or a program without scopes).

A fusion takes the phase of its own ``op_name``, but for one case: where
its fused instructions hold ``update`` work together with forward or
backward work (XLA fuses LARS's gradient norms into the backward's
weight-gradient fusions), it is ``mixed``, so that neither ``update`` nor
``backward`` holds time that a change of fusion would move between them.

Host side: the training loop wraps each step in a ``train_step`` step
annotation holding the spans ``loop.batch``, ``loop.release``,
``loop.dispatch``, ``loop.wait`` and ``loop.readback`` (and
``loop.checkpoint``, ``loop.eval``), all on the profiler's host plane.

Every function returns None where the trace holds nothing it reads, as a
program without those names gives.
"""
from __future__ import annotations

import functools
import re
import statistics
from typing import Dict, List, Optional

from perfbench import trace as tr

PHASES = ("forward", "backward", "update", "mixed", "exchange")
LOOP_SPANS = ("loop.batch", "loop.release", "loop.dispatch", "loop.wait",
              "loop.readback")
STEP_SPAN = "train_step"

_EXCHANGE = re.compile(r"^(?:ar|rs|ag)_[bg]\d+$")
_WRAPPED = re.compile(r"^(?:[\w\-]+\()+")
_METADATA = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\bmetadata=\{[^}]*?"
    r"\bop_name=\"([^\"]*)\"")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_FUSION = re.compile(r"\bfusion\(.*?\bcalls=%?([\w.\-]+)")


def scope_of(op_name: str) -> Optional[str]:
    """The phase an ``op_name`` belongs to: one of ``PHASES`` or None."""
    parts = op_name.split("/")
    for k in range(len(parts) - 1, -1, -1):
        base = _WRAPPED.sub("", parts[k]).rstrip(")")
        if _EXCHANGE.match(base):
            return "exchange"
        if base == "update":
            return "update"
        if base == "forward":
            if any("transpose(" in p for p in parts[:k + 1]):
                return "backward"
            return "forward"
    return None


@functools.lru_cache(maxsize=2)
def scope_map(hlo_text: str) -> Dict[str, Optional[str]]:
    """{instruction name: phase} for every instruction of a compiled HLO
    module that carries an ``op_name``: each by its own metadata, and a
    fusion whose fused instructions mix ``update`` with forward or
    backward work ``mixed``."""
    out, held, calls, computation = {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _METADATA.match(line)
        if not m:
            continue
        out[m.group(1)] = phase = scope_of(m.group(2))
        held.setdefault(computation, set()).add(phase)
        f = _FUSION.search(line)
        if f:
            calls[m.group(1)] = f.group(1)
    for name, body in calls.items():
        phases = held.get(body, set()) | {out[name]}
        if "update" in phases and phases & {"forward", "backward"}:
            out[name] = "mixed"
    return out


def _hlo(ctx) -> Optional[str]:
    return getattr(ctx.raw, "hlo", None) if ctx.trace is not None else None


def phase_ms(ctx, phase: str) -> Optional[float]:
    """Device milliseconds per traced step in which an op of ``phase``
    runs: the union of those ops' intervals in the window on each chip,
    the mean over the chips. None where no op carries the phase."""
    hlo = _hlo(ctx)
    if hlo is None or ctx.steps == 0:
        return None
    phases = scope_map(hlo)
    per_chip = []
    for ops in ctx.trace.devices.values():
        mine = [(o.start, o.end) for o in ops if phases.get(o.name) == phase]
        per_chip.append(tr.length(tr.union(tr.clip(mine,
                                                   *ctx.trace.window))))
    if not any(per_chip):
        return None
    return 1e-6 * statistics.mean(per_chip) / ctx.steps


def unscoped(ctx, n: int = 5):
    """(ms per step of device time outside every phase on the first chip,
    its ``n`` largest operations as [[name, ms per step], ...]): busy time
    less the union of the phases' ops. None without scopes."""
    hlo = _hlo(ctx)
    if hlo is None or ctx.steps == 0:
        return None
    phases = scope_map(hlo)
    if not any(phases.values()):
        return None
    ops = next(iter(ctx.trace.devices.values()))
    w = ctx.trace.window
    busy = tr.union(tr.clip([(o.start, o.end) for o in ops], *w))
    scoped = tr.union(tr.clip([(o.start, o.end) for o in ops
                               if phases.get(o.name)], *w))
    rest = [o for o in ops if not phases.get(o.name)]
    top = [[name, 1e3 * s / ctx.steps]
           for name, s in tr.top_ops(rest, w, n)]
    return 1e-6 * tr.minus(busy, scoped) / ctx.steps, top


def host_spans(ctx, name: str) -> List[tr.Interval]:
    """Intervals of the host spans called ``name`` that start inside the
    traced window."""
    if ctx.trace is None:
        return []
    lo, hi = ctx.trace.window
    return [(s, e) for s, e, what in ctx.trace.host
            if what == name and lo <= s < hi]


def idle_split(t: tr.Trace) -> Optional[Dict[str, float]]:
    """Seconds of the window in which the chip that idled most ran
    nothing, split by what the host was doing: under each of
    ``LOOP_SPANS`` inside a ``train_step``, under a ``train_step`` but none
    of them (``loop.other``: the loop's own overhead), and outside every
    step (``outside``). None where the host has no ``train_step``."""
    def in_window(intervals):
        return tr.union(tr.clip(intervals, *t.window))

    def spans(name):
        return in_window([(s, e) for s, e, what in t.host if what == name])

    steps = spans(STEP_SPAN)
    if not steps or not t.devices:
        return None
    ops = min(t.devices.values(), key=lambda v: tr.busy_s(v, t.window))
    busy = in_window([(o.start, o.end) for o in ops])
    # a span's idle time less the part of it outside every step
    busy_or_step = tr.union(busy + steps)
    out = {}
    for name in LOOP_SPANS:
        under = spans(name)
        out[name] = (tr.minus(under, busy)
                     - tr.minus(under, busy_or_step)) * 1e-9
    in_steps = tr.minus(steps, busy) * 1e-9
    out["loop.other"] = in_steps - sum(out.values())
    out["outside"] = (t.window_s - tr.length(busy) * 1e-9) - in_steps
    return out
