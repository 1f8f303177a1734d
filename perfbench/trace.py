"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

The harness marks the traced window with a host annotation
(``WINDOW``); every interval below is clipped to it. On each device plane
(``/device:TPU:<n>``) the line ``XLA Ops`` holds one event per operation
that ran, named by its HLO instruction (``%fusion.75 = ... fusion(...)``),
and the line ``Async XLA Ops`` the spans of asynchronous operations
(copies, collectives) from their start to their done.

- busy: the length of the union of the operations' intervals;
- an operation's kind (``classify``) comes from the compiled program's
  HLO: ``convolution`` for a convolution or a fusion whose computation
  holds one, ``collective`` for a collective's start, done or whole op,
  and else its opcode;
- collective time: the union of the collective operations' intervals,
  synchronous and asynchronous; exposed collective time: the part of it
  in which no other operation runs on that device.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "perfbench_window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv")
Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Op:
    start: float        # ns
    end: float
    name: str           # HLO instruction name
    category: str       # "convolution", "collective" or the opcode


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]
    window: Interval
    host: List[Tuple[float, float, str]]
    asyncs: Dict[str, List[Op]] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _collective_opcode(opcode: str) -> bool:
    return any(opcode.startswith(c) for c in COLLECTIVES)


def is_collective(op: Op) -> bool:
    return op.category == "collective" or (
        not op.category and _collective_opcode(op.name))


def is_convolution(op: Op) -> bool:
    return op.category == "convolution" or (
        not op.category and op.name.startswith("convolution"))


_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


def _opcode(rhs: str) -> str:
    """The opcode of an HLO instruction's right-hand side
    (``f32[2]{0} add(...)``, ``(f32[2], s32[]) fusion(...)``)."""
    depth, i = 0, 0
    while i < len(rhs):
        c = rhs[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    m = re.match(r"\s*([a-z][\w\-]*)\(", rhs[i:])
    return m.group(1) if m else ""


def classify(hlo_text: str) -> Dict[str, str]:
    """{instruction name: kind} for every instruction of a compiled HLO
    module: ``convolution`` where the instruction or a computation it calls
    convolves, ``collective`` for collective ops and their async halves,
    and else the opcode."""
    comps: Dict[str, List[Tuple[str, str, List[str]]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            cur.append((m.group(1), _opcode(m.group(2)),
                        _CALLS.findall(m.group(2))))
    memo: Dict[str, bool] = {}

    def convolves(comp: str) -> bool:
        if comp not in memo:
            memo[comp] = False
            memo[comp] = any(op == "convolution"
                             or any(convolves(c) for c in called)
                             for _, op, called in comps.get(comp, []))
        return memo[comp]

    kinds = {}
    for instrs in comps.values():
        for name, op, called in instrs:
            if op == "convolution" or (op == "fusion"
                                       and any(convolves(c) for c in called)):
                kinds[name] = "convolution"
            elif _collective_opcode(op):
                kinds[name] = "collective"
            else:
                kinds[name] = op
    return kinds


def union(intervals) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(merged) -> float:
    return sum(b - a for a, b in merged)


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def minus(a_merged, b_merged) -> float:
    """Length of the union ``a_merged`` not covered by ``b_merged`` (both
    merged and sorted)."""
    covered, j = 0.0, 0
    for a0, a1 in a_merged:
        while j < len(b_merged) and b_merged[j][1] <= a0:
            j += 1
        k = j
        while k < len(b_merged) and b_merged[k][0] < a1:
            covered += min(a1, b_merged[k][1]) - max(a0, b_merged[k][0])
            k += 1
    return length(a_merged) - covered


def find_xplane(directory: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


_NAME = re.compile(r"^%?([\w.\-]+)")


def load(path: str, kinds: Optional[Dict[str, str]] = None) -> Trace:
    """The trace at ``path``; ``kinds`` (from ``classify``) gives each
    operation's kind."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    kinds = kinds or {}
    devices: Dict[str, List[Op]] = {}
    asyncs: Dict[str, List[Op]] = {}
    host, window = [], None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                into = {"XLA Ops": devices,
                        "Async XLA Ops": asyncs}.get(line.name)
                if into is None:
                    continue
                ops = into.setdefault(plane.name, [])
                for ev in line.events:
                    m = _NAME.match(ev.name)
                    name = m.group(1) if m else ev.name
                    ops.append(Op(ev.start_ns, ev.end_ns, name,
                                  kinds.get(name, "")))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.end_ns)
                    else:
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation on the host")
    if any(devices.values()) and not any(
            o.end > window[0] and o.start < window[1]
            for v in devices.values() for o in v):
        raise ValueError(f"{path}: no device operation lies in the host's "
                         f"window; the clocks do not line up")
    inside = lambda v: [o for o in v if o.end > window[0]
                        and o.start < window[1]]
    return Trace({k: inside(v) for k, v in devices.items()}, window, host,
                 {k: inside(v) for k, v in asyncs.items()})


def busy_s(ops: List[Op], window: Interval) -> float:
    return length(union(clip([(o.start, o.end) for o in ops],
                             *window))) * 1e-9


def collective_s(ops: List[Op], window: Interval,
                 asyncs: List[Op] = ()) -> Tuple[float, float]:
    """(collective seconds, exposed collective seconds) on one device:
    ``asyncs`` adds the spans of asynchronous collectives."""
    coll = union(clip([(o.start, o.end) for o in list(ops) + list(asyncs)
                       if is_collective(o)], *window))
    other = union(clip([(o.start, o.end) for o in ops
                        if not is_collective(o)], *window))
    return length(coll) * 1e-9, minus(coll, other) * 1e-9


def kind_s(ops: List[Op], window: Interval, pred) -> float:
    """Summed device seconds of the operations that ``pred`` selects."""
    return sum(b - a for a, b in clip([(o.start, o.end) for o in ops
                                       if pred(o)], *window)) * 1e-9


_SUFFIX = re.compile(r"[.\-_]\d+$")


def top_ops(ops: List[Op], window: Interval, n: int = 10):
    """[[operation, seconds], ...]: the operations that took most device
    time, numbered instances of one operation summed."""
    tot = defaultdict(float)
    for o, (a, b) in zip(ops, [(max(o.start, window[0]),
                                min(o.end, window[1])) for o in ops]):
        if b > a:
            name = _SUFFIX.sub("", o.name)
            tot[f"{name} [{o.category}]" if o.category else name] += \
                (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops: List[Op], window: Interval, host, n: int = 10):
    """[[what the host was doing, seconds], ...]: the longest stretches of
    the window in which nothing ran on the device, each named by the
    innermost host event that spans its middle."""
    busy = union(clip([(o.start, o.end) for o in ops], *window))
    gaps, t = [], window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if window[1] > t:
        gaps.append((t, window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        spans = [(e - s, name) for s, e, name in host if s <= mid <= e]
        what = min(spans)[1] if spans else "no host event"
        out.append([f"host: {what}", (b - a) * 1e-9])
    return out


def idle_pct(t: Trace) -> float:
    """Idle share of the window on the chip that idled most, percent."""
    busy = min(busy_s(ops, t.window) for ops in t.devices.values())
    return 100 * (1 - busy / t.window_s)
