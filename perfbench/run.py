#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print one JSON line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
The last line of standard output is the result; the numbers compared to
decide ``correct`` come last on standard error and under ``checks`` in the
result. Without an accelerator, or with fewer chips than the cell asks
for, it prints no result and exits 3.

JAX's persistent compilation cache is kept in ``<checkout>/.jax_cache``,
whatever the environment says, so that only a cell's first run in a
checkout compiles; a trace is written under ``<checkout>/.perfbench`` and
deleted once read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".jax_cache"
WORK = ROOT / ".perfbench"
NO_CHIP = 3

if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    # libtpu would otherwise log to a fixed path under /tmp
    os.environ["TPU_LOG_DIR"] = "disabled"

from perfbench import clock  # noqa: E402  (notes the time first)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None, *, cell=None, require_chip: bool = True) -> int:
    """``cell`` and ``require_chip=False`` let a test drive a run at a
    small size on the CPU; a benchmark run passes neither."""
    args = parse(argv)
    t_process = clock.process_start()
    import jax
    from types import SimpleNamespace

    from perfbench import oracle, peaks, spec, trace as tr

    if cell is None:
        cell = spec.Cell(spec.benchmark(), args.workload)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        say(f"{cell.name} needs {cell.chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s)")
        return NO_CHIP
    if devices[0].platform != "cpu":
        jax.config.update("jax_compilation_cache_dir", str(CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = devices[0].device_kind
    pk = peaks.peaks(kind if require_chip else "TPU v5 lite")
    say(f"device: {devices[0].platform} {kind} x{cell.chips}; cell "
        f"{cell.name}, seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}")
    log = clock.CompileLog()
    raw = cell.kind().run(cell, args.seed, args.seconds, bool(args.trace),
                          log, t_process, WORK / cell.name, say)
    cm = cell.config_module
    # what every reader may take: the cell (its config, traffic and config
    # module), the run's raw readings, and a few quantities derived from them
    ctx = SimpleNamespace(
        cell=cell, raw=raw, items=cm.ITEMS, chips=cell.chips, peaks=pk,
        trace=None,
        items_per_step=raw.global_batch * cm.items_per_row(cell.config,
                                                           cell.traffic),
        flops_per_item=cm.flops_per_item(cell.config, cell.traffic),
        steps=raw.steps, step_s=raw.step_s, window_s=raw.window_s,
        setup_s=raw.setup_s, compile_s=raw.compile_s,
        memory_peak=raw.memory_peak, say=say)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": raw.memory_peak}
    line = {}
    if args.trace:
        path = tr.find_xplane(str(raw.trace_dir))
        t = tr.load(path, tr.classify(raw.hlo) if raw.hlo else None)
        planes = [f"/device:TPU:{d.id}" for d in raw.devices]
        t.devices = {p: t.devices.get(p, []) for p in planes}
        ctx.trace = t
        busy = [tr.busy_s(ops, t.window) for ops in t.devices.values()]
        device.update(busy_s=sum(busy) / len(busy), window_s=t.window_s)
        first = t.devices[planes[0]]
        line["breakdown"] = {
            "device_ops": tr.top_ops(first, t.window),
            "idle_gaps": tr.idle_gaps(first, t.window, t.host)}
        import shutil
        shutil.rmtree(raw.trace_dir, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = oracle.checks(raw.values, cell.limits["limits"],
                           raw.window_compiles)
    result = {"correct": oracle.passed(checks), "attempted": raw.steps,
              "failed": raw.failed, "metrics": metrics, "device": device,
              **line, "checks": checks}
    for name, c in checks.items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
