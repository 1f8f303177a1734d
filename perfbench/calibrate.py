#!/usr/bin/env python3
"""Readings from which a training cell's limits are set.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--faults half_batch,...] [--out <file.jsonl>]

In one process, at the cell's own size: for every seed, the program's
first three steps (as a benchmark run's set-up drives them) against the
float32 reference's; for the first three seeds, the control (the
reference in float8) against the reference; then, for each planted fault
(``faults.py``), the program with that fault on the first three seeds.
Each reading is one JSON line: the numbers the oracle compares and, for
the per-tensor numbers, the tensor that gave them. A fault of a state
left unchanged reads 1 by construction and is not run. Needs the chips
the cell asks for; not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"

#: seeds of the control and of each fault: the least the limits' upper
#: readings may come from
FEW = 3


def _summary(who: str, seed: int, prog: dict, ref: dict) -> dict:
    from perfbench import oracle
    keep = oracle.kept_leaves(ref["grad0"])
    out = {"who": who, "seed": seed, **oracle.gaps(prog, ref),
           "losses": prog["losses"], "ref_losses": ref["losses"],
           "left_out": sorted(set(ref["grad0"]) - set(keep))}
    for name in ("grad1", "change3"):
        g = oracle.leaf_gaps(prog[name], ref[name], keep)
        worst = max(g, key=g.get)
        out[f"{name}_worst"] = [worst, g[worst], prog[name][worst],
                                ref[name][worst]]
    if ref["stats"]:
        out["bn_stats_layers"] = oracle.stats_gaps(prog["stats"],
                                                   ref["stats"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from perfbench import faults, spec
    from perfbench.kinds import train

    cell = spec.Cell(spec.benchmark(), args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    sink = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    t0 = time.perf_counter()
    prog = train.build(cell)
    reference = train.reference(cell, "f32")
    control = train.reference(cell, "fp8")
    refs = {}

    def program_readings(prog, seed):
        train.seed_state(cell, prog, seed)
        readings = train.first_steps(cell, prog, seed)
        dev = prog.devices[0]
        batches = [jax.device_put(b, dev) for b in prog.feed.pool[:3]]
        prog.state = prog.feed = None
        gc.collect()
        return readings, batches, dev

    for i, seed in enumerate(seeds):
        readings, batches, dev = program_readings(prog, seed)
        refs[seed] = train.run_reference(cell, reference, batches, seed, dev)
        emit(_summary("program", seed, readings, refs[seed]))
        if i < FEW:
            ctl = train.run_reference(cell, control, batches, seed, dev)
            emit(_summary("control", seed, ctl, refs[seed]))
        del batches
        print(f"# seed {seed} done at {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    prog = None
    gc.collect()
    for fault in [f for f in args.faults.split(",") if f]:
        with faults.planted(fault):
            fprog = train.build(cell)
            for seed in seeds[:FEW]:
                readings, _, _ = program_readings(fprog, seed)
                emit(_summary(fault, seed, readings, refs[seed]))
        fprog = None
        gc.collect()
    print(f"# calibration took {time.perf_counter() - t0:.1f} s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
