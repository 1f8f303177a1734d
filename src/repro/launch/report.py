"""Aggregate dry-run JSON records into the EXPERIMENTS.md roofline tables.

  PYTHONPATH=src python -m repro.launch.report --dir experiments/dryrun/baseline
"""
from __future__ import annotations

import argparse
import glob
import json
import os


def fmt_t(x: float) -> str:
    if x == 0:
        return "0"
    if x < 1e-4:
        return f"{x*1e6:.1f}µs"
    if x < 0.1:
        return f"{x*1e3:.2f}ms"
    return f"{x:.3f}s"


def fmt_b(x: float) -> str:
    for unit, div in (("TB", 1e12), ("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if x >= div:
            return f"{x/div:.2f}{unit}"
    return f"{x:.0f}B"


def load(dirpath: str):
    recs = []
    for p in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        with open(p) as f:
            recs.append(json.load(f))
    return recs


def hint(rec) -> str:
    d = rec["dominant"]
    if d == "collective":
        kinds = rec.get("coll_by_kind", {})
        top = max(kinds, key=kinds.get) if kinds else "all-reduce"
        return (f"{top} dominates — larger per-device batch, bf16 wire "
                f"dtype, or resharding to cut {top} volume")
    if d == "memory":
        if rec["kind"] == "decode":
            return ("KV/state cache streaming bound — in-place cache "
                    "update, quantized cache, or batching more requests")
        return ("activation traffic bound — fused loss, bf16 "
                "intermediates, larger per-device batch (fewer chips) or "
                "flash-style fusion")
    return "MXU-bound — already near roofline; only algorithmic wins left"


def table(recs, mesh: str) -> str:
    rows = [
        "| arch | shape | dom | t_comp | t_mem | t_coll | HLO GF/dev | "
        "HBM/dev | coll/dev | useful | what would move the dominant term |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        if r["mesh"] != mesh:
            continue
        rows.append(
            f"| {r['arch']} | {r['shape']} | **{r['dominant'][:4]}** "
            f"| {fmt_t(r['t_compute_s'])} | {fmt_t(r['t_memory_s'])} "
            f"| {fmt_t(r['t_collective_s'])} "
            f"| {r['flops_per_dev']/1e9:.1f} "
            f"| {fmt_b(r['hbm_bytes_per_dev'])} "
            f"| {fmt_b(r['coll_bytes_per_dev'])} "
            f"| {r['useful_flops_ratio']:.2f} "
            f"| {hint(r)} |")
    return "\n".join(rows)


def dryrun_table(recs) -> str:
    rows = [
        "| arch | shape | mesh | compile | peak mem/dev | collectives |",
        "|---|---|---|---|---|---|",
    ]
    for r in recs:
        ma = r.get("memory_analysis", {})
        peak = (ma.get("temp_size_in_bytes", 0)
                + ma.get("argument_size_in_bytes", 0)
                + ma.get("output_size_in_bytes", 0)) / max(r["chips"], 1) \
            if ma else 0
        # memory_analysis is per-device already on this backend; record raw
        peak = ma.get("temp_size_in_bytes", 0) + ma.get(
            "argument_size_in_bytes", 0)
        kinds = ", ".join(f"{k}:{fmt_b(v)}"
                          for k, v in sorted(r["coll_by_kind"].items()))
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compile_s']:.1f}s | {fmt_b(peak)} | {kinds or '—'} |")
    return "\n".join(rows)


def compare_table(base_recs, opt_recs, mesh="16x16") -> str:
    """Baseline vs optimized dominant-term deltas per (arch, shape)."""
    key = lambda r: (r["arch"], r["shape"])
    base = {key(r): r for r in base_recs if r["mesh"] == mesh}
    opt = {key(r): r for r in opt_recs if r["mesh"] == mesh}
    rows = ["| arch | shape | baseline dom (t) | optimized dom (t) | Δ dominant |",
            "|---|---|---|---|---|"]
    for k in sorted(base):
        if k not in opt:
            continue
        b, o = base[k], opt[k]
        tb = max(b["t_compute_s"], b["t_memory_s"], b["t_collective_s"])
        to = max(o["t_compute_s"], o["t_memory_s"], o["t_collective_s"])
        rows.append(
            f"| {k[0]} | {k[1]} | {b['dominant'][:4]} {fmt_t(tb)} "
            f"| {o['dominant'][:4]} {fmt_t(to)} "
            f"| {100 * (to - tb) / tb:+.1f}% |")
    return "\n".join(rows)


PRODUCTION_DP_AXES = {
    # mesh tag -> (gradient all-reduce axes, their sizes); 'model' is TP
    "16x16": (("data",), (16,)),
    "2x16x16": (("pod", "data"), (2, 16)),
}


def comm_section(payload_bytes: float = None, bucket_mb: float = 4.0) -> str:
    """Per-schedule alpha-beta predicted comm time for the production
    meshes (repro/comm/cost.py), fastest first within each mesh. Default
    payload: the ResNet-50 gradient in bf16 (paper §III-C/§IV)."""
    import math

    from repro.comm import cost
    from repro.configs import get_config, param_count

    if payload_bytes is None:
        payload_bytes = param_count(get_config("resnet50")) * 2   # bf16
    n_buckets = max(1, math.ceil(payload_bytes / (bucket_mb * 2 ** 20)))
    rows = [f"### Predicted all-reduce time, {fmt_b(payload_bytes)} "
            f"gradient in {n_buckets} buckets\n",
            "| mesh | schedule | msgs | wire/dev | predicted t | phases |",
            "|---|---|---|---|---|---|"]
    for tag, (axes, sizes) in PRODUCTION_DP_AXES.items():
        for r in cost.predict_table(axes, sizes, payload_bytes,
                                    n_buckets=n_buckets):
            phases = " + ".join(p.name for p in r.phases) or "—"
            rows.append(f"| {tag} | {r.schedule} | {r.n_messages} "
                        f"| {fmt_b(r.wire_bytes)} | {fmt_t(r.time_s)} "
                        f"| {phases} |")
    return "\n".join(rows)


def autotune_section(arch: str = "resnet50") -> str:
    """Per-schedule autotuned bucket plan + predicted overlap efficiency
    for the production meshes (repro/comm/autotune.py). Backward time comes
    from the family-aware FLOPs model at the paper's 320 images/device."""
    from repro.comm import available
    from repro.comm.autotune import CANDIDATES_MB, autotune
    from repro.configs import get_config
    from repro.models.registry import build_model

    cfg = get_config(arch)
    model = build_model(cfg)
    rows = [f"### Autotuned bucket plan ({arch} gradients, bf16 wire; "
            f"candidates {', '.join(f'{c:g}' for c in CANDIDATES_MB)} MB)\n",
            "| mesh | schedule | bucket MB | buckets | t_comm | exposed "
            "| overlap eff | t_step |",
            "|---|---|---|---|---|---|---|---|"]
    for tag, (axes, sizes) in PRODUCTION_DP_AXES.items():
        tuned = [autotune(model.param_pd, schedule=s, axes=axes, sizes=sizes,
                          family=cfg.family)
                 for s in available()]
        best = min(tuned, key=lambda t: (t.sim.t_step_s, t.n_buckets))
        for t in sorted(tuned, key=lambda t: t.sim.t_step_s):
            star = " **<-**" if (t.schedule == best.schedule
                                 and t.bucket_mb == best.bucket_mb) else ""
            rows.append(
                f"| {tag} | {t.schedule} | {t.bucket_mb:g} "
                f"| {t.n_buckets} | {fmt_t(t.sim.t_comm_s)} "
                f"| {fmt_t(t.sim.t_exposed_s)} | {t.sim.overlap_eff:.2f} "
                f"| {fmt_t(t.sim.t_step_s)}{star} |")
    return "\n".join(rows)


def shard_update_section(arch: str = "resnet50") -> str:
    """Sharding-policy byte/time accounting (docs/comm.md): per schedule
    at its autotuned bucket size, the replicated timeline (AR(g) + full
    update) vs sharding='zero1' (in-backward RS(g) + update/n + AG(p) at
    both gather issue points) vs sharding='zero2' (replicated forward, no
    gather; fp32 step-end write-back AG) vs sharding='zero3' (just-in-time
    AG in the forward; gather='per_group' re-gathers in the backward,
    'ahead' retains), plus the zero3-vs-zero1 peak-param-memory reduction
    (``comm.cost.param_memory_reduction``, n-independent)."""
    from repro.comm import available, cost as cost_mod
    from repro.comm.autotune import autotune
    from repro.configs import get_config
    from repro.core import bucketing
    from repro.models.registry import build_model

    cfg = get_config(arch)
    model = build_model(cfg)
    rows = [f"### Sharding-policy accounting ({arch}, bf16 wire): "
            "replicated vs zero1 (RS+update/n+AG) vs zero2 (replicated "
            "fwd, fp32 AG) vs zero3 (AG in forward)\n",
            "| mesh | schedule | bucket MB | replicated | zero1 at_end "
            "| zero1 ahead | zero2 | zero3 per_group | zero3 ahead "
            "| update | peak-mem ↓ |",
            "|---|---|---|---|---|---|---|---|---|---|---|"]
    for tag, (axes, sizes) in PRODUCTION_DP_AXES.items():
        for s in available():
            ar = autotune(model.param_pd, schedule=s, axes=axes,
                          sizes=sizes, family=cfg.family)
            sh = autotune(model.param_pd, schedule=s, axes=axes,
                          sizes=sizes, family=cfg.family, sharding="zero1")
            # the alternative policies priced on the SAME plan as the
            # zero1/ahead row, so the t_step deltas are purely the gather
            # issue point / sharding level
            same = dict(schedule=s, axes=axes, sizes=sizes,
                        family=cfg.family, candidates=(sh.bucket_mb,))
            end = autotune(model.param_pd, sharding="zero1",
                           gather="at_end", **same)
            z2 = autotune(model.param_pd, sharding="zero2",
                          gather="at_end", **same)
            z3 = autotune(model.param_pd, sharding="zero3",
                          gather="per_group", **same)
            z3r = autotune(model.param_pd, sharding="zero3",
                           gather="ahead", **same)
            plan = bucketing.make_plan(model.param_pd,
                                       bucket_mb=sh.bucket_mb)
            red = cost_mod.param_memory_reduction(
                plan, cost_mod.shard_axis_size(axes, sizes)[1])
            rows.append(
                f"| {tag} | {s} | {sh.bucket_mb:g} "
                f"| {fmt_t(ar.sim.t_step_s)} | {fmt_t(end.sim.t_step_s)} "
                f"| {fmt_t(sh.sim.t_step_s)} | {fmt_t(z2.sim.t_step_s)} "
                f"| {fmt_t(z3.sim.t_step_s)} "
                f"| {fmt_t(z3r.sim.t_step_s)} "
                f"| {fmt_t(ar.sim.t_update_s)}→{fmt_t(sh.sim.t_update_s)} "
                f"| {100 * red:.1f}% |")
    return "\n".join(rows)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun/baseline")
    ap.add_argument("--compare", default=None,
                    help="second records dir: emit baseline-vs-optimized")
    ap.add_argument("--section", default="roofline",
                    choices=["roofline", "dryrun", "comm", "autotune",
                             "shard"])
    args = ap.parse_args()
    if args.section == "comm":
        print(comm_section())
        return
    if args.section == "autotune":
        print(autotune_section())
        return
    if args.section == "shard":
        print(shard_update_section())
        return
    recs = load(args.dir)
    if args.compare:
        print(compare_table(recs, load(args.compare)))
    elif args.section == "roofline":
        print("### Single-pod (16×16 = 256 chips)\n")
        print(table(recs, "16x16"))
    else:
        print(dryrun_table(recs))


if __name__ == "__main__":
    main()
