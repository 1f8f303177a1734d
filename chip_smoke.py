#!/usr/bin/env python3
"""Smoke test: full-width ResNet-50 training on a TPU through the launcher.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # data-parallel training on four chips

One chip: trains the published ResNet-50 (224x224 images, 1000 classes,
width 64; random weights from seed 0) with LARS and ``--comm xla`` for a
few steps through ``repro.launch.train.main``, in this process. It then
evaluates the step-0 loss on the first images of the step-0 batch on the
chip and on the CPU backend, and compares the two.

Four chips: trains the same model on a 4-chip ``data`` mesh three ways,
``--comm xla``, ``--comm ring`` and ``--comm ring --sharding zero1``, with
the same seed and global batch, and compares their per-step losses and
what each chip holds.

Earlier lines report the device, compile seconds, the number of
``train_step`` compilations, per-step losses and peak HBM. The last line
is one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every check passed. Without a TPU the script exits non-zero before any
work.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the chip-vs-CPU comparison needs the CPU backend beside the TPU one
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

STEPS = 5
BATCH_PER_CHIP = 256        # 9.1 GB of step temporaries on a 16 GB v5e
REF_IMAGES = 8
SEED = 0
#: chip vs CPU, both computing in bf16 with f32 accumulation. The two
#: round differently in the last bf16 bit (2^-8), and a randomly initialised
#: ResNet with batch-norm amplifies that from layer to layer: the logits
#: differ by about a tenth in L2 (printed, not checked), while the loss,
#: averaged over the images and classes, agrees to about 1e-3.
LOSS_RTOL = 2e-2
#: four chips. ring and ring+zero1 run the same explicit-DP forward on the
#: same parameters, so their step-0 losses differ only by reduction order.
STEP0_SAME_RTOL = 1e-4
#: xla takes batch-norm statistics over the global batch, the explicit-DP
#: paths per chip (paper §III-A.2), so their step-0 losses differ. On the
#: CPU at full width that difference is 4.6e-3, 2.3e-3 and 1.1e-3 relative
#: at 8, 16 and 32 images per chip: it falls as 1/batch.
STEP0_BN_RTOL = 5e-3
#: every step, every pair of runs: the paths also exchange gradients in
#: bf16 in different summation orders, and zero1 gathers bf16 parameters.
LOSS_AGREE_RTOL = 2e-2
#: HBM in use may differ by this share across the chips of a mesh
MEM_SPREAD = 0.10

FAILURES = []


def check(ok: bool, what: str) -> None:
    """Record a failed check; every check runs, and any failure fails the
    smoke."""
    if not ok:
        FAILURES.append(what)
        print(f"FAILED: {what}", file=sys.stderr)


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(1.0, abs(b))


def spread(xs) -> float:
    return (max(xs) - min(xs)) / max(xs)


class CompileLog:
    """Counts and times compilations from jax's monitoring events."""

    def __init__(self):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, seconds, **kw):
        if event.startswith(("/jax/core/compile/",
                             "/jax/compilation_cache/cache_retrieval")):
            self.events.append((event, kw.get("fun_name", ""), seconds))

    def mark(self) -> int:
        return len(self.events)

    def summary(self, since: int, fun: str = "train_step") -> dict:
        mine = [(e, s) for e, f, s in self.events[since:]
                if f in (fun, f"jit({fun})")]
        backend = [s for e, s in mine if e.endswith("backend_compile_duration")]
        hits = [e for e, _, _ in self.events[since:]
                if e.startswith("/jax/compilation_cache/")]
        return {"compiles": len(backend), "seconds": sum(s for _, s in mine),
                "backend_seconds": sum(backend), "cache_hits": len(hits)}


def device_report() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def train(argv, log: CompileLog):
    """One run of the launcher. Returns (state, losses, report)."""
    from repro.launch import train as launcher
    from repro.obs import metrics as obs_metrics
    mark = log.mark()
    sink = obs_metrics.MemorySink()
    t0 = time.perf_counter()
    with obs_metrics.default_registry().use_sink(sink):
        state, history = launcher.main(argv)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in history if "loss" in h]
    ts = [e.ts for e in sink.find("train_step")]
    rep = log.summary(mark)
    rep["wall_s"] = wall
    if len(ts) >= 3:
        rep["host_step_s"] = (ts[-1] - ts[1]) / (len(ts) - 2)
    print(f"run {' '.join(argv)}")
    print(f"  compile cache: {jax.config.jax_compilation_cache_dir}")
    print(f"  train_step compiles: {rep['compiles']}, compile seconds: "
          f"{rep['seconds']:.3f} (backend {rep['backend_seconds']:.3f}), "
          f"persistent cache hits (all programs): {rep['cache_hits']}, "
          f"run wall seconds: {wall:.3f}, host-clock seconds per step after "
          f"step 1: {rep.get('host_step_s', float('nan')):.4f}")
    print(f"  losses: {losses}")
    check(len(losses) == STEPS, f"{len(losses)} losses, expected {STEPS}")
    check(all(math.isfinite(x) for x in losses), f"nonfinite loss {losses}")
    check(rep["compiles"] == 1,
          f"train_step compiled {rep['compiles']} times, expected once")
    return state, losses, rep


def launcher_argv(chips: int, *extra: str):
    return ["--arch", "resnet50", "--batch", str(BATCH_PER_CHIP * chips),
            "--steps", str(STEPS), "--devices", str(chips),
            "--log-every", "1", "--seed", str(SEED), *extra]


def chip_vs_cpu() -> None:
    """Step-0 loss and logits of the launcher's initial state on the
    first images of its step-0 batch, on the chip and on the CPU."""
    import numpy as np
    from repro.configs import get_config
    from repro.configs.shapes import InputShape
    from repro.data.synthetic import make_batch_fn
    from repro.launch.mesh import make_local_mesh
    from repro.models.registry import build_model
    from repro.train.state import init_state
    from repro.train.step import make_loss_fn

    cfg = get_config("resnet50")
    model = build_model(cfg)
    mesh = make_local_mesh(devices=1)
    state = init_state(model, SEED, mesh)
    batch = make_batch_fn(cfg, InputShape("cli", "train", 128,
                                          BATCH_PER_CHIP),
                          seed=SEED, mesh=mesh)(state.step)
    batch = jax.tree.map(lambda x: x[:REF_IMAGES], batch)
    loss_fn = make_loss_fn(model, smoothing=0.1)

    @jax.jit
    def loss_and_logits(params, batch, bn):
        loss = loss_fn(params, batch, bn)[1][0]["loss"]
        (logits, _), _ = model.forward_train(params, batch, None, bn)
        return loss, logits

    inputs = (state.params, batch, state.bn_state)
    chip = jax.device_get(loss_and_logits(*inputs))
    cpu_dev = jax.devices("cpu")[0]
    cpu = jax.device_get(loss_and_logits(*jax.device_put(inputs, cpu_dev)))
    loss_chip, loss_cpu = float(chip[0]), float(cpu[0])
    lc, lr = np.asarray(chip[1], np.float64), np.asarray(cpu[1], np.float64)
    l2 = float(np.linalg.norm(lc - lr) / np.linalg.norm(lr))
    print(f"step-0 loss on {REF_IMAGES} images: chip {loss_chip:.6f}, cpu "
          f"{loss_cpu:.6f}, difference {loss_chip - loss_cpu:+.6f} "
          f"(tolerance {LOSS_RTOL} relative)")
    print(f"step-0 logits: relative L2 difference chip vs cpu {l2:.3e}")
    check(rel(loss_chip, loss_cpu) <= LOSS_RTOL,
          "chip and CPU step-0 losses disagree")


def memory_stats(devices) -> list:
    return [d.memory_stats() for d in devices]


def peak_hbm(stats: dict) -> str:
    """The TPU runtime counts buffers (arrays) as in use and reserves the
    compiled programs' temporaries apart; both peaks together bound the
    step's HBM from above."""
    buf, tmp = stats["peak_bytes_in_use"], stats["peak_bytes_reserved"]
    return (f"{buf} bytes of buffers (peak_bytes_in_use) + {tmp} bytes of "
            f"program temporaries (peak_bytes_reserved), at most "
            f"{(buf + tmp) / 2**30:.3f} GiB")


def one_chip(log: CompileLog) -> None:
    train(launcher_argv(1), log)
    stats = memory_stats(jax.devices()[:1])[0]
    print(f"memory_stats: {json.dumps(stats, sort_keys=True)}")
    print(f"peak HBM: {peak_hbm(stats)}")
    chip_vs_cpu()


def per_device_state_bytes(state, devices) -> list:
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return [held[d] for d in devices]


def four_chips(log: CompileLog) -> None:
    devices = jax.devices()[:4]
    if len(devices) < 4:
        check(False, f"{len(devices)} devices, need 4")
        return
    runs = {}
    for name, extra in (("xla", ()), ("ring", ("--comm", "ring")),
                        ("ring+zero1", ("--comm", "ring",
                                        "--sharding", "zero1"))):
        state, losses, _ = train(launcher_argv(4, *extra), log)
        held = per_device_state_bytes(state, devices)
        in_use = [m["bytes_in_use"] for m in memory_stats(devices)]
        print(f"  state bytes held per chip: {held}")
        print(f"  HBM bytes in use per chip: {in_use}")
        check(spread(held) <= 0.01,
              f"{name}: chips hold unequal shares of the state {held}")
        check(spread(in_use) <= MEM_SPREAD,
              f"{name}: HBM in use differs across chips {in_use}")
        if name == "ring+zero1":
            for leaf in (*state.shards, *state.mom):
                sizes = {s.data.size for s in leaf.addressable_shards}
                check(sizes == {leaf.size // 4},
                      f"zero1 buffer of {leaf.size} not split four ways")
        runs[name] = losses
        del state
    print(f"losses: {json.dumps(runs)}")
    check(rel(runs["ring+zero1"][0], runs["ring"][0]) <= STEP0_SAME_RTOL,
          f"step 0: ring+zero1 {runs['ring+zero1'][0]} vs ring "
          f"{runs['ring'][0]} beyond {STEP0_SAME_RTOL}")
    check(rel(runs["ring"][0], runs["xla"][0]) <= STEP0_BN_RTOL,
          f"step 0: ring {runs['ring'][0]} vs xla {runs['xla'][0]} beyond "
          f"{STEP0_BN_RTOL}")
    for a, b in (("ring", "xla"), ("ring+zero1", "xla"),
                 ("ring+zero1", "ring")):
        worst = max(rel(x, y) for x, y in zip(runs[a], runs[b]))
        print(f"{a} vs {b}: largest relative loss difference {worst:.3e} "
              f"(tolerance {LOSS_AGREE_RTOL})")
        check(worst <= LOSS_AGREE_RTOL, f"{a} vs {b} losses disagree")
    stats = memory_stats(devices)
    for i, m in enumerate(stats):
        print(f"chip {i} peak HBM: {peak_hbm(m)}")
    for key in ("peak_bytes_in_use", "peak_bytes_reserved"):
        peaks = [m[key] for m in stats]
        check(spread(peaks) <= MEM_SPREAD,
              f"{key} differs across chips by more than {MEM_SPREAD}: "
              f"{peaks}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    dev = device_report()
    print(f"device: {dev}")
    if dev["platform"] != "tpu":
        print(f"no TPU: jax reports {dev['platform']}", file=sys.stderr)
        return 1
    enable_compile_cache()
    (one_chip if args.chips == 1 else four_chips)(CompileLog())
    if FAILURES:
        print(f"{len(FAILURES)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
