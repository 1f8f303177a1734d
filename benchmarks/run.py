"""Benchmark harness — one benchmark per paper table/figure, plus kernel
microbenches. Prints ``name,us_per_call,derived`` CSV rows.

  Table I  -> projected ResNet-50/ImageNet epoch + 90-epoch time on v5e
              meshes (roofline model), vs the paper's 74.7 s on 2048 V100.
  Fig. 2   -> scalability: projected images/sec vs chip count; derived =
              parallel efficiency at 2048 chips (paper: 77.0%).
  Fig. 3   -> REAL small-scale training: final eval accuracy vs global
              batch (LARS + warmup + smoothing recipe) on prototype-ImageNet.
  Fig. 4   -> train-vs-val accuracy gap for the Fig.3 run (overfit check).
  ablation -> LARS vs SGD-M at high lr; label smoothing on/off (§III-A).
  kernels  -> batched-norm / fused-LARS / smoothed-xent vs unfused baselines.
  comm     -> bucketed vs per-tensor allreduce on 8 host devices (§III-C).

Run: PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh

ROWS = []


def _host_mesh_env() -> dict:
    """Env of the 8-host-device child processes: they emulate a mesh on
    the CPU backend and must never load, or wait for, an accelerator."""
    return {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}


def emit(name: str, us_per_call: float, derived: str):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6, out


# ----------------------------------------------------------- Table I / Fig 2

V5E_PEAK = 197e12       # bf16 flops/chip
V5E_ICI = 50e9          # bytes/s/link
RESNET_FLOPS_IMG = 3 * 4.1e9          # train flops per 224x224 image
RESNET_BYTES = 25.6e6 * 2             # bf16 gradient bytes per replica


def projected_images_per_sec(chips: int, *, global_batch: int = 81920,
                             mfu: float = 0.45) -> float:
    """Roofline-style projection: per-step compute at `mfu` of peak,
    overlapped with a ring all-reduce of the gradients on the DP axis
    (the paper's §III-C overlap ⇒ step time = max(compute, comm) + bucket
    tail latency)."""
    per_chip = global_batch / chips
    t_compute = per_chip * RESNET_FLOPS_IMG / (V5E_PEAK * mfu)
    ring = 2 * RESNET_BYTES * (chips - 1) / chips / V5E_ICI
    n_buckets = max(1, int(RESNET_BYTES / (4 * 2**20)))
    tail = ring / n_buckets                      # last bucket can't overlap
    t_step = max(t_compute, ring) + tail
    return global_batch / t_step


def bench_table1(quick: bool):
    """Paper Table I analogue: time-to-90-epochs projections."""
    t0 = time.perf_counter()
    for chips, batch in [(256, 81920), (512, 81920), (2048, 81920)]:
        ips = projected_images_per_sec(chips, global_batch=batch)
        t_epoch = 1_281_167 / ips
        t90 = 90 * t_epoch
        emit(f"table1.v5e_{chips}chips_b{batch}",
             (time.perf_counter() - t0) * 1e6,
             f"proj {ips/1e6:.2f}M img/s; 90ep {t90:.0f}s "
             f"(paper@2048V100: 74.7s / 1.73M img/s)")


def bench_fig2(quick: bool):
    t0 = time.perf_counter()
    base = None
    for chips in [16, 64, 256, 512, 1024, 2048]:
        ips = projected_images_per_sec(chips)
        if base is None:
            base = ips / 16
        eff = ips / (base * chips)
        emit(f"fig2.scalability_{chips}", (time.perf_counter() - t0) * 1e6,
             f"{ips/1e6:.2f}M img/s eff={eff*100:.1f}%"
             + (" (paper: 77.0%)" if chips == 2048 else ""))


# ------------------------------------------------------------- Fig 3 / Fig 4

def _train_resnet(batch: int, steps: int, *, lr=None, smoothing=0.1,
                  opt="lars", warmup_frac=0.15, seed=0):
    from repro.configs import get_config
    from repro.configs.shapes import InputShape
    from repro.core import lars as lars_mod
    from repro.core.schedule import ScheduleConfig, linear_scaled_lr, \
        make_schedule
    from repro.data.synthetic import make_batch_fn, prototype_imagenet
    from repro.models.registry import build_model
    from repro.train import state as st
    from repro.train.step import make_eval_step, make_train_step

    cfg = get_config("resnet50").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    model = build_model(cfg)
    if lr is None:
        lr = linear_scaled_lr(16.0, batch) / 4     # tuned for the toy task
        # (LARS trust_coef=1e-3 makes effective matrix lr ~1e-3*base)
    sched = make_schedule(ScheduleConfig(
        base_lr=lr, warmup_steps=int(steps * warmup_frac),
        total_steps=steps, decay="poly2"))
    step = jax.jit(make_train_step(
        model, lars_mod.OptConfig(kind=opt), sched, smoothing=smoothing,
        mesh=mesh))
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, batch), seed=seed,
                       mesh=mesh)
    s = st.init_state(model, seed)
    hist = []
    for _ in range(steps):
        s, m = step(s, bf(s.step))
        hist.append(float(m["acc"]))
    ev = jax.jit(make_eval_step(model, mesh=mesh))
    accs = []
    for k in range(4):
        eb = prototype_imagenet(cfg, batch=64, step=jnp.int32(10_000 + k),
                                seed=seed)
        accs.append(float(ev(s.params, eb, s.bn_state)["acc"]))
    return float(np.mean(accs)), hist


def bench_fig3(quick: bool):
    """Accuracy vs batch size with the paper's recipe, at FIXED total
    examples (the paper fixes epochs: bigger batch = fewer updates — that
    scarcity is exactly the large-batch challenge of §IV/Fig.3)."""
    total_examples = 64 * (25 if quick else 60)
    for batch in ([16, 64] if quick else [16, 64, 256]):
        steps = max(total_examples // batch, 8)
        t0 = time.perf_counter()
        acc, _ = _train_resnet(batch, steps)
        emit(f"fig3.acc_vs_batch_b{batch}", (time.perf_counter() - t0) * 1e6,
             f"eval_acc={acc:.3f} steps={steps} (fixed {total_examples} "
             f"examples)")


def bench_fig4(quick: bool):
    steps = 25 if quick else 60
    t0 = time.perf_counter()
    acc, hist = _train_resnet(64, steps)
    train_acc = float(np.mean(hist[-5:]))
    emit("fig4.train_vs_val_gap", (time.perf_counter() - t0) * 1e6,
         f"train_acc={train_acc:.3f} val_acc={acc:.3f} "
         f"gap={train_acc-acc:+.3f}")


# ----------------------------------------------- ablations (paper §III-A)

def bench_lars_ablation(quick: bool):
    """LARS vs plain SGD-momentum at aggressive lr (paper's core claim)."""
    steps = 20 if quick else 40
    for opt in ("lars", "sgdm"):
        t0 = time.perf_counter()
        acc, _ = _train_resnet(64, steps, lr=8.0, opt=opt)
        emit(f"ablation.highlr_{opt}", (time.perf_counter() - t0) * 1e6,
             f"eval_acc={acc:.3f} @lr=8 (paper: LARS stays usable at the "
             f"large-batch lr where plain SGD degrades)")


def bench_bn_momentum_ablation(quick: bool):
    """Paper SIII-A.2: 'we tuned some hyper-parameters to optimize the
    moving averages' — BN momentum sweep at the eval boundary."""
    import dataclasses
    from repro.configs import get_config
    steps = 20 if quick else 40
    for mom in (0.8, 0.9, 0.99):
        t0 = time.perf_counter()
        import repro.configs.resnet50 as r50
        base = get_config("resnet50").reduced()
        cfg = dataclasses.replace(base, bn_momentum=mom)
        acc, _ = _train_resnet_cfg(cfg, 64, steps)
        emit(f"ablation.bn_momentum_{mom}", (time.perf_counter() - t0) * 1e6,
             f"eval_acc={acc:.3f}")


def _train_resnet_cfg(cfg, batch, steps, *, lr=None, smoothing=0.1,
                      opt="lars", seed=0):
    from repro.configs.shapes import InputShape
    from repro.core import lars as lars_mod
    from repro.core.schedule import ScheduleConfig, linear_scaled_lr, \
        make_schedule
    from repro.data.synthetic import make_batch_fn, prototype_imagenet
    from repro.models.registry import build_model
    from repro.train import state as st
    from repro.train.step import make_eval_step, make_train_step
    mesh = make_mesh((1, 1), ("data", "model"))
    model = build_model(cfg)
    if lr is None:
        lr = linear_scaled_lr(16.0, batch) / 4
    sched = make_schedule(ScheduleConfig(
        base_lr=lr, warmup_steps=int(steps * 0.15), total_steps=steps,
        decay="poly2"))
    step = jax.jit(make_train_step(
        model, lars_mod.OptConfig(kind=opt), sched, smoothing=smoothing,
        mesh=mesh))
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, batch), seed=seed,
                       mesh=mesh)
    s = st.init_state(model, seed)
    for _ in range(steps):
        s, m = step(s, bf(s.step))
    ev = jax.jit(make_eval_step(model, mesh=mesh))
    accs = [float(ev(s.params, prototype_imagenet(
        cfg, batch=64, step=jnp.int32(10_000 + k), seed=seed),
        s.bn_state)["acc"]) for k in range(4)]
    return float(np.mean(accs)), None


def bench_smoothing_ablation(quick: bool):
    steps = 20 if quick else 40
    for sm in (0.0, 0.1):
        t0 = time.perf_counter()
        acc, _ = _train_resnet(64, steps, smoothing=sm)
        emit(f"ablation.smoothing_{sm}", (time.perf_counter() - t0) * 1e6,
             f"eval_acc={acc:.3f}")


# ----------------------------------------------------------------- kernels

def bench_kernel_batched_norm(quick: bool):
    """Paper §III-B.2: batched norms vs one-reduce-per-tensor."""
    from repro.core import bucketing
    from repro.kernels import ops, ref
    n_tensors, chunks_each = (16, 4) if quick else (64, 8)
    n_chunks = n_tensors * chunks_each
    seg = jnp.asarray(np.repeat(np.arange(n_tensors), chunks_each)
                      .astype(np.int32))
    flat = jax.random.normal(jax.random.PRNGKey(0),
                             (n_chunks * bucketing.CHUNK,))
    tensors = [flat[i * chunks_each * bucketing.CHUNK:
                    (i + 1) * chunks_each * bucketing.CHUNK]
               for i in range(n_tensors)]

    @jax.jit
    def per_tensor():
        return jnp.stack([jnp.sum(t * t) for t in tensors])

    @jax.jit
    def packed():
        return ref.batched_sumsq(flat, seg, n_tensors)

    us_sep, a = _timeit(per_tensor)
    us_pack, b = _timeit(packed)
    np.testing.assert_allclose(a, b, rtol=1e-4)
    # kernel correctness cross-check (interpret mode; CPU timing meaningless)
    c = ops.batched_sumsq(flat, seg, n_tensors)
    np.testing.assert_allclose(np.asarray(c), np.asarray(a), rtol=1e-4)
    emit("kernel.batched_norm_packed", us_pack,
         f"vs per-tensor {us_sep:.0f}us ({us_sep/us_pack:.2f}x) "
         f"n_tensors={n_tensors}")


def bench_kernel_smoothed_xent(quick: bool):
    from repro.core.label_smoothing import smoothed_xent
    from repro.kernels import ref
    T, V = (2048, 8192) if quick else (4096, 32_768)
    k = jax.random.PRNGKey(1)
    logits = jax.random.normal(k, (T, V))
    labels = jax.random.randint(jax.random.fold_in(k, 1), (T,), 0, V)

    naive = jax.jit(lambda l, y: smoothed_xent(l, y, smoothing=0.1)[0])
    fused = jax.jit(lambda l, y: ref.smoothed_xent_rows(
        l, y, smoothing=0.1).mean())
    us_naive, a = _timeit(naive, logits, labels)
    us_fused, b = _timeit(fused, logits, labels)
    np.testing.assert_allclose(a, b, rtol=1e-4)
    emit("kernel.smoothed_xent", us_fused,
         f"vs naive {us_naive:.0f}us T={T} V={V}")


def bench_kernel_lars_update(quick: bool):
    from repro.core import bucketing
    from repro.kernels import ref
    n_chunks = 64 if quick else 256
    N = n_chunks * bucketing.CHUNK
    k = jax.random.PRNGKey(2)
    p = jax.random.normal(k, (N,))
    g = jax.random.normal(jax.random.fold_in(k, 1), (N,))
    m = jnp.zeros(N)
    n_tensors = 8
    seg = jnp.asarray(np.repeat(np.arange(n_tensors), n_chunks // n_tensors)
                      .astype(np.int32))
    trust = jnp.abs(jax.random.normal(jax.random.fold_in(k, 3),
                                      (n_tensors,)))

    fused = jax.jit(lambda: ref.lars_packed_update(
        p, g, m, trust, seg, lr=0.1, momentum=0.9, wd=1e-4))
    us, _ = _timeit(fused)
    emit("kernel.lars_packed_update", us, f"N={N} fp32 fused step")


# ------------------------------------------------- comm (paper §III-C)

def bench_comm_bucketing(quick: bool):
    """Bucketed vs per-tensor psum wall time on 8 host devices (subprocess:
    jax device count locks at init)."""
    import subprocess
    import sys
    t0 = time.perf_counter()
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, time
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro.core import bucketing, ddp
from repro.core.compat import shard_map
mesh = make_mesh((8,), ("data",))
ks = jax.random.split(jax.random.PRNGKey(0), 120)
tree = {f"t{i}": jax.random.normal(ks[i], ((i % 7 + 1) * 96, 128))
        for i in range(120)}
plan = bucketing.make_plan(tree, bucket_mb=4.0)
def naive(t):
    return ddp.allreduce_grads(t, strategy="naive", axes=("data",))
def bucketed(t):
    return ddp.allreduce_grads(t, strategy="bucketed", axes=("data",),
                               plan=plan)
spec = jax.tree.map(lambda _: P(), tree)
for name, fn in [("naive", naive), ("bucketed", bucketed)]:
    f = jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,),
                          out_specs=spec))
    jax.block_until_ready(f(tree))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(f(tree))
    print(f"{name},{(time.perf_counter()-t0)/5*1e6:.0f}")
"""
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=_host_mesh_env())
    res = dict(line.split(",") for line in r.stdout.strip().splitlines()
               if "," in line)
    if "naive" in res and "bucketed" in res:
        sp = float(res["naive"]) / float(res["bucketed"])
        # host-CPU psum is memcpy-bound with no message latency; project
        # the interconnect time with an alpha-beta model on v5e ICI:
        alpha_us, bw = 10.0, 50e9
        grad_bytes = sum((i % 7 + 1) * 96 * 128 * 4 for i in range(120))
        ring_us = 2 * grad_bytes * 7 / 8 / bw * 1e6
        t_naive = 120 * alpha_us + ring_us
        t_bucketed = 13 * alpha_us + ring_us
        emit("comm.bucketed_allreduce", float(res["bucketed"]),
             f"wall(hostCPU)={sp:.2f}x; v5e alpha-beta projection: "
             f"{t_naive:.0f}us -> {t_bucketed:.0f}us = "
             f"{t_naive/t_bucketed:.2f}x (120->13 messages, paper SIII-C.1)")
    else:
        emit("comm.bucketed_allreduce", (time.perf_counter() - t0) * 1e6,
             f"FAILED: {r.stderr[-200:]}")


def bench_comm_schedules(quick: bool):
    """Sweep the registered collective schedules (repro/comm/) on 8 host
    devices. Schedules are interleaved round-robin within each timing round
    and the median per schedule is reported — wall times on this box drift
    tens of percent between processes, so never compare across runs. The
    derived column projects each schedule onto the production meshes with
    the alpha-beta model (single-host psum is memcpy-bound and can't show
    topology wins end-to-end)."""
    import subprocess
    import sys

    from repro.comm import cost

    n_tensors, rounds = (30, 3) if quick else (80, 7)
    t0 = time.perf_counter()
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro import comm
from repro.core import bucketing, ddp
from repro.core.compat import shard_map

N_TENSORS = %d
ROUNDS = %d
ks = jax.random.split(jax.random.PRNGKey(0), N_TENSORS)
tree = {f"t{i}": jax.random.normal(ks[i], ((i %% 7 + 1) * 96, 128))
        for i in range(N_TENSORS)}
plan = bucketing.make_plan(tree, bucket_mb=1.0)
mesh = make_mesh((2, 4), ("pod", "data"))
spec = jax.tree.map(lambda _: P(), tree)

def mk(s):
    def fn(t):
        return ddp.allreduce_grads(t, strategy=s, axes=("pod", "data"),
                                   plan=plan)
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))

fns = {s: mk(s) for s in comm.available()}
for f in fns.values():
    jax.block_until_ready(f(tree))       # compile + warm
times = {s: [] for s in fns}
for r in range(ROUNDS):                  # interleave within each round
    for s, f in fns.items():
        t0 = time.perf_counter()
        jax.block_until_ready(f(tree))
        times[s].append(time.perf_counter() - t0)
print("n_buckets," + str(plan.n_buckets))
for s in fns:
    print(f"{s},{float(np.median(times[s])) * 1e6:.0f}")
""" % (n_tensors, rounds)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600,
                       env=_host_mesh_env())
    res = dict(line.split(",") for line in r.stdout.strip().splitlines()
               if "," in line)
    if not res:
        emit("comm.schedules", (time.perf_counter() - t0) * 1e6,
             f"FAILED: {r.stderr[-200:]}")
        return
    # wire bytes: ddp defaults to a bf16 wire (2 B/elem), matching the
    # bucket plan's dtype_bytes and report.comm_section's convention
    grad_bytes = sum((i % 7 + 1) * 96 * 128 * 2 for i in range(n_tensors))
    nb = int(res.pop("n_buckets", 1))
    for s in sorted(res):
        p1 = cost.predict(s, ("data",), (16,), grad_bytes, n_buckets=nb)
        p2 = cost.predict(s, ("pod", "data"), (2, 16), grad_bytes,
                          n_buckets=nb)
        emit(f"comm.schedule_{s}", float(res[s]),
             f"hostCPU median of {rounds} interleaved rounds; v5e "
             f"alpha-beta: 16x16={p1.time_s*1e6:.0f}us "
             f"2x16x16={p2.time_s*1e6:.0f}us")


def bench_comm_overlap(quick: bool):
    """Overlap on/off x schedule sweep (§III-C.2): real train steps on 8
    host devices, overlap toggled via CommConfig. Variants are interleaved
    within each timing round and medians reported (wall times drift tens of
    percent between processes — never compare across runs). Host-CPU
    collectives are memcpy-bound, so the derived column adds the v5e
    alpha-beta overlap prediction (repro/comm/autotune.py) where the
    topology/overlap win actually shows."""
    import subprocess
    import sys

    from repro.comm.autotune import autotune
    from repro.configs import get_config
    from repro.models.registry import build_model

    schedules = ["psum"] if quick else ["psum", "ring", "dbtree"]
    rounds = 5 if quick else 9
    t0 = time.perf_counter()
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.configs.base import CommConfig
from repro.configs.shapes import InputShape
from repro.core import lars
from repro.core.schedule import ScheduleConfig, make_schedule
from repro.data.synthetic import make_batch_fn
from repro.models.registry import build_model
from repro.train import state as st
from repro.train.step import make_train_step

SCHEDULES = %r
ROUNDS = %d
mesh = make_mesh((8, 1), ("data", "model"))
cfg = get_config("resnet50").reduced()
model = build_model(cfg)
sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                     total_steps=50))
bf = make_batch_fn(cfg, InputShape("t", "train", 0, 32), mesh=mesh)
s0 = st.init_state(model, 0)
batch = bf(s0.step)
fns = {}
for sname in SCHEDULES:
    for ov in (False, True):
        cc = CommConfig(strategy=sname, bucket_mb=0.25, overlap=ov)
        fns[(sname, ov)] = jax.jit(make_train_step(
            model, lars.OptConfig(kind="lars"), sched, mesh=mesh, comm=cc))
for f in fns.values():
    jax.block_until_ready(f(s0, batch))     # compile + warm
times = {k: [] for k in fns}
for r in range(ROUNDS):                     # interleave within each round
    for k, f in fns.items():
        t0 = time.perf_counter()
        jax.block_until_ready(f(s0, batch))
        times[k].append(time.perf_counter() - t0)
for (sname, ov), ts in times.items():
    print(f"{sname}|{int(ov)},{float(np.median(ts)) * 1e6:.0f}")
""" % (schedules, rounds)
    try:
        r = subprocess.run([sys.executable, "-c", script],
                           capture_output=True, text=True, timeout=900,
                           env=_host_mesh_env())
    except subprocess.TimeoutExpired:
        emit("comm.overlap", (time.perf_counter() - t0) * 1e6,
             "FAILED: 900s subprocess timeout")
        return
    res = dict(line.split(",") for line in r.stdout.strip().splitlines()
               if "," in line)
    if not res:
        emit("comm.overlap", (time.perf_counter() - t0) * 1e6,
             f"FAILED: {r.stderr[-200:]}")
        return
    model = build_model(get_config("resnet50"))
    for s in schedules:
        if f"{s}|0" not in res or f"{s}|1" not in res:
            emit(f"comm.overlap_{s}", (time.perf_counter() - t0) * 1e6,
                 f"MISSING rows: {r.stderr[-120:]}")
            continue
        off, on = float(res[f"{s}|0"]), float(res[f"{s}|1"])
        tuned = autotune(model.param_pd, schedule=s, axes=("data",),
                         sizes=(16,), family="conv")
        emit(f"comm.overlap_{s}", on,
             f"post-backward {off:.0f}us -> overlapped {on:.0f}us "
             f"({off/on:.2f}x, hostCPU median of {rounds} interleaved "
             f"rounds); v5e 16x16 predicted overlap eff "
             f"{tuned.sim.overlap_eff:.2f} @ {tuned.bucket_mb:g}MB buckets")


def bench_comm_shard_update(quick: bool):
    """ZeRO-1 sharded update on/off x schedule sweep (docs/comm.md): real
    train steps on 8 host devices, variants interleaved per round, medians
    reported. Host-CPU collectives are memcpy-bound and the interpret-mode
    update runs via the packed-jnp oracle, so the wall columns mostly show
    parity; the derived column carries the v5e alpha-beta + update-time
    accounting (AR(g)+update vs RS(g)+update/n+AG(bf16 p)) where the win
    is."""
    import subprocess
    import sys

    from repro.comm.autotune import autotune
    from repro.configs import get_config
    from repro.models.registry import build_model

    schedules = ["ring"] if quick else ["ring", "2d_torus", "hierarchical"]
    rounds = 5 if quick else 9
    t0 = time.perf_counter()
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.configs.base import CommConfig
from repro.configs.shapes import InputShape
from repro.core import lars
from repro.core.schedule import ScheduleConfig, make_schedule
from repro.data.synthetic import make_batch_fn
from repro.models.registry import build_model
from repro.train import state as st
from repro.train.step import make_train_step

SCHEDULES = %r
ROUNDS = %d
mesh = make_mesh((8, 1), ("data", "model"))
cfg = get_config("resnet50").reduced()
model = build_model(cfg)
sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                     total_steps=50))
bf = make_batch_fn(cfg, InputShape("t", "train", 0, 32), mesh=mesh)
batch = None
fns, states = {}, {}
for sname in SCHEDULES:
    for sh in (False, True):
        cc = CommConfig(strategy=sname, bucket_mb=0.25,
                        sharding="zero1" if sh else "replicated")
        step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                               mesh=mesh, comm=cc)
        s0 = st.init_state(model, 0,
                           sharded_plan=step.bucket_plan if sh else None,
                           n_shards=step.n_shards if sh else 1)
        fns[(sname, sh)] = jax.jit(step)
        states[(sname, sh)] = s0
        if batch is None:
            batch = bf(s0.step)
for k, f in fns.items():
    jax.block_until_ready(f(states[k], batch))    # compile + warm
times = {k: [] for k in fns}
for r in range(ROUNDS):                           # interleave within rounds
    for k, f in fns.items():
        t0 = time.perf_counter()
        jax.block_until_ready(f(states[k], batch))
        times[k].append(time.perf_counter() - t0)
for (sname, sh), ts in times.items():
    print(f"{sname}|{int(sh)},{float(np.median(ts)) * 1e6:.0f}")
""" % (schedules, rounds)
    try:
        r = subprocess.run([sys.executable, "-c", script],
                           capture_output=True, text=True, timeout=900,
                           env=_host_mesh_env())
    except subprocess.TimeoutExpired:
        emit("comm.shard_update", (time.perf_counter() - t0) * 1e6,
             "FAILED: 900s subprocess timeout")
        return
    res = dict(line.split(",") for line in r.stdout.strip().splitlines()
               if "," in line)
    if not res:
        emit("comm.shard_update", (time.perf_counter() - t0) * 1e6,
             f"FAILED: {r.stderr[-200:]}")
        return
    model = build_model(get_config("resnet50"))
    for s in schedules:
        if f"{s}|0" not in res or f"{s}|1" not in res:
            emit(f"comm.shard_update_{s}", (time.perf_counter() - t0) * 1e6,
                 f"MISSING rows: {r.stderr[-120:]}")
            continue
        off, on = float(res[f"{s}|0"]), float(res[f"{s}|1"])
        ar = autotune(model.param_pd, schedule=s, axes=("data",),
                      sizes=(16,), family="conv")
        sh = autotune(model.param_pd, schedule=s, axes=("data",),
                      sizes=(16,), family="conv", sharding="zero1")
        emit(f"comm.shard_update_{s}", on,
             f"replicated {off:.0f}us -> sharded {on:.0f}us "
             f"({off/on:.2f}x hostCPU, {rounds} interleaved rounds); v5e "
             f"16x16 predicted t_step {ar.sim.t_step_s*1e3:.2f}ms -> "
             f"{sh.sim.t_step_s*1e3:.2f}ms @ {sh.bucket_mb:g}MB")


def bench_shard_update_plan(quick: bool):
    """Pure cost-accounting rows (no training; part of --smoke): the ZeRO-1
    acceptance numbers — AR(g)+full-update vs RS(g)+update/n+AG(bf16 p)
    for the ring schedule at each path's autotuned bucket size."""
    from repro.comm.autotune import autotune
    from repro.configs import get_config
    from repro.models.registry import build_model

    model = build_model(get_config("resnet50"))
    for tag, axes, sizes in [("16x16", ("data",), (16,)),
                             ("2x16x16", ("pod", "data"), (2, 16))]:
        t0 = time.perf_counter()
        ar = autotune(model.param_pd, schedule="ring", axes=axes,
                      sizes=sizes, family="conv")
        sh = autotune(model.param_pd, schedule="ring", axes=axes,
                      sizes=sizes, family="conv", sharding="zero1")
        assert sh.sim.t_step_s < ar.sim.t_step_s, (sh.sim, ar.sim)
        emit(f"comm.shard_update_plan_{tag}",
             (time.perf_counter() - t0) * 1e6,
             f"ring AR(g)+update {ar.sim.t_step_s*1e3:.2f}ms -> "
             f"RS(g)+update/{sizes[-1]}+AG(bf16 p) "
             f"{sh.sim.t_step_s*1e3:.2f}ms @ {sh.bucket_mb:g}MB "
             f"(update {ar.sim.t_update_s*1e6:.0f}us -> "
             f"{sh.sim.t_update_s*1e6:.0f}us, gather "
             f"{sh.sim.t_gather_s*1e6:.0f}us hidden behind next fwd)")


def bench_gather_ahead_plan(quick: bool):
    """Gather-ahead accounting rows (part of --smoke, asserted in CI): the
    sharded path's param all-gather at its two issue points — step end
    (fully exposed) vs gather-ahead (issued from the persistent shards at
    the start of the next forward, ddp.gather_ahead_params, so it hides
    under forward compute). Ring schedule, autotuned bucket sizes."""
    from repro.comm.autotune import autotune
    from repro.configs import get_config
    from repro.models.registry import build_model

    model = build_model(get_config("resnet50"))
    for tag, axes, sizes in [("16x16", ("data",), (16,)),
                             ("2x16x16", ("pod", "data"), (2, 16))]:
        t0 = time.perf_counter()
        ga = autotune(model.param_pd, schedule="ring", axes=axes,
                      sizes=sizes, family="conv", sharding="zero1")
        # AG@end priced on the SAME plan, so the delta is purely the
        # gather issue point
        end = autotune(model.param_pd, schedule="ring", axes=axes,
                       sizes=sizes, family="conv", sharding="zero1",
                       gather="at_end", candidates=(ga.bucket_mb,))
        assert end.sim.mode == "shard_update"
        assert ga.sim.mode == "shard_update+gather_ahead"
        # hiding the gather can only help, and on these meshes it fully
        # disappears behind the forward window
        assert ga.sim.t_step_s <= end.sim.t_step_s, (ga.sim, end.sim)
        hidden = end.sim.t_exposed_s - ga.sim.t_exposed_s
        emit(f"comm.gather_ahead_plan_{tag}",
             (time.perf_counter() - t0) * 1e6,
             f"ring AG(bf16 p) {ga.sim.t_gather_s*1e6:.0f}us: step-end "
             f"t_step {end.sim.t_step_s*1e3:.2f}ms -> gather-ahead "
             f"{ga.sim.t_step_s*1e3:.2f}ms ({hidden*1e6:.0f}us of gather "
             f"hidden under next fwd) @ {ga.bucket_mb:g}MB")


def bench_zero3_plan(quick: bool):
    """ZeRO-3 accounting rows (part of --smoke, asserted in CI): the
    just-in-time per-group forward gather priced against the ZeRO-1
    gather-ahead baseline on both production meshes, plus the peak
    param-memory row — ``cost.param_memory``'s analytic byte accounting
    (the host-CPU CI mesh cannot measure device memory), asserting the
    reduction clears the (n-1)/n floor at n=8, the shard count the
    8-device equivalence matrix actually runs."""
    from repro.comm import cost as cost_mod
    from repro.comm.autotune import autotune
    from repro.configs import get_config
    from repro.core import bucketing
    from repro.models.registry import build_model

    model = build_model(get_config("resnet50"))
    for tag, axes, sizes in [("16x16", ("data",), (16,)),
                             ("2x16x16", ("pod", "data"), (2, 16))]:
        t0 = time.perf_counter()
        z1 = autotune(model.param_pd, schedule="ring", axes=axes,
                      sizes=sizes, family="conv", sharding="zero1")
        # both gather policies priced on the SAME bucket size, so the
        # deltas are purely the policy
        z3 = autotune(model.param_pd, schedule="ring", axes=axes,
                      sizes=sizes, family="conv", sharding="zero3",
                      candidates=(z1.bucket_mb,))
        z3r = autotune(model.param_pd, schedule="ring", axes=axes,
                       sizes=sizes, family="conv", sharding="zero3",
                       gather="ahead", candidates=(z1.bucket_mb,))
        z2 = autotune(model.param_pd, schedule="ring", axes=axes,
                      sizes=sizes, family="conv", sharding="zero2",
                      candidates=(z1.bucket_mb,))
        assert z3.sim.mode == "zero3_jit_gather", z3.sim
        assert z3r.sim.mode == "zero3_retain", z3r.sim
        assert z2.sim.mode == "zero2", z2.sim
        # retain skips the remat re-gather (one AG per group, backward
        # unstretched), so it can only be <= per_group
        assert z3r.sim.t_step_s <= z3.sim.t_step_s, (z3r.sim, z3.sim)
        emit(f"comm.zero3_plan_{tag}", (time.perf_counter() - t0) * 1e6,
             f"ring zero1 gather-ahead t_step {z1.sim.t_step_s*1e3:.2f}ms "
             f"-> zero3 per_group {z3.sim.t_step_s*1e3:.2f}ms / retain "
             f"{z3r.sim.t_step_s*1e3:.2f}ms @ {z1.bucket_mb:g}MB (AG "
             f"{z3r.sim.t_gather_s*1e6:.0f}us, remat-doubled "
             f"{z3.sim.t_gather_s*1e6:.0f}us); zero2 baseline "
             f"{z2.sim.t_step_s*1e3:.2f}ms (fp32 step-end AG, fully "
             f"exposed)")
    # peak param memory: analytic and n-independent — zero1 keeps the 4N
    # fp32 replica plus the full wire image, zero3 keeps one group's wire
    # bucket + fp32 tensors at a time (docs/comm.md byte accounting)
    t0 = time.perf_counter()
    n = 8
    plan = bucketing.make_plan(model.param_pd, bucket_mb=1.0)
    z1m = cost_mod.param_memory(plan, n, sharding="zero1")
    z3m = cost_mod.param_memory(plan, n, sharding="zero3")
    red = cost_mod.param_memory_reduction(plan, n)
    assert red >= (n - 1) / n, (
        f"zero3 peak-param reduction {red:.4f} below the (n-1)/n={n-1}/{n} "
        f"floor: zero1 peak {z1m.peak_bytes}B vs zero3 {z3m.peak_bytes}B")
    emit("comm.zero3_param_mem", (time.perf_counter() - t0) * 1e6,
         f"peak live param bytes zero1 {z1m.peak_bytes/2**20:.1f}MB "
         f"(4N fp32 replica + bf16 wire image) -> zero3 "
         f"{z3m.peak_bytes/2**20:.1f}MB (largest group only) = "
         f"{100*red:.1f}% reduction @ 1MB buckets, >= {n-1}/{n} floor")
    # giant-leaf model at n=16: without leaf splitting the 778M-element
    # qwen1.5-32b embedding would own one oversized bucket (~2.4% of N
    # live at once — the bar breaks for n >= ~42); with splitting every
    # span fits the budget and the (n-1)/n floor holds at n=16 too
    t0 = time.perf_counter()
    n16 = 16
    big = build_model(get_config("qwen1.5-32b"))
    splan = bucketing.make_plan(big.param_pd, bucket_mb=4.0)
    widest = max(int(np.prod(s.shape) or 1) for s in splan.slots)
    assert any(s.elem_offset for s in splan.slots), \
        "qwen1.5-32b must exercise the leaf-splitting path at 4MB buckets"
    sred = cost_mod.param_memory_reduction(splan, n16, sharding="zero3")
    assert sred >= (n16 - 1) / n16, (
        f"split-leaf zero3 peak-param reduction {sred:.4f} below the "
        f"(n-1)/n={n16-1}/{n16} floor (widest leaf {widest} elems)")
    emit("comm.zero3_param_mem_split", (time.perf_counter() - t0) * 1e6,
         f"qwen1.5-32b @ 4MB buckets, n={n16}: widest leaf "
         f"{widest/2**20:.0f}Mi elems split across "
         f"{len(splan.slots) - splan.n_tensors + 1} spans; zero3 peak "
         f"param mem reduction {100*sred:.1f}% >= {n16-1}/{n16} floor")


def bench_ckpt_roundtrip(quick: bool):
    """Elastic-layer accounting row (part of --smoke, asserted in CI):
    atomic checkpoint save -> checksum-verified load -> n->m master
    reshard (docs/elastic.md) for the reduced-ResNet ZeRO-1 state — wall
    time per leg plus the committed payload size."""
    import tempfile

    from repro.configs import get_config
    from repro.configs.base import CommConfig
    from repro.core import lars as lars_mod
    from repro.core.schedule import ScheduleConfig, make_schedule
    from repro.models.registry import build_model
    from repro.train import checkpoint as ckpt_mod
    from repro.train import elastic
    from repro.train import state as st_mod
    from repro.train.step import make_train_step

    model = build_model(get_config("resnet50").reduced())
    mesh = make_mesh((1, 1), ("data", "model"))
    sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                         total_steps=10))
    cc = CommConfig(strategy="ring", bucket_mb=0.25, sharding="zero1")
    step = make_train_step(model, lars_mod.OptConfig(kind="lars"), sched,
                           mesh=mesh, comm=cc)
    s = st_mod.init_state(model, 0, sharded_plan=step.bucket_plan,
                          n_shards=step.n_shards)
    tmpl = st_mod.init_state(model, 1, sharded_plan=step.bucket_plan,
                             n_shards=step.n_shards)
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        path = ckpt_mod.save(s, d, tag=ckpt_mod.step_tag(0),
                             comm_plan=step.comm_plan)
        t_save = time.perf_counter() - t0
        nbytes = os.path.getsize(path)
        t0 = time.perf_counter()
        r = ckpt_mod.load(tmpl, d)          # checksum-verified
        jax.block_until_ready(r.shards)
        t_load = time.perf_counter() - t0
        t0 = time.perf_counter()
        new = elastic.reshard_buffers(list(r.shards), step.bucket_plan,
                                      step.n_shards, step.bucket_plan, 4)
        jax.block_until_ready(new)
        t_reshard = time.perf_counter() - t0
    emit("ckpt.roundtrip", (t_save + t_load + t_reshard) * 1e6,
         f"atomic save {t_save*1e3:.0f}ms + verified load "
         f"{t_load*1e3:.0f}ms + reshard {step.n_shards}->4 "
         f"{t_reshard*1e3:.0f}ms; payload {nbytes/2**20:.2f}MB "
         f"(+CommPlan, sha256 manifest)")


def _guard_bench_setup():
    """Shared construction for the guard benches (one guarded + one
    unguarded reduced-ResNet ZeRO-1 step; the guarded compile is the
    expensive part, so build once)."""
    from repro.configs import get_config
    from repro.configs.base import CommConfig
    from repro.configs.shapes import InputShape
    from repro.core import lars as lars_mod
    from repro.core.schedule import ScheduleConfig, make_schedule
    from repro.data.synthetic import make_batch_fn
    from repro.models.registry import build_model
    from repro.train import state as st_mod
    from repro.train.step import make_train_step
    if _GUARD_CACHE:
        return _GUARD_CACHE["v"]
    cfg = get_config("resnet50").reduced()
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=2,
                                         total_steps=10))
    cc = CommConfig(strategy="ring", bucket_mb=0.25, sharding="zero1")
    mk = lambda g: make_train_step(model, lars_mod.OptConfig(kind="lars"),  # noqa: E731
                                   sched, mesh=mesh, comm=cc, guard=g)
    step_off, step_on = mk(False), mk(True)
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, 8), seed=0,
                       mesh=mesh)
    init = lambda: st_mod.init_state(  # noqa: E731
        model, 0, mesh, sharded_plan=step_on.bucket_plan,
        n_shards=step_on.n_shards)
    _GUARD_CACHE["v"] = (step_off, step_on, bf, init)
    return _GUARD_CACHE["v"]


_GUARD_CACHE = {}


def bench_guard_overhead(quick: bool):
    """Numerical-guard happy-path cost (part of --smoke, asserted in CI —
    docs/elastic.md §Numerical faults): the in-graph sentinel's nonfinite
    counts + grad-norm ride out on the metrics dict with no extra host
    sync, so a guarded step should cost within ~2% of the unguarded one.
    Measured as deployed: ``loop.train`` jits the step with
    ``donate_argnums=(0,)``, which lets XLA alias the cond-gated commit
    into the donated state buffers instead of copying it — undonated the
    same comparison reads ~14% because the commit becomes a full-state
    memcpy. Batch 32 so compute (which scales with batch) dominates the
    sentinel reductions (which don't — they run over the packed grads).
    Guard-on and guard-off steps are interleaved per timing round and the
    MIN per variant compared (min, not median: the sentinel is a fixed
    additive cost, and min strips scheduler noise on a shared CI box)."""
    from repro.configs import get_config
    from repro.configs.shapes import InputShape
    from repro.data.synthetic import make_batch_fn
    from repro.train import guard as guard_mod
    step_off, step_on, _, init = _guard_bench_setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    bf = make_batch_fn(get_config("resnet50").reduced(),
                       InputShape("t", "train", 0, 32), seed=0, mesh=mesh)
    rounds = 7 if quick else 15
    f_off = jax.jit(step_off, donate_argnums=(0,))
    f_on = jax.jit(step_on, donate_argnums=(0,))
    s_off, s_on = init(), init()
    b = bf(0)
    neutral = guard_mod.neutral_inputs()
    s_off, _ = f_off(s_off, b)                   # compile + warm
    s_on, _ = f_on(s_on, b, neutral)
    jax.block_until_ready((s_off, s_on))
    t_off, t_on = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        s_off, _ = f_off(s_off, b)
        jax.block_until_ready(s_off)
        t_off.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        s_on, _ = f_on(s_on, b, neutral)
        jax.block_until_ready(s_on)
        t_on.append(time.perf_counter() - t0)
    mn_off, mn_on = min(t_off), min(t_on)
    pct = (mn_on - mn_off) / mn_off * 100.0
    emit("guard.overhead", mn_on * 1e6,
         f"unguarded {mn_off*1e6:.0f}us -> guarded {mn_on*1e6:.0f}us "
         f"({pct:+.2f}%, claim <2%; donated jit as in loop.train, batch "
         f"32, min of {rounds} interleaved rounds, hostCPU) — sentinel "
         f"rides the metrics dict, cond commit aliases into the donated "
         f"state")


def bench_guard_recovery(quick: bool):
    """Recovery-ladder wall cost (part of --smoke, asserted in CI): a
    guarded run through ``nan@1,spike@3:50`` — one sentinel skip-and-replay
    plus one detector trip with in-memory ring rollback (no checkpoint IO)
    — must converge, and the row carries the whole-run wall time. The
    skip/rollback counts are hard gates: the fault kinds must actually
    drive their rungs."""
    import tempfile

    from repro.obs import metrics as obs_metrics
    from repro.train import guard as guard_mod
    from repro.train import loop as loop_mod
    _, step_on, bf, init = _guard_bench_setup()
    mem = obs_metrics.MemorySink()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as d:
        with obs_metrics.default_registry().use_sink(mem):
            fin, _ = loop_mod.train(
                init(), step_on, bf, steps=6, log_every=0, ckpt_dir=d,
                faults="nan@1,spike@3:50",
                guard=guard_mod.GuardConfig(spike_factor=5.0))
    wall = time.perf_counter() - t0
    skips = len(mem.find("guard_skip"))
    rollbacks = len(mem.find("guard_rollback"))
    assert skips == 1 and rollbacks == 1, (
        f"recovery ladder did not fire as injected: {skips} skips, "
        f"{rollbacks} rollbacks (want 1 each)")
    assert int(fin.step) == 6, int(fin.step)
    emit("guard.recovery", wall * 1e6,
         f"nan@1+spike@3:50 over 6 steps: {skips} sentinel skip, "
         f"{rollbacks} ring rollback (no ckpt IO), run converged to step "
         f"{int(fin.step)} — replayed, not dropped")


def bench_autotune_plan(quick: bool):
    """Pure cost-model rows (no training): the autotuner's joint
    (schedule x bucket size) pick per production mesh — the plan
    ``CommConfig(bucket_mb='auto')`` resolves to."""
    from repro.comm.autotune import best_plan
    from repro.configs import get_config
    from repro.models.registry import build_model

    model = build_model(get_config("resnet50"))
    for tag, axes, sizes in [("16x16", ("data",), (16,)),
                             ("2x16x16", ("pod", "data"), (2, 16))]:
        t0 = time.perf_counter()
        b = best_plan(model.param_pd, axes=axes, sizes=sizes, family="conv")
        emit(f"comm.autotune_{tag}", (time.perf_counter() - t0) * 1e6,
             f"best={b.schedule}@{b.bucket_mb:g}MB n_buckets={b.n_buckets} "
             f"t_comm={b.sim.t_comm_s*1e6:.0f}us "
             f"exposed={b.sim.t_exposed_s*1e6:.0f}us "
             f"overlap_eff={b.sim.overlap_eff:.2f}")


ALL = [bench_table1, bench_fig2, bench_fig3, bench_fig4,
       bench_lars_ablation, bench_smoothing_ablation,
       bench_bn_momentum_ablation,
       bench_kernel_batched_norm, bench_kernel_smoothed_xent,
       bench_kernel_lars_update, bench_comm_bucketing,
       bench_comm_schedules, bench_comm_overlap, bench_comm_shard_update,
       bench_autotune_plan, bench_shard_update_plan,
       bench_gather_ahead_plan, bench_zero3_plan, bench_ckpt_roundtrip,
       bench_guard_overhead, bench_guard_recovery]

# --smoke: the CI micro-run — pure-math projection/accounting rows plus the
# in-process guard pair (one guarded reduced-ResNet compile shared by
# both), finishes in a few minutes and emits the JSON artifact that tracks
# the bench trajectory per-PR (including the sharded-update, gather-ahead
# and guard rows)
SMOKE = [bench_table1, bench_fig2, bench_autotune_plan,
         bench_shard_update_plan, bench_gather_ahead_plan,
         bench_zero3_plan, bench_ckpt_roundtrip, bench_guard_overhead,
         bench_guard_recovery]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI micro-run: projection benches only + --json")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as a JSON array")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for fn in (SMOKE if args.smoke else ALL):
        if args.only and args.only not in fn.__name__:
            continue
        fn(args.smoke or args.quick)
    if args.json:
        import json
        payload = [{"name": n, "us_per_call": us, "derived": d}
                   for n, us, d in ROWS]
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(payload)} rows to {args.json}", flush=True)


if __name__ == "__main__":
    main()
