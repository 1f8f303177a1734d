"""Training cells: the program's own loop drives its own step on batches
from the pool.

Set-up wires the program as its launcher does (``repro.launch.train``):
``make_train_step`` with LARS and the launcher's defaults, the cell's
exchange (``--comm``), sharding and buckets, the state from
``init_state`` on the seed. Its feed hands ``repro.train.loop.train`` the
pool's batches in turn. Set-up drives that one step and state through
the first three steps, through ``loop.train`` as the window does, and
keeps the readings the oracle compares. Then the window: ``loop.train`` runs on,
its first ``warm_steps`` steps are set-up too, and the window holds every
whole step that starts within ``seconds`` after. Each step ends in the
loop's ``block_until_ready``; the feed notes the time as the next one
starts. The window closes by the feed raising ``WindowClosed`` before a
step, so the loop never starts a step the window does not count.

With ``trace`` the profiler records ``trace_seconds`` of the window
instead, and the window ends there. Once the window has closed and the
device's peak memory is read, the program's state is dropped and the
reference follows the same three steps on the same batches.
"""
from __future__ import annotations

import gc
import math
import shutil
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from perfbench import generator, oracle
from perfbench.reference import common
from perfbench.reference.steps import Reference, change_norms

LONG = 10 ** 9
SEED_MODULUS = 2 ** 31 - 1


class WindowClosed(Exception):
    """Raised by the feed, between two steps, when the window is over."""


class Feed:
    """The loop's batch function: the pool's next batch at every call."""

    def __init__(self, pool):
        self.pool = list(pool)
        self.calls = 0
        self.hook = None

    def __call__(self, step):
        if self.hook is not None:
            self.hook(time.perf_counter())
        batch = self.pool[self.calls % len(self.pool)]
        self.calls += 1
        return batch


def seed31(seed: int) -> int:
    """The program takes a 32-bit seed; ``--seed`` may be larger."""
    return seed % SEED_MODULUS


def schedule(traffic: dict, global_batch: int) -> dict:
    """The launcher's defaults: lr = lr_per_256 * batch / 256, a tenth of
    the run warms up, polynomial decay to ``end_lr``."""
    s = traffic["schedule"]
    return {"base_lr": s["lr_per_256"] * global_batch / 256,
            "warmup": s["steps"] // 10, "total": s["steps"],
            "end_lr": s["end_lr"]}


def flatten(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): v for path, v in flat}


@jax.jit
def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def build(cell):
    """The program's step, wired as ``repro.launch.train._run`` wires it."""
    from repro.configs.base import CommConfig
    from repro.core import lars
    from repro.core.schedule import ScheduleConfig, make_schedule
    from repro.launch.mesh import make_local_mesh
    from repro.models.registry import build_model
    from repro.train.step import make_train_step

    t = cell.traffic
    cfg = cell.config_module.program_config(cell.config)
    mesh = make_local_mesh(1, devices=cell.chips)
    model = build_model(cfg)
    gb = t["batch_per_chip"] * cell.chips
    sd = schedule(t, gb)
    sched = make_schedule(ScheduleConfig(
        base_lr=sd["base_lr"], warmup_steps=sd["warmup"],
        total_steps=sd["total"], decay=t["schedule"]["decay"],
        end_lr=sd["end_lr"]))
    o = t["optimizer"]
    opt = lars.OptConfig(kind="lars", momentum=o["momentum"],
                         weight_decay=o["weight_decay"],
                         trust_coef=o["trust_coef"], eps=o["eps"])
    comm = CommConfig(strategy=t["comm"], bucket_mb=t["bucket_mb"],
                      overlap=t["overlap"], sharding=t["sharding"])
    train_step = make_train_step(model, opt, sched,
                                 smoothing=t["smoothing"], mesh=mesh,
                                 comm=comm)
    return SimpleNamespace(mesh=mesh, model=model, train_step=train_step,
                           global_batch=gb, sched=sd, state=None, feed=None,
                           devices=list(mesh.devices.flat))


def seed_state(cell, prog, seed: int) -> None:
    """The initial state from ``init_state`` on the seed, as the launcher
    makes it, and the feed of the seed's batch pool."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.train.state import init_state

    ts = prog.train_step
    sharded = getattr(ts, "shard_update", False)
    sharding = getattr(ts, "sharding", "replicated")
    prog.state = init_state(prog.model, seed31(seed), prog.mesh,
                            opt_kind="lars",
                            sharded_plan=ts.bucket_plan if sharded else None,
                            n_shards=ts.n_shards if sharded else 1,
                            materialize_params=sharding != "zero3",
                            shard_params=sharding != "zero2")
    pool = generator.make_pool(
        cell.traffic, cell.config_module.sizes(cell.config),
        prog.global_batch, seed31(seed),
        NamedSharding(prog.mesh, P("data")))
    prog.feed = Feed(pool)
    prog.loop_kw = dict(log_every=cell.traffic["log_every"],
                        seed=seed31(seed))


def first_steps(cell, prog, seed: int) -> dict:
    """Drives the program through its first three steps (``loop.train``
    for the first, and again for the next two) and reads what the oracle
    compares: the three losses, the batch statistics of the first step as
    the state keeps them (where the configuration's module reads them:
    ``first_batch_stats``), the momentum after one step over the first
    learning rate, and the weights' change after three. Leaves the state
    in ``prog.state``. A tensor that the reference has and the program
    lacks reads infinity."""
    from repro.train import loop

    cm = cell.config_module
    want = cm.reference(cell.config, cell.traffic, cell.chips, "f32")[0]
    kw = dict(prog.loop_kw, log_every=1)
    prog.state, hist = loop.train(prog.state, prog.train_step, prog.feed,
                                  steps=1, **kw)
    lr0 = common.learning_rate(0, prog.sched)
    grad1 = {p: float(v) / lr0
             for p, v in _norms(flatten(prog.state.mom)).items()}
    read_stats = getattr(cm, "first_batch_stats", None)
    stats = read_stats(prog.state, cell.config) if read_stats else {}
    prog.state, more = loop.train(prog.state, prog.train_step, prog.feed,
                                  steps=3, **kw)
    losses = [float(h["loss"]) for h in hist + more]
    flat = flatten(prog.state.params)
    same = {k: v for k, v in want.items()
            if k in flat and tuple(flat[k].shape) == tuple(v[0])}
    change3 = change_norms(same)({k: flat[k] for k in same},
                                 jnp.int32(seed31(seed)))
    return {"losses": losses, "stats": stats,
            "grad1": {k: grad1.get(k, math.inf) for k in want},
            "change3": {k: float(change3[k]) if k in same else math.inf
                        for k in want}}


def reference(cell, compute: str = "f32") -> Reference:
    """The cell's reference (``compute`` ``"fp8"``: the control), built
    once and run for any seed with ``run_reference``."""
    spec, loss_fn, stacked = cell.config_module.reference(
        cell.config, cell.traffic, cell.chips, compute)
    return Reference(loss_fn, spec, cell.traffic["optimizer"], stacked)


def run_reference(cell, ref: Reference, batches, seed: int,
                  device=None) -> dict:
    """The reference's readings of the three steps on ``batches``."""
    sd = schedule(cell.traffic, cell.traffic["batch_per_chip"] * cell.chips)
    return ref.run(seed31(seed), batches, sd, device)


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, st.get("peak_bytes_in_use", 0)
                   + st.get("peak_bytes_reserved", 0))
    return peak


def run(cell, seed: int, seconds: float, trace: bool, log, t_process: float,
        out_dir, say=print):
    """One run of a training cell. Returns the harness's raw readings."""
    from repro.obs import metrics as obs_metrics
    from repro.train import loop

    t = cell.traffic
    prog = build(cell)
    seed_state(cell, prog, seed)
    program = first_steps(cell, prog, seed)
    hlo = None
    if trace:
        # the program the loop runs, as compiled (from the cache), whose
        # instruction names the trace's operations carry
        hlo = jax.jit(prog.train_step, donate_argnums=(0,)).lower(
            prog.state, prog.feed.pool[0]).compile().as_text()

    warm = t["warm_steps"]
    limit = min(seconds, t["trace_seconds"]) if trace else seconds
    times, marks = [], {}
    trace_dir = out_dir / "trace"
    annotation = None

    def hook(now):
        nonlocal annotation
        times.append(now)
        n = len(times) - 1
        if n == warm:
            marks["setup"] = time.time() - t_process
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(str(trace_dir))
                annotation = jax.profiler.TraceAnnotation("perfbench_window")
                annotation.__enter__()
                times[-1] = time.perf_counter()
            marks["start"] = log.mark()
        elif n > warm and times[-1] - times[warm] >= limit:
            if annotation is not None:
                annotation.__exit__(None, None, None)
            marks["end"] = log.mark()
            raise WindowClosed

    prog.feed.hook = hook
    state, prog.state = prog.state, None
    with obs_metrics.default_registry().use_sink(
            obs_metrics.MemorySink()) as sink:
        try:
            loop.train(state, prog.train_step, prog.feed, steps=LONG,
                       **prog.loop_kw)
        except WindowClosed:
            pass
    del state
    if trace:
        jax.profiler.stop_trace()
    logged = [e.value["loss"] for e in sink.find("train_step")]
    window = times[warm:]
    step_s = [b - a for a, b in zip(window, window[1:])]
    memory_peak = _memory_peak(prog.devices)
    window_compiles = log.compiles(marks["start"], marks["end"])
    say(f"window: {len(step_s)} steps in {window[-1] - window[0]:.6f} s, "
        f"compile events inside it: {window_compiles}")
    if step_s:
        slow = max(range(len(step_s)), key=step_s.__getitem__)
        say(f"longest step: {1e3 * step_s[slow]:.3f} ms, step {slow} of the "
            f"window; median {1e3 * sorted(step_s)[len(step_s) // 2]:.3f} ms")

    device = prog.devices[0]
    batches = [jax.device_put(b, device) for b in prog.feed.pool[:3]]
    devices, gb = prog.devices, prog.global_batch
    prog = None
    gc.collect()
    t0 = time.perf_counter()
    ref = run_reference(cell, reference(cell), batches, seed, device)
    say(f"reference: {time.perf_counter() - t0:.3f} s; losses: program "
        f"{program['losses']}, reference {ref['losses']}")
    return SimpleNamespace(
        steps=len(step_s), step_s=step_s, window_s=window[-1] - window[0],
        setup_s=marks["setup"], compile_s=log.seconds(0, marks["start"]),
        window_compiles=window_compiles, memory_peak=memory_peak,
        devices=devices, values=oracle.gaps(program, ref),
        failed=sum(1 for x in program["losses"] + logged
                   if not math.isfinite(x)),
        trace_dir=trace_dir if trace else None, hlo=hlo, global_batch=gb)
