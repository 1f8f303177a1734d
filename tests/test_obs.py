"""Observability stack: metrics registry, step-timeline tracer, drift.

Tier-1 covers the pure pieces in-process (sinks/registry, span assembly
with an injected fake clock, Chrome-trace schema + round-trip, drift math
against a synthetic CommPlan, the measured forward-time profile) plus a
1-device traced collective. The 8-device span invariants and the
``launch.train --trace`` acceptance run live in tier-2 subprocesses (jax
locks the device count at first import, same as tests/test_comm.py).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.comm import cost
from repro.comm import plan as comm_plan_mod
from repro.comm.autotune import (BackwardProfile, measure_backward_profile,
                                 simulate)
from repro.configs.base import CommConfig
from repro.core import bucketing, ddp
from repro.core.compat import shard_map
from repro.obs import drift as obs_drift
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import (Event, JsonlSink, MemorySink, Registry,
                               StdoutSink)
from repro.obs.trace import Span, Tracer
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.tier1


# ------------------------------------------------------- metrics registry

def test_stdout_sink_legacy_line_format(capsys):
    """Byte-for-byte the old ``mlperf_log`` line: the elastic subprocess
    tests (and any external parser) grep this exact shape."""
    StdoutSink().emit(Event(name="run_start", kind="event", value=None,
                            ts=1234.5, where="repro/train/loop.py"))
    StdoutSink().emit(Event(name="train_step", kind="event",
                            value={"step": 3}, ts=2.0,
                            where="repro/train/loop.py"))
    out = capsys.readouterr().out.splitlines()
    assert out[0] == (":::MLPv0.5.0 repro 1234.500000000 "
                     "(repro/train/loop.py) run_start")
    assert out[1] == (":::MLPv0.5.0 repro 2.000000000 "
                     "(repro/train/loop.py) train_step: {'step': 3}")


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "m" / "metrics.jsonl")   # dir auto-created
    sink = JsonlSink(path)
    sink.emit(Event(name="a", kind="event", value={"x": 1}, ts=1.0,
                    where="w", step=7))
    sink.emit(Event(name="b", kind="gauge", value=0.5, ts=2.0, where="w"))
    sink.close()
    rows = [json.loads(ln) for ln in open(path)]
    assert rows[0] == {"name": "a", "kind": "event", "value": {"x": 1},
                       "ts": 1.0, "where": "w", "step": 7}
    assert rows[1]["kind"] == "gauge" and "step" not in rows[1]


def test_registry_counter_gauge_use_sink():
    reg = Registry()
    with reg.use_sink(MemorySink()) as mem:
        assert reg.counter("retries") == 1
        assert reg.counter("retries", 2) == 3     # running total
        reg.gauge("drift", 0.25, step=4)
        reg.event("note", "hello")
    # detached after the with-block: further emits don't land in mem
    reg.event("after")
    assert [e.name for e in mem.events] == ["retries", "retries", "drift",
                                            "note"]
    assert mem.find("retries")[-1].value == 3
    assert mem.find("drift")[0].kind == "gauge"
    assert mem.find("drift")[0].step == 4
    assert not mem.find("after")


def test_mlperf_log_flows_through_registry(capsys):
    """loop.mlperf_log is now a registry event: captured by attached sinks
    AND still printed in the legacy format by the default StdoutSink."""
    from repro.train.loop import mlperf_log
    reg = obs_metrics.default_registry()
    with reg.use_sink(MemorySink()) as mem:
        mlperf_log("run_final", {"converged": True})
    evs = mem.find("run_final")
    assert len(evs) == 1 and evs[0].value == {"converged": True}
    assert evs[0].where == "repro/train/loop.py"
    line = capsys.readouterr().out
    assert ":::MLPv0.5.0 repro " in line and "run_final" in line


def test_fault_injector_emits_event(capsys):
    from repro.train import faults
    reg = obs_metrics.default_registry()
    with reg.use_sink(MemorySink()) as mem:
        faults._log_fault("sigkill", 5, "after save")
    evs = mem.find("fault_injected")
    assert len(evs) == 1
    assert evs[0].value["kind"] == "sigkill" and evs[0].step == 5
    assert "fault_injected" in capsys.readouterr().out


# ----------------------------------------------------------- span tracer

class FakeClock:
    """Deterministic monotone clock: every read ticks 1.0s."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_tracer_assembles_min_begin_max_end():
    tr = Tracer(clock=FakeClock())
    tr.begin_step()                                   # step B @ 1
    b = tr.callback("rs[b0]", cat="comm", phase="B")
    e = tr.callback("rs[b0]", cat="comm", phase="E")
    b(); b()                                          # device fires @ 2, 3
    e(); e()                                          # @ 4, 5
    tr.callback("late", cat="compute", phase="E")()   # E-only @ 6
    tr.end_step(9)                                    # step E @ 7
    spans = {s.name: s for s in tr.spans(step=9)}
    assert spans["rs[b0]"].t0 == 2.0 and spans["rs[b0]"].t1 == 5.0
    assert spans["rs[b0]"].cat == "comm" and spans["rs[b0]"].dur_s == 3.0
    assert spans["step"].t0 == 1.0 and spans["step"].t1 == 7.0
    # E-only probes yield a degenerate span, not a silent drop
    assert spans["late"].t0 == spans["late"].t1 == 6.0
    assert all(s.step == 9 for s in spans.values())


def test_tracer_drops_stale_events_and_abort():
    tr = Tracer(clock=FakeClock())
    tr.begin_step()
    tr.callback("hung", phase="B")()
    tr.abort_step()                     # watchdog path: window discarded
    tr.callback("straggler", phase="E")()   # trickles in from dead step
    tr.begin_step()                     # clears the straggler too
    tr.end_step(0)
    names = {s.name for s in tr.spans()}
    assert names == {"step"}
    assert tr.spans(step=0)[0].name == "step"


def test_tracer_host_span_and_instant():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.host_span("checkpoint_commit", step=3, path="/tmp/x"):
        clk()                                       # work takes one tick
    tr.instant("watchdog_timeout", step=3, attempt=1)
    sp = {s.name: s for s in tr.spans(step=3)}
    ck = sp["checkpoint_commit"]
    assert ck.cat == "host" and ck.t1 - ck.t0 == 2.0
    assert ck.arg("path") == "/tmp/x"
    wt = sp["watchdog_timeout"]
    assert wt.dur_s == 0.0 and wt.arg("attempt") == 1


def test_chrome_trace_schema_and_roundtrip(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.begin_step()
    b = tr.callback("ar[b0]", phase="B"); e = tr.callback("ar[b0]",
                                                          phase="E")
    b(); e()
    tr.end_step(0)
    tr.instant("preempt_drain", step=0)
    obj = obs_trace.chrome_trace(tr)
    obs_trace.validate_chrome(obj)                  # no raise
    # one thread_name row per category + the X events
    meta = [ev for ev in obj["traceEvents"] if ev["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == set(
        obs_trace.CATEGORY_TIDS)
    path = str(tmp_path / "t" / "trace.json")
    obs_trace.export_chrome(tr, path)
    spans = obs_trace.spans_from_chrome(obs_trace.load_chrome(path))
    got = {(s.name, s.cat, s.step) for s in spans}
    assert got == {("step", "step", 0), ("ar[b0]", "comm", 0),
                   ("preempt_drain", "host", 0)}
    ar = [s for s in spans if s.name == "ar[b0]"][0]
    assert ar.dur_s == pytest.approx(1.0, abs=1e-6)   # us-quantized


def test_validate_chrome_rejects_malformed():
    with pytest.raises(ValueError):
        obs_trace.validate_chrome({"events": []})
    with pytest.raises(ValueError):
        obs_trace.validate_chrome({"traceEvents": {}})
    with pytest.raises(ValueError):
        obs_trace.validate_chrome({"traceEvents": ["nope"]})
    with pytest.raises(ValueError):
        obs_trace.validate_chrome(
            {"traceEvents": [{"ph": "X", "name": "a", "pid": 0}]})
    with pytest.raises(ValueError):
        obs_trace.validate_chrome(
            {"traceEvents": [{"ph": "X", "name": "a", "pid": 0, "tid": 0,
                              "ts": 1.0, "dur": -2.0}]})


def test_mark_is_noop_without_tracer():
    """tracer=None must leave the graph byte-identical — tracing is a
    run-level opt-in, not a tax on every step."""
    def f(x):
        obs_trace.span_deps(None, "rs[b0]", [x], [x])
        return x * 2.0

    def g(x):
        return x * 2.0

    x = jnp.ones((4,))
    assert str(jax.make_jaxpr(f)(x)) == str(jax.make_jaxpr(g)(x))

    def traced(x):
        obs_trace.mark(Tracer(), "rs[b0]", "B", [x])
        return x * 2.0

    assert "callback" in str(jax.make_jaxpr(traced)(x))


def test_traced_allreduce_spans_1dev():
    """End-to-end probe plumbing on the in-process 1-device mesh: one
    ``ar[bi]`` span per bucket per step, inside the step window."""
    mesh = make_mesh((1,), ("data",))
    tree = {"a": jnp.ones((3000,)), "b": jnp.ones((3000,))}
    plan = bucketing.make_plan(tree, bucket_mb=0.005)  # several buckets
    assert plan.n_buckets >= 2
    tr = Tracer()
    spec = jax.tree.map(lambda _: P(), tree)
    f = jax.jit(shard_map(
        lambda t: ddp.allreduce_grads(t, strategy="psum", axes=("data",),
                                      plan=plan, tracer=tr),
        mesh=mesh, in_specs=(spec,), out_specs=spec))
    for s in range(2):
        tr.begin_step()
        jax.block_until_ready(f(tree))
        tr.end_step(s)
    for s in range(2):
        spans = tr.spans(step=s)
        ar = [sp for sp in spans if sp.name.startswith("ar[")]
        assert len(ar) == plan.n_buckets, [sp.name for sp in spans]
        step = [sp for sp in spans if sp.cat == "step"][0]
        assert all(step.t0 <= sp.t0 and sp.t1 <= step.t1 for sp in ar)


# ---------------------------------------------------------------- drift

def _synthetic_cplan(shard_update: bool):
    tree = {f"t{i}": jnp.zeros((20000,)) for i in range(3)}
    plan = bucketing.make_plan(tree, bucket_mb=0.1)
    cc = CommConfig(strategy="ring", bucket_mb=0.1,
                    shard_update=shard_update)
    return plan, comm_plan_mod.make(
        cc, plan, resolved_bucket_mb=0.1, mesh_axes=("data",),
        mesh_sizes=(8,), shard_axis="data",
        n_shards=8 if shard_update else 1, overlap=False,
        gather_ahead=False)


def test_predicted_span_times_match_taxonomy():
    plan, cp_sh = _synthetic_cplan(True)
    pred = obs_drift.predicted_span_times(cp_sh)
    want = {f"rs[b{b}]" for b in range(plan.n_buckets)} | {
        f"ag[b{b}]" for b in range(plan.n_buckets)}
    assert set(pred) == want
    _, cp_rep = _synthetic_cplan(False)
    pred_rep = obs_drift.predicted_span_times(cp_rep)
    assert set(pred_rep) == {f"ar[b{b}]" for b in range(plan.n_buckets)}
    # values are the cost model's, on the wire payload
    payload = plan.bucket_sizes[0] * cp_rep.wire_dtype_bytes
    assert pred_rep["ar[b0]"] == pytest.approx(cost.predict(
        "ring", ("data",), (8,), payload).time_s)
    assert all(v > 0 for v in pred.values())


def test_drift_compute_from_dict_and_rel_err():
    plan, cplan = _synthetic_cplan(True)
    pred = obs_drift.predicted_span_times(cplan)
    measured = {n: 2.0 * t for n, t in pred.items()}
    measured["update"] = 5.0          # non-comm span: ignored
    measured["rs[b99]"] = 1.0         # unplanned span: skipped
    drifts = obs_drift.compute(measured, cplan)
    assert len(drifts) == 2 * plan.n_buckets
    assert all(d.rel_err == pytest.approx(1.0) for d in drifts)
    assert obs_drift.aggregate(drifts) == pytest.approx(1.0)


def test_drift_aggregate_is_volume_weighted():
    drifts = (obs_drift.Drift("rs[b0]", "rs", 10.0, 10.0),   # exact
              obs_drift.Drift("rs[b1]", "rs", 0.1, 0.2))     # 2x, tiny
    # per-span mean would say +50%; volume weighting says ~+1%
    assert obs_drift.aggregate(drifts) == pytest.approx(0.1 / 10.1,
                                                        rel=1e-6)
    assert drifts[1].rel_err == pytest.approx(1.0)
    assert obs_drift.Drift("x", "rs", 0.0, 1.0).rel_err == float("inf")
    assert obs_drift.aggregate(()) == 0.0


def test_drift_emit_rows(capsys):
    plan, cplan = _synthetic_cplan(True)
    pred = obs_drift.predicted_span_times(cplan)
    drifts = obs_drift.compute({n: 1.5 * t for n, t in pred.items()},
                               cplan)
    reg = Registry()
    mem = reg.add_sink(MemorySink())
    agg = obs_drift.emit(drifts, cplan, registry=reg)
    assert agg == pytest.approx(0.5)
    rows = mem.find("obs.drift.span")
    assert len(rows) == 2 * plan.n_buckets
    assert {r.value["kind"] for r in rows} == {"rs", "ag"}
    assert all(r.value["rel_err"] == pytest.approx(0.5, abs=1e-3)
               for r in rows)
    g = mem.find("obs.drift.ring.rel_err")
    assert len(g) == 1 and g[0].kind == "gauge"
    assert g[0].value == pytest.approx(0.5, abs=1e-3)


def test_measured_span_times_skips_warmup_steps():
    spans = [Span("rs[b0]", "comm", 0.0, 9.0, step=0),    # compile-skewed
             Span("rs[b0]", "comm", 0.0, 1.0, step=1),
             Span("rs[b0]", "comm", 0.0, 3.0, step=2),
             Span("forward", "compute", 0.0, 1.0, step=1)]
    m = obs_drift.measured_span_times(spans)
    assert set(m) == {"rs[b0]"}                      # comm spans only
    assert m["rs[b0]"] == pytest.approx(2.0)         # median of steps 1,2
    # fewer steps than skip_steps: keep them rather than return nothing
    m0 = obs_drift.measured_span_times(spans[:1])
    assert m0["rs[b0]"] == pytest.approx(9.0)


# ----------------------------------- measured forward time (satellite 1)

def test_backward_profile_measures_forward_time():
    params = {"w1": jnp.ones((64, 64)), "w2": jnp.ones((64, 64))}

    def loss(p):
        h = jnp.tanh(jnp.ones((8, 64)) @ p["w1"])
        return jnp.sum((h @ p["w2"]) ** 2)

    prof = measure_backward_profile(loss, params, bucket_mb=0.01)
    assert prof.t_forward_s is not None and prof.t_forward_s > 0
    plan = bucketing.make_plan(params, bucket_mb=0.01)
    assert len(prof.cum_elems) == plan.n_buckets
    assert prof.total_s > 0


def test_simulate_prefers_measured_forward_budget():
    """Gather-ahead pricing: explicit t_forward_s > profile's measured
    value > the t_backward/2 heuristic. The exposed-time delta between a
    zero forward budget and the heuristic is exactly min(t_gather,
    t_backward/2) — the part of the gather the heuristic hides."""
    tree = {"t": jnp.zeros((200000,))}
    plan = bucketing.make_plan(tree, bucket_mb=0.2)
    kw = dict(t_backward_s=0.01, shard_update=True, gather_ahead=True)
    total = int(sum(plan.bucket_sizes))
    prof_zero = BackwardProfile((total,), (0.01,), t_forward_s=0.0)
    prof_none = BackwardProfile((total,), (0.01,))
    s_zero = simulate(plan, "ring", ("data",), (8,), profile=prof_zero,
                      **kw)
    s_none = simulate(plan, "ring", ("data",), (8,), profile=prof_none,
                      **kw)
    delta = s_zero.t_exposed_s - s_none.t_exposed_s
    assert delta == pytest.approx(min(s_zero.t_gather_s, 0.005))
    # explicit override outranks the profile's measurement: an infinite
    # forward budget hides the whole gather, a zero budget charges it all
    s_expl = simulate(plan, "ring", ("data",), (8,), profile=prof_zero,
                      t_forward_s=1e9, **kw)
    assert (s_zero.t_exposed_s - s_expl.t_exposed_s
            == pytest.approx(s_zero.t_gather_s))
    # profile measured on a different-scale run is rescaled like the
    # backward curve: half-of-total forward == the heuristic
    prof_half = BackwardProfile((total,), (0.02,), t_forward_s=0.01)
    s_half = simulate(plan, "ring", ("data",), (8,), profile=prof_half,
                      **kw)
    assert s_half.t_exposed_s == pytest.approx(s_none.t_exposed_s)


# --------------------------- 8-device span invariants (subprocess, tier2)

OVERLAP_SPAN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.configs.base import CommConfig
from repro.configs.shapes import InputShape
from repro.core import lars
from repro.core.schedule import ScheduleConfig, make_schedule
from repro.data.synthetic import make_batch_fn
from repro.models.registry import build_model
from repro.obs.trace import Tracer
from repro.train import state as st
from repro.train.step import make_train_step

mesh = make_mesh((8, 1), ("data", "model"))
cfg = get_config("resnet50").reduced()
model = build_model(cfg)
sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                     total_steps=10))
bf = make_batch_fn(cfg, InputShape("t", "train", 0, 8), mesh=mesh)
out = {}
for overlap in (False, True):
    tr = Tracer()
    cc = CommConfig(strategy="ring", bucket_mb=1.0, shard_update=True,
                    overlap=overlap, gather_ahead=False)
    step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                           mesh=mesh, comm=cc, tracer=tr)
    s = st.init_state(model, 0, sharded_plan=step.bucket_plan,
                      n_shards=step.n_shards)
    f = jax.jit(step)
    for i in range(2):
        batch = bf(s.step)
        tr.begin_step()
        s, m = jax.block_until_ready(f(s, batch))
        tr.end_step(i)
    out[str(int(overlap))] = {
        "n_buckets": step.bucket_plan.n_buckets,
        "spans": [[sp.name, sp.cat, sp.t0, sp.t1]
                  for sp in tr.spans(step=1)],
    }
print("SPANS;" + json.dumps(out), flush=True)
"""


@pytest.mark.tier2
def test_traced_step_span_invariants_8dev():
    """Span nesting/count invariants under overlap=True and False on the
    real 8-device sharded step: per step exactly one rs + one ag span per
    bucket, the forward/backward/update compute spans, everything nested
    inside the step window, and the forward span opening the timeline."""
    r = subprocess.run([sys.executable, "-c", OVERLAP_SPAN_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"})
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("SPANS;")]
    assert line, (r.stdout[-2000:], r.stderr[-3000:])
    out = json.loads(line[0].split(";", 1)[1])
    for overlap in ("0", "1"):
        nb = out[overlap]["n_buckets"]
        spans = {name: (t0, t1)
                 for name, cat, t0, t1 in out[overlap]["spans"]}
        assert len(spans) == len(out[overlap]["spans"])   # unique names
        rs = sorted(n for n in spans if n.startswith("rs["))
        ag = sorted(n for n in spans if n.startswith("ag["))
        assert rs == [f"rs[b{b}]" for b in sorted(range(nb), key=str)]
        assert ag == [f"ag[b{b}]" for b in sorted(range(nb), key=str)]
        for name in ("forward", "backward", "update", "step"):
            assert name in spans, (overlap, sorted(spans))
        t0s, t1s = spans["step"]
        for name, (a, b) in spans.items():
            assert t0s <= a <= b <= t1s, (overlap, name)
        # the forward span opens the compute timeline (its begin probe
        # depends only on the step's inputs)
        assert spans["forward"][0] <= spans["backward"][0] + 1e-3
        assert spans["forward"][0] <= spans["update"][0] + 1e-3
        # gather_ahead=False: every bucket's AG completes after its RS
        # (the collective is a cross-device barrier; small slack for
        # async callback delivery)
        for b in range(nb):
            assert spans[f"ag[b{b}]"][1] >= spans[f"rs[b{b}]"][1] - 0.05


TRACE_CLI_SCRIPT_ARGS = [
    "--arch", "resnet50", "--reduced", "--batch", "8", "--steps", "2",
    "--comm", "ring", "--bucket-mb", "1.0", "--shard-update",
]


@pytest.mark.tier2
def test_trace_cli_acceptance_8dev(tmp_path):
    """The ISSUE's acceptance run: ``launch.train --trace out.json
    --metrics out.jsonl`` on an 8-device CPU mesh writes a Chrome-loadable
    trace whose per-step RS/AG span counts equal the BucketPlan's bucket
    count, plus the metrics JSONL artifact and the drift rows."""
    trace = str(tmp_path / "trace.json")
    metrics = str(tmp_path / "metrics.jsonl")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         *TRACE_CLI_SCRIPT_ARGS, "--trace", trace, "--metrics", metrics],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])

    # the exact BucketPlan the launcher builds (packing is static)
    from repro.configs import get_config
    from repro.models.registry import build_model
    model = build_model(get_config("resnet50").reduced())
    plan = bucketing.make_plan(model.param_pd, bucket_mb=1.0,
                               dtype_bytes=2)

    obj = obs_trace.load_chrome(trace)               # validates schema
    spans = obs_trace.spans_from_chrome(obj)
    steps = sorted({s.step for s in spans if s.step >= 0})
    assert steps == [0, 1]
    for st_ in steps:
        names = [s.name for s in spans if s.step == st_]
        assert sum(n.startswith("rs[") for n in names) == plan.n_buckets
        assert sum(n.startswith("ag[") for n in names) == plan.n_buckets
        assert "step" in names and "forward" in names

    rows = [json.loads(ln) for ln in open(metrics)]
    by_name = {r_["name"] for r_ in rows}
    assert "trace_written" in by_name
    assert "train_step" in by_name
    assert "obs.drift.ring.rel_err" in by_name or "obs.drift.no_spans" \
        in by_name
    assert "obs.drift.span" in r.stdout
