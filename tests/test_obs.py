"""Observability: the metrics registry and its sinks, and the measured
forward-time profile of the bucket autotuner. The step's named scopes and
the loop's profiler spans are tested in tests/test_spans.py.
"""
import json

import jax.numpy as jnp
import pytest

from repro.comm.autotune import (BackwardProfile, measure_backward_profile,
                                 simulate)
from repro.core import bucketing
from repro.obs import metrics as obs_metrics
from repro.obs.metrics import (Event, JsonlSink, MemorySink, Registry,
                               StdoutSink)

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
