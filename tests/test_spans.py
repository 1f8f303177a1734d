"""The step's named scopes and the loop's profiler spans.

The train step names its phases with ``jax.named_scope`` and the compiled
HLO keeps them in each instruction's ``op_name``: the forward under
``forward``, the backward under ``transpose(jvp(forward))``, the optimizer
under ``update`` and each bucket's collective under ``ar_b<k>``/``rs_b<k>``
/``ag_b<k>``/``ag_g<k>``. The compiled steps are checked on a 4-device CPU
mesh in one subprocess (jax fixes the device count at import), with the
reduced ResNet cut to one block per stage to keep four compiles short.

The training loop wraps each step in a ``train_step`` step annotation
holding ``loop.batch``, ``loop.dispatch``, ``loop.release``, ``loop.wait``
on the step before (the loop keeps one step in flight) and
``loop.readback`` of a logged step one step later or at the final drain;
a CPU profile of ``loop.train`` must hold them in that order.
``launch.train --trace DIR`` writes the profile.
"""
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.train import loop
from repro.train.state import TrainState

pytestmark = pytest.mark.tier1

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
       "JAX_PLATFORMS": "cpu"}

#: the four paths: (name, strategy, sharding)
PATHS = [("xla", "xla", "replicated"), ("ring", "ring", "replicated"),
         ("zero1", "ring", "zero1"), ("zero3", "ring", "zero3")]

SCOPES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re, sys
import jax
from repro.configs import get_config
from repro.configs.base import CommConfig
from repro.configs.shapes import InputShape
from repro.core import lars
from repro.core.schedule import ScheduleConfig, make_schedule
from repro.data.synthetic import make_batch_fn
from repro.launch.mesh import make_local_mesh
from repro.models import resnet
from repro.models.registry import build_model
from repro.train.state import init_state
from repro.train.step import make_train_step

resnet.STAGES = tuple((1, base) for _, base in resnet.STAGES)
mesh = make_local_mesh(1, devices=4)
cfg = get_config("resnet50").reduced()
model = build_model(cfg)
sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                     total_steps=10))
batch = make_batch_fn(cfg, InputShape("t", "train", 0, 8), mesh=mesh)(0)
out = {}
for name, strategy, sharding in json.loads(sys.argv[1]):
    step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                           mesh=mesh, comm=CommConfig(
                               strategy=strategy, bucket_mb=0.25,
                               sharding=sharding))
    sharded = getattr(step, "shard_update", False)
    state = jax.eval_shape(lambda: init_state(
        model, 0, mesh, opt_kind="lars",
        sharded_plan=step.bucket_plan if sharded else None,
        n_shards=step.n_shards if sharded else 1,
        materialize_params=sharding != "zero3"))
    hlo = jax.jit(step).lower(state, batch).compile().as_text()
    out[name] = {
        "op_names": sorted(set(re.findall(r'op_name="([^"]*)"', hlo))),
        "n_buckets": (step.bucket_plan.n_buckets
                      if strategy != "xla" else 0)}
print("SCOPES " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def children(tmp_path_factory):
    """The module's two subprocesses, started together so that they
    overlap: the scopes script over ``PATHS``, and a traced
    ``launch.train`` run of three steps of a reduced decoder (the smallest
    trace) writing its profile and metrics under a temporary directory."""
    out = tmp_path_factory.mktemp("launch")
    kw = dict(stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
              env=ENV, cwd=ROOT)
    procs = {
        "scopes": subprocess.Popen(
            [sys.executable, "-c", SCOPES_SCRIPT, json.dumps(PATHS)], **kw),
        "launch": subprocess.Popen(
            [sys.executable, "-m", "repro.launch.train", "--arch",
             "qwen1.5-0.5b", "--reduced", "--seq", "8", "--batch", "2",
             "--steps", "3", "--trace", str(out / "profile"),
             "--metrics", str(out / "metrics.jsonl")], **kw)}
    yield procs, out
    for p in procs.values():
        if p.poll() is None:
            p.kill()
        p.communicate()


def _finish(proc) -> str:
    stdout, stderr = proc.communicate(timeout=600)
    assert proc.returncode == 0, stderr[-3000:]
    return stdout


@pytest.fixture(scope="module")
def compiled_op_names(children):
    """{path: {"op_names": every op_name of its compiled step,
    "n_buckets": its bucket plan's size}}."""
    stdout = _finish(children[0]["scopes"])
    line = [x for x in stdout.splitlines() if x.startswith("SCOPES ")][-1]
    return json.loads(line[len("SCOPES "):])


@pytest.mark.tier2
@pytest.mark.parametrize("path", [p[0] for p in PATHS])
def test_compiled_step_carries_phase_scopes(compiled_op_names, path):
    names = compiled_op_names[path]["op_names"]
    parts = [n.split("/") for n in names]
    forward = [p for p in parts if "jvp(forward)" in p
               and not any("transpose(" in x for x in p)]
    # the backward: the forward differentiated, or under zero3's
    # rematerialisation the forward recomputed inside a transpose
    backward = [p for p in parts if "transpose(jvp(forward))" in p
                or ("forward" in p and any(x.startswith("transpose(")
                                           for x in p))]
    update = [p for p in parts if "update" in p]
    assert forward and backward and update, names[:50]
    assert any("conv_general_dilated" in p[-1] for p in forward)
    assert any("conv_general_dilated" in p[-1] for p in backward)
    exchange = {m.group(0) for n in names
                for m in [re.search(r"\b(ar|rs|ag)_[bg]\d+\b", n)] if m}
    if path == "xla":
        # GSPMD inserts the exchange itself: no bucket scope exists
        assert not exchange
    else:
        assert exchange, names[:50]


@pytest.mark.tier2
def test_every_ring_bucket_has_its_exchange_scope(compiled_op_names):
    ring = compiled_op_names["ring"]
    found = {int(k) for n in ring["op_names"]
             for k in re.findall(r"\bar_b(\d+)\b", n)}
    assert ring["n_buckets"] >= 2
    assert found == set(range(ring["n_buckets"]))


def _host_events(profile_dir: str):
    """[(start_ns, end_ns, name, step_num or None)] of every host event
    in the profile under ``profile_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                step = (dict(ev.stats).get("step_num")
                        if ev.name == "train_step" else None)
                out.append((ev.start_ns, ev.end_ns, ev.name, step))
    return out


def test_loop_spans_nest_in_each_step_in_order(tmp_path):
    """Three steps under the profiler, one in flight: three ``train_step``
    annotations, each holding ``loop.batch``, ``loop.dispatch`` and
    ``loop.release`` in that order, then ``loop.wait`` on the previous
    step (none in step 0, which has none); ``loop.readback`` of logged
    step 0 in step 1, and of logged step 2 at the drain that ends step 2,
    after a second ``loop.wait``."""
    def train_step(state, batch):
        w = state.params["w"] - 0.1 * batch["x"]
        return (state._replace(step=state.step + 1, params={"w": w}),
                {"loss": jnp.sum(w * w)})

    state = TrainState(jnp.int32(0), {"w": jnp.ones((4,))}, None, None)
    batch = {"x": jnp.full((4,), 0.5)}
    with jax.profiler.trace(str(tmp_path)):
        state, history = loop.train(state, train_step, lambda s: batch,
                                    steps=3, log_every=2)
    assert int(state.step) == 3 and [h["step"] for h in history] == [0, 2]
    events = _host_events(str(tmp_path))
    steps = sorted((e for e in events if e[2] == "train_step"),
                   key=lambda e: e[0])
    assert [int(e[3]) for e in steps] == [0, 1, 2]
    head = ["loop.batch", "loop.dispatch", "loop.release"]
    want = [head, head + ["loop.wait", "loop.readback"],
            head + ["loop.wait", "loop.wait", "loop.readback"]]
    for i, (s0, s1, _, _) in enumerate(steps):
        inside = sorted((e for e in events if e[2].startswith("loop.")
                         and s0 <= e[0] and e[1] <= s1), key=lambda e: e[0])
        assert [e[2] for e in inside] == want[i]
    # no span of the loop lies outside a step
    assert all(any(s0 <= e[0] and e[1] <= s1 for s0, s1, _, _ in steps)
               for e in events if e[2].startswith("loop."))


@pytest.mark.tier2
def test_launch_train_trace_writes_a_profile(children):
    procs, out = children
    _finish(procs["launch"])
    profile = str(out / "profile")
    (xplane,) = glob.glob(os.path.join(profile, "**", "*.xplane.pb"),
                          recursive=True)
    assert glob.glob(os.path.join(profile, "**", "perfetto_trace.json.gz"),
                     recursive=True)
    with open(out / "metrics.jsonl") as f:
        written = [json.loads(x) for x in f]
    (event,) = [e for e in written if e["name"] == "trace_written"]
    assert event["value"]["path"] == xplane
    names = {e[2] for e in _host_events(profile)}
    assert {"train_step", "loop.batch", "loop.release", "loop.dispatch",
            "loop.wait", "loop.readback"} <= names
