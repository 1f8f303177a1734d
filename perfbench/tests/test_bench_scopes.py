"""The reduction of the program's named scopes and loop spans, on a
hand-written HLO module and hand-made trace events."""
from types import SimpleNamespace

import pytest

from perfbench import scopes, spec
from perfbench.trace import Op, Trace

pytestmark = pytest.mark.tier1

MS = 1e6        # ns

HLO = """HloModule jit_train_step, entry_computation_layout={}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %maximum.2 = f32[8]{0} maximum(%p, %p), metadata={op_name="jit(train_step)/jvp(forward)/jit(relu)/max" source_file="resnet.py" source_line=40}
}

%fused_computation.2 (p.1: f32[8]) -> (f32[], f32[8]) {
  %p.1 = f32[8]{0} parameter(0)
  %multiply.3 = f32[8]{0} multiply(%p.1, %p.1), metadata={op_name="jit(train_step)/transpose(jvp(forward))/mul"}
  %reduce.4 = f32[] reduce(%multiply.3, %c), dimensions={0}, to_apply=%add, metadata={op_name="jit(train_step)/update/reduce_sum"}
  ROOT %tuple.5 = (f32[], f32[8]) tuple(%reduce.4, %multiply.3)
}

%fused_computation.3 (p.2: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  ROOT %multiply.6 = f32[8]{0} multiply(%p.2, %p.2), metadata={op_name="jit(train_step)/update/mul" source_file="lars.py" source_line=9}
}

ENTRY %main.9 (a: bf16[2,4,4,3], b: bf16[3,3,3,8]) -> f32[8] {
  %a = bf16[2,4,4,3]{3,2,1,0} parameter(0)
  %b = bf16[3,3,3,8]{3,2,1,0} parameter(1)
  %convolution.1 = bf16[2,4,4,8]{3,2,1,0} convolution(%a, %b), window={size=3x3}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(train_step)/jvp(forward)/conv_general_dilated" source_file="resnet.py" source_line=70}
  %fusion.2 = f32[8]{0} fusion(%convolution.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(forward))/conv_general_dilated"}
  %fusion.7 = (f32[], f32[8]) fusion(%fusion.2), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(train_step)/transpose(jvp(forward))/mul"}
  %get-tuple-element.8 = f32[8]{0} get-tuple-element(%fusion.7), index=1
  %all-reduce.3 = f32[8]{0} all-reduce(%get-tuple-element.8), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(train_step)/shard_map/transpose(jvp(ar_b0))/psum"}
  %fusion.4 = f32[8]{0} fusion(%all-reduce.3), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/update/mul"}
  ROOT %copy.5 = f32[8]{0} copy(%fusion.4)
}
"""


@pytest.mark.parametrize("op_name, phase", [
    ("jit(train_step)/jvp(forward)/conv_general_dilated", "forward"),
    ("jit(train_step)/forward/convert_element_type", "forward"),
    ("jit(train_step)/shard_map/transpose(jvp(forward))/jit(relu)/max",
     "backward"),
    ("jit(train_step)/shard_map/transpose(jvp(jvp()))/checkpoint/"
     "rematted_computation/forward/conv_general_dilated", "backward"),
    ("jit(train_step)/update/jit(_where)/select_n", "update"),
    ("jit(train_step)/shard_map/transpose(jvp(forward))/"
     "transpose(jvp(rs_b12))/reduce_scatter", "exchange"),
    ("jit(train_step)/shard_map/update/ag_g3/all_gather", "exchange"),
    ("jit(train_step)/shard_map/transpose(jvp())/div", None),
    ("jit(train_step)/broadcast.217", None),
], ids=["forward", "forward_cast", "backward", "rematerialised",
        "update", "exchange_in_backward", "exchange_in_update",
        "custom_vjp_glue", "unscoped"])
def test_scope_of(op_name, phase):
    assert scopes.scope_of(op_name) == phase


def test_a_fusion_takes_its_own_metadata():
    m = scopes.scope_map(HLO)
    # fusion.2 calls a computation whose root says ``jvp(forward)``: its own
    # metadata (the backward's) decides
    assert m["fusion.2"] == "backward"
    assert m["maximum.2"] == "forward"
    assert m["convolution.1"] == "forward"
    assert m["all-reduce.3"] == "exchange"
    assert m["fusion.4"] == m["multiply.6"] == "update"
    assert "copy.5" not in m                   # no metadata, no phase


def one_fusion(root: str, fused: str) -> str:
    """A module whose one fusion, named ``root``, fuses an instruction
    named ``fused``."""
    return f"""%fused_computation.1 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %multiply.2 = f32[8]{{0}} multiply(%p, %p), metadata={{op_name="jit(train_step)/{fused}"}}
}}

ENTRY %main.3 (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  ROOT %fusion.4 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(train_step)/{root}"}}
}}
"""


@pytest.mark.parametrize("root, fused, phase", [
    ("transpose(jvp(forward))/dot_general", "update/square", "mixed"),
    ("update/reduce_sum", "forward/convert_element_type", "mixed"),
    ("jvp(forward)/add", "update/mul", "mixed"),
    ("update/mul", "update/add", "update"),
    ("transpose(jvp(forward))/mul", "jvp(forward)/max", "backward"),
    ("update/mul", "shard_map/update/ag_g2/all_gather", "update"),
], ids=["norm_in_backward", "cast_in_update", "update_in_forward",
        "update_alone", "forward_in_backward", "exchange_in_update"])
def test_a_fusion_holding_update_and_other_work_is_mixed(root, fused,
                                                         phase):
    assert scopes.scope_map(one_fusion(root, fused))["fusion.4"] == phase


def op(a, b, name, cat=""):
    return Op(a * MS, b * MS, name, cat)


def host(a, b, name):
    return (a * MS, b * MS, name)


#: two 10 ms steps on one chip: forward 2 ms, backward 3 ms, a weight
#: gradient with its norm (mixed) 1 ms which the bucket's all-reduce
#: overlaps, update 1 ms and an unscoped copy 0.5 ms each; the loop's
#: spans around them, a readback on the first
OPS = [op(1, 3, "convolution.1"), op(3, 6, "fusion.2"),
       op(6, 7, "fusion.7"), op(6, 8, "all-reduce.3"), op(8, 9, "fusion.4"),
       op(9, 9.5, "copy.5", "copy"),
       op(11, 13, "convolution.1"), op(13, 16, "fusion.2"),
       op(16, 17, "fusion.7"), op(16, 18, "all-reduce.3"),
       op(18, 19, "fusion.4"), op(19, 19.5, "copy.5", "copy")]
HOST = [host(0, 10, "train_step"), host(0, 0.5, "loop.batch"),
        host(0.5, 1, "loop.dispatch"), host(1, 9.7, "loop.wait"),
        host(9.7, 9.9, "loop.readback"),
        host(10, 20, "train_step"), host(10, 10.4, "loop.batch"),
        host(10.4, 10.45, "loop.release"), host(10.45, 11, "loop.dispatch"),
        host(11, 19.5, "loop.wait"),
        host(20.5, 21, "loop.dispatch")]          # after the window


def ctx(hlo=HLO, ops=OPS, host_events=HOST, steps=2):
    said = []
    t = Trace({"/device:TPU:0": ops}, (0, 20 * MS), list(host_events))
    return SimpleNamespace(trace=t, raw=SimpleNamespace(hlo=hlo),
                           steps=steps, say=said.append, said=said)


READINGS = {"fwd_ms": 2.0, "bwd_ms": 3.0, "update_ms": 1.0,
            "mixed_ms": 1.0, "dispatch_ms": 0.525, "readback_ms": 0.1}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_reader_on_a_small_trace(metric):
    c = ctx()
    assert spec.reader(metric)(c) == pytest.approx(READINGS[metric])


def test_forward_reader_says_the_whole_split():
    c = ctx()
    spec.reader("fwd_ms")(c)
    (line,) = c.said
    assert "exchange 2.0" in line and "unscoped 0.5" in line
    assert "['copy [copy]', 0.5]" in line


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_reader_finds_nothing_without_the_names(metric):
    """The parent program has no scopes and no loop spans: every reader
    returns None and none raises."""
    bare = "\n".join(line.split(", metadata=")[0]
                     for line in HLO.splitlines())
    untraced = SimpleNamespace(trace=None, raw=SimpleNamespace(hlo=None),
                               steps=5, say=print)
    assert spec.reader(metric)(ctx(hlo=bare, host_events=[])) is None
    assert spec.reader(metric)(untraced) is None


def test_phase_time_is_the_mean_over_chips():
    c = ctx()
    c.trace.devices["/device:TPU:1"] = [op(1, 7, "convolution.1")]
    # chip 0: 4 ms of forward, chip 1: 6 ms, over two steps
    assert scopes.phase_ms(c, "forward") == pytest.approx(2.5)


def test_idle_split_over_the_loop_spans():
    split = scopes.idle_split(ctx().trace)
    want = {"loop.batch": 0.9, "loop.release": 0.05, "loop.dispatch": 1.05,
            "loop.wait": 0.2, "loop.readback": 0.2, "loop.other": 0.6,
            "outside": 0.0}
    assert split == pytest.approx({k: v * 1e-3 for k, v in want.items()})
    assert scopes.idle_split(ctx(host_events=[]).trace) is None


def test_idle_under_a_span_outside_every_step_counts_as_outside():
    """The window opens inside a step whose ``train_step`` began before the
    profiler: idle under its spans lies outside every recorded step."""
    split = scopes.idle_split(ctx(host_events=HOST[5:]).trace)
    # step 0's idle (0-1 ms and 9.5-10 ms) is outside; step 1's as before
    assert split["outside"] == pytest.approx(1.5e-3)
    assert split["loop.dispatch"] == pytest.approx(0.55e-3)
    assert split["loop.other"] == pytest.approx(0.5e-3)
