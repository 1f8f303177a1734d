"""The trace reduction on hand-made device events."""
import pytest

from perfbench import trace
from perfbench.trace import Op, Trace

pytestmark = pytest.mark.tier1

MS = 1e6        # ns


def op(a, b, name="fusion.1", cat="loop fusion"):
    return Op(a * MS, b * MS, name, cat)


def test_busy_is_the_union_of_overlapping_ops_clipped_to_the_window():
    ops = [op(0, 4), op(2, 6), op(8, 9), op(9.5, 12)]
    # window 1..11 ms: busy 1-6, 8-9, 9.5-11 = 5 + 1 + 1.5 ms
    assert trace.busy_s(ops, (1 * MS, 11 * MS)) == pytest.approx(7.5e-3)


def test_idle_share_takes_the_chip_that_idled_most():
    t = Trace({"/device:TPU:0": [op(0, 10)], "/device:TPU:1": [op(0, 4)]},
              (0, 10 * MS), [])
    assert trace.idle_pct(t) == pytest.approx(60.0)


def test_a_collective_half_hidden_by_compute():
    ops = [op(0, 4, "fusion.3", "convolution"),
           op(2, 2.5, "collective-permute-start.1", "collective"),
           op(7, 8, "all-reduce.2", "collective")]
    asyncs = [op(2, 6, "collective-permute-start.1", "collective"),
              op(0, 9, "copy-start.4", "copy-start")]
    coll, exposed = trace.collective_s(ops, (0, 10 * MS), asyncs)
    assert coll == pytest.approx(5e-3)          # 2-6 and 7-8
    assert exposed == pytest.approx(3e-3)       # 4-6 and 7-8


def test_kinds_by_classification_or_name():
    assert trace.is_convolution(op(0, 1, "fusion.7", "convolution"))
    assert trace.is_convolution(op(0, 1, "convolution.2", ""))
    assert not trace.is_convolution(op(0, 1, "fusion.8", "fusion"))
    assert trace.is_collective(op(0, 1, "all-gather.1", ""))
    assert not trace.is_collective(op(0, 1, "copy.1", "copy"))
    ops = [op(0, 2, "fusion.1", "convolution"), op(1, 5, "fusion.2", "")]
    assert trace.kind_s(ops, (0, 10 * MS), trace.is_convolution) == \
        pytest.approx(2e-3)


HLO = """HloModule jit_f, entry_computation_layout={}

%fused_computation.1 (param_0: bf16[2,4,4,3], param_1: bf16[3,3,3,8]) -> bf16[2,4,4,8] {
  %param_0 = bf16[2,4,4,3]{3,2,1,0} parameter(0)
  %param_1 = bf16[3,3,3,8]{3,2,1,0} parameter(1)
  ROOT %convolution.3 = bf16[2,4,4,8]{3,2,1,0} convolution(%param_0, %param_1), window={size=3x3}, dim_labels=b01f_01io->b01f
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p, %p)
}

ENTRY %main.9 (Arg_0.1: bf16[2,4,4,3], Arg_1.2: bf16[3,3,3,8]) -> bf16[2,4,4,8] {
  %Arg_0.1 = bf16[2,4,4,3]{3,2,1,0} parameter(0)
  %fusion.7 = bf16[2,4,4,8]{3,2,1,0} fusion(%Arg_0.1, %Arg_1.2), kind=kOutput, calls=%fused_computation.1
  %fusion.8 = (f32[8]{0}, s32[]) fusion(%all-reduce.4), kind=kLoop, calls=%fused_computation.2
  %collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) collective-permute-start(%y), source_target_pairs={{0,1}}
  %all-reduce.4 = f32[8]{0} all-reduce(%z), to_apply=%fused_computation.2
  ROOT %tuple = (bf16[2,4,4,8], f32[8]) tuple(%fusion.7, %fusion.8)
}
"""


def test_classify_reads_kinds_from_the_compiled_hlo():
    kinds = trace.classify(HLO)
    assert kinds["fusion.7"] == "convolution"
    assert kinds["fusion.8"] == "fusion"        # reads a collective's result
    assert kinds["collective-permute-start.1"] == "collective"
    assert kinds["all-reduce.4"] == "collective"
    assert kinds["add.1"] == "add"


def test_a_gap_is_named_by_the_innermost_host_event_over_it():
    ops = [op(0, 2), op(6, 7)]
    host = [(0, 10 * MS, "loop.train"), (3 * MS, 5.5 * MS, "batch_fn")]
    gaps = trace.idle_gaps(ops, (0, 10 * MS), host)
    assert gaps[0] == ["host: batch_fn", pytest.approx(4e-3)]
    assert gaps[1] == ["host: loop.train", pytest.approx(3e-3)]


def test_top_ops_sum_numbered_instances():
    ops = [op(0, 1, "fusion.1"), op(1, 3, "fusion.2"),
           op(3, 4, "convolution.9", "convolution")]
    top = trace.top_ops(ops, (0, 10 * MS))
    assert top[0] == ["fusion [loop fusion]", pytest.approx(3e-3)]
    assert top[1] == ["convolution [convolution]", pytest.approx(1e-3)]


def test_minus_subtracts_covered_stretches():
    a = trace.union([(0, 10), (20, 30)])
    b = trace.union([(5, 25)])
    assert trace.minus(a, b) == pytest.approx(10)
