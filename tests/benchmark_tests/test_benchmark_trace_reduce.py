"""The trace reduction on a real v5e trace (five stock ResNet-50 steps, the
``.profile/`` fixture tracked in git) and on hand-made intervals; the tagger
on instruction texts as that trace spells them."""
import os

import pytest

from perfbench import hlo_tag, layer_metrics, trace_reduce

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_FIXTURE = os.path.join(_ROOT, ".profile", "20260730-200902", "plugins",
                        "profile", "2026_07_30_20_11_09", "vm.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    trace = trace_reduce.load(_FIXTURE, host_prefix="$ndarray.py")
    assert [d.index for d in trace.devices] == [0]
    return trace_reduce.summarize(trace, r"^jit_step\(")


def test_fixture_has_five_steps_of_100_6_ms():
    dev = trace_reduce.load(_FIXTURE).devices[0]
    ms = [d * 1e-6 for _, _, d in trace_reduce.modules_matching(
        dev, r"^jit_step\(")]
    assert len(ms) == 5 and all(100.60 < v < 100.63 for v in ms), ms
    assert len(dev.ops) == 22410 and len(dev.async_ops) == 9080


def test_fixture_device_is_busy_all_through_the_steps(summary):
    assert summary["window_s"] == pytest.approx(0.50318, abs=2e-5)
    idle = 1 - summary["busy_s"] / summary["window_s"]
    assert 0 < idle < 0.002, idle


@pytest.mark.parametrize("pattern,ms_per_step", [
    (r"convert_reduce_fusion", 23.0),   # docs/PERF.md's 23.0 ms of BN stats
    (r"^other\.fusion\.", 57.6),
    (r"maximum_add_fusion", 8.0),
    (r"copy_add_fusion", 6.2),
])
def test_fixture_op_families(summary, pattern, ms_per_step):
    spec = {"reader": "trace_op", "pattern": pattern, "reduce": "sum"}
    got = layer_metrics.read(spec, {"trace": summary})
    assert got == pytest.approx(ms_per_step, abs=0.06)


def test_fixture_busy_metric_and_roofline_reader(summary):
    facts = {"trace": summary, "peaks": {"bf16_flops_per_s": 197e12},
             "counters": {"flops": 256 * 6 * 3.858e9}}
    busy = layer_metrics.read(
        {"reader": "trace_op", "pattern": ".", "reduce": "union"}, facts)
    assert busy == pytest.approx(100.6, abs=0.05)
    share = layer_metrics.read(
        {"reader": "roofline", "pattern": ".", "work_counter": "flops",
         "peak": "bf16_flops_per_s"}, facts)
    assert share == pytest.approx(100 * 256 * 6 * 3.858e9 / 197e12
                                  / (busy * 1e-3))
    assert 25 < share < 35


def test_fixture_breakdown_lists(summary):
    ops, gaps = summary["device_ops"], summary["idle_gaps"]
    assert len(ops) == 10 and len(gaps) == 5
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    assert all(set(name) <= set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQR"
                                "STUVWXYZ0123456789_.-") for name, _ in ops)
    # the gaps between steps fall while the host waits for the loss
    assert gaps[0][0].endswith("wait_to_read") and gaps[0][1] < 1e-4


def test_readers_return_nothing_without_a_trace():
    facts = {"trace": None, "counters": {}, "setup_parts": {"trace_s": 1.5}}
    for spec in ({"reader": "trace_op", "pattern": ".", "reduce": "sum"},
                 {"reader": "roofline", "pattern": ".", "work_counter": "f",
                  "peak": "bf16_flops_per_s"},
                 {"reader": "setup_part", "key": "absent"}):
        assert layer_metrics.read(spec, facts) is None
    assert layer_metrics.read({"reader": "setup_part", "key": "trace_s"},
                              facts) == 1.5
    with pytest.raises(ValueError):
        layer_metrics.read({"reader": "guess"}, facts)


def test_fixture_asynchronous_copies_are_kept_for_the_log(summary):
    assert summary["n_modules"] == 5
    assert summary["async_ops"][0][0].startswith("other.copy-start")


def test_union_does_not_count_a_nested_op_twice():
    ops = [("outer", 0, 100), ("inner", 10, 20), ("later", 150, 50)]
    assert trace_reduce.merged(ops, 0, 1000) == [[0, 100], [150, 200]]
    assert trace_reduce.merged(ops, 50, 160) == [[50, 100], [150, 160]]
    dev = trace_reduce.Device(0, modules=[("jit_f(1)", 0, 200)], ops=ops)
    s = trace_reduce.summarize(
        trace_reduce.Trace([dev], [("bench.sync", 90, 70)]), "jit_f")
    assert s["busy_s"] == pytest.approx(150e-9)
    assert s["idle_gaps"] == [["bench.sync", pytest.approx(50e-9)]]
    assert trace_reduce.op_ms(s, ".") == pytest.approx(170e-6)
    assert trace_reduce.op_ms(s, ".", union=True) == pytest.approx(150e-6)


def test_summarize_says_which_modules_ran_when_none_matches():
    dev = trace_reduce.Device(0, modules=[("jit_f(1)", 0, 10)], ops=[])
    with pytest.raises(ValueError, match="jit_f"):
        trace_reduce.summarize(trace_reduce.Trace([dev], []), "jit_step")


_HLO = """
HloModule jit_step

%fused_computation.7 (p0: bf16[256,64,56,56], p1: bf16[64,64,3,3]) -> bf16[256,64,56,56] {
  %p0 = bf16[256,64,56,56]{0,3,2,1:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[64,64,3,3]{0,3,2,1} parameter(1)
  %convolution.3 = bf16[256,64,56,56]{0,3,2,1} convolution(%p0, %p1), window={size=3x3 pad=1_1x1_1}, dim_labels=bf01_oi01->bf01
  ROOT %maximum.1 = bf16[256,64,56,56]{0,3,2,1} maximum(%convolution.3, %p0)
}

%fused_computation.8 (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  ROOT %add.1 = f32[256]{0} add(%p0, %p0)
}

ENTRY %main (a: bf16[256,64,56,56], b: bf16[64,64,3,3], c: f32[256]) -> f32[256] {
  %a = bf16[256,64,56,56]{0,3,2,1} parameter(0)
  %b = bf16[64,64,3,3]{0,3,2,1} parameter(1)
  %c = f32[256]{0} parameter(2)
  %fusion.12 = bf16[256,64,56,56]{0,3,2,1:T(8,128)(2,1)} fusion(%a, %b), kind=kOutput, calls=%fused_computation.7
  %all-reduce-start.1 = (f32[256]{0}, f32[256]{0}) all-reduce-start(%c), replica_groups={{0,1,2,3}}
  %all-reduce-done.1 = f32[256]{0} all-reduce-done(%all-reduce-start.1)
  %dot.5 = f32[8,1000]{1,0} dot(%c, %c), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %fusion.13 = f32[256]{0} fusion(%c), kind=kLoop, calls=%fused_computation.8
}
"""


def test_kinds_come_from_the_computation_a_fusion_calls():
    kinds = hlo_tag.kinds_from_hlo(_HLO)
    assert kinds["fusion.12"] == "conv" and "fusion.13" not in kinds
    assert kinds["all-reduce-start.1"] == "coll" and kinds["dot.5"] == "dot"


@pytest.mark.parametrize("text,tagged", [
    ("%fusion.12 = bf16[256,64,56,56]{0,3,2,1:T(8,128)(2,1)} fusion(bf16[256"
     ",64,56,56]{0,3,2,1} %a), kind=kOutput, calls=%fused_computation.7",
     "conv.fusion.12.bf16-256x64x56x56"),
    ("fusion.12", "conv.fusion.12"),
    ("%fusion.13 = f32[256]{0} fusion(f32[256]{0} %c), kind=kLoop, "
     "calls=%fused_computation.8", "other.fusion.13.f32-256"),
    ("%all-reduce-start.1 = (f32[256]{0}, f32[256]{0}) all-reduce-start("
     "f32[256]{0} %c)", "coll.all-reduce-start.1.f32-256"),
    ("all-gather.3", "coll.all-gather.3"),
    ("%xor.5 = u32[]{:T(128)} xor(u32[] %a, u32[] %b)",
     "other.xor.5.u32-scalar"),
])
def test_tag(text, tagged):
    assert hlo_tag.tag(text, hlo_tag.kinds_from_hlo(_HLO)) == tagged
