"""``hlo_scope`` on hand-written HLO, the ``scope_op`` reader and the
``roofline`` reader's ``scope`` and bytes on hand-made facts with the metric
files ``perfbench/layer_metrics/`` really holds, and ``scope_report`` on a
tiny cell defined wholly under
``tests/benchmark_tests/data_scope/``, on the CPU (where every device metric
is None)."""
import json
import os
import time

import pytest

from perfbench import hlo_scope, layer_metrics, scope_report
from perfbench.readers import scope_op

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))
_DATA = os.path.join(_HERE, "data_scope")

PHASES = ["fwd_ms.train", "bwd_ms.train", "update_ms.train",
          "scale_guard_ms.train", "unscoped_ms.train"]
FAMILIES = ["norm_act_ms.train", "pool_ms.train"]

_FWD = "jit(step)/jvp(step.forward)/Net.net0/"
_BWD = "jit(step)/transpose(jvp(step.forward))/Net.net0/"

HLO = """HloModule jit_step, is_scheduled=true

%fused_bn (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  %mul.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="@FWD@BatchNorm.bn0/op.BatchNorm/mul"}
  %add.1 = bf16[8]{0} add(%mul.1, %p), metadata={op_name="@FWD@BatchNorm.bn0/op.BatchNorm/mul"}
  ROOT %max.1 = bf16[8]{0} maximum(%add.1, %p), metadata={op_name="@FWD@op.Activation/max"}
}

%fused_unnamed (p: bf16[8]) -> bf16[8] {
  %p.1 = bf16[8]{0} parameter(0)
  ROOT %copy.9 = bf16[8]{0} copy(%p.1)
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0), metadata={op_name="x"}
  %convert.1 = bf16[8]{0} convert(%x), metadata={op_name="jit(step)/jvp(step.forward)/step.cast/convert_element_type"}
  %fusion.1 = bf16[8]{0} fusion(%convert.1), kind=kLoop, calls=%fused_bn, metadata={op_name="@FWD@op.Activation/max"}
  %fusion.2 = bf16[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_bn
  %fusion.3 = bf16[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_unnamed
  %pad.36 = bf16[8]{0} pad(%fusion.3), metadata={op_name="@BWD@MaxPool2D.pool0/op.Pooling/pad;@BWD@MaxPool2D.pool0/op.Pooling/jit(_where)/select_n"}
  %all-reduce.4 = bf16[8]{0} all-reduce(%pad.36), metadata={op_name="@BWD@Conv2D.conv0/op.Convolution/conv_general_dilated"}
  %fusion.5 = bf16[8]{0} fusion(%all-reduce.4), kind=kLoop, calls=%fused_bn, metadata={op_name="jit(step)/step.select/jit(_where)/select_n"}
  %reduce.6 = pred[]{} reduce(%fusion.5), metadata={op_name="jit(step)/step.guard/reduce_and"}
  %xor.7 = u32[2]{0} xor(%x, %x), metadata={op_name="jit(step)/jit(_threefry_split)/TrainStep._make_plain_step.<locals>.step/xor"}
  ROOT %copy.8 = bf16[8]{0} copy(%fusion.5)
}
""".replace("@FWD@", _FWD).replace("@BWD@", _BWD)


@pytest.fixture(scope="module")
def scopes():
    return hlo_scope.scopes_from_hlo(HLO)


def test_an_instruction_takes_its_own_op_name_without_the_jit_elements(scopes):
    assert scopes["convert.1"] == \
        "jvp(step.forward)/step.cast/convert_element_type"
    assert scopes["fusion.5"] == "step.select/select_n"
    assert scopes["xor.7"] == "TrainStep._make_plain_step.<locals>.step/xor"
    # a fusion carries its root's name, whatever else it holds
    assert scopes["fusion.1"] == "jvp(step.forward)/Net.net0/op.Activation/max"


def test_of_names_joined_by_a_semicolon_the_first_counts(scopes):
    assert scopes["pad.36"] == ("transpose(jvp(step.forward))/Net.net0/"
                                "MaxPool2D.pool0/op.Pooling/pad")


def test_a_fusion_with_no_name_takes_its_computations_commonest(scopes):
    assert scopes["fusion.2"] == \
        "jvp(step.forward)/Net.net0/BatchNorm.bn0/op.BatchNorm/mul"


def test_with_no_name_anywhere_it_is_unscoped(scopes):
    assert scopes["fusion.3"] == scopes["copy.8"] == hlo_scope.UNSCOPED
    assert scopes["x"] == "x"  # a parameter's own name is no jit element


def test_mixed_lists_what_else_a_fusion_holds():
    assert hlo_scope.mixed(HLO, "fusion.1") == ["op.Activation",
                                                "op.BatchNorm"]
    assert hlo_scope.mixed(HLO, "fusion.3") == []
    assert hlo_scope.mixed(HLO, "pad.36") == []


@pytest.mark.parametrize("path,leaf", [
    ("transpose(jvp(step.forward))/Net.net0/MaxPool2D.pool0/op.Pooling/pad",
     "op.Pooling"),
    ("jvp(step.forward)/Net.net0/BatchNorm.bn0/add", "BatchNorm.bn0"),
    ("transpose(jvp(step.forward))/convert_element_type", "step.forward"),
    ("step.update/shard_map/step.update.zero/all_gather", "step.update.zero"),
    ("unscoped", "unscoped"),
])
def test_leaf_is_the_innermost_named_element(path, leaf):
    assert hlo_scope.leaf(path) == leaf


@pytest.mark.parametrize("tag,name", [
    ("other.fusion.570.bf16-64", "fusion.570"),
    ("other.pad.36.bf16-64x64x224x224", "pad.36"),
    ("conv.convert_reduce_fusion.6.f32-64", "convert_reduce_fusion.6"),
    ("coll.all-reduce.4.bf16-4096x25088", "all-reduce.4"),
    ("other.reduce.6.pred-scalar", "reduce.6"),
    ("other.copy.8", "copy.8"),
    ("other.add_add_fusion.bf16-256x64x114x114", "add_add_fusion"),
])
def test_a_tag_gives_its_instruction_name_back(tag, name):
    assert scope_op.instruction(tag) == name


class _Program:
    def as_text(self):
        return HLO


def _facts():
    """Two traced modules; durations in ns, so 2e6 ns a module read 1 ms."""
    ms = {"other.convert.1.bf16-8": 1, "other.fusion.1.bf16-8": 2,
          "other.fusion.2.bf16-8": 4, "other.fusion.3.bf16-8": 8,
          "other.pad.36.bf16-8": 16, "coll.all-reduce.4.bf16-8": 32,
          "other.fusion.5.bf16-8": 64, "other.reduce.6.pred-scalar": 128,
          "other.xor.7.u32-2": 256, "other.copy.8.bf16-8": 512,
          "other.not_in_the_text.9.bf16-8": 1024}
    ops = [(tag, 0, v * 1e6) for tag, v in ms.items() for _ in range(2)]
    trace = {"n_modules": 2, "ops": ops,
             "device_ops": [[t, v * 2e-3] for t, v in ms.items()][:10]}
    return {"trace": trace, "programs": [_Program()]}


def _spec(metric, root=os.path.join(_ROOT, "perfbench")):
    with open(os.path.join(root, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("metric,ms", [
    ("fwd_ms.train", 1 + 2 + 4),
    ("bwd_ms.train", 16),           # the all-reduce is collective_ms's
    ("update_ms.train", 64),
    ("scale_guard_ms.train", 128),
    ("unscoped_ms.train", 8 + 256 + 512 + 1024),
    ("norm_act_ms.train", 1 + 2 + 4),
    ("pool_ms.train", 16),
])
def test_each_metric_file_reads_its_scopes(metric, ms, capsys):
    assert scope_op.read(_spec(metric), _facts()) == pytest.approx(ms)


def test_the_family_metrics_and_the_convolutions_add_up_to_fwd_plus_bwd():
    facts = _facts()
    facts["trace"]["ops"] += [("conv.fusion.1.bf16-8", 0, 6e6)] * 2
    fwd_bwd = sum(scope_op.read(_spec(m), facts) for m in PHASES[:2])
    conv = scope_op.read(dict(_spec("fwd_ms.train"), kind=r"^conv\."), facts)
    assert conv == pytest.approx(6)
    assert conv + sum(scope_op.read(_spec(m), facts) for m in FAMILIES) == \
        pytest.approx(fwd_bwd)


def test_the_phase_metrics_partition_the_non_collective_ops(capsys):
    facts = _facts()
    total = sum(d for tag, _, d in facts["trace"]["ops"]
                if not tag.startswith("coll.")) * 1e-6 / 2
    assert sum(scope_op.read(_spec(m), facts) for m in PHASES) == \
        pytest.approx(total)
    # the join is made and logged once a run, whatever the number of metrics
    out = capsys.readouterr().out
    assert out.count("scope_op:") == 10
    assert ("other.fusion.1.bf16-8 | jvp(step.forward)/Net.net0/"
            "op.Activation/max | op.Activation op.BatchNorm") in out


@pytest.mark.parametrize("trace", [None, {"n_modules": 0, "ops": []}])
def test_without_a_trace_the_reader_reads_nothing(trace):
    assert scope_op.read(_spec("fwd_ms.train"),
                         {"trace": trace, "programs": [_Program()]}) is None


def test_a_program_that_names_nothing_reads_none_and_does_not_raise(capsys):
    class Parent:
        def as_text(self):
            return HLO.replace("step.", "st_ep.")

    facts = dict(_facts(), programs=[Parent()])
    for metric in PHASES[:4]:
        assert scope_op.read(_spec(metric), facts) is None, metric
    assert scope_op.read(_spec("unscoped_ms.train"), facts) > 0


def test_the_data_directory_holds_the_metric_files_perfbench_holds():
    for metric in PHASES + FAMILIES:
        assert _spec(metric, os.path.join(_DATA, "bench")) == _spec(metric)


def test_layer_metrics_read_finds_scope_op_as_a_module_of_readers(capsys):
    from perfbench import run

    cell = run.load_cell(_DATA, "tiny_scoped")
    registered = {m["name"]: m["spec"] for m in cell["per_layer"]}
    assert set(PHASES + FAMILIES) <= set(registered)
    for metric in PHASES + FAMILIES:
        assert registered[metric]["reader"] == "scope_op"
        got = layer_metrics.read(registered[metric], _facts())
        assert got == scope_op.read(_spec(metric), _facts()) and got > 0
    # a reader that is neither built in nor a module of perfbench/readers/
    with pytest.raises(ValueError, match="unknown reader 'no_such_reader'"):
        layer_metrics.read({"reader": "no_such_reader"}, _facts())


_PEAKS = {"bf16_flops_per_s": 2e12, "hbm_bytes_per_s": 1e9}
_ROOFLINE = {"reader": "roofline", "pattern": r"^other\.fusion\.",
             "work_counter": "kernel_flops", "peak": "bf16_flops_per_s"}
_WITH_BYTES = dict(_ROOFLINE, bytes_counter="kernel_bytes",
                   bytes_peak="hbm_bytes_per_s")
# ``pattern`` selects fusion.1, .2, .3 and .5 of ``_facts()``, which follow
# one another: 2 + 4 + 8 + 64 = 78 ms a module; under step.forward only the
# first two, 6 ms.  2e12 FLOP/s x 1 ms = 2e9 FLOP; 1e9 B/s x 1 ms = 1e6 B.


@pytest.mark.parametrize("spec,counters,share,bound", [
    pytest.param(_ROOFLINE, {"kernel_flops": 78e9}, 50.0, None,
                 id="none_of_the_new_keys_reads_as_before"),
    pytest.param(_WITH_BYTES, {"kernel_flops": 78e9, "kernel_bytes": 7.8e6},
                 50.0, "compute", id="compute_bound_bytes_side_smaller"),
    pytest.param(_WITH_BYTES, {"kernel_flops": 78e9, "kernel_bytes": 58.5e6},
                 75.0, "bytes", id="hbm_bound_bytes_side_larger"),
    pytest.param(dict(_WITH_BYTES, scope=r"\bstep\.forward\b"),
                 {"kernel_flops": 3e9, "kernel_bytes": 3e6}, 50.0, "bytes",
                 id="scope_narrows_pattern"),
    pytest.param(_WITH_BYTES, {"kernel_flops": 78e9, "kernel_bytes": 93.6e6},
                 120.0, "bytes", id="over_100_passed_through"),
    pytest.param(_WITH_BYTES, {"kernel_flops": 78e9}, None, None,
                 id="a_counter_the_runner_did_not_return_reads_none"),
    pytest.param(dict(_ROOFLINE, scope="no_such_scope"),
                 {"kernel_flops": 78e9}, None, None,
                 id="a_scope_nothing_carries_reads_none"),
])
def test_roofline_takes_the_larger_of_operations_and_bytes(
        spec, counters, share, bound, capsys):
    facts = dict(_facts(), counters=counters, peaks=_PEAKS)
    facts["trace"]["ops"] = [          # laid end to end, so none overlaps
        (tag, i * 2e9, dur)
        for i, (tag, _, dur) in enumerate(facts["trace"]["ops"])]
    got = layer_metrics.read(spec, facts)
    assert got == (None if share is None else pytest.approx(share))
    out = capsys.readouterr().out
    if bound is None:
        assert "roofline:" not in out
    else:
        assert "roofline: %s bound" % bound in out


_MFU = {"reader": "mfu", "rate": "train_samples_per_s",
        "work_counter": "flops_per_sample", "peak": "bf16_flops_per_s"}


@pytest.mark.parametrize("rate,counters,peaks,want", [
    # 250 samples/s x 4e9 FLOP over two chips of 2e12 FLOP/s
    pytest.param(250.0, {"flops_per_sample": 4e9}, _PEAKS, 25.0,
                 id="rate_times_work_over_the_peak_of_the_chips_used"),
    pytest.param(250.0, {"flops_per_sample": 4e9}, None, None,
                 id="no_peaks_off_the_tpu_reads_none"),
    pytest.param(None, {"flops_per_sample": 4e9}, _PEAKS, None,
                 id="a_cell_that_does_not_report_the_rate_reads_none"),
    pytest.param(250.0, {}, _PEAKS, None, id="no_work_count_reads_none"),
])
def test_mfu_is_the_timed_windows_rate_times_the_work_count_over_the_peak(
        rate, counters, peaks, want):
    facts = {"end_to_end": {"train_samples_per_s": rate}, "trace": None,
             "counters": counters, "peaks": peaks, "devices": ["a", "b"]}
    got = layer_metrics.read(_MFU, facts)
    assert got == (None if want is None else pytest.approx(want))
    # the file the benchmark registers names exactly these parameters
    assert {k: v for k, v in _spec("step_mfu.train").items()
            if k != "what"} == _MFU


def test_trace_op_takes_a_scope_too_and_reads_what_scope_op_reads(capsys):
    spec = {"reader": "trace_op", "pattern": "^(?!coll\\.)", "reduce": "sum",
            "scope": _spec("fwd_ms.train")["scope"]}
    assert layer_metrics.read(spec, _facts()) == pytest.approx(
        scope_op.read(_spec("fwd_ms.train"), _facts()))


def test_scope_report_names_every_metric_of_a_cell_defined_beside_it():
    out = scope_report.report(_DATA, "tiny_scoped", "cpu", 2 ** 31 + 5, 0.2,
                              time.monotonic())
    assert out["correct"] is True
    assert set(out["metrics"]) == set(
        PHASES + FAMILIES + ["trace_s.train", "device_busy_ms.train"])
    assert out["metrics"]["trace_s.train"] > 0
    # no /device:TPU plane on the CPU: no device metric, and empty tables
    assert all(out["metrics"][m] is None
               for m in PHASES + FAMILIES + ["device_busy_ms.train"])
    assert out["families"] == out["ops"] == out["unscoped"] == []
    assert out["device"]["platform"] == "cpu"
    assert json.loads(json.dumps(out)) == out


def test_tables_group_the_step_by_direction_family_and_kind(capsys):
    got = scope_report.tables(_facts(), n_ops=2)
    families = dict(got["families"])
    assert families["fwd op.BatchNorm other"] == pytest.approx(4)
    assert families["bwd op.Pooling other"] == pytest.approx(16)
    assert families["bwd op.Convolution coll"] == pytest.approx(32)
    assert families["- step.select other"] == pytest.approx(64)
    assert families["- unscoped other"] == pytest.approx(8 + 512 + 1024)
    assert sum(families.values()) == pytest.approx(2047)
    assert [row[0] for row in got["ops"]] == [
        "other.not_in_the_text.9.bf16-8", "other.copy.8.bf16-8"]
    assert got["ops"][0][2:] == [hlo_scope.UNSCOPED, []]
    assert [row[0] for row in got["unscoped"]] == [
        "other.not_in_the_text.9.bf16-8", "other.copy.8.bf16-8"]
    assert [row[0] for row in scope_report.tables(_facts(), 9)["unscoped"]] \
        == ["other.not_in_the_text.9.bf16-8", "other.copy.8.bf16-8",
            "other.fusion.3.bf16-8"]
