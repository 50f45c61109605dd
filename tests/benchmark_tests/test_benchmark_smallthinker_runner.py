"""The decoder runner (``perfbench/runners/train_decoder.py``) over the
``smallthinker`` family at a tiny size on the CPU, through a cell that is
defined wholly by files under ``tests/benchmark_tests/data_smallthinker/``:
its result lines, the numbers ``correct`` is decided from, the counters of
the family's reference module, and every per-layer metric file the
benchmark's cell ``smallthinker_21b_train_16k`` is listed under against the
family's step program.  (A CPU run is a test of control flow; it never
yields a metric of the device.)"""
import json
import os
import re
import time

import pytest

from perfbench import checks, hlo_scope, run
from perfbench.readers import counter as counter_reader

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "data_smallthinker")
_BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
_CELL = "smallthinker_21b_train_16k"
_METRICS = [m["name"] for m in _BENCH["per_layer"]
            if _CELL in m.get("workloads", ())]
_NEW = ["attn_window_ms.train", "attn_window_roofline.train"]


@pytest.fixture(scope="module")
def facts():
    """One traced run of the tiny cell: the cell and what its runner
    returned."""
    return run.cell_facts(_DATA, "tiny_smallthinker_train", "cpu",
                          2 ** 31 + 7, 0.3, True, time.monotonic(),
                          checks.CompileCounter())


def _spec(metric):
    return json.load(open(os.path.join(run.ROOT, "perfbench",
                                       "layer_metrics", metric + ".json")))


def _paths(facts):
    return set(hlo_scope.scopes_from_hlo(
        facts[1]["programs"][0].as_text()).values())


def test_result_lines_of_a_plain_and_a_traced_run(facts):
    cell, f = facts
    plain, traced = (run.result_line(cell, f, t) for t in (False, True))
    assert list(plain) == ["correct", "attempted", "failed", "metrics",
                           "device", "compared"]
    assert plain["correct"] is True and plain["failed"] == 0
    assert plain["attempted"] >= 2 and plain["attempted"] % 2 == 0
    assert set(plain["metrics"]) == {"train_samples_per_s", "setup_s"}
    # no device trace on the CPU: what reads a counter or a part of set-up
    assert set(traced["metrics"]) == {"moe_load_max_over_mean.train",
                                      "reference_check_s.train"}
    assert list(plain["compared"]) == [
        "ref_loss0_rel", "ref_loss1_rel", "ref_loss2_rel",
        "route_refused_share", "route_moved_share",
        "grad_worst_attention", "grad_worst_experts", "grad_worst_router",
        "grad_worst_other", "nonfinite_losses",
        "last_chunk_min_loss_over_first", "programs_built_in_window",
        "arrays_off_device", "assignments_dropped"]
    for name, (value, limit) in plain["compared"].items():
        assert value <= limit, name
    assert plain["compared"]["programs_built_in_window"] == [0, 0]
    assert plain["compared"]["assignments_dropped"] == [0, 0]
    assert json.loads(json.dumps(plain)) == plain


def test_counters_follow_the_shapes_and_the_steps_own_routing(facts):
    cell, f = facts
    c, config = f["counters"], cell["config"]
    tokens = 2 * config["seq_len"]
    # three expert layers, two choices a token, none dropped
    assert c["assignments_routed"] == tokens * 2 * 3
    assert 0 < c["assignments_held"] < c["assignments_routed"]
    assert c["assignments_dropped"] == 0
    d, f_ = config["hidden_size"], config["moe_ffn_hidden_size"]
    assert c["expert_flops_per_module"] == \
        3 * 2 * c["assignments_held"] * 3 * d * f_
    assert c["dispatch_bytes_per_module"] == \
        c["assignments_held"] * d * 2 * 2 * 4
    # 32 positions: 528 pairs a head under a causal mask, 36 + 24 x 8 = 228
    # under a window of 8; 4 heads of 8, 2 sequences, one full layer and two
    # window layers
    window = 2 * 228 * 2 * 8 * 4 * 2
    assert c["attn_window_flops_per_module"] == 3 * 2 * window
    assert c["attn_flops_per_module"] == 3 * 2 * (528 * 2 * 8 * 4 * 2
                                                  + window)
    assert c["flops_per_module_per_chip"] == c["flops_per_sample"] * 2
    assert counter_reader.read({"counter": "moe_load_max_over_mean"}, f) == \
        c["moe_load_max_over_mean"]


def test_the_published_cells_counters_are_the_issues_arithmetic():
    """At the published widths, from the configuration's file alone: the
    attention's operations over one full and three window layers at 16,384
    tokens, the window layers' part, the experts' and dispatch's work from a
    router that sends every expert its even share."""
    import numpy as np

    from perfbench.references import smallthinker_21b as ref

    cell = run.load_cell(run.ROOT, _CELL)
    config = cell["config"]
    assert cell["traffic"]["runner"] == "train_decoder"
    assert cell["traffic"]["chunk_steps"] == 4
    assert cell["chips"] == 1 and config["seq_len"] == 16384
    loads = [("layer%d_moe_counts" % i, np.full(64, 1536.0))
             for i in range(4)]
    c = ref.counters(config, loads, 1)
    full, window = 16384 * 16385 // 2, 58722304
    assert ref.admitted_pairs(16384, None) == full == 134225920
    assert ref.admitted_pairs(16384, 4096) == window
    assert c["attn_window_flops_per_module"] == \
        3 * 2 * 3 * 2 * window * 128 * 28
    assert c["attn_flops_per_module"] == \
        3 * 2 * 2 * (full + 3 * window) * 128 * 28
    assert abs(c["attn_flops_per_module"] / 6 - 2.22e12) < 0.01e12
    assert c["assignments_held"] == 4 * 8 * 1536
    assert c["expert_flops_per_module"] == \
        3 * 3 * 2 * 2560 * 768 * 4 * 12288
    # the REAL bytes of a moved row: 2,560 values of two bytes, not the
    # 4,096 of the slab it travels as
    assert c["dispatch_bytes_per_module"] == 4 * 12288 * 2560 * 2 * 2 * 4
    assert config["fwd_macs_per_sample"] == 133160960 * 16384
    assert c["flops_per_sample"] == 6 * (
        config["fwd_macs_per_sample"] + 2 * (full + 3 * window) * 128 * 28
        + 4 * 12288 * 3 * 2560 * 768)
    assert abs(c["flops_per_sample"] - 2.8e13) < 0.05e13
    assert c["assignments_dropped"] == 0 and c["moe_load_max_over_mean"] == 1


@pytest.mark.parametrize("metric", _METRICS)
def test_a_metric_of_the_cell_reads_what_the_families_step_carries(facts,
                                                                   metric):
    """Each per-layer file the cell is listed under: a scope it names is a
    scope the family's step program really carries (here the tiny preset's,
    compiled for the CPU: the paths are the program's, whatever the
    backend), an op it names is one the registry has, a counter or a part of
    set-up it reads is one the runner returns."""
    from incubator_mxnet_tpu.ops import registry

    cell, f = facts
    spec = _spec(metric)
    paths = _paths(facts) | {hlo_scope.UNSCOPED}
    if "scope" in spec:
        assert any(re.search(spec["scope"], path) for path in paths), metric
        for stem, ends in re.findall(r"(_contrib_\w*)(?:\(([\w|]+)\))?",
                                     spec["scope"]):
            for end in ends.split("|"):
                assert stem + end in registry.OPS, (metric, stem + end)
    else:
        assert spec["reader"] in ("setup_part", "trace_op", "roofline", "mfu",
                                  "counter"), metric
    for key in ("work_counter", "bytes_counter", "counter"):
        if key in spec:
            assert f["counters"].get(spec[key]), (metric, spec[key])
    if spec["reader"] == "setup_part":
        assert spec["key"] in f["setup_parts"], metric
    if spec["reader"] == "mfu":
        assert spec["rate"] in f["end_to_end"], metric


def test_the_two_new_metrics_read_the_window_layers_attention_alone(facts):
    assert set(_NEW) <= set(_METRICS) and len(_METRICS) == 21 + 2
    for m in _BENCH["per_layer"]:
        if m["name"] in _NEW:
            assert m["workloads"] == [_CELL]
            assert m["layer"] == "flash attention kernels"
            assert m["moves"] == "train_samples_per_s"
    scope = _spec(_NEW[0])["scope"]
    assert _spec(_NEW[1])["scope"] == scope
    assert _spec(_NEW[1])["work_counter"] == "attn_window_flops_per_module"
    paths = _paths(facts)
    attn = {p for p in paths
            if re.search(_spec("attn_ms.train")["scope"], p)}
    window = {p for p in paths if re.search(scope, p)}
    # a part of what attn_ms.train reads: the tiny preset's layers 1 and 2,
    # forward and backward, and nothing of layer 0, the full layer
    assert window and window < attn
    for part in (r"SmallThinkerLayer\.\w+_layer1/GroupedAttention\.\w+/",
                 r"SmallThinkerLayer\.\w+_layer2/", r"transpose\(jvp"):
        assert any(re.search(part, p) for p in window), part
    assert not [p for p in window if re.search(r"_layer0\b", p)]
    assert [p for p in attn - window if re.search(r"_layer0\b", p)]
    # the router's ops stand under the block and before its attention's in
    # no scope of the attention block
    assert any(re.search(r"SmallThinkerLayer\.\w+_layer1/ExpertFFN\.\w+/"
                         r"op\._contrib_moe_router", p) for p in paths)


def test_the_family_brings_a_reference_module_and_no_runner():
    from perfbench.references import smallthinker_21b

    for name in ("model_cfg", "counters", "GRAD_GROUPS", "CONTROLS", "Blocks",
                 "balance", "gradients", "apply", "step"):
        assert hasattr(smallthinker_21b, name), name
    assert not [f for f in os.listdir(os.path.join(run.ROOT, "perfbench",
                                                    "runners"))
                if "smallthinker" in f]
    # the reference imports nothing of the system it is compared with
    assert "incubator_mxnet_tpu" not in re.sub(
        r'""".*?"""', "", open(smallthinker_21b.__file__).read(), flags=re.S)
