"""The decoder runner (``perfbench/runners/train_decoder.py``) over the
``kimi_linear`` family at a tiny size on the CPU, through a cell that is
defined wholly by files under ``tests/benchmark_tests/data_kimi/``: its
result lines, the numbers ``correct`` is decided from, the counters of the
family's reference module, and every per-layer metric file the benchmark's
cell ``kimi_linear_train_16k`` is listed under against the family's step
program, with the three KDA files that are not registered in
``BENCHMARK.json`` (``_NEW``: the setup reader's test holds the last ten
entries of ``per_layer``, so they wait for a benchmark change).  (A CPU run is a test of control flow; it never yields a metric of
the device.)"""
import json
import os
import re
import time

import pytest

from perfbench import checks, hlo_scope, run

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "data_kimi")
_BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
_CELL = "kimi_linear_train_16k"
_METRICS = [m["name"] for m in _BENCH["per_layer"]
            if _CELL in m.get("workloads", ())]
_NEW = ["kda_ms.train", "kda_roofline.train", "kda_gates_ms.train"]


@pytest.fixture(scope="module")
def facts():
    """One traced run of the tiny cell: the cell and what its runner
    returned."""
    return run.cell_facts(_DATA, "tiny_kimi_train", "cpu", 2 ** 31 + 11,
                          0.3, True, time.monotonic(),
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
    assert plain["correct"] is True and plain["failed"] == 0
    assert plain["attempted"] >= 2 and plain["attempted"] % 2 == 0
    assert set(plain["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(traced["metrics"]) == {"moe_load_max_over_mean.train",
                                      "reference_check_s.train"}
    assert list(plain["compared"]) == [
        "ref_loss0_rel", "ref_loss1_rel", "ref_loss2_rel",
        "route_refused_share", "route_moved_share", "grad_worst_kda",
        "grad_worst_attention", "grad_worst_experts", "grad_worst_router",
        "grad_worst_other", "nonfinite_losses",
        "last_chunk_min_loss_over_first", "programs_built_in_window",
        "arrays_off_device", "assignments_dropped"]
    for name, (value, limit) in plain["compared"].items():
        assert value <= limit, name


def test_counters_follow_the_shapes_and_the_steps_own_routing(facts):
    cell, f = facts
    c, config = f["counters"], cell["config"]
    tokens = 2 * config["seq_len"]
    # two expert layers (the first is dense), two choices a token
    assert c["assignments_routed"] == tokens * 2 * 2
    assert 0 < c["assignments_held"] < c["assignments_routed"]
    assert c["assignments_dropped"] == 0
    # two KDA layers of 2 heads of 16: 3 x 16 x 16 a token and head forward
    assert c["kda_flops_per_module"] == 3 * 2 * tokens * 2 * 3 * 16 * 16 * 2
    # q, k, v and o in bf16 and g in float32 a channel, beta a head, each
    # way
    assert c["kda_bytes_per_module"] == \
        2 * 2 * tokens * (2 * 16 * 12 + 2 * 4)
    # one latent attention layer: 96 positions, 4656 pairs a head under a
    # causal mask, 4 heads, q k^T over 8 + 8 columns and p v over 8
    assert c["attn_flops_per_module"] == 3 * 2 * 4656 * 4 * 2 * (16 + 8)
    proj = 32 * 4 * 16 + 32 * (16 + 8) + 16 * 4 * (8 + 8) + 4 * 8 * 32
    assert c["mla_proj_flops_per_module"] == 3 * 2 * proj * tokens
    assert c["flops_per_module_per_chip"] == c["flops_per_sample"] * 2


def test_the_published_cells_counters_are_the_configurations_arithmetic():
    """At the published widths, from the configuration's file alone: the
    recurrence's 3 x 128 x 128 a token and head over four KDA layers, the
    latent attention's pairs at 16,384 tokens, the experts from a router
    that sends every held expert its even share."""
    import numpy as np

    from perfbench.references import kimi_linear as ref

    cell = run.load_cell(run.ROOT, _CELL)
    config = cell["config"]
    assert cell["traffic"]["runner"] == "train_decoder"
    assert cell["traffic"]["chunk_steps"] == 4
    assert cell["chips"] == 1 and config["seq_len"] == 16384
    loads = [("layer%d_moe_counts" % i, np.full(256, 512.0))
             for i in range(1, 5)]
    c = ref.counters(config, loads, 1)
    assert c["kda_flops_per_module"] == 6 * 16384 * 32 * 3 * 128 * 128 * 4
    assert abs(c["kda_flops_per_module"] - 6.18e11) < 0.01e11
    assert c["kda_bytes_per_module"] == 2 * 4 * 16384 * (
        4096 * 12 + 32 * 4)
    pairs = 16384 * 16385 // 2
    assert c["attn_flops_per_module"] == 6 * pairs * 32 * (192 + 128)
    assert c["mla_proj_flops_per_module"] == 6 * 29114368 * 16384
    assert c["assignments_held"] == 4 * 8 * 512
    assert c["expert_flops_per_module"] == 6 * 4 * 4096 * 3 * 2304 * 1024
    assert config["fwd_macs_per_sample"] == 328515584 * 16384
    assert c["flops_per_sample"] == 6 * (
        config["fwd_macs_per_sample"] + 16384 * 32 * 3 * 128 * 128 * 4
        + pairs * 32 * 320 + 4 * 4096 * 3 * 2304 * 1024)
    assert abs(c["flops_per_sample"] - 4.1e13) < 0.1e13
    assert c["assignments_dropped"] == 0 and c["moe_load_max_over_mean"] == 1


@pytest.mark.parametrize("metric", _METRICS + [m for m in _NEW
                                                 if m not in _METRICS])
def test_a_metric_of_the_cell_reads_what_the_families_step_carries(facts,
                                                                   metric):
    """Each per-layer file the cell is listed under, and each KDA file: a
    scope it names is a scope the family's step program really carries
    (here the tiny preset's, compiled for the CPU: the paths are the
    program's, whatever the backend), an op it names is one the registry
    has, a counter or a part of set-up it reads is one the runner
    returns."""
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


def test_the_three_new_metrics_tell_the_recurrence_from_its_gates(facts):
    assert len(set(_METRICS) - set(_NEW)) == 23
    for m in _BENCH["per_layer"]:
        if m["name"] in _NEW:
            assert m["workloads"] == [_CELL]
            assert m["moves"] == "train_samples_per_s"
    paths = _paths(facts)
    kda = {p for p in paths if re.search(_spec("kda_ms.train")["scope"], p)}
    gates = {p for p in paths
             if re.search(_spec("kda_gates_ms.train")["scope"], p)}
    assert _spec("kda_roofline.train")["scope"] == \
        _spec("kda_ms.train")["scope"]
    # the recurrence's own op, forward and backward, in both KDA layers
    assert kda and not kda & gates
    for part in (r"KimiLinearLayer\.\w+_layer0/KimiDeltaAttention\.\w+/"
                 r"op\._contrib_kda\b", r"_layer2/", r"transpose\(jvp"):
        assert any(re.search(part, p) for p in kda), part
    # the convolutions, the norms, the gates and the gated norm
    for op in ("conv", "qk_norm", "gate", "out_norm"):
        assert any(re.search(r"op\._contrib_kda_%s\b" % op, p)
                   for p in gates), op
    # latent attention's projections are not the recurrence's
    mla = {p for p in paths
           if re.search(_spec("mla_proj_ms.train")["scope"], p)}
    assert mla and not mla & (kda | gates)


def test_the_family_brings_a_reference_module_and_no_runner():
    from perfbench.references import kimi_linear

    for name in ("model_cfg", "counters", "GRAD_GROUPS", "CONTROLS", "Blocks",
                 "balance", "gradients", "apply", "step"):
        assert hasattr(kimi_linear, name), name
    assert not [f for f in os.listdir(os.path.join(run.ROOT, "perfbench",
                                                    "runners"))
                if "kimi" in f]
    # the reference imports nothing of the system it is compared with
    assert "incubator_mxnet_tpu" not in re.sub(
        r'""".*?"""', "", open(kimi_linear.__file__).read(), flags=re.S)
