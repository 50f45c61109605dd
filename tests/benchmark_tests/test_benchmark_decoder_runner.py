"""The decoder runner (``perfbench/runners/train_decoder.py``) at a tiny size
on the CPU, through a cell that is defined wholly by files under
``tests/benchmark_tests/data_decoder/``: its result lines, the numbers
``correct`` is decided from, the counters of the family's reference module,
and every per-layer metric file the benchmark's cell
``glm47_flash_train_8k`` is listed under against the family's step program.
(A CPU run is a test of control flow; it never yields a metric of the
device.)"""
import json
import os
import re
import time

import pytest

from perfbench import checks, hlo_scope, run
from perfbench.readers import counter as counter_reader
from perfbench.runners import train_decoder as td

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "data_decoder")
_BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
_CELL = "glm47_flash_train_8k"
_METRICS = [m["name"] for m in _BENCH["per_layer"]
            if _CELL in m.get("workloads", ())]
_NEW = ["mla_proj_ms.train", "mla_proj_roofline.train", "mtp_ms.train"]


@pytest.fixture(scope="module")
def facts():
    """One traced run of the tiny cell: the cell and what its runner
    returned."""
    return run.cell_facts(_DATA, "tiny_glm4_train", "cpu", 2 ** 31 + 5, 0.3,
                          True, time.monotonic(), checks.CompileCounter())


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
        "grad_worst_mtp", "grad_worst_other", "nonfinite_losses",
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
    # two expert layers and the module's, two choices a token, none dropped
    assert c["assignments_routed"] == tokens * 2 * 3
    assert 0 < c["assignments_held"] < c["assignments_routed"]
    assert c["assignments_dropped"] == 0
    d, f_ = config["hidden_size"], config["moe_intermediate_size"]
    assert c["expert_flops_per_module"] == \
        3 * 2 * c["assignments_held"] * 3 * d * f_
    # 32 positions: 528 pairs a head under a causal mask; 4 heads, 2
    # sequences, three blocks and the module's; q k^T over 8 + 8 columns and
    # p v over 16
    pairs = 528 * 4 * 2 * 4
    assert c["attn_flops_per_module"] == 3 * 2 * pairs * (16 + 16)
    # the five projections of a latent attention, a token
    proj = 32 * 24 + 24 * 4 * 16 + 32 * (16 + 8) + 16 * 4 * (8 + 16) \
        + 4 * 16 * 32
    assert c["mla_proj_flops_per_module"] == 3 * 2 * proj * tokens * 4
    assert c["flops_per_module_per_chip"] == c["flops_per_sample"] * 2
    assert counter_reader.read({"counter": "moe_load_max_over_mean"}, f) == \
        c["moe_load_max_over_mean"]


def test_the_published_cells_counters_are_the_issues_arithmetic():
    """At the published widths, from the configuration's file alone: the
    attention's operations at head size 256 over six blocks, the latent
    projections' 21.76 M a token, the dense products 329.0 M a token."""
    import numpy as np

    from perfbench.references import glm47_flash as ref

    cell = run.load_cell(run.ROOT, _CELL)
    config = cell["config"]
    assert cell["traffic"]["runner"] == "train_decoder"
    assert cell["traffic"]["chunk_steps"] == 4
    loads = [("layer%d_moe_counts" % i, np.full(64, 512.0))
             for i in range(1, 6)]
    c = ref.counters(config, loads, 1)
    pairs = 8192 * 8193 // 2
    assert c["attn_flops_per_module"] == 3 * 2 * 2 * pairs * 256 * 20 * 6
    assert c["mla_proj_flops_per_module"] == 3 * 2 * 21757952 * 8192 * 6
    assert abs(c["mla_proj_flops_per_module"] - 6.4e12) < 0.05e12
    assert c["assignments_held"] == 5 * 8 * 512
    assert c["expert_flops_per_module"] == 6 * 5 * 4096 * 3 * 2048 * 1536
    assert config["fwd_macs_per_sample"] == 328990720 * 8192
    assert c["flops_per_sample"] == 6 * (
        config["fwd_macs_per_sample"] + 2 * pairs * 256 * 20 * 6
        + 5 * 4096 * 3 * 2048 * 1536)
    assert c["assignments_dropped"] == 0


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
    spec = json.load(open(os.path.join(run.ROOT, "perfbench",
                                       "layer_metrics", metric + ".json")))
    paths = set(hlo_scope.scopes_from_hlo(
        f["programs"][0].as_text()).values()) | {hlo_scope.UNSCOPED}
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


def test_the_three_new_metrics_tell_their_parts_of_the_program_apart(facts):
    assert set(_NEW) <= set(_METRICS) and len(_METRICS) == 21 + 3
    paths = set(hlo_scope.scopes_from_hlo(
        facts[1]["programs"][0].as_text()).values())
    spec = {m: json.load(open(os.path.join(
        run.ROOT, "perfbench", "layer_metrics", m + ".json"))) for m in _NEW}
    mla = {p for p in paths if re.search(spec["mla_proj_ms.train"]["scope"],
                                         p)}
    assert spec["mla_proj_roofline.train"]["scope"] == \
        spec["mla_proj_ms.train"]["scope"]
    # everything a LatentAttention builds but the flash op: projections,
    # latent norms, rotary, the shared key's broadcast; forward, recomputed
    # and backward
    assert mla and not [p for p in mla if "_contrib_flash_attention" in p]
    for part in (r"Dense\.\w+_attn_kv_b/", r"RMSNorm\.\w+_attn_q_a_norm/",
                 r"op\._contrib_rotary", r"op\.broadcast_axis",
                 r"rematted_computation", r"transpose\(jvp"):
        assert any(re.search(part, p) for p in mla), part
    mtp = {p for p in paths if re.search(spec["mtp_ms.train"]["scope"], p)}
    # the module's projection, block, own pass through the head, loss term
    for part in (r"MTPModule\.\w+_layer3/Dense\.\w+_eh_proj/",
                 r"MTPModule\.\w+/Glm4MoeLiteLayer\.\w+_layer3/",
                 r"MTPModule\.\w+_layer3/Dense\.\w+_head/",
                 r"SoftmaxCrossEntropyLoss\.\w+_next2/"):
        assert any(re.search(part, p) for p in mtp), part
    # the main path's head and loss term are not the module's
    assert not [p for p in mtp if re.search(
        r"SoftmaxCrossEntropyLoss\.\w+_next/", p)]
    assert any(re.search(r"Glm4MoeLiteDecoder\.\w+/Dense\.\w+_head/", p)
               for p in paths - mtp)


def test_the_runner_takes_what_is_the_familys_from_its_reference_module():
    from perfbench.references import glm47_flash, trinity_mini

    for name in ("model_cfg", "counters", "GRAD_GROUPS", "CONTROLS", "Blocks",
                 "balance", "gradients", "apply", "step"):
        assert hasattr(glm47_flash, name), name
    assert td.CONFIG_KEYS == ("seq_len", "vocab_rows", "experts_held",
                              "num_layers", "loss")
    # the reference imports nothing of the system it is compared with
    for module in (glm47_flash, trinity_mini):
        assert "incubator_mxnet_tpu" not in re.sub(
            r'""".*?"""', "", open(module.__file__).read(), flags=re.S)
