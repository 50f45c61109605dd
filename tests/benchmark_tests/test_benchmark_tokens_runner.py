"""The token runner (``perfbench/runners/train_tokens.py``) at a tiny size on
the CPU, through a cell that is defined wholly by files under
``tests/benchmark_tests/data_tokens/``: its result line, the numbers
``correct`` is decided from, its counters and the ``counter`` reader.  (A CPU
run is a test of control flow; it never yields a metric of the device.)"""
import json
import os
import time

import pytest

from perfbench import checks, run
from perfbench.readers import counter as counter_reader
from perfbench.runners import train_tokens as tt

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "data_tokens")


@pytest.fixture(scope="module")
def facts():
    """One traced run of the tiny cell: the cell and what its runner
    returned."""
    return run.cell_facts(_DATA, "tiny_afmoe_train", "cpu", 2 ** 31 + 5, 0.3,
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
    assert traced["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1.0
    assert set(plain["compared"]) == {
        "ref_loss0_rel", "ref_loss1_rel", "ref_loss2_rel",
        "route_refused_share", "route_moved_share",
        "grad_worst_attention", "grad_worst_experts", "grad_worst_router",
        "grad_worst_other", "nonfinite_losses",
        "last_chunk_min_loss_over_first", "programs_built_in_window",
        "arrays_off_device", "assignments_dropped"}
    for name, (value, limit) in plain["compared"].items():
        assert value <= limit, name
    assert plain["compared"]["programs_built_in_window"] == [0, 0]
    assert plain["compared"]["assignments_dropped"] == [0, 0]
    assert json.loads(json.dumps(plain)) == plain


def test_counters_follow_the_shapes_and_the_steps_own_routing(facts):
    cell, f = facts
    c, config = f["counters"], cell["config"]
    tokens = 2 * config["seq_len"]
    # two expert layers, two choices a token, none dropped
    assert c["assignments_routed"] == tokens * 2 * 2
    assert 0 < c["assignments_held"] < c["assignments_routed"]
    assert c["assignments_dropped"] == 0
    d, f_ = config["hidden_size"], config["moe_intermediate_size"]
    assert c["expert_flops_per_module"] == \
        3 * 2 * c["assignments_held"] * 3 * d * f_
    # window 8 over 32 positions: 36 + 24 * 8 pairs a head on the two
    # sliding layers, 528 on the full one; 4 heads, 2 sequences, head_dim 8
    pairs = (2 * (36 + 24 * 8) + 528) * 4 * 2
    assert c["attn_flops_per_module"] == 3 * 2 * 2 * pairs * 8
    assert tt.admitted_pairs(8192, 2048) == 14681088
    assert tt.admitted_pairs(8192, None) == 8192 * 8193 // 2
    assert c["flops_per_module_per_chip"] == c["flops_per_sample"] * 2
    assert counter_reader.read({"counter": "moe_load_max_over_mean"}, f) == \
        c["moe_load_max_over_mean"]
    assert counter_reader.read({"counter": "no_such_counter"}, f) is None
    assert counter_reader.read({"counter": "x"}, {}) is None


@pytest.mark.parametrize("metric", [
    "attn_ms.train", "moe_experts_ms.train", "moe_route_ms.train",
    "recompute_ms.train", "head_loss_ms.train", "norm_rope_ms.train"])
def test_scope_metrics_of_the_decoder_cell_name_scopes_the_program_has(
        facts, metric):
    """Each ``scope_op`` file of the decoder cell reads scopes that the
    family's step program really carries (here the tiny preset's, compiled
    for the CPU: the paths are the program's, whatever the backend), and
    names no op that does not exist."""
    import re

    from perfbench import hlo_scope

    spec = json.load(open(os.path.join(run.ROOT, "perfbench",
                                       "layer_metrics", metric + ".json")))
    assert spec["reader"] == "scope_op"
    paths = set(hlo_scope.scopes_from_hlo(
        facts[1]["programs"][0].as_text()).values())
    assert any(re.search(spec["scope"], path) for path in paths), metric
    from incubator_mxnet_tpu.ops import registry

    for stem, ends in re.findall(r"(_contrib_\w*)(?:\(([\w|]+)\))?",
                                 spec["scope"]):
        for end in ends.split("|"):
            assert stem + end in registry.OPS, (metric, stem + end)


def test_weights_are_a_function_of_the_seed_alone(facts):
    import numpy as np

    net = tt.build_net(facts[0]["config"], 2)
    assert all(p._data is None for p, name in tt.short_names(net).items()
               if not name.endswith(("_bias", "_counts")))
    assert [name for name in tt.short_names(net).values()
            if name.endswith("_chosen")] == ["layer1_moe_chosen",
                                             "layer2_moe_chosen"]
    a, b = tt.Weights(net, 7).by_name(), tt.Weights(net, 7).by_name()
    other = tt.Weights(net, 8).by_name()
    for name in a:
        np.testing.assert_array_equal(a[name], b[name])
    assert float(abs(a["head_weight"] - other["head_weight"]).max()) > 0
    assert float(a["layer0_norm1_gamma"].min()) == 1.0
    bound = (6.0 / (32 + 16)) ** 0.5    # an expert's matrix: its own widths
    assert float(abs(a["layer1_moe_w1"]).max()) <= bound
