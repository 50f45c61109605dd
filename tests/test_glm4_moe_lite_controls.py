"""The benchmark's comparison (``perfbench/runners/train_decoder.py``) of the
``glm4_moe_lite`` family's tiny cell with its plain reference: the sound
step passes, and a step broken in each of the four ways of
``perfbench/references/glm47_flash.py::CONTROLS`` fails.  About a minute."""
import os
import time

import pytest

from perfbench.references import glm47_flash as ref
from perfbench.runners import train_decoder as td

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DATA = os.path.join(_ROOT, "tests", "benchmark_tests", "data_decoder")


# ---------------------------------------------------------------------------
# the benchmark's comparison on a step broken on purpose
# ---------------------------------------------------------------------------

_COMPARED = {
    "ref_loss0_rel", "ref_loss1_rel", "ref_loss2_rel", "route_refused_share",
    "route_moved_share", "grad_worst_attention", "grad_worst_experts",
    "grad_worst_router", "grad_worst_mtp", "grad_worst_other"}


@pytest.fixture(scope="module")
def tiny_setup():
    """The tiny cell's set-up (float32 on both sides)."""
    from perfbench import run

    return td.SetUp(run.load_cell(_DATA, "tiny_glm4_train"), 11,
                    time.monotonic())


@pytest.mark.parametrize("broken", [None] + sorted(ref.CONTROLS))
def test_a_broken_step_fails_the_comparison_with_the_reference(tiny_setup,
                                                               broken):
    step, _, losses, applied, chosen = tiny_setup.first_steps(broken)
    del step
    tiny_setup.release()
    compared, problems = tiny_setup.reference(losses, applied, chosen)
    over = [k for k, (value, limit) in compared.items() if not value <= limit]
    assert bool(problems) == bool(over), compared
    # every number that was compared stands beside its limit
    assert set(compared) == _COMPARED
    assert bool(over) == (broken is not None), compared
    if broken is None:
        assert compared["route_moved_share"][0] == 0.0
        # the module's router is balanced with the others
        assert sorted(tiny_setup.weights.fixed) == [
            "layer1_moe_bias", "layer2_moe_bias", "layer3_moe_bias"]
    if broken == "expert":
        # the expert that was left out shows in ITS matrices
        assert compared["grad_worst_experts"][0] > 0.99
    if broken == "mtp_shift":
        # the main path's loss term stands; the module's does not
        assert "ref_loss0_rel" in over and "grad_worst_mtp" in over


def test_the_families_controls_are_the_four_the_cell_must_fail():
    assert sorted(ref.CONTROLS) == ["expert", "float8", "mtp_shift", "rope"]
    from incubator_mxnet_tpu.ops import registry

    for target, replace in ref.CONTROLS.values():
        assert target in registry.OPS and callable(replace(lambda *a: a))
    assert [g for g, _ in ref.GRAD_GROUPS] == [
        "attention", "experts", "router", "mtp", "other"]
