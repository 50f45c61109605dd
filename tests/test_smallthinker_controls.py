"""The benchmark's comparison (``perfbench/runners/train_decoder.py``) of the
``smallthinker`` family's tiny cell with its plain reference: the sound step
passes, a step broken in each of the seven ways of
``perfbench/references/smallthinker_21b.py::CONTROLS`` fails, and so does a
step whose router reads the normed state after the attention, where the
experts read, in place of the block's input.  About a minute."""
import os
import time

import pytest

from incubator_mxnet_tpu.gluon.model_zoo.text import smallthinker
from perfbench.references import smallthinker_21b as ref
from perfbench.runners import train_decoder as td

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DATA = os.path.join(_ROOT, "tests", "benchmark_tests", "data_smallthinker")

_COMPARED = {
    "ref_loss0_rel", "ref_loss1_rel", "ref_loss2_rel", "route_refused_share",
    "route_moved_share", "grad_worst_attention", "grad_worst_experts",
    "grad_worst_router", "grad_worst_other"}


@pytest.fixture(scope="module")
def tiny_setup():
    """The tiny cell's set-up (float32 on both sides)."""
    from perfbench import run

    return td.SetUp(run.load_cell(_DATA, "tiny_smallthinker_train"), 11,
                    time.monotonic())


def _over(setup, broken=None):
    """The names of what the comparison finds over its limit for a step
    built while ``control(broken)`` holds, and everything compared."""
    step, _, losses, applied, chosen = setup.first_steps(broken)
    del step
    setup.release()
    compared, problems = setup.reference(losses, applied, chosen)
    over = [k for k, (value, limit) in compared.items() if not value <= limit]
    assert bool(problems) == bool(over), compared
    # every number that was compared stands beside its limit
    assert set(compared) == _COMPARED
    return over, compared


@pytest.mark.parametrize("broken", [None] + sorted(ref.CONTROLS))
def test_a_broken_step_fails_the_comparison_with_the_reference(tiny_setup,
                                                               broken):
    over, compared = _over(tiny_setup, broken)
    assert bool(over) == (broken is not None), compared
    if broken is None:
        # a token on the edge between two experts may fall either way
        assert compared["route_moved_share"][0] <= 0.02
        # one selection bias a router
        assert sorted(tiny_setup.weights.fixed) == [
            "layer0_moe_bias", "layer1_moe_bias", "layer2_moe_bias"]
    if broken == "expert":
        # the expert that was left out shows in ITS matrices
        assert compared["grad_worst_experts"][0] > 0.99
    if broken in ("gate", "score"):
        assert "grad_worst_experts" in over
    if broken == "centre":
        # the choice is another; nothing else is
        assert "route_moved_share" in over


def test_a_router_that_reads_the_experts_input_fails_the_comparison(
        tiny_setup, monkeypatch):
    """The router's placement is no op's attribute, so it is broken in the
    block: ``r = N2(a) Wr^T``, after the attention, as in the other two
    families."""
    def after_attention(self, F, x):  # noqa: N803
        a = x + self.attn(self.norm1(x))
        return a + self.ffn(self.norm2(a))

    monkeypatch.setattr(smallthinker.SmallThinkerLayer, "hybrid_forward",
                        after_attention)
    over, compared = _over(tiny_setup)
    assert compared["route_moved_share"][0] > 0.1, compared
    assert {"grad_worst_router", "grad_worst_experts"} <= set(over), compared


def test_the_families_controls_are_the_seven_the_cell_must_fail():
    assert sorted(ref.CONTROLS) == ["centre", "expert", "float8", "gate",
                                    "rope", "score", "window"]
    from incubator_mxnet_tpu.ops import registry

    for target, replace in ref.CONTROLS.values():
        assert target in registry.OPS and callable(replace(lambda *a: a))
    assert [g for g, _ in ref.GRAD_GROUPS] == [
        "attention", "experts", "router", "other"]
