"""Each control of the ``kimi_linear`` reference module
(``perfbench/references/kimi_linear.py::CONTROLS``) through the decoder
runner's own comparison (``perfbench/runners/train_decoder.py``: the step
built while the control holds, its first losses and applied gradient
against the plain reference, the configuration's limits), at the tiny cell
of ``tests/benchmark_tests/data_kimi/`` on the CPU: every one comes out not
correct, and by the group of the gradient that holds what it breaks (each
breaks the KDA layer: the ``kda`` group).  The limits there are float32's
against float32; the chip's readings against the cell's bf16 limits are in
PERF.md section 4."""
import os
import time

import pytest

from perfbench import run
from perfbench.references import kimi_linear as ref
from perfbench.runners import train_decoder as td

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "data_kimi")


@pytest.fixture(scope="module")
def setup():
    """The tiny cell's set-up, once: its batch, net, seeded weights (the
    decay's drawn by the reference's ``balance``) and reference."""
    return td.SetUp(run.load_cell(_DATA, "tiny_kimi_train"), 2 ** 31 + 11,
                    time.monotonic())


@pytest.mark.parametrize("name", sorted(ref.CONTROLS))
def test_each_control_comes_out_not_correct_through_the_comparison(setup,
                                                                   name):
    step, _, losses, applied, chosen = setup.first_steps(name)
    del step
    setup.release()
    compared, problems = setup.reference(losses, applied, chosen)
    value, limit = compared["grad_worst_kda"]
    assert problems and value > limit, (name, compared)
