"""The train runner at a tiny size on the CPU, through cells that are defined
wholly by files under ``tests/benchmark_tests/data/`` (so adding a cell, a
configuration or a layer metric edits nothing under ``perfbench/``); a cell
of token ids through a runner that the test installs; the
result line's keys; and ``run.py`` as the driver runs it, which must FAIL
here: this machine has no accelerator."""
import json
import os
import subprocess
import sys
import time

import pytest

from perfbench import checks, run

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def counter():
    return checks.CompileCounter()


@pytest.fixture(scope="module")
def lines(counter):
    """One plain and one traced run of the tiny one-chip cell."""
    return {traced: run.run_cell(_DATA, "tiny_train", "cpu", 2 ** 31 + 77,
                                 0.5, traced, time.monotonic(), counter)
            for traced in (False, True)}


def test_plain_run_reports_every_end_to_end_metric_of_the_cell(lines):
    line = lines[False]
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    # every number correct was decided from, beside its limit
    assert set(line["compared"]) == {
        "ref_loss0_rel", "ref_loss1_rel", "nonfinite_losses",
        "last_chunk_min_loss_over_first", "programs_built_in_window",
        "arrays_off_device"}
    assert line["compared"]["ref_loss0_rel"][0] <= \
        line["compared"]["ref_loss0_rel"][1] == 0.1
    assert line["compared"]["last_chunk_min_loss_over_first"][1] == "inf"
    assert line["compared"]["programs_built_in_window"] == [0, 0]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2 and line["attempted"] % 2 == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["metrics"]["train_samples_per_s"]["unit"] == "samples/s"
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": line["device"]["count"],
                              "memory_peak_bytes":
                                  line["device"]["memory_peak_bytes"]}
    assert line["device"]["memory_peak_bytes"] > 0
    assert json.loads(json.dumps(line)) == line


def test_traced_run_reports_layer_metrics_and_no_device_number_on_a_cpu(lines):
    line = lines[True]
    # what is read from set-up is there; what only a device trace gives is
    # left out, because the CPU's trace has no /device:TPU plane
    assert set(line["metrics"]) == {"trace_s.train", "compile_s.train",
                                    "reference_check_s.train"}
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["correct"] is True and list(line)[-1] == "compared"


def test_mesh_cell_runs_on_four_virtual_devices(counter):
    line = run.run_cell(_DATA, "tiny_train_dp4", "cpu", 3, 0.2, False,
                        time.monotonic(), counter)
    assert line["correct"] is True and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
def test_a_cell_of_token_ids_runs_through_a_runner_that_is_not_train(
        traced, toy_tokens_runner, counter):
    line = run.run_cell(_DATA, "tiny_tokens", "cpu", 2 ** 31 + 11, 0.2,
                        traced, time.monotonic(), counter)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == (
        {"trace_s.train", "compile_s.train", "reference_check_s.train"}
        if traced else {"train_samples_per_s", "setup_s"})
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] > 0
    assert json.loads(json.dumps(line)) == line


def test_a_runner_that_is_no_module_of_runners_is_an_import_error():
    # without the fixture the toy is not there: a mix can only name a file
    # of perfbench/runners/
    with pytest.raises(ModuleNotFoundError, match="toy_tokens"):
        run.run_cell(_DATA, "tiny_tokens", "cpu", 1, 0.1, False,
                     time.monotonic())


def test_a_cell_that_asks_for_more_chips_than_there_are_is_refused():
    with pytest.raises(checks.NoChip):
        checks.require_devices("cpu", 1000)
    with pytest.raises(checks.NoChip):
        checks.require_devices("tpu", 1)


def test_result_line_leaves_out_a_metric_its_reader_cannot_read():
    cell = run.load_cell(_DATA, "tiny_train")

    class Dev:
        platform, device_kind = "cpu", "cpu"

    facts = {"problems": ["x"], "attempted": 4, "failed": 1, "setup_s": 2.0,
             "end_to_end": {"train_samples_per_s": 10.0},
             "setup_parts": {"trace_s": 0.5}, "counters": {}, "trace": None,
             "devices": [Dev()], "device_count": 1,
             "memory": {"allocator": 123, "compiler": 45}}
    line = run.result_line(cell, facts, traced=True)
    assert line["correct"] is False and line["failed"] == 1
    assert line["metrics"] == {"trace_s.train": {"value": 0.5, "unit": "s"}}
    assert line["compared"] == {}
    facts["compared"] = {"x": [float("nan"), 0.1]}
    assert run.result_line(cell, facts, traced=True)["compared"] == {
        "x": ["nan", 0.1]}
    assert line["device"]["memory_peak_bytes"] == 123
    plain = run.result_line(cell, facts, traced=False)
    assert plain["metrics"]["setup_s"] == {"value": 2.0, "unit": "s"}


@pytest.mark.parametrize("losses,first,last,fall,problem", [
    ([3.0, 2.0, 1.0], 3.0, [2.0, 1.0], True, None),
    ([3.0, 3.5, 3.2], 3.0, [3.5, 3.2], True, "did not fall"),
    ([3.0, 3.5, 3.2], 3.0, [3.5, 3.2], False, None),
    ([3.0, float("nan"), 1.0], 3.0, [1.0], True, "not finite"),
    ([3.0, float("inf")], 3.0, [float("inf")], False, "not finite"),
])
def test_losses_problem(losses, first, last, fall, problem):
    got = checks.losses_problem(losses, first, last, fall)
    assert (got is None) if problem is None else (problem in got)


def test_compile_counter_sees_a_program_built_while_armed(counter):
    import jax
    import jax.numpy as jnp

    with counter:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert counter.count >= 1
    built = counter.count
    jax.jit(lambda x: x * 5 + 2)(jnp.arange(7.0)).block_until_ready()
    assert counter.count == built  # disarmed: not counted


def test_weights_come_from_the_seed_and_keep_the_initialisers_distribution():
    import numpy as np

    from perfbench.runners import train

    config = run.load_cell(_DATA, "tiny_train")["config"]
    net = train.build_net(config)
    params = list(net.collect_params().values())
    drawn = [np.asarray(p.data()._data) for p in params]

    def weights_of(seed):
        for p, d in zip(params, drawn):
            p.set_data(d)
        train.Weights(net, seed).restore()
        return [np.asarray(p.data()._data) for p in params]

    a, again, b = (weights_of(s) for s in (2 ** 31 + 9, 2 ** 31 + 9, 10))
    assert all(np.array_equal(x, y) for x, y in zip(a, again))
    for d, x, y in zip(drawn, a, b):
        assert np.array_equal(np.abs(x), np.abs(d))       # signs only
        if d.ndim >= 2:
            assert 0.4 < np.mean(x != y) < 0.6            # another draw
            assert abs(np.mean(np.sign(x))) < 0.1
        else:
            assert np.array_equal(x, d)


def test_memory_peaks_keeps_the_allocator_and_the_compiler_apart():
    class Dev:
        def __init__(self, peak):
            self._peak = peak

        def memory_stats(self):
            return None if self._peak is None else {
                "peak_bytes_in_use": self._peak}

    class Program:
        def __init__(self, arg, out, temp, alias):
            self._m = type("M", (), dict(
                argument_size_in_bytes=arg, output_size_in_bytes=out,
                temp_size_in_bytes=temp, alias_size_in_bytes=alias))

        def memory_analysis(self):
            return self._m

    got = checks.memory_peaks([Dev(7), Dev(None), Dev(11)],
                              [Program(5, 3, 100, 3), Program(1, 1, 1, 0)])
    assert got == {"allocator": 11, "compiler": 105}
    assert checks.memory_peaks([Dev(None)]) == {"allocator": 0, "compiler": 0}


def test_state_off_the_device_is_noticed():
    import jax.numpy as jnp

    here = [jnp.ones(3), jnp.zeros((2, 2))]
    assert checks.off_device(here, "cpu") == []
    assert len(checks.off_device(here, "tpu")) == 2


def test_main_without_a_tpu_exits_non_zero_and_prints_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "perfbench", "run.py"),
         "--workload", "r50_train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, cwd=_ROOT, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "needs 1 tpu device" in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
