"""The ``setup_span`` reader and the ten metric files that use it
(``perfbench/layer_metrics/{import_s,param_init_s,step_build_s,lower_s,lint_s,
setup_programs,setup_cache_misses,setup_python_s,setup_load_s,
setup_unaccounted_s}.train.json``): on a hand-made timeline, where every
number can be checked by hand, and through ``run_cell`` on the tiny CPU cells
of ``tests/benchmark_tests/data/`` with the ten registered beside them (a
copy under ``tmp_path``: the test data itself is not edited)."""
import json
import math
import os
import shutil
import sys
import time

import pytest

from incubator_mxnet_tpu import profiler
from perfbench import checks, layer_metrics, run
from perfbench.readers import setup_span

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))
_METRICS = os.path.join(_ROOT, "perfbench", "layer_metrics")

TEN = ["import_s.train", "param_init_s.train", "step_build_s.train",
       "lower_s.train", "lint_s.train", "setup_programs.train",
       "setup_cache_misses.train", "setup_python_s.train",
       "setup_load_s.train", "setup_unaccounted_s.train"]
NEED_NO_CUT = ["import_s.train"]


def _spec(name):
    with open(os.path.join(_METRICS, name + ".json")) as f:
        return json.load(f)


def _entries():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, [m for m in bench["per_layer"] if m["name"] in TEN]


def test_the_ten_are_registered_last_for_every_cell_and_move_setup_s():
    bench, entries = _entries()
    assert [m["name"] for m in bench["per_layer"][-10:]] == TEN
    layers = {"runtime start-up", "parameters", "step builder",
              "trace-time analysis"}
    for m in entries:
        # no list of cells: they read the process's own timeline whatever
        # the cell, so every cell that reports setup_s reports them
        assert m["moves"] == "setup_s" and "workloads" not in m
        for w in bench["workloads"]:
            assert m["name"] in [c["name"] for c in run.load_cell(
                _ROOT, w["name"])["per_layer"]]
        assert m["source"] in ("program_span", "program_counter")
        assert m["layer"] in layers and m["better"] == "lower"
        spec = _spec(m["name"])
        assert spec["reader"] == "setup_span" and spec["what"]
        assert sum(k in spec for k in ("spans", "programs",
                                       "unaccounted")) == 1
    assert {m["layer"] for m in entries} == layers


# ---------------------------------------------------------------------------
# a hand-made timeline: T_START at 100 s, the window opens 50 s later
# ---------------------------------------------------------------------------

def _span(name, start_s, end_s, span_id, parent=None, **args):
    return {"ph": "X", "name": name, "cat": "setup", "pid": 0, "tid": 1,
            "ts": profiler.clock_us(100.0 + start_s),
            "dur": (end_s - start_s) * 1e6, "id": span_id, "parent": parent,
            "args": args}


def _program(name, trace, lower, compile_, cache, span_id, parent=None):
    """``trace``, ``lower``, ``compile_``: ``(start_s, end_s)`` or None."""
    args = {"cache": cache, "compile_s": compile_[1] - compile_[0],
            "trace_s": 0.0, "lower_s": 0.0}
    for key, part in (("trace", trace), ("lower", lower)):
        if part:
            args[key + "_s"] = part[1] - part[0]
            args[key + "_ts"] = profiler.clock_us(100.0 + part[0])
    return {"ph": "X", "name": name, "cat": "setup.program", "pid": 0,
            "tid": 1, "ts": profiler.clock_us(100.0 + compile_[0]),
            "dur": (compile_[1] - compile_[0]) * 1e6, "id": span_id,
            "parent": parent, "args": args}


RECORDS = [
    _span("mx.import", 12.0, 14.0, 1, jax_was_imported=True),
    _span("mx.import.jax", 12.0, 12.0, 2, parent=1),
    # the reference check's net and step: 1 s of parameters, a step with
    # lint off that its first call builds
    _span("mx.block.shape_init", 15.0, 16.0, 3),
    _span("mx.params.shape_only", 15.0, 15.5, 22, parent=3),
    _span("mx.params.materialize", 15.5, 16.0, 4, parent=3),
    _program("jit(make)", (15.5, 15.6), (15.6, 15.7), (15.7, 16.0), "hit",
             5, parent=4),
    _span("mx.step.build", 20.0, 21.0, 6),
    _program("jit(step)", (21.0, 24.0), (24.0, 25.0), (25.0, 26.0), "hit", 7),
    # an eager one-op program under no span, compiled anew
    _program("jit(add)", None, None, (27.0, 27.5), "miss", 8),
    # the timed step: build 2 s, place 1 + 0.5 s, trace 6 s with 0.25 s of
    # lint in it, lower 1.5 s, load 3 s
    _span("mx.params.materialize", 29.0, 30.0, 9),
    _span("mx.step.build", 30.0, 32.0, 10),
    _span("mx.step.place", 32.0, 33.0, 11, what="state"),
    _span("mx.step.place", 33.0, 33.5, 12, what="batch"),
    _span("mx.step.trace", 34.0, 40.0, 13),
    _span("mx.step.lint", 39.5, 39.75, 14, parent=13, findings=0),
    _span("mx.step.lower", 40.0, 41.5, 15),
    _span("mx.step.compile", 41.5, 44.5, 16, cache="hit"),
    _program("jit(step)", (34.0, 39.5), (40.0, 41.5), (41.5, 44.5), "hit",
             17, parent=16),
    # after the cut: the reference's steps in the decoder cells
    _span("mx.step.build", 61.0, 63.0, 18),
    _span("mx.step.lower", 63.0, 64.0, 19),
    _span("mx.block.shape_init", 60.0, 61.0, 20),
    _program("jit(step)", (63.0, 63.5), (63.5, 64.0), (64.0, 70.0), "miss",
             21),
]

WANT = {
    "import_s.train": 2.0,
    "param_init_s.train": 2.0,          # 15-16 (nested, once) and 29-30
    "step_build_s.train": 3.5,          # 2 + 1 + 0.5, the LAST build's
    "lower_s.train": 1.5,
    "lint_s.train": 0.25,
    "setup_programs.train": 4,
    "setup_cache_misses.train": 1,
    "setup_python_s.train": 0.1 + 0.1 + 3.0 + 1.0 + 5.5 + 1.5,
    "setup_load_s.train": 0.3 + 1.0 + 0.5 + 3.0,
    # covered: 12-14, 15-16, 20-26, 27-27.5, 29-33.5, 34-44.5 = 24.5 of 50
    "setup_unaccounted_s.train": 25.5,
}


@pytest.fixture
def hand_made(monkeypatch):
    monkeypatch.setattr(profiler, "setup_records",
                        lambda reset=False: [dict(r) for r in RECORDS])
    monkeypatch.setattr(sys.modules["__main__"], "T_START", 100.0,
                        raising=False)
    return {"setup_s": 50.0}


@pytest.mark.parametrize("name", TEN)
def test_each_metric_reads_what_ended_before_the_window(hand_made, name):
    assert layer_metrics.read(_spec(name), hand_made) == \
        pytest.approx(WANT[name])


def test_a_record_stamped_after_the_cut_is_left_out(hand_made):
    early = dict(hand_made, setup_s=35.0)   # the cut inside the timed trace
    late = dict(hand_made, setup_s=80.0)    # the cut after everything
    read = lambda name, facts: layer_metrics.read(_spec(name), dict(facts))
    assert read("setup_programs.train", late) == 5
    assert read("setup_cache_misses.train", late) == 2
    assert read("param_init_s.train", late) == pytest.approx(3.0)
    # with the cut after it, the last build is the reference's after the
    # window: that is why the cut is asked for
    assert read("step_build_s.train", late) == pytest.approx(2.0)
    assert read("lower_s.train", late) == pytest.approx(1.0)
    # a span that has not ended at the cut is no part of the timed step yet
    assert read("setup_programs.train", early) == 3
    assert read("lower_s.train", early) == 0.0
    assert read("step_build_s.train", early) == pytest.approx(3.5)
    # 12-14, 15-16, 20-26, 27-27.5, 29-33.5 = 14 of 35 (the open trace span
    # is not counted: it ends after the cut)
    assert read("setup_unaccounted_s.train", early) == pytest.approx(21.0)


def test_without_t_start_only_what_needs_no_cut_is_read(hand_made,
                                                        monkeypatch):
    monkeypatch.delattr(sys.modules["__main__"], "T_START")
    for name in TEN:
        value = layer_metrics.read(_spec(name), dict(hand_made))
        assert (value is not None) == (name in NEED_NO_CUT), name


def test_a_program_without_the_timeline_reads_none_and_raises_nothing(
        hand_made, monkeypatch):
    monkeypatch.delattr(profiler, "setup_records")
    for name in TEN:
        assert layer_metrics.read(_spec(name), dict(hand_made)) is None


def test_a_cell_with_no_step_built_reads_zero_seconds_of_one(
        hand_made, monkeypatch):
    monkeypatch.setattr(profiler, "setup_records",
                        lambda reset=False: [dict(r) for r in RECORDS[:2]])
    read = lambda name: layer_metrics.read(_spec(name), dict(hand_made))
    assert read("import_s.train") == pytest.approx(2.0)
    # no step built, no parameters made: 0 s of each, and no None, since
    # every cell owes the ten
    for name in TEN[1:5]:
        assert read(name) == 0.0, name
    assert read("setup_programs.train") == 0
    assert read("setup_unaccounted_s.train") == pytest.approx(48.0)
    with pytest.raises(ValueError, match="spans, programs or unaccounted"):
        setup_span.read({"reader": "setup_span"}, dict(hand_made))


# ---------------------------------------------------------------------------
# through run_cell, at the tiny CPU size
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tests/benchmark_tests/data`` with the ten metrics registered and
    their files, the repo's own, beside the cell's."""
    root = str(tmp_path_factory.mktemp("setup_reader") / "data")
    shutil.copytree(os.path.join(_HERE, "data"), root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in _entries()[1]:
        bench["per_layer"].append(entry)
        shutil.copy(os.path.join(_METRICS, entry["name"] + ".json"),
                    os.path.join(root, "bench", "layer_metrics"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def counter():
    return checks.CompileCounter()


def _run(root, cell, counter, seconds=0.3):
    """One traced run of the cell as ``run.py`` makes it: ``T_START`` on
    ``__main__``.  The worker's earlier records are out of the way (they
    may have filled the timeline to its cap), and since the package's import
    went with them, a span stands in for it."""
    profiler.setup_records(reset=True)
    with pytest.MonkeyPatch.context() as patch:
        t_start = time.monotonic()
        profiler.setup_span("mx.import", t_start, time.monotonic(),
                            jax_was_imported=True)
        patch.setattr(sys.modules["__main__"], "T_START", t_start,
                      raising=False)
        cell, facts = run.cell_facts(root, cell, "cpu", 2 ** 31 + 38,
                                     seconds, True, t_start, counter)
        return facts, run.result_line(cell, facts, True)


@pytest.fixture(scope="module")
def one_chip(root, counter):
    return _run(root, "tiny_train", counter)


@pytest.mark.parametrize("name", TEN)
def test_a_traced_run_reports_each_of_the_ten_as_a_finite_number(one_chip,
                                                                 name):
    facts, line = one_chip
    metric = line["metrics"][name]
    assert math.isfinite(metric["value"]) and metric["value"] >= 0
    assert metric["unit"] == ("programs" if name in (
        "setup_programs.train", "setup_cache_misses.train") else "s")
    assert json.loads(json.dumps(line)) == line and line["correct"] is True


def test_the_parts_fit_inside_setup_s(one_chip):
    facts, line = one_chip
    value = {k: m["value"] for k, m in line["metrics"].items()}
    setup_s = facts["setup_s"]
    assert 0 <= value["setup_unaccounted_s.train"] <= setup_s
    # the timed step's program once, the reference check's two steps, and
    # the one-op programs around them
    timeline = facts["setup_span"]
    steps = [r for r in timeline["records"]
             if r["cat"] == "setup.program" and r["name"] == "jit(step)"
             and r["ts"] + r["dur"] <= timeline["cut"]]
    assert len(steps) == 3 and value["setup_programs.train"] > 3
    assert value["setup_cache_misses.train"] <= value["setup_programs.train"]
    # the spans time from inside what aot_compile()'s numbers time from
    # outside
    assert value["lint_s.train"] <= value["trace_s.train"]
    assert value["lower_s.train"] <= value["trace_s.train"]
    assert value["setup_load_s.train"] >= value["compile_s.train"] - 5e-3
    assert value["setup_python_s.train"] >= value["lower_s.train"]
    for name in TEN[1:5] + TEN[7:]:
        assert value[name] <= setup_s, name
    assert 0 < value["import_s.train"] < 1


def test_a_mesh_cell_counts_what_it_places_in_the_step_build(root, counter):
    facts, line = _run(root, "tiny_train_dp4", counter, seconds=0.2)
    timeline = facts["setup_span"]
    before = [r for r in timeline["records"]
              if r["cat"] == "setup" and r["ts"] + r["dur"] <= timeline["cut"]]
    last_build = max(r["ts"] for r in before if r["name"] == "mx.step.build")
    placed = [r for r in before
              if r["name"] == "mx.step.place" and r["ts"] >= last_build]
    assert sorted(r["args"]["what"] for r in placed) == ["batch", "state"]
    assert all(r["args"]["mesh"] == "dp=4" for r in placed)
    build = [r for r in before if r["ts"] == last_build][0]
    assert line["metrics"]["step_build_s.train"]["value"] == pytest.approx(
        (build["dur"] + sum(r["dur"] for r in placed)) * 1e-6)
    assert line["correct"] is True
