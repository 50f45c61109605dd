"""The set-up timeline of ``mx.profiler`` (docs/PROFILING.md, "Why did my job
take N seconds before step 0"): ``Setup`` spans are kept with the profiler
stopped, nest by thread, and are bounded; every XLA program jax builds or
loads leaves one record under the span that caused it; the step's build,
trace, lint, lowering and compile are spans opened in line; a train step
writes nothing."""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, profiler
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import make_mesh, make_train_step

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def timeline():
    """An empty timeline: the worker's earlier tests may have filled it to
    its cap."""
    profiler.setup_records(reset=True)
    yield
    profiler.setup_records(reset=True)


def _spans(name=None):
    return [r for r in profiler.setup_records()
            if r["cat"] == "setup" and name in (None, r["name"])]


def _programs():
    return [r for r in profiler.setup_records()
            if r["cat"] == "setup.program"]


def _tiny_step(mesh=None, **kwargs):
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    net.shape_init((4, 3))
    x = mx.nd.array(np.ones((4, 3), "float32"))
    y = mx.nd.array(np.zeros((4,), "float32"))
    step = make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                           mesh=mesh, **kwargs)
    return step, x, y


def test_setup_spans_are_kept_with_the_profiler_stopped_and_others_are_not():
    assert not profiler.is_running()
    with profiler.Setup("kept", rows=3):
        with profiler.Task("not_kept"):
            pass
        profiler.Marker("nor_this").mark()
    (record,) = profiler.setup_records()
    assert record["name"] == "kept" and record["cat"] == "setup"
    assert record["args"] == {"rows": 3} and record["dur"] >= 0
    assert record["id"] is not None and record["parent"] is None
    assert "not_kept" not in profiler.dumps() \
        and "nor_this" not in profiler.dumps()


def test_nesting_gives_each_record_its_parent_and_a_self_time():
    with profiler.Setup("outer") as outer:
        with profiler.Setup("first") as first:
            time.sleep(0.02)
        second = profiler.Setup("second")
        second.start()
        time.sleep(0.01)
        second.stop()
    after = profiler.Setup("after")
    after.start()
    after.stop()
    by_name = {r["name"]: r for r in profiler.setup_records()}
    assert by_name["outer"]["parent"] is None
    assert by_name["first"]["parent"] == by_name["second"]["parent"] \
        == outer.id == by_name["outer"]["id"]
    assert by_name["after"]["parent"] is None
    assert len({r["id"] for r in by_name.values()}) == 4
    # self time: the span's duration less what its children cover
    line = [ln for ln in profiler.setup_report().splitlines()
            if ln.startswith("outer")][0].split()
    total_s, self_s = float(line[2]), float(line[3])
    children_s = (by_name["first"]["dur"] + by_name["second"]["dur"]) * 1e-6
    assert total_s >= 0.03 and abs(total_s - children_s - self_s) < 2e-3
    assert self_s < 0.01
    assert first.id != second.id


def test_the_cap_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(profiler, "_SETUP_CAP", 3)
    for i in range(5):
        with profiler.Setup("span%d" % i):
            pass
    assert [r["name"] for r in profiler.setup_records()] == \
        ["span0", "span1", "span2"]
    assert profiler._setup_dropped == 2
    assert "2 records dropped" in profiler.setup_report()
    profiler.setup_records(reset=True)
    assert profiler._setup_dropped == 0 and not profiler.setup_records()


def test_an_exception_leaves_no_span_open():
    with pytest.raises(ZeroDivisionError):
        with profiler.Setup("outer"):
            leaked = profiler.Setup("leaked")
            leaked.start()
            1 / 0
    with profiler.Setup("next"):
        pass
    by_name = {r["name"]: r for r in profiler.setup_records()}
    # the span an exception left open went with its parent: it is no
    # record, and no later span's parent
    assert set(by_name) == {"outer", "next"}
    assert by_name["next"]["parent"] is None


def test_spans_nest_by_thread():
    seen = {}

    def work():
        with profiler.Setup("on_thread") as span:
            seen["parent"] = span.parent

    with profiler.Setup("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive() and seen["parent"] is None
    tids = {r["name"]: r["tid"] for r in profiler.setup_records()}
    assert tids["on_thread"] != tids["main"]


def test_a_span_from_two_readings_takes_the_open_span_as_its_parent():
    t0 = time.monotonic()
    with profiler.Setup("open") as span:
        t1 = time.monotonic()
        child = profiler.setup_span("afterwards", t0, t1, cache="hit")
    by_name = {r["name"]: r for r in profiler.setup_records()}
    assert by_name["afterwards"]["id"] == child
    assert by_name["afterwards"]["parent"] == span.id
    assert by_name["afterwards"]["args"] == {"cache": "hit"}
    # the records' clock converts to time.monotonic()
    assert by_name["afterwards"]["ts"] == pytest.approx(profiler.clock_us(t0))
    assert by_name["afterwards"]["dur"] == pytest.approx((t1 - t0) * 1e6)
    assert by_name["open"]["ts"] <= profiler.clock_us(t1) <= \
        by_name["open"]["ts"] + by_name["open"]["dur"]


def test_the_package_import_is_a_span_with_jax_as_its_child():
    # in a process of its own: this one's timeline was reset
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, incubator_mxnet_tpu as mx; "
         "print(json.dumps(mx.profiler.setup_records()))"],
        cwd=_ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, check=True).stdout
    records = json.loads(out.strip().splitlines()[-1])
    assert [r["name"] for r in records] == ["mx.import", "mx.import.jax"]
    whole, of_jax = records
    assert whole["ts"] == 0 and whole["args"] == {"jax_was_imported": False}
    assert of_jax["parent"] == whole["id"] and whole["parent"] is None
    assert 0 < of_jax["dur"] < whole["dur"] < 120e6


def test_a_jitted_function_in_a_span_leaves_one_program_record():
    def timeline_probe(x):
        return jnp.tanh(x) * 3 + 1

    x = jnp.ones((5, 7))
    n = len(_programs())
    with profiler.Setup("builds") as span:
        fn = jax.jit(timeline_probe)
        fn(x).block_until_ready()
        fn(x).block_until_ready()  # the second call builds nothing
    mine = [p for p in _programs()[n:] if p["name"] == "jit(timeline_probe)"]
    assert len(mine) == 1
    (record,) = mine
    assert record["parent"] == span.id
    args = record["args"]
    assert args["trace_s"] > 0 and args["lower_s"] > 0 \
        and args["compile_s"] > 0
    assert args["cache"] in ("off", "miss", "hit")
    # each part has its own start on the recorder's clock, in order
    assert args["trace_ts"] < args["lower_ts"] < record["ts"]
    assert record["dur"] == pytest.approx(args["compile_s"] * 1e6)
    (kept,) = _spans("builds")
    assert kept["ts"] <= args["trace_ts"] and \
        record["ts"] + record["dur"] <= kept["ts"] + kept["dur"]
    # outside any span a program has no parent
    jax.jit(lambda v: v - 2)(x)
    assert _programs()[-1]["parent"] is None


_CACHE_SCRIPT = """
import json, sys
sys.path.insert(0, %(root)r)
import jax, jax.numpy as jnp
import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import _backend
assert _backend.use_compile_cache() == %(cache)r

def cached_probe(x):
    return jnp.sin(x) @ x.T

x = jnp.ones((8, 8))
for _ in range(2):
    with mx.profiler.Setup("build"):
        jax.jit(cached_probe)(x).block_until_ready()
    jax.clear_caches()
print(json.dumps([r for r in mx.profiler.setup_records()
                  if r["name"] == "jit(cached_probe)"]))
"""


def test_a_program_built_twice_reads_miss_then_hit(tmp_path):
    cache = str(tmp_path / "cache")
    os.makedirs(cache)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT % {"root": _ROOT,
                                                "cache": cache}],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=cache), check=True).stdout
    first, second = json.loads(out.strip().splitlines()[-1])
    assert first["args"]["cache"] == "miss"
    assert "retrieval_s" not in first["args"]
    assert second["args"]["cache"] == "hit"
    assert second["args"]["retrieval_s"] > 0 and "saved_s" in second["args"]
    assert first["parent"] != second["parent"]
    assert os.listdir(cache)


def test_aot_compile_leaves_one_span_each_in_order():
    step, x, y = _tiny_step()
    profiler.setup_records(reset=True)
    times = step.aot_compile(x, y)
    names = ["mx.step.build", "mx.step.trace", "mx.step.lint",
             "mx.step.lower", "mx.step.compile"]
    spans = sorted(_spans(), key=lambda r: r["ts"])
    assert [r["name"] for r in spans] == names
    build, trace, lint, lower, compile_ = spans
    # lint lies inside trace; the others follow one another
    assert lint["parent"] == trace["id"]
    assert trace["ts"] <= lint["ts"] and \
        lint["ts"] + lint["dur"] <= trace["ts"] + trace["dur"]
    assert lint["args"] == {"findings": 0}
    for earlier, later in ((build, trace), (trace, lower), (lower, compile_)):
        assert earlier["ts"] + earlier["dur"] <= later["ts"]
        assert later["parent"] is None
    # what aot_compile() returns is what the spans hold
    assert (trace["dur"] + lower["dur"]) * 1e-6 == \
        pytest.approx(times["trace"], abs=5e-3)
    assert compile_["dur"] * 1e-6 == pytest.approx(times["compile"],
                                                    abs=5e-3)
    # the step's program: once, under the compile span, which says what
    # the persistent cache did
    (program,) = [p for p in _programs() if p["name"] == "jit(step)"]
    assert program["parent"] == compile_["id"]
    assert compile_["args"] == {"cache": program["args"]["cache"]}
    assert program["args"]["trace_s"] > 0 and program["args"]["lower_s"] > 0
    # a second aot_compile builds nothing anew
    step.aot_compile(x, y)
    assert len(_spans("mx.step.build")) == 1


def test_lint_off_leaves_no_lint_span_and_a_first_call_builds_under_none():
    step, x, y = _tiny_step(lint="off")
    profiler.setup_records(reset=True)
    step(x, y).wait_to_read()  # no aot_compile: the first call builds
    assert [r["name"] for r in _spans()] == ["mx.step.build"]
    (program,) = [p for p in _programs() if p["name"] == "jit(step)"]
    assert program["parent"] is None and program["args"]["trace_s"] > 0


def test_a_train_step_appends_no_record():
    step, x, y = _tiny_step()
    step.aot_compile(x, y)
    step(x, y).wait_to_read()
    assert not profiler.is_running()
    before = len(profiler.setup_records())
    for _ in range(100):
        loss = step(x, y)
    loss.wait_to_read()
    assert len(profiler.setup_records()) == before
    mx.nd.relu(x).wait_to_read()  # builds its program: one record
    n = len(profiler.setup_records())
    for _ in range(10):  # an eager op, once built, writes nothing either
        mx.nd.relu(x).wait_to_read()
    assert len(profiler.setup_records()) == n


def test_a_mesh_step_records_what_it_places():
    step, x, y = _tiny_step(mesh=make_mesh({"dp": 4},
                                           devices=jax.devices()[:4]))
    profiler.setup_records(reset=True)
    step.aot_compile(x, y)
    state, batch = sorted(_spans("mx.step.place"), key=lambda r: r["ts"])
    assert state["args"]["what"] == "state" \
        and batch["args"]["what"] == "batch"
    assert state["args"]["mesh"] == batch["args"]["mesh"] == "dp=4"
    # 3x8 + 8 + 8x4 + 4 float32 parameters, as many of momentum, and more
    assert state["args"]["bytes"] >= 2 * 4 * (24 + 8 + 32 + 4)
    assert batch["args"]["bytes"] == x._data.nbytes + y._data.nbytes
    build = _spans("mx.step.build")[0]
    assert build["ts"] + build["dur"] <= state["ts"] <= batch["ts"]
    n = len(profiler.setup_records())
    for _ in range(3):
        step(x, y).wait_to_read()
    assert len(_spans("mx.step.place")) == 2
    assert len(profiler.setup_records()) == n


def test_the_report_and_the_dump_hold_the_timeline_without_a_run(tmp_path):
    assert not profiler.is_running()
    step, x, y = _tiny_step()
    step.aot_compile(x, y)
    report = profiler.setup_report(programs=3)
    lines = report.splitlines()
    assert lines[0].startswith("set-up: ") and "XLA programs" in lines[0]
    tree = [ln.split()[0] for ln in lines[2:]]
    for name in ("mx.block.shape_init", "mx.params.materialize",
                 "mx.step.build", "mx.step.trace", "mx.step.lint",
                 "mx.step.lower", "mx.step.compile", "(under"):
        assert name in tree, (name, report)
    # a child is indented under its parent
    assert any(ln.startswith("  mx.step.lint") for ln in lines)
    heaviest = lines[lines.index([ln for ln in lines
                                  if ln.startswith("Program (3 heaviest")][0])
                     + 1:]
    assert len(heaviest) == 3 and any("jit(step)" in ln for ln in heaviest)
    # dump() writes the same records into the Chrome trace
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.dump()
    with open(str(tmp_path / "p.json")) as f:
        events = json.load(f)["traceEvents"]
    dumped = [e for e in events if e["cat"].startswith("setup")]
    assert [(e["name"], e["id"]) for e in dumped] == \
        [(r["name"], r["id"]) for r in profiler.setup_records()]
    assert all(e["ph"] == "X" and "ts" in e and "dur" in e for e in dumped)


def test_setup_spans_are_trace_annotations_while_the_device_trace_runs(
        tmp_path):
    import glob

    from jax.profiler import ProfileData

    profiler.set_config(filename=str(tmp_path / "p.json"),
                        profile_device=True)
    try:
        profiler.set_state("run")
        with profiler.Setup("mx.test.annotated"):
            jnp.ones(3).block_until_ready()
    finally:
        profiler.set_state("stop")
        profiler.set_config(profile_device=False)
    (found,) = glob.glob(os.path.join(str(tmp_path / "p_xplane"), "**",
                                      "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(found).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert "mx.test.annotated" in names
    assert [r["name"] for r in _spans()] == ["mx.test.annotated"]
