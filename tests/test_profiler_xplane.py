"""``mx.profiler`` with ``profile_device=True`` writes ONE trace: the user's
scopes, the op-dispatch events and the train steps land on the host plane of
the ``*.xplane.pb`` that holds the device ops, and a trace that cannot start
is an error, not silence (docs/PROFILING.md)."""
import glob
import os

import jax
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import make_train_step


@pytest.fixture
def device_profile(tmp_path):
    mx.profiler.set_config(filename=str(tmp_path / "p.json"),
                           profile_device=True)
    yield str(tmp_path / "p_xplane")
    mx.profiler.set_state("stop")
    mx.profiler.set_config(profile_device=False)


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    return [e.name for plane in ProfileData.from_file(found[0]).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_scopes_ops_and_steps_land_on_the_host_plane_of_the_device_trace(
        device_profile):
    net = nn.HybridSequential()
    net.add(nn.Dense(4))
    net.initialize()
    x = mx.nd.array(np.ones((2, 3), "float32"))
    y = mx.nd.array(np.zeros((2,), "float32"))
    net(x)
    step = make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd")
    step(x, y).wait_to_read()  # compiled before the trace starts

    mx.profiler.set_state("run")
    with mx.profiler.Task("my_task"):
        for _ in range(2):
            step(x, y).wait_to_read()
        mx.nd.relu(x).wait_to_read()
    mx.profiler.Marker("my_marker").mark()
    mx.profiler.pause()
    with mx.profiler.Frame("while_paused"):
        pass
    mx.profiler.resume()
    mx.profiler.set_state("stop")

    names = _host_events(device_profile)
    assert names.count("my_task") == 1 and names.count("my_marker") == 1
    assert names.count("mx.train_step") == 2
    assert "relu" in names
    assert "while_paused" not in names
    # and the chrome-trace events are still collected beside it
    assert "my_task" in mx.profiler.dumps(reset=True)


def test_run_raises_when_the_device_trace_cannot_start(device_profile,
                                                       tmp_path):
    jax.profiler.start_trace(str(tmp_path / "someone_elses"))
    try:
        with pytest.raises(RuntimeError, match="[Oo]nly one profile"):
            mx.profiler.set_state("run")
        assert not mx.profiler.is_running()
    finally:
        jax.profiler.stop_trace()
    mx.profiler.set_state("run")  # and starts once it can
    assert mx.profiler.is_running()


def test_no_annotation_is_opened_while_no_device_trace_runs(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("TraceAnnotation opened with no trace running")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    mx.profiler.set_state("run")
    try:
        with mx.profiler.Task("plain"):
            mx.nd.relu(mx.nd.array(np.ones(3, "float32"))).wait_to_read()
        mx.profiler.Marker("m").mark()
    finally:
        mx.profiler.set_state("stop")
    assert "plain" in mx.profiler.dumps(reset=True)
