"""The step program names itself (docs/PROFILING.md): ``jax.named_scope`` on
the step's phases (``train_step.STEP_SCOPES``), on every hybrid block
(``<Class>.<name>``) and on every op (``op.<name>``) reaches the op names of
the COMPILED program, where a device trace finds it; it reaches neither the
lowered text, which the compile caches hash, nor an eager call."""
import re

import jax
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import make_mesh, make_train_step
from incubator_mxnet_tpu.parallel.train_step import STEP_SCOPES

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _net():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
            nn.Activation("relu"), nn.MaxPool2D(2, 2), nn.Flatten(),
            nn.Dense(10))
    net.initialize()
    return net


def _batch(rows=8):
    rng = np.random.RandomState(0)
    return (mx.nd.array(rng.rand(rows, 3, 8, 8).astype("float32")),
            mx.nd.array(rng.randint(0, 10, (rows,)).astype("float32")))


def _compiled(mesh=None, zero=0):
    """``(net, lowered text, op names of the compiled text)`` of the
    benchmark's kind of step over the tiny net.  Compiled with JAX's
    persistent cache off: scope names are metadata, which the cache's key
    leaves out, so an executable that an earlier test of the same worker
    built from a net whose blocks were numbered otherwise (a benchmark
    runner turns the cache on) would come back under ITS names."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    net = _net()
    x, y = _batch()
    net(x)
    step = make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                           momentum=0.9, loss_scale="dynamic",
                           compute_dtype="bfloat16", mesh=mesh, zero=zero)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        step.aot_compile(x, y)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    args = ([p._data._data for p in step._gp],
            [p._data._data for p in step._aux], step.opt_state,
            *(step._place_batch(x._data, y._data) if mesh is not None
              else (x._data, y._data)),
            step._key_dev, step._step_dev, step._scaler_dev)
    lowered = step._jit.lower(*args).as_text()
    return net, lowered, _OP_NAME.findall(step.compiled.as_text())


@pytest.fixture(scope="module")
def one_chip():
    return _compiled()


@pytest.fixture(scope="module")
def dp4_zero1():
    return _compiled(make_mesh({"dp": 4}, devices=jax.devices()[:4]), zero=1)


@pytest.mark.parametrize("phase",
                         [s for s in STEP_SCOPES if s != "step.update.zero"])
def test_every_phase_names_instructions_of_the_compiled_step(one_chip, phase):
    _, _, names = one_chip
    assert any(re.search(r"(^|[/(])%s([/)]|$)" % re.escape(phase), n)
               for n in names), phase


def test_phases_nest_as_the_tuple_says(one_chip):
    _, _, names = one_chip
    assert any("jvp(step.forward)/step.cast/" in n for n in names)
    assert not any("step.update.zero" in n for n in names)  # no ZeRO here


def test_a_block_is_named_on_the_forward_and_on_the_backward_pass(one_chip):
    net, _, names = one_chip
    conv = "/Conv2D.%s/op.Convolution/" % net[0].name
    assert any(n.startswith("jit(step)/jvp(step.forward)/") and conv in n
               for n in names)
    assert any(n.startswith("jit(step)/transpose(jvp(step.forward))/")
               and conv in n for n in names)


def test_blocks_nest_and_the_loss_block_is_named_too(one_chip):
    net, _, names = one_chip
    nested = "/HybridSequential.%s/BatchNorm.%s/op.BatchNorm/" % (
        net.name, net[1].name)
    assert any(nested in n for n in names)
    assert any(re.search(r"/SoftmaxCrossEntropyLoss\.\w+/op\.\w+", n)
               for n in names)


def test_the_max_pool_backward_sits_under_the_pooling_scope(one_chip):
    net, _, names = one_chip
    under = ("transpose(jvp(step.forward))/HybridSequential.%s/MaxPool2D.%s/"
             "op.Pooling/" % (net.name, net[3].name))
    built = {n.split(";")[0].rsplit("/", 1)[-1] for n in names if under in n}
    # reduce_window's own transpose: one select-and-scatter a pool, and no
    # pad of the pool input's shape per in-window offset
    assert "select_and_scatter" in built and "pad" not in built, built


def test_the_zero_all_gather_sits_under_step_update_zero(dp4_zero1):
    _, _, names = dp4_zero1
    gathers = [n for n in names if n.endswith("/all_gather")]
    assert gathers
    assert all(re.search(r"/step\.update/shard_map/step\.update\.zero/", n)
               for n in gathers)
    # the optimizer's arithmetic keeps the plain scope
    assert any("/step.update/shard_map/" in n and "zero" not in n
               and n.endswith("/mul") for n in names)


@pytest.mark.parametrize("program", ["one_chip", "dp4_zero1"])
def test_no_scope_reaches_the_lowered_text_the_caches_hash(request, program):
    net, lowered, _ = request.getfixturevalue(program)
    for name in STEP_SCOPES + ("op.Convolution", "Conv2D." + net[0].name):
        assert name not in lowered, name


def test_an_eager_call_opens_no_scope_and_a_traced_one_does(monkeypatch):
    opened = []
    real = jax.named_scope

    def spy(name):
        opened.append(name)
        return real(name)

    monkeypatch.setattr(jax, "named_scope", spy)
    net = _net()
    x, _ = _batch()
    net(x).wait_to_read()
    assert opened == []
    net.hybridize()
    net(x).wait_to_read()  # the CachedOp's whole-graph trace
    assert "HybridSequential." + net.name in opened
    assert "op.Convolution" in opened
