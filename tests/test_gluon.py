"""Gluon tests (reference: tests/python/unittest/test_gluon.py)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu.gluon import nn


def test_parameter():
    p = gluon.Parameter("weight", shape=(2, 3))
    p.initialize(init=mx.init.Xavier())
    assert p.data().shape == (2, 3)
    assert p.grad().shape == (2, 3)
    p.set_data(nd.ones((2, 3)))
    np.testing.assert_allclose(p.data().asnumpy(), np.ones((2, 3)))


def test_grad_req_null_lets_the_gradient_buffer_go():
    """Setting ``grad_req`` to ``"null"`` drops the gradient buffer the
    parameter's data carries for autograd, so that a fused train step that
    keeps its own gradients does not also hold one zero array a parameter;
    back to ``"write"`` gives it a buffer again."""
    import gc
    import weakref

    p = gluon.Parameter("weight", shape=(2, 3))
    p.initialize(init=mx.init.Xavier())
    buffer = weakref.ref(p.grad())
    p.grad_req = "null"
    gc.collect()
    assert buffer() is None and p.data().grad is None
    assert not autograd.requires_grad(p.data())
    p.grad_req = "write"
    assert p.grad().shape == (2, 3) and p.data().grad is p.grad()


def test_dense_forward():
    layer = nn.Dense(4, in_units=3)
    layer.initialize()
    x = nd.ones((2, 3))
    out = layer(x)
    assert out.shape == (2, 4)
    w = layer.weight.data().asnumpy()
    b = layer.bias.data().asnumpy()
    np.testing.assert_allclose(out.asnumpy(), np.ones((2, 3)) @ w.T + b, rtol=1e-5)


def test_dense_deferred_init():
    layer = nn.Dense(4)
    layer.initialize()
    out = layer(nd.ones((2, 7)))
    assert out.shape == (2, 4)
    assert layer.weight.shape == (4, 7)


def test_sequential():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
    net.initialize()
    out = net(nd.ones((4, 5)))
    assert out.shape == (4, 2)
    assert len(net) == 2


def test_hybridize_matches_eager():
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(8), nn.Dense(3))
    net.initialize()
    x = nd.random.uniform(shape=(5, 10))
    eager = net(x).asnumpy()
    net.hybridize()
    jit1 = net(x).asnumpy()
    jit2 = net(x).asnumpy()
    np.testing.assert_allclose(eager, jit1, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jit1, jit2, rtol=1e-7)


def test_hybridize_grad_matches_eager():
    net = nn.Dense(4, in_units=6)
    net.initialize()
    x = nd.random.uniform(shape=(3, 6))

    def grads():
        with autograd.record():
            y = net(x).sum()
        y.backward()
        return {k: p.grad().asnumpy().copy()
                for k, p in net.collect_params().items()}

    g_eager = grads()
    net.hybridize()
    g_jit = grads()
    for k in g_eager:
        np.testing.assert_allclose(g_eager[k], g_jit[k], rtol=1e-5, atol=1e-6)


def test_conv_block():
    layer = nn.Conv2D(8, kernel_size=3, padding=1, in_channels=3)
    layer.initialize()
    out = layer(nd.ones((2, 3, 8, 8)))
    assert out.shape == (2, 8, 8, 8)
    # deferred channels
    layer2 = nn.Conv2D(4, kernel_size=1)
    layer2.initialize()
    assert layer2(nd.ones((1, 5, 4, 4))).shape == (1, 4, 4, 4)


def test_pool_blocks():
    x = nd.ones((1, 2, 8, 8))
    assert nn.MaxPool2D()(x).shape == (1, 2, 4, 4)
    assert nn.AvgPool2D(pool_size=4)(x).shape == (1, 2, 2, 2)
    assert nn.GlobalAvgPool2D()(x).shape == (1, 2, 1, 1)


def test_batchnorm_running_stats():
    bn = nn.BatchNorm(in_channels=3, momentum=0.5)
    bn.initialize()
    x = nd.random.normal(loc=5.0, scale=2.0, shape=(16, 3, 4, 4))
    with autograd.record():
        bn(x)
    rm = bn.running_mean.data().asnumpy()
    assert (rm > 1.0).all(), "running mean should move toward batch mean 5, got %s" % rm
    # inference uses running stats
    out = bn(x)
    assert out.shape == x.shape


def test_batchnorm_running_stats_hybridized():
    bn = nn.BatchNorm(in_channels=3, momentum=0.0)  # full update
    bn.initialize()
    bn.hybridize()
    x = nd.random.normal(loc=2.0, scale=1.0, shape=(32, 3, 2, 2))
    with autograd.record():
        bn(x)
    rm = bn.running_mean.data().asnumpy()
    batch_mean = x.asnumpy().mean(axis=(0, 2, 3))
    np.testing.assert_allclose(rm, batch_mean, rtol=1e-3, atol=1e-3)


def test_losses():
    pred = nd.array([[1.0, 2.0, 3.0], [3.0, 2.0, 1.0]])
    label = nd.array([2, 0])
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    assert l.shape == (2,)
    expected = -np.log(np.exp(3) / np.exp([1, 2, 3]).sum())
    np.testing.assert_allclose(l.asnumpy()[0], expected, rtol=1e-3)

    l2 = gluon.loss.L2Loss()(nd.array([1.0, 2.0]), nd.array([0.0, 0.0]))
    np.testing.assert_allclose(l2.asnumpy(), [0.5, 2.0])

    l1 = gluon.loss.L1Loss()(nd.array([1.0, -2.0]), nd.array([0.0, 0.0]))
    np.testing.assert_allclose(l1.asnumpy(), [1.0, 2.0])

    h = gluon.loss.HuberLoss()(nd.array([0.5, 3.0]), nd.array([0.0, 0.0]))
    np.testing.assert_allclose(h.asnumpy(), [0.125, 2.5])


def test_trainer_sgd_step():
    net = nn.Dense(1, in_units=2, use_bias=False)
    net.initialize(init=mx.init.Constant(1.0))
    trainer = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.5})
    x = nd.array([[1.0, 1.0]])
    with autograd.record():
        y = net(x).sum()
    y.backward()
    trainer.step(batch_size=1)
    # w <- 1 - 0.5 * 1 = 0.5
    np.testing.assert_allclose(net.weight.data().asnumpy(), [[0.5, 0.5]], rtol=1e-6)


@pytest.mark.slow  # tier-1 budget (~23 s): many-epoch MLP convergence;
# test_rnn.py::test_lstm_lm_learns stays as the in-budget learns leg
def test_train_mlp_convergence():
    """End-to-end: learn XOR-ish separable data (reference tests/python/train)."""
    mx.random.seed(0)
    np.random.seed(0)
    X = np.random.uniform(-1, 1, (256, 2)).astype(np.float32)
    Y = (X[:, 0] * X[:, 1] > 0).astype(np.float32)

    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="tanh"), nn.Dense(2))
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})

    data, label = nd.array(X), nd.array(Y)
    for _ in range(150):
        with autograd.record():
            out = net(data)
            loss = loss_fn(out, label)
        loss.backward()
        trainer.step(batch_size=X.shape[0])
    pred = net(data).argmax(axis=1).asnumpy()
    acc = (pred == Y).mean()
    assert acc > 0.9, "convergence failed: acc=%.3f" % acc


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net.initialize()
    f = str(tmp_path / "net.params")
    net.save_parameters(f)
    w0 = net[0].weight.data().asnumpy()

    net2 = nn.HybridSequential()
    net2.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net2.load_parameters(f)
    np.testing.assert_allclose(net2[0].weight.data().asnumpy(), w0)


def test_dropout_block():
    d = nn.Dropout(0.5)
    x = nd.ones((100, 100))
    out = d(x)  # inference = identity
    np.testing.assert_allclose(out.asnumpy(), x.asnumpy())
    with autograd.record():
        out = d(x)
    assert 0.2 < (out.asnumpy() == 0).mean() < 0.8


def test_embedding_block():
    e = nn.Embedding(10, 4)
    e.initialize()
    out = e(nd.array([1, 2, 3]))
    assert out.shape == (3, 4)


def test_collect_params_select():
    net = nn.HybridSequential()
    net.add(nn.Dense(2, in_units=2), nn.Dense(2, in_units=2))
    params = net.collect_params()
    assert len(params) == 4
    weights = net.collect_params(".*weight")
    assert len(weights) == 2
    assert all(k.endswith("weight") for k in weights)


def test_lambda_blocks():
    lam = nn.HybridLambda("relu")
    out = lam(nd.array([-1.0, 1.0]))
    np.testing.assert_allclose(out.asnumpy(), [0.0, 1.0])


def test_global_norm_clip():
    arrays = [nd.ones((2, 2)) * 3, nd.ones((2,)) * 4]
    norm = gluon.utils.clip_global_norm(arrays, 1.0)
    assert norm > 1.0
    total = np.sqrt(sum((a.asnumpy() ** 2).sum() for a in arrays))
    np.testing.assert_allclose(total, 1.0, rtol=1e-4)


def test_split_and_load():
    data = nd.arange(0, 12).reshape(6, 2)
    slices = gluon.split_and_load(data, [mx.cpu(0), mx.cpu(0)])
    assert len(slices) == 2 and slices[0].shape == (3, 2)


def test_load_and_fused_rnn_initializers():
    """Load (initializer.py:319): init from a name->array dict with
    arg:/aux: stripping and default fallback.  FusedRNN (:720): unpack
    the packed blob, apply the inner init, pin the LSTM forget-gate
    bias slice, repack."""
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.rnn import rnn_cell

    init = mx.init.Load({"arg:w": nd.array(np.full((2, 2), 7.0, np.float32))},
                        default_init=mx.init.Zero())
    w = nd.array(np.ones((2, 2), np.float32))
    init("w", w)
    np.testing.assert_array_equal(w.asnumpy(), np.full((2, 2), 7.0))
    other = nd.array(np.ones(3, np.float32))
    init("other", other)
    np.testing.assert_array_equal(other.asnumpy(), np.zeros(3))

    # FusedRNN: build a real packed blob via the cell, re-init it
    cell = rnn_cell.FusedRNNCell(4, 1, "lstm", prefix="")
    unpacked = {"l0_i2h_weight": nd.array(np.zeros((16, 3), np.float32)),
                "l0_h2h_weight": nd.array(np.zeros((16, 4), np.float32)),
                "l0_i2h_bias": nd.array(np.zeros(16, np.float32)),
                "l0_h2h_bias": nd.array(np.zeros(16, np.float32))}
    packed = cell.pack_weights(unpacked)["parameters"]
    fr = mx.init.FusedRNN(mx.init.Constant(0.25), 4, 1, "lstm",
                          forget_bias=2.0)
    fr._init_weight(mx.init.InitDesc("parameters"), packed)
    back = cell.unpack_weights({"parameters": packed})
    np.testing.assert_allclose(back["l0_i2h_weight"].asnumpy(),
                               np.full((16, 3), 0.25))
    bias = back["l0_i2h_bias"].asnumpy()
    # gate order (i, f, c, o): the f slice carries the forget bias; the
    # other gates route through the suffix-based bias init (zeros),
    # exactly like the reference's per-gate flow
    np.testing.assert_allclose(bias[4:8], np.full(4, 2.0))
    np.testing.assert_allclose(bias[:4], np.zeros(4))
