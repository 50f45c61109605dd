"""ZeRO-1 weight-update sharding (arXiv:2004.13336) on the 8-dev CPU mesh.

The headline acceptance: ``make_train_step(..., zero=1)`` — per-rank
grad shards, dp-sharded optimizer state (+ f32 master weights under
``multi_precision=True``), all-gathered params — matches the unsharded
step's losses AND final params to 1e-5 over 3 steps, for sgd-momentum
and adam, on dp and dp x pp meshes, while the per-device optimizer-state
bytes drop by ~the dp axis size (asserted via ``.addressable_shards``).
Plus the FunctionalOptimizer regressions the restructuring folded in:
adam's first-step bias correction (1-based step count, f32 — not the
silent f64 promotion) and ``rescale_grad`` parity.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import (FunctionalOptimizer, make_mesh,
                                          make_train_step)

FEAT = 16
LOSS = gluon.loss.SoftmaxCrossEntropyLoss


def _build(seed=3, widths=(FEAT, FEAT, FEAT, FEAT), dtype=None):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    for w in widths:
        net.add(nn.Dense(w, activation="tanh"))
    net.initialize(init=mx.init.Xavier())
    net(nd.ones((2, FEAT)))
    if dtype is not None:
        net.cast(dtype)
    return net


def _batch(batch=16):
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(batch, FEAT).astype(np.float32))
    y = nd.array((np.arange(batch) % 4).astype(np.float32))
    return x, y


_OPT_KW = {
    "sgd": dict(optimizer="sgd", learning_rate=0.1, momentum=0.9),
    "adam": dict(optimizer="adam", learning_rate=0.01),
    # no momentum: the one optimizer with an EMPTY state under ZeRO
    "sgd_plain": dict(optimizer="sgd", learning_rate=0.1, momentum=0.0),
    "adamw": dict(optimizer="adamw", learning_rate=0.01, wd=0.1),
    # the CNN cells' own update (vgg16_train_dp4: momentum, weight decay,
    # float32 master weights of bf16 parameters, ZeRO-1)
    "sgd_master": dict(optimizer="sgd", learning_rate=0.1, momentum=0.9,
                       wd=5e-4, multi_precision=True),
}


def _state_bytes(opt_state, per_device):
    """Total optimizer-state bytes — global, or of ONE device's shards."""
    tot = 0
    for leaf in jax.tree_util.tree_leaves(opt_state):
        if per_device:
            dev0 = leaf.addressable_shards[0].device
            tot += sum(s.data.nbytes for s in leaf.addressable_shards
                       if s.device == dev0)
        else:
            tot += leaf.nbytes
    return tot


def _run_parity(optimizer, axes, pipeline=False, widths=(FEAT,) * 4,
                seed=3):
    """zero=1 vs the unsharded single-device step: 3 steps, losses and
    final params to 1e-5; returns the zero step for state assertions.
    Master weights are kept of bf16 parameters."""
    x, y = _batch()
    opt_kw = _OPT_KW[optimizer]
    dtype = "bfloat16" if opt_kw.get("multi_precision") else None
    s_ref = make_train_step(_build(seed, widths, dtype), LOSS(), **opt_kw)
    ref = [float(s_ref(x, y).asscalar()) for _ in range(3)]
    ndev = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:ndev])
    kw = dict(pipeline_stages=4, num_micro=4) if pipeline else {}
    s_z = make_train_step(_build(seed, widths, dtype), LOSS(), **opt_kw,
                          mesh=mesh, zero=1, lint="error", **kw)
    got = [float(s_z(x, y).asscalar()) for _ in range(3)]
    np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-6)
    for p1, p2 in zip(s_ref.net.collect_params().values(),
                      s_z.net.collect_params().values()):
        np.testing.assert_allclose(
            p1.data().asnumpy().astype(np.float32),
            p2.data().asnumpy().astype(np.float32), rtol=1e-5, atol=1e-5)
    return s_z


@pytest.mark.parametrize("optimizer", sorted(_OPT_KW))
def test_zero1_parity_and_state_bytes_dp(optimizer):
    """dp=8: parity to 1e-5 AND per-device opt-state bytes ~1/8 of the
    global (every leading dim here divides, so exactly 1/8).  Every
    elementwise update shards (lamb's is not one:
    test_zero1_validation_errors)."""
    step = _run_parity(optimizer, {"dp": 8})
    per_dev = _state_bytes(step._opt_state, per_device=True)
    total = _state_bytes(step._opt_state, per_device=False)
    assert per_dev * 8 == total, (per_dev, total)
    if optimizer == "sgd_plain":
        assert step._opt_state == []
        return
    # and the dp sharding is real: N shards per leaf, 1/N rows each
    leaf = jax.tree_util.tree_leaves(step._opt_state)[0]
    assert len(leaf.addressable_shards) == 8
    assert leaf.addressable_shards[0].data.shape[0] * 8 == leaf.shape[0]


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_zero1_parity_dp_pp_pipeline(optimizer):
    """dp x pp: ZeRO over the dp axis of a pipelined step — microbatch
    grads accumulate in the scan transpose and reduce over dp once."""
    step = _run_parity(optimizer, {"dp": 2, "pp": 4}, pipeline=True, seed=7)
    per_dev = _state_bytes(step._opt_state, per_device=True)
    total = _state_bytes(step._opt_state, per_device=False)
    # state shards over dp (2); each pp rank keeps a dp-shard copy
    assert per_dev * 2 == total, (per_dev, total)


def test_zero1_ragged_leading_dim_pads_and_slices():
    """A param whose leading dim (13) does not divide dp=8 is padded to
    16 and sharded — never silently replicated — with exact parity."""
    step = _run_parity("sgd", {"dp": 8}, widths=(FEAT, 13, FEAT, FEAT),
                       seed=5)
    # the Dense(13) weight's momentum is stored padded to 16 rows
    shapes = [jax.tree_util.tree_leaves(s)[0].shape
              for s in step._opt_state]
    assert (16, FEAT) in shapes  # padded from (13, FEAT)
    for leaf in jax.tree_util.tree_leaves(step._opt_state):
        assert len(leaf.addressable_shards) == 8
        assert leaf.addressable_shards[0].data.shape[0] * 8 == leaf.shape[0]


def test_zero1_multi_precision_master_weights():
    """bf16 params + multi_precision: momentum AND the f32 master copy
    live dp-sharded in the state; params stay bf16; loss decreases."""
    x, y = _batch()
    mesh = make_mesh({"dp": 8})
    net = _build(9, dtype="bfloat16")
    step = make_train_step(net, LOSS(), optimizer="sgd", learning_rate=0.1,
                           momentum=0.9, multi_precision=True, mesh=mesh,
                           zero=1, lint="error")
    losses = [float(step(x, y).asscalar()) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    assert list(net.collect_params().values())[0].data().dtype == "bfloat16"
    for mom32, w32 in step._opt_state:
        assert mom32.dtype == jnp.float32 and w32.dtype == jnp.float32
        assert len(w32.addressable_shards) == 8
    # f32 master accumulation tracks the f32 reference loss curve to
    # bf16 resolution (the bf16-momentum path drifts further)
    s_ref = make_train_step(_build(9), LOSS(), optimizer="sgd",
                            learning_rate=0.1, momentum=0.9)
    ref = [float(s_ref(x, y).asscalar()) for _ in range(3)]
    np.testing.assert_allclose(ref, losses, rtol=2e-2)


def test_zero1_validation_errors():
    """Fail-loudly contract: zero without a dp axis, and non-elementwise
    optimizers (lamb's global trust ratio), are rejected at build."""
    net = _build()
    with pytest.raises(ValueError, match="mesh"):
        make_train_step(net, LOSS(), optimizer="sgd", zero=1)
    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="dp"):
        make_train_step(net, LOSS(), optimizer="sgd", mesh=mesh, zero=1)
    mesh = make_mesh({"dp": 8})
    with pytest.raises(ValueError, match="trust ratio|elementwise"):
        make_train_step(net, LOSS(), optimizer="lamb", mesh=mesh, zero=1)
    with pytest.raises(ValueError, match="zero"):
        make_train_step(net, LOSS(), optimizer="sgd", mesh=mesh, zero=3)


def test_adam_first_step_bias_correction():
    """Regression for the 1 - beta**t off-by-one: apply() at the INITIAL
    step (t=1, 1-based — the fused step increments before applying)
    produces the finite, hand-computed bias-corrected update, in f32
    (not the silent f64 promotion beta**int32 used to trigger)."""
    opt = FunctionalOptimizer("adam", learning_rate=0.01, beta1=0.9,
                              beta2=0.999, epsilon=1e-8, wd=0.0)
    p = jnp.asarray(np.linspace(-1, 1, 8, dtype=np.float32))
    g = jnp.asarray(np.linspace(0.5, -0.5, 8, dtype=np.float32))
    state = opt.init([p])
    [w1], [s1] = opt.apply([p], [g], state, jnp.int32(1))
    assert w1.dtype == jnp.float32, w1.dtype
    assert np.isfinite(np.asarray(w1)).all()
    gn = np.asarray(g, np.float64)
    m1 = 0.1 * gn
    v1 = 0.001 * gn ** 2
    lr1 = 0.01 * np.sqrt(1 - 0.999) / (1 - 0.9)
    want = np.asarray(p, np.float64) - lr1 * m1 / (np.sqrt(v1) + 1e-8)
    np.testing.assert_allclose(np.asarray(w1), want, rtol=1e-5, atol=1e-7)
    # the whole first-step magnitude is ~lr (bias-corrected), not ~lr/10
    # (uncorrected m1/sqrt(v1) would already be ~1, but an off-by-one
    # t=0 would divide by zero and NaN out)
    [w2], [s2] = opt.apply([w1], [g], [s1], jnp.int32(2))
    assert np.isfinite(np.asarray(w2)).all()


def test_rescale_grad_parity_with_trainer():
    """rescale_grad flows Trainer → fused step → the reference update
    ops: scaling the loss by 1/c and setting rescale_grad=c matches the
    unscaled run exactly."""
    x, y = _batch()
    c = 4.0

    class ScaledLoss(gluon.loss.SoftmaxCrossEntropyLoss):
        def hybrid_forward(self, F, pred, label, *a, **k):
            return super().hybrid_forward(F, pred, label, *a, **k) * c

    s_ref = make_train_step(_build(11), LOSS(), optimizer="sgd",
                            learning_rate=0.1, momentum=0.9)
    ref = [float(s_ref(x, y).asscalar()) for _ in range(2)]

    net = _build(11)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9,
                             "rescale_grad": 1.0 / c})
    step = trainer.make_fused_step(net, ScaledLoss())
    got = [float(step(x, y).asscalar()) / c for _ in range(2)]
    np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-6)
    for p1, p2 in zip(s_ref.net.collect_params().values(),
                      net.collect_params().values()):
        np.testing.assert_allclose(p1.data().asnumpy(), p2.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6)
