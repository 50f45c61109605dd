"""Fused ghost-BN Pallas kernels (parallel/fused_bn.py) and the resnet
perf variants (s2d stem, ghost_bn blocks) — CPU interpret-mode tests.

Reference semantics: BatchNorm (src/operator/nn/batch_norm.cc) with
group (ghost) statistics; at group == N the result must equal stock
BatchNorm + ReLU exactly.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import fused_bn as fb
from incubator_mxnet_tpu.parallel.fused_bn import (ghost_bn_act,
                                                   ghost_bn_stats_merge)


def _ref(x, gamma, beta, residual=None, eps=1e-3, group=4):
    n, c, h, w = x.shape
    g = n // group
    xg = x.astype(jnp.float32).reshape(g, group, c, h, w)
    m = xg.mean(axis=(1, 3, 4))
    v = ((xg - m[:, None, :, None, None]) ** 2).mean(axis=(1, 3, 4))
    y = ((xg - m[:, None, :, None, None])
         * jax.lax.rsqrt(v + eps)[:, None, :, None, None])
    y = (y * gamma[None, None, :, None, None]
         + beta[None, None, :, None, None]).reshape(n, c, h, w)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return jnp.maximum(y, 0.0).astype(x.dtype), m, v


@pytest.mark.parametrize("c,call_group,kernel_group", [
    # LNC kernel: the cap picks group 4 of batch 8
    (256, 4, 4),
    # LCN kernel: group == full lane block (the whole batch)
    (64, 8, 8),
    # LCN shape with a SUB-block cap: the kernel's lane-block group
    # would violate the declared bn_group semantics, so the jnp
    # fallback honors the cap exactly (per-group parity asserted)
    (64, 4, 4),
])
def test_ghost_bn_fwd_bwd_matches_reference(c, call_group, kernel_group):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(size=(8, c, 6, 6)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(8, c, 6, 6)).astype(np.float32))
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, c).astype(np.float32))
    beta = jnp.asarray(rng.normal(size=c).astype(np.float32) * 0.2)
    residuals = (None, res) if c >= 128 else (None,)
    for residual in residuals:
        y, m, v = ghost_bn_act(x, gamma, beta, residual=residual,
                               group=call_group)
        yr, mr, vr = _ref(x, gamma, beta, residual=residual,
                          group=kernel_group)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(np.asarray(m), np.asarray(mr),
                                   rtol=1e-4, atol=1e-5)

        def lk(x, gamma, beta, r):
            y, _, _ = ghost_bn_act(x, gamma, beta, residual=r,
                                   group=call_group)
            return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()

        def lr(x, gamma, beta, r):
            y, _, _ = _ref(x, gamma, beta, residual=r, group=kernel_group)
            return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()

        argn = (0, 1, 2) if residual is None else (0, 1, 2, 3)
        gk = jax.grad(lk, argnums=argn)(x, gamma, beta, residual)
        gr = jax.grad(lr, argnums=argn)(x, gamma, beta, residual)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)


def test_ghost_bn_stats_merge_equals_full_batch():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(size=(8, 32, 5, 5)).astype(np.float32))
    gamma = jnp.ones(32, jnp.float32)
    beta = jnp.zeros(32, jnp.float32)
    _, m, v = ghost_bn_act(x, gamma, beta, group=4)
    bm, bv = ghost_bn_stats_merge(m, v)
    np.testing.assert_allclose(np.asarray(bm),
                               np.asarray(x.mean(axis=(0, 2, 3))),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(bv),
                               np.asarray(x.var(axis=(0, 2, 3))),
                               rtol=1e-4, atol=1e-5)


def test_ghost_bn_block_matches_batchnorm_at_full_group():
    """GhostBNReLU(group=N) == BatchNorm + relu exactly (output, grads,
    running stats)."""
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import GhostBNReLU

    mx.random.seed(0)
    gbn = GhostBNReLU(group=8, epsilon=1e-3)
    gbn.initialize()
    gbn.shape_init((1, 16, 5, 5))
    bn = nn.BatchNorm(epsilon=1e-3)
    bn.initialize()
    bn.shape_init((1, 16, 5, 5))
    x = nd.random.uniform(shape=(8, 16, 5, 5))
    x.attach_grad()
    with autograd.record():
        y = gbn(x)
        (y * y).sum().backward()
    g1 = x.grad.asnumpy().copy()
    x2 = nd.array(x.asnumpy())
    x2.attach_grad()
    with autograd.record():
        y2 = nd.relu(nd.BatchNorm(x2, bn.gamma.data(), bn.beta.data(),
                                  bn.running_mean.data(),
                                  bn.running_var.data(), eps=1e-3))
        (y2 * y2).sum().backward()
    np.testing.assert_allclose(y.asnumpy(), y2.asnumpy(), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(g1, x2.grad.asnumpy(), rtol=1e-3, atol=1e-4)
    assert np.abs(gbn.running_mean.data().asnumpy()).sum() > 0


def test_ghost_bn_noact_nostats_does_not_rectify():
    """GhostBN(track_stats=False) — the pipelined downsample-branch
    norm — must NOT apply ReLU (regression: the stats-free branch used
    to hardcode the ReLU op regardless of the subclass)."""
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import (GhostBN,
                                                                   GhostBNReLU)

    mx.random.seed(0)
    x = nd.random.normal(shape=(4, 8, 6, 6))
    outs = {}
    for cls in (GhostBN, GhostBNReLU):
        layer = cls(group=2, track_stats=False, in_channels=8)
        layer.initialize()
        with autograd.record():
            outs[cls] = layer(x).asnumpy()
    assert (outs[GhostBN] < 0).any(), "no-act form was rectified"
    assert not (outs[GhostBNReLU] < 0).any()
    np.testing.assert_allclose(np.maximum(outs[GhostBN], 0.0),
                               outs[GhostBNReLU], rtol=1e-5, atol=1e-5)


def _ghost_resnet_trains(factory):
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import make_train_step

    mx.random.seed(0)
    net = factory(classes=10, ghost_bn=8)
    net.initialize(init=mx.init.Xavier())
    net.shape_init((1, 3, 32, 32))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = make_train_step(net, loss_fn, optimizer="sgd",
                           learning_rate=0.01, momentum=0.9)
    x = nd.random.uniform(shape=(8, 3, 32, 32))
    y = nd.array(np.random.RandomState(0).randint(0, 10, 8)
                 .astype(np.float32))
    losses = [float(step(x, y).asscalar()) for _ in range(6)]
    assert min(losses[2:]) < losses[0]
    rm = net.features[1].running_mean.data().asnumpy()
    assert np.abs(rm).sum() > 0
    # eval-mode forward uses moving stats
    out = net(x)
    assert out.shape == (8, 10)


def test_resnet18_ghost_bn_trains_and_updates_stats():
    """Fast tier-1 representative (basic blocks + GhostBN downsample
    branches); the bottleneck resnet50 clone runs under -m slow."""
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    _ghost_resnet_trains(vision.resnet18_v1)


@pytest.mark.slow
def test_resnet50_ghost_bn_trains_and_updates_stats():
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    _ghost_resnet_trains(vision.resnet50_v1)


def test_s2d_stem_exact():
    """Space-to-depth stem == the 7x7/s2 conv exactly (same weights)."""
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import \
        _S2DStemConv

    mx.random.seed(0)
    conv = nn.Conv2D(16, 7, 2, 3, use_bias=False, in_channels=3)
    conv.initialize(init=mx.init.Xavier())
    conv.shape_init((1, 3, 64, 64))
    s2d = _S2DStemConv(16)
    s2d.initialize()
    s2d.shape_init((1, 3, 64, 64))
    s2d.weight.set_data(conv.weight.data())
    x = nd.random.uniform(shape=(2, 3, 64, 64))
    np.testing.assert_allclose(conv(x).asnumpy(), s2d(x).asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_ghost_bn_export_symbol_parity():
    """The ghost-BN perf variant must survive the export->symbol->Executor
    path with identical inference numerics (deploy parity)."""
    import os
    import tempfile

    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.resnet18_v1(classes=10, ghost_bn=8)
    net.initialize(init=mx.init.Xavier())
    net.shape_init((1, 3, 32, 32))
    x = nd.random.uniform(shape=(4, 3, 32, 32))
    ref = net(x).asnumpy()
    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "g")
        net.export(prefix)
        sym, args, aux = mx.model.load_checkpoint(prefix, 0)
    binds = dict(args)
    binds["data"] = x
    out = sym.bind(mx.cpu(), args=binds, aux_states=aux) \
        .forward(is_train=False)[0].asnumpy()
    np.testing.assert_allclose(ref, out, rtol=1e-4, atol=1e-4)


def test_ghost_bn_hybrid_bwd_matches_pallas_bwd(monkeypatch):
    """The fwd-only hybrid (Pallas fwd + jnp bwd over the same ghost
    groups) must produce the same gradients as the fully-fused path.
    Every operand has its own VMEM window, aliased or not: a residual
    layer's fwd costs 3 (X, R, Y) and its bwd 5 (gY, X, Y, dX, dR), so
    a budget between the two forces the hybrid."""
    from incubator_mxnet_tpu.parallel import fused_bn as fb

    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.normal(size=(8, 256, 6, 6)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(8, 256, 6, 6)).astype(np.float32))
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, 256).astype(np.float32))
    beta = jnp.asarray(rng.normal(size=256).astype(np.float32) * 0.2)

    def loss(x, gamma, beta, r):
        y, _, _ = fb.ghost_bn_act(x, gamma, beta, residual=r, group=4,
                                  donate_residual=True)
        return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()

    full_plan = fb._plan(8, 256, 36, 4, 4, True)
    assert full_plan is not None and full_plan[2], "precondition: full fuse"
    g_full = jax.grad(loss, argnums=(0, 1, 2, 3))(x, gamma, beta, res)

    # shrink the budget so exactly the bwd (5 windows) no longer fits
    # while the fwd (3) does;
    # tiling is disabled (_MAX_TILES=1) so the plan can't upgrade the
    # bwd to the round-20 spatial-tiled form — the jnp hybrid is still
    # reachable (prime L) and must keep matching
    itemsize = 4
    padded = 36 * fb._rup(4, fb._sublane(itemsize)) * fb._rup(256, 128) \
        * itemsize
    monkeypatch.setattr(fb, "_WINDOW_BUDGET", 3 * 2 * padded)
    monkeypatch.setattr(fb, "_MAX_TILES", 1)
    hybrid_plan = fb._plan(8, 256, 36, itemsize, 4, True)
    assert hybrid_plan is not None and not hybrid_plan[2], \
        "budget shrink must force the fwd-only hybrid, got %r" % (
            hybrid_plan,)
    g_hyb = jax.grad(loss, argnums=(0, 1, 2, 3))(x, gamma, beta, res)
    for a, b in zip(g_full, g_hyb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# round 20: lane-fold, spatial-tiled, and dual-cotangent kernel forms
# ---------------------------------------------------------------------------


def _plan_of(fb, shape, itemsize, group, has_res, dual=False):
    n, c, h, w = shape
    return fb._plan(n, c, h * w, itemsize, group, has_res, dual)


@pytest.mark.parametrize("dtype,tol", [(np.float32, 5e-4),
                                       (jnp.bfloat16, 2e-2)])
def test_ghost_bn_lanefold_matches_reference(monkeypatch, dtype, tol):
    """C < 128 pads its lanes to 128 anyway; the lane-fold form packs
    k = 128/C rows of L into that padding, shrinking the VMEM window by
    k with the same one-read forward kernel; the backward of a
    lane-folded layer is jnp by plan (it reads the folded view the
    forward saved).  Forced here by a budget under the whole-L window
    cost; outputs AND gradients must match the jnp ghost reference at
    the plan's own group."""
    from incubator_mxnet_tpu.parallel import fused_bn as fb

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.normal(size=(256, 32, 4, 4)), dtype)
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, 32), dtype)
    beta = jnp.asarray(rng.normal(size=32) * 0.2, dtype)
    itemsize = np.dtype(dtype).itemsize
    monkeypatch.setattr(fb, "_WINDOW_BUDGET", 200000 * itemsize // 4)
    plan = _plan_of(fb, x.shape, itemsize, 8, False)
    assert plan is not None and plan.variant == "lanefold" \
        and plan.bwd_variant == "jnp" and not plan.bwd_pallas \
        and plan.fold == 128 // 32, plan
    ng = plan.ab[0]

    y, m, v = ghost_bn_act(x, gamma, beta, group=8)
    yr, mr, vr = _ref(x, gamma, beta, group=ng)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr),
                               rtol=1e-3, atol=1e-3)

    def lk(x, gamma, beta):
        y, _, _ = ghost_bn_act(x, gamma, beta, group=8)
        return (y.astype(jnp.float32)
                * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()

    def lr(x, gamma, beta):
        y, _, _ = _ref(x, gamma, beta, group=ng)
        return (y.astype(jnp.float32)
                * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()

    gk = jax.grad(lk, argnums=(0, 1, 2))(x, gamma, beta)
    gr = jax.grad(lr, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol * 20, atol=tol * 20)


@pytest.mark.parametrize("dual", [False, True])
def test_ghost_bn_tiled_residual_matches_reference(monkeypatch, dual):
    """Spatial tiling with cross-tile stat accumulation: a budget under
    every whole-L window count forces the two-phase tiled kernels in
    BOTH directions (the 56x56x256 identity-exit regime).  Gradients —
    including the residual cotangent and, when ``dual``, the separate
    conv-path/shortcut cotangent pair — must match the jnp ghost
    reference."""
    from incubator_mxnet_tpu.parallel import fused_bn as fb

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.normal(size=(32, 128, 6, 6)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(32, 128, 6, 6)).astype(np.float32))
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, 128).astype(np.float32))
    beta = jnp.asarray(rng.normal(size=128).astype(np.float32) * 0.2)
    # whole-L window 36*16*128*4 = 294 912 B; 4-row tiles are 32 768 B:
    # 3 fwd windows x2 fit at 9 tiles, the 4 bwd phase-1 windows too
    # (262 144 B), the dual form's 5 only at 12 tiles of 3 rows
    monkeypatch.setattr(fb, "_WINDOW_BUDGET", 270000)
    plan = _plan_of(fb, x.shape, 4, 16, True, dual=dual)
    assert plan is not None and plan.variant == "tiled" \
        and plan.bwd_variant == "tiled" and plan.l_tile > 0, plan
    if dual:
        # the extra gY2 window forces a smaller bwd tile
        nd = _plan_of(fb, x.shape, 4, 16, True, dual=False)
        assert plan.l_tile_bwd < nd.l_tile_bwd, (plan, nd)
    ng = plan.ab[0]

    w1 = jnp.cos(jnp.arange(x.size).reshape(x.shape))
    w2 = jnp.sin(jnp.arange(x.size).reshape(x.shape))

    def lk(x, gamma, beta, r):
        if dual:
            y1, y2, _, _ = ghost_bn_act(x, gamma, beta, residual=r,
                                        group=16, dual_out=True)
            return (y1 * w1).sum() + (y2 * w2).sum()
        y, _, _ = ghost_bn_act(x, gamma, beta, residual=r, group=16)
        return (y * w1).sum() + (y * w2).sum()

    def lr(x, gamma, beta, r):
        y, _, _ = _ref(x, gamma, beta, residual=r, group=ng)
        return (y * w1).sum() + (y * w2).sum()

    gk = jax.grad(lk, argnums=(0, 1, 2, 3))(x, gamma, beta, res)
    gr = jax.grad(lr, argnums=(0, 1, 2, 3))(x, gamma, beta, res)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_ghost_bn_dual_whole_l_bitexact_vs_single(monkeypatch):
    """The dual-output block exit (``dual_out=True``) exists to absorb
    the residual-join ``add_any`` into the bwd kernel's window load; on
    the whole-L kernels the summed cotangent path must be BIT-exact
    against the single-output form."""
    from incubator_mxnet_tpu.parallel import fused_bn as fb

    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.normal(size=(32, 128, 6, 6)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(32, 128, 6, 6)).astype(np.float32))
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, 128).astype(np.float32))
    beta = jnp.asarray(rng.normal(size=128).astype(np.float32) * 0.2)
    plan = _plan_of(fb, x.shape, 4, 16, True, dual=True)
    assert plan is not None and plan.variant == "fused" \
        and plan.bwd_variant == "fused", plan

    w1 = jnp.cos(jnp.arange(x.size).reshape(x.shape))
    w2 = jnp.sin(jnp.arange(x.size).reshape(x.shape))

    def l_dual(x, gamma, beta, r):
        y1, y2, _, _ = ghost_bn_act(x, gamma, beta, residual=r, group=16,
                                    dual_out=True)
        return (y1 * w1).sum() + (y2 * w2).sum()

    def l_single(x, gamma, beta, r):
        y, _, _ = ghost_bn_act(x, gamma, beta, residual=r, group=16)
        return (y * w1).sum() + (y * w2).sum()

    y1, y2, m, v = ghost_bn_act(x, gamma, beta, residual=res, group=16,
                                dual_out=True)
    ys, ms, vs = ghost_bn_act(x, gamma, beta, residual=res, group=16)
    assert np.array_equal(np.asarray(y1), np.asarray(ys))
    assert np.array_equal(np.asarray(y2), np.asarray(ys))
    assert np.array_equal(np.asarray(m), np.asarray(ms))
    gd = jax.grad(l_dual, argnums=(0, 1, 2, 3))(x, gamma, beta, res)
    gs = jax.grad(l_single, argnums=(0, 1, 2, 3))(x, gamma, beta, res)
    for a, b in zip(gd, gs):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_ghost_bn_mixed_fused_fwd_tiled_bwd(monkeypatch):
    """Budget band where the whole-L fwd fits but the 5-window residual
    bwd does not: the plan keeps the one-read fwd and tiles only the
    backward (fused/tiled mix), and gradients still match the fully
    fused form."""
    from incubator_mxnet_tpu.parallel import fused_bn as fb

    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.normal(size=(8, 256, 6, 6)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(8, 256, 6, 6)).astype(np.float32))
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, 256).astype(np.float32))
    beta = jnp.asarray(rng.normal(size=256).astype(np.float32) * 0.2)

    def loss(x, gamma, beta, r):
        y, _, _ = ghost_bn_act(x, gamma, beta, residual=r, group=4,
                               donate_residual=True)
        return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum()

    g_full = jax.grad(loss, argnums=(0, 1, 2, 3))(x, gamma, beta, res)
    # whole-L window = 36*8*256*4 B; the fwd needs 3x2 of those
    # (1 769 472 B), the bwd 5x2 (2 949 120 B) — a budget between
    # forces the mix
    monkeypatch.setattr(fb, "_WINDOW_BUDGET", 1800000)
    plan = _plan_of(fb, x.shape, 4, 4, True)
    assert plan is not None and plan.variant == "fused" \
        and plan.bwd_variant == "tiled" and plan.l_tile_bwd > 0, plan
    g_mix = jax.grad(loss, argnums=(0, 1, 2, 3))(x, gamma, beta, res)
    for a, b in zip(g_full, g_mix):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# -- round-20 plan table: the ResNet-50 shapes at the REAL 104 MB budget --

# every distinct batch-256 bf16 BN layer of the bench workload, with the
# docs/PERF.md window arithmetic asserted in BYTES: padded window =
# rows x rup(ng, 16) x rup(lanes, 128) x itemsize, rows halved by the
# lane-fold factor, lanes = C (x fold for lane-fold), rows = l_tile for
# the spatial-tiled form.  (c, hw, res, dual) -> (variant, bwd, fold,
# l_tile, l_tile_bwd, window_bytes).  A donated residual is no column:
# it saves an HBM buffer, never a VMEM window, so it changes no plan.
R50_PLAN_TABLE = [
    # stem: 51.4 MB whole-L window can't fit 2 fwd windows double-
    # buffered; fold 2 packs the 64 channels twice into 128 lanes.  The
    # bwd's 3 windows (gY, X, dX) x 2 x 25.7 MB do not fit even folded
    ((64, 112, False, False),
     ("lanefold", "jnp", 2, 0, 0, 6272 * 16 * 128 * 2)),
    # C=64 at 56x56 pads to 128 lanes but fits whole-L
    ((64, 56, False, False),
     ("fused", "fused", 1, 0, 0, 3136 * 16 * 128 * 2)),
    # the 56x56x256 downsample shortcut (no residual): the whole-L fwd
    # fits (2 x 2 x 25.7 = 102.8 MB), the 3-window bwd tiles in half
    ((256, 56, False, False),
     ("fused", "tiled", 1, 0, 1568, 3136 * 16 * 256 * 2)),
    # 56x56x256 block exits: 3 fwd windows can't fit whole-L -> two-
    # phase tiled both directions; the dual bwd's 5 phase-1 windows
    # need quarter-L tiles
    ((256, 56, True, True),
     ("tiled", "tiled", 1, 1568, 784, 1568 * 16 * 256 * 2)),
    # 28x28x512 residual dual exit: the whole-L bwd is 6 windows x 2 x
    # 12.85 MB = 154 MB (the v5e compiler's "Scoped allocation with
    # size 122.50M" for 5) -> tiled bwd in half-L tiles
    ((512, 28, True, True),
     ("fused", "tiled", 1, 0, 392, 784 * 16 * 512 * 2)),
    # the 28x28 body convs and the 28x28x512 shortcut BN (no residual:
    # 3 bwd windows x 2 x 12.85 MB = 77 MB) fit whole-L both ways
    ((128, 28, False, False),
     ("fused", "fused", 1, 0, 0, 784 * 16 * 128 * 2)),
    ((512, 28, False, False),
     ("fused", "fused", 1, 0, 0, 784 * 16 * 512 * 2)),
    # deep stages: everything whole-L
    ((1024, 14, True, True),
     ("fused", "fused", 1, 0, 0, 196 * 16 * 1024 * 2)),
    ((2048, 7, True, False),
     ("fused", "fused", 1, 0, 0, 49 * 16 * 2048 * 2)),
]


@pytest.mark.parametrize("layer,want", R50_PLAN_TABLE,
                         ids=["%dx%d%s%s" % (c, hw, "_res" if r else "",
                                             "_dual" if du else "")
                              for (c, hw, r, du), _ in R50_PLAN_TABLE])
def test_round20_r50_plan_table(layer, want):
    """Shape -> variant selection at the real 104 MB VMEM budget, with
    the PERF.md window-byte arithmetic pinned exactly.  A budget or
    selection-order change that silently reshuffles which bench layers
    run which kernel form fails HERE with the layer named."""
    assert fb._WINDOW_BUDGET == 104 * 1024 * 1024
    c, hw, res, dual = layer
    variant, bwd, fold, lt, ltb, wb = want
    plan = fb._plan(256, c, hw * hw, 2, 16, res, dual)
    assert plan is not None, layer
    assert (plan.variant, plan.bwd_variant) == (variant, bwd), plan
    assert plan.bwd_pallas == (bwd != "jnp"), plan
    assert plan.fold == fold, plan
    assert (plan.l_tile or 0, plan.l_tile_bwd or 0) == (lt, ltb), plan
    assert plan.window_bytes == wb, (plan.window_bytes, wb)
