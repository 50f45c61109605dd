"""``FunctionalOptimizer``, the update every fused step runs, against its
mathematics written out in plain numpy (float64) in this file.

Two steps, so that state carried from the first is used by the second and
the bias corrections see t = 1 and t = 2; two tensors of different shapes,
so that lamb's trust ratio is one number a TENSOR; the deltas are compared,
not the weights, so that an update of 1e-5 on a weight of 1 cannot hide in
the weight's own rounding.

The formulas (g' = clip(rescale_grad * g, +-clip_gradient)):

- ``sgd``:           w -= lr * (g' + wd * w)
- ``sgd``, momentum: v = momentum * v - lr * (g' + wd * w);  w += v
- ``adam`` (the reference's ``adam_update``: weight decay INSIDE the
  gradient):  g'' = g' + wd * w;  m = b1 m + (1 - b1) g'';
  v = b2 v + (1 - b2) g''^2;
  w -= lr * sqrt(1 - b2^t) / (1 - b1^t) * m / (sqrt(v) + eps)
- ``adamw`` (Loshchilov & Hutter, arXiv:1711.05101: decoupled decay):
  m, v from g';  u = (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps) + wd * w;
  w -= lr * u
- ``lamb`` (You et al., arXiv:1904.00962): adamw's u, scaled a tensor by
  ||w|| / ||u|| (1 where either norm is 0)
- ``multi_precision``: the same update on a float32 master copy kept in the
  state; the parameter is the master copy rounded to its own dtype.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from incubator_mxnet_tpu.parallel import FunctionalOptimizer

LR, MOMENTUM, B1, B2, EPS = 0.1, 0.9, 0.9, 0.999, 1e-8
KINDS = {
    "sgd": dict(name="sgd", momentum=0.0),
    "sgd_momentum": dict(name="sgd", momentum=MOMENTUM),
    "adam": dict(name="adam"),
    "adamw": dict(name="adamw"),
    "lamb": dict(name="lamb"),
}
SHAPES = ((6, 5), (7,))


def _tensors(seed, scale):
    rng = np.random.RandomState(seed)
    return [(scale * rng.standard_normal(s)).astype(np.float32)
            for s in SHAPES]


def _reference(kind, w, grads, lr, wd, clip, rescale):
    """One tensor through ``len(grads)`` steps in float64: the weight after
    each step and the state leaves after the last."""
    w = w.astype(np.float64)
    mom = m = v = np.zeros_like(w)
    out = []
    for t, g in enumerate(grads, 1):
        g = rescale * g.astype(np.float64)
        if clip > 0:
            g = np.clip(g, -clip, clip)
        if kind == "sgd":
            w = w - lr * (g + wd * w)
        elif kind == "sgd_momentum":
            mom = MOMENTUM * mom - lr * (g + wd * w)
            w = w + mom
        else:
            if kind == "adam":
                g = g + wd * w
            m = B1 * m + (1 - B1) * g
            v = B2 * v + (1 - B2) * g * g
            if kind == "adam":
                w = w - (lr * np.sqrt(1 - B2 ** t) / (1 - B1 ** t)
                         * m / (np.sqrt(v) + EPS))
            else:
                u = (m / (1 - B1 ** t)) / (np.sqrt(v / (1 - B2 ** t))
                                           + EPS) + wd * w
                if kind == "lamb":
                    r1, r2 = np.linalg.norm(w), np.linalg.norm(u)
                    u = u * (r1 / r2 if r1 > 0 and r2 > 0 else 1.0)
                w = w - lr * u
        out.append(w)
    state = {"sgd": (), "sgd_momentum": (mom,)}.get(kind, (m, v))
    return out, state


def _leaves(state):
    if state is None:
        return ()
    return tuple(state) if isinstance(state, (tuple, list)) else (state,)


def _two_steps(opt, weights, grads):
    """``apply`` twice, the fused step's way: a 1-based step count, the
    second step fed the first one's weights and state."""
    states = opt.init(weights)
    seen = []
    for t, g in enumerate(grads, 1):
        weights, states = opt.apply(weights, g, states, jnp.int32(t))
        seen.append(weights)
    return seen, states


@pytest.mark.parametrize("rescale", [1.0, 1.0 / 256], ids=["r1", "r256"])
@pytest.mark.parametrize("clip", [False, True], ids=["noclip", "clip"])
@pytest.mark.parametrize("wd", [0.0, 0.01], ids=["wd0", "wd"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_two_steps_match_the_formulas(kind, wd, clip, rescale):
    w0 = _tensors(1, 1.0)
    # gradients as a loss scaled by 1 / rescale leaves them
    grads = [_tensors(2, 0.1 / rescale), _tensors(3, 0.1 / rescale)]
    # half a standard deviation of the rescaled gradient: it bites
    clip_at = 0.05 if clip else -1.0
    opt = FunctionalOptimizer(learning_rate=LR, wd=wd, beta1=B1, beta2=B2,
                              epsilon=EPS, clip_gradient=clip_at,
                              rescale_grad=rescale, **KINDS[kind])
    got, states = _two_steps(opt, [jnp.asarray(w) for w in w0],
                             [[jnp.asarray(g) for g in gs] for gs in grads])
    assert opt.has_state == (kind != "sgd")
    for i, w in enumerate(w0):
        want, want_state = _reference(kind, w, [gs[i] for gs in grads], LR,
                                      wd, clip_at, rescale)
        before = w.astype(np.float64)
        for step in (0, 1):
            assert got[step][i].dtype == jnp.float32
            # 4e-7: a float32 weight of size 1 is known to 6e-8
            np.testing.assert_allclose(
                np.asarray(got[step][i], np.float64) - before,
                want[step] - before, rtol=1e-4, atol=4e-7,
                err_msg="%s tensor %d step %d" % (kind, i, step + 1))
        got_state = _leaves(states[i]) if opt.has_state else ()
        assert len(got_state) == len(want_state)
        for a, b in zip(got_state, want_state):
            np.testing.assert_allclose(np.asarray(a, np.float64), b,
                                       rtol=1e-4, atol=1e-12)


@pytest.mark.parametrize("wd", [0.0, 0.01], ids=["wd0", "wd"])
@pytest.mark.parametrize("kind", ["sgd", "sgd_momentum", "adam"])
def test_master_weights_take_the_update_the_bf16_parameter_cannot(kind, wd):
    """``multi_precision`` on bf16 parameters: the update is the float32
    formula applied to the master copy in the state's last leaf, and the
    parameter is that copy rounded.  The learning rate is chosen so that
    one update (1e-5 of a weight near 1, where a bf16 ulp is 4e-3 to 8e-3)
    is lost on a bf16 weight: the parameter stands still, the master copy
    moves, and after two steps it has moved twice."""
    lr = 1e-4
    bf16 = jnp.bfloat16
    w0 = [np.asarray(jnp.asarray(w, bf16).astype(jnp.float32))
          for w in _tensors(1, 1.0)]
    grads = [[np.asarray(jnp.asarray(g, bf16).astype(jnp.float32))
              for g in _tensors(seed, 0.1)] for seed in (2, 3)]
    opt = FunctionalOptimizer(learning_rate=lr, wd=wd, beta1=B1, beta2=B2,
                              epsilon=EPS, multi_precision=True,
                              **KINDS[kind])
    got, states = _two_steps(
        opt, [jnp.asarray(w, bf16) for w in w0],
        [[jnp.asarray(g, bf16) for g in gs] for gs in grads])
    for i, w in enumerate(w0):
        want, want_state = _reference(kind, w, [gs[i] for gs in grads], lr,
                                      wd, -1.0, 1.0)
        *accumulators, master = _leaves(states[i])
        assert master.dtype == jnp.float32
        assert all(a.dtype == jnp.float32 for a in accumulators)
        before = w.astype(np.float64)
        np.testing.assert_allclose(np.asarray(master, np.float64) - before,
                                   want[1] - before, rtol=1e-3, atol=2e-7)
        for a, b in zip(accumulators, want_state):
            np.testing.assert_allclose(np.asarray(a, np.float64), b,
                                       rtol=1e-4, atol=1e-12)
        for step in (0, 1):
            assert got[step][i].dtype == bf16
        np.testing.assert_array_equal(
            np.asarray(got[1][i].astype(jnp.float32)),
            np.asarray(master.astype(bf16).astype(jnp.float32)))
        # the master copy moved where the rounded parameter could not
        moved = np.abs(np.asarray(master, np.float64) - before)
        stood = np.asarray(got[1][i].astype(jnp.float32)) == w
        assert stood.mean() > 0.75 and (moved[stood] > 0).all()
