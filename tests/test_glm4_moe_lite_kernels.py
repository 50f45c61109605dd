"""What the ``glm4_moe_lite`` family asks of the shared kernels, at its own
shapes: latent attention against a formula head by head, with the shared
rotary key's gradient the sum over heads; the flash kernels at head size 256
over 20 ungrouped heads; the expert layer at 4 of 64 with scale 1.8 against
a loop over tokens, and the eight shares that add up; the row movers of
``parallel/moe_rows.py`` at 4 choices and rows of 1536 against the
``jax.numpy`` forms they replaced.  Pallas runs in interpret mode here; the
file takes under a minute."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.gluon.block import pure_forward
from incubator_mxnet_tpu.gluon.model_zoo import text
from incubator_mxnet_tpu.ndarray import NDArray
from incubator_mxnet_tpu.parallel import flash_attention, moe
from incubator_mxnet_tpu.parallel.ring_attention import attention_reference
from perfbench.references import glm47_flash as ref


# ---------------------------------------------------------------------------
# latent attention against a formula head by head
# ---------------------------------------------------------------------------

def _per_head(p, x, heads, nope, rope_dim, vd, latent, theta, eps=1e-5):
    """One sequence ``(S, d)``: every head's scores written out."""
    s = x.shape[0]
    cq = ref.rms_norm(x @ p["q_a"].T, p["q_a_norm"], eps)
    q = (cq @ p["q_b"].T).reshape(s, heads, nope + rope_dim)
    kv = x @ p["kv_a"].T
    kr = ref.rotate_half(kv[:, latent:], theta)
    up = (ref.rms_norm(kv[:, :latent], p["kv_a_norm"], eps)
          @ p["kv_b"].T).reshape(s, heads, nope + vd)
    keep = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for h in range(heads):
        q_h = jnp.concatenate([q[:, h, :nope],
                               ref.rotate_half(q[:, h, nope:], theta)], -1)
        k_h = jnp.concatenate([up[:, h, :nope], kr], -1)
        scores = jnp.where(keep, q_h @ k_h.T / math.sqrt(nope + rope_dim),
                           -jnp.inf)
        outs.append(jax.nn.softmax(scores, -1) @ up[:, h, nope:])
    return jnp.concatenate(outs, -1) @ p["o"].T, kr


def test_latent_attention_is_the_per_head_formula_and_sums_the_shared_key():
    heads, nope, rope_dim, vd, latent = 3, 8, 8, 16, 12
    block = text.LatentAttention(32, heads, 20, latent, nope, rope_dim, vd,
                                 rope_theta=1e6, prefix="attn_")
    block.initialize(init=mx.init.Xavier())
    x = jnp.asarray(np.random.RandomState(2).normal(size=(1, 24, 32)),
                    jnp.float32)
    block(mx.nd.array(x))     # resolves the deferred shapes
    params = {p.name[len("attn_"):]: p for p in
              block.collect_params().values()}
    assert sorted(params) == [
        "kv_a_norm_gamma", "kv_a_weight", "kv_b_weight", "o_weight",
        "q_a_norm_gamma", "q_a_weight", "q_b_weight"]
    assert params["kv_a_weight"].shape == (latent + rope_dim, 32)
    assert params["kv_b_weight"].shape == (heads * (nope + vd), latent)
    names = sorted(params)
    vals = [params[n].data()._data for n in names]

    def model(vals, x):
        return pure_forward(block, [params[n] for n in names], vals, x)[0]

    def formula(vals, x):
        p = {n.rsplit("_", 1)[0]: v for n, v in zip(names, vals)}
        return _per_head(p, x[0], heads, nope, rope_dim, vd, latent, 1e6)[0]

    want = jax.jit(formula)(vals, x)
    np.testing.assert_allclose(jax.jit(model)(vals, x)[0], want, rtol=2e-5,
                               atol=2e-6)
    weight = jnp.cos(want)
    got = jax.jit(jax.grad(lambda v, x: (model(v, x)[0] * weight).sum(),
                           (0, 1)))(vals, x)
    exp = jax.jit(jax.grad(lambda v, x: (formula(v, x) * weight).sum(),
                           (0, 1)))(vals, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(exp)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)
    # kr is ONE vector a position for all the heads: its gradient is the
    # sum of what each head's copy of it receives
    p = {n.rsplit("_", 1)[0]: v for n, v in zip(names, vals)}
    _, kr = _per_head(p, x[0], heads, nope, rope_dim, vd, latent, 1e6)

    def with_keys(copies):
        """The same attention with a rotary key of its own for each head."""
        s = x.shape[1]
        cq = ref.rms_norm(x[0] @ p["q_a"].T, p["q_a_norm"], 1e-5)
        q = (cq @ p["q_b"].T).reshape(s, heads, nope + rope_dim)
        up = (ref.rms_norm((x[0] @ p["kv_a"].T)[:, :latent], p["kv_a_norm"],
                           1e-5) @ p["kv_b"].T).reshape(s, heads, nope + vd)
        q = jnp.concatenate([q[..., :nope], jax.vmap(
            lambda a: ref.rotate_half(a, 1e6), 1, 1)(q[..., nope:])], -1)
        k = jnp.concatenate([up[..., :nope], copies], -1)
        out = attention_reference(
            q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
            up[..., nope:].transpose(1, 0, 2)[None], causal=True)
        return (out[0].transpose(1, 0, 2).reshape(s, -1) @ p["o"].T
                * weight).sum()

    each = jax.jit(jax.grad(with_keys))(jnp.broadcast_to(
        kr[:, None], (24, heads, rope_dim)))
    one = jax.jit(jax.grad(lambda kr: with_keys(jnp.broadcast_to(
        kr[:, None], (24, heads, rope_dim)))))(kr)
    np.testing.assert_allclose(one, each.sum(1), rtol=1e-5, atol=1e-7)
    assert float(jnp.abs(each[:, 0] - each[:, 1]).max()) > 1e-4


# ---------------------------------------------------------------------------
# the flash kernels at this family's shape
# ---------------------------------------------------------------------------

def test_flash_kernels_at_head_size_256_over_20_ungrouped_heads():
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 20, 64, 256)) * 0.5,
                           jnp.float32) for _ in range(3))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=16,
                               use_pallas=True)

    def dense(q, k, v):
        return attention_reference(q, k, v, causal=True)

    calls = str(jax.make_jaxpr(jax.grad(lambda *a: flash(*a).sum()))(q, k, v))
    assert [len(re.findall(r"name=%s\b" % name, calls)) for name in
            ("flash_fwd", "flash_bwd", "flash_bwd_dq", "flash_bwd_dkv")] == [
                1, 1, 0, 0]
    want = dense(q, k, v)
    np.testing.assert_allclose(flash(q, k, v), want, rtol=2e-5, atol=2e-5)
    weight = jnp.cos(want)
    got = jax.grad(lambda *a: (flash(*a) * weight).sum(), (0, 1, 2))(q, k, v)
    exp = jax.grad(lambda *a: (dense(*a) * weight).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, exp):
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-5)


def test_the_attention_op_runs_the_kernels_where_it_is_told_to(monkeypatch):
    """Without a window or grouped heads the op's default on a chip is XLA's
    fused attention, which holds heads x S x S scores; ``use_pallas`` is
    how latent attention asks for the kernels."""
    from incubator_mxnet_tpu import _backend

    monkeypatch.setattr(_backend, "pallas_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 2, 64, 16), jnp.float32)

    def op(**kw):
        return str(jax.make_jaxpr(lambda q, k, v: mx.nd.contrib.flash_attention(
            NDArray(q), NDArray(k), NDArray(v), causal=True, **kw)._data)(
                q, q, q))

    assert "pallas_call" not in op()
    assert "pallas_call" in op(use_pallas=True)


# ---------------------------------------------------------------------------
# the expert layer and its row movers at this family's numbers
# ---------------------------------------------------------------------------

_SCALE = 1.8


def _expert_weights(e, d, f, seed=0):
    rng = np.random.RandomState(seed)
    return {"router_weight": rng.normal(size=(e, d)) * 0.5,
            "bias": rng.normal(size=(e,)) * 0.01,
            "w1": rng.normal(size=(e, d, f)) * 0.3,
            "w3": rng.normal(size=(e, d, f)) * 0.3,
            "w2": rng.normal(size=(e, f, d)) * 0.3}


def _token_loop(x, w, held, top_k):
    """Token by token, choice by choice: the held experts' part."""
    first, count = held
    out = np.zeros_like(x)
    for t, row in enumerate(x):
        scores = 1.0 / (1.0 + np.exp(-(w["router_weight"] @ row)))
        chosen = np.argsort(-(scores + w["bias"]), kind="stable")[:top_k]
        weight = scores[chosen] / (scores[chosen].sum() + 1e-20) * _SCALE
        for e, c in zip(chosen, weight):
            if first <= e < first + count:
                gate = row @ w["w1"][e]
                h = gate / (1.0 + np.exp(-gate)) * (row @ w["w3"][e])
                out[t] += c * (h @ w["w2"][e])
    return out


@pytest.mark.parametrize("held", [(0, 8), (24, 8), (0, 64)])
def test_expert_layer_at_4_of_64_matches_a_loop_over_tokens(held):
    w = _expert_weights(64, 16, 24)
    x = np.random.RandomState(1).normal(size=(48, 16))
    first, count = held
    as32 = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    weights, sel, counts = moe.moe_route(
        jnp.asarray(x, jnp.float32), as32["router_weight"], as32["bias"],
        top_k=4, route_norm=True, route_scale=_SCALE)
    rows, sizes, row, order = moe.moe_dispatch(
        jnp.asarray(x, jnp.float32), sel, experts_held=held)
    assert rows.shape[0] == 48 * 4      # room for every assignment
    ys = moe.moe_experts(rows, *(as32[k][first:first + count]
                                 for k in ("w1", "w3", "w2")), sizes)
    got = moe.moe_combine(ys, weights, sizes, row, order)
    np.testing.assert_allclose(got, _token_loop(x, w, held, 4), rtol=1e-4,
                               atol=1e-5)
    assert float(counts.sum()) == 48 * 4
    np.testing.assert_allclose(weights.sum(-1), _SCALE, rtol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer_at_4_of_64():
    """What each of eight chips computes of one expert layer (its own 8 of
    the 64 experts' part, plus the shared expert that every chip computes
    alike), with the shared expert counted once, is the uncut reference's
    layer."""
    d, f = 32, 24
    cfg = dict(num_experts_per_tok=4, route_norm=True, route_scale=_SCALE,
               experts_held=(0, 64))
    whole = _expert_weights(64, d, f, seed=5)
    rng = np.random.RandomState(6)
    whole.update({"shared_w1_weight": rng.normal(size=(f, d)) * 0.3,
                  "shared_w3_weight": rng.normal(size=(f, d)) * 0.3,
                  "shared_w2_weight": rng.normal(size=(d, f)) * 0.3})
    whole = {k: jnp.asarray(v, jnp.float32) for k, v in whole.items()}
    x = mx.nd.array(np.random.RandomState(4).normal(size=(2, 12, d)))
    total = 0.0
    for chip in range(8):
        first, count = 8 * chip, 8
        block = text.ExpertFFN(d, 64, 4, f, experts_held=(first, count),
                               route_scale=_SCALE, prefix="moe_")
        block.initialize(init=mx.init.Xavier())
        block(x)    # resolves the deferred shapes
        for p in block.collect_params().values():
            name = p.name[len(block.prefix):]
            if name != "counts":
                value = whole[name]
                p.set_data(value[first:first + count]
                           if name in ("w1", "w3", "w2") else value)
        total = total + block(x).asnumpy()
    flat = x._data.reshape(-1, d)
    shared = ref.gated_ffn(flat, whole["shared_w1_weight"],
                           whole["shared_w3_weight"],
                           whole["shared_w2_weight"]).reshape(2, 12, d)
    want, _ = ref.expert_ffn(whole, "", flat, cfg)
    np.testing.assert_allclose(total - 7 * np.asarray(shared),
                               np.asarray(want).reshape(2, 12, d),
                               rtol=1e-4, atol=1e-5)


_T, _K, _D = 96, 4, 1536    # 384 rows of 1536: a slab padded to 2048 words


def _pick(ys, row, held):
    return jnp.where(held[..., None], ys[row], 0).astype(jnp.float32)


def _rows_scaled(x, ys, weights, row, order, n):
    """``_weighted_sum_bwd`` as it was before the row kernels, ``x`` the
    tokens' cotangent."""
    held = row < n
    w_row = jnp.where(held, weights, 0.0).reshape(-1)[order]
    dys = w_row[:, None] * x[order // _K].astype(jnp.float32)
    dw = jnp.sum(x[:, None, :].astype(jnp.float32) * _pick(ys, row, held), -1)
    return dys.astype(ys.dtype), dw


#: each mover beside the ``jax.numpy`` form it replaced
_MOVERS = {
    "rows_scaled": (
        lambda x, ys, w, row, order, n:
        moe._weighted_sum_bwd((ys, w, row, order, n), x)[:2], _rows_scaled),
    "tokens_weighted": (
        lambda x, ys, w, row, order, n:
        moe._weighted_sum(ys, w, row, order, n),
        lambda x, ys, w, row, order, n:
        jnp.sum(w[..., None] * _pick(ys, row, row < n), 1).astype(ys.dtype)),
    "tokens_plain": (
        lambda x, ys, w, row, order, n:
        moe._gather_rows_bwd((row, n), ys)[0],
        lambda x, ys, w, row, order, n:
        jnp.sum(_pick(ys, row, row < n), 1).astype(ys.dtype)),
}


@pytest.mark.parametrize("n", [0, 1, 257, _T * _K])
@pytest.mark.parametrize("name", sorted(_MOVERS))
def test_a_row_mover_at_4_choices_and_rows_of_1536_is_the_form_it_replaced(
        name, n):
    """Rows of 1536 values do not fill a slab of whole (8, 128) tiles of
    words (2048 values in bfloat16): the padding is the kernels' own."""
    rng = np.random.RandomState(6)
    order = jnp.asarray(rng.permutation(_T * _K), jnp.int32)
    mover, oracle = map(jax.jit, _MOVERS[name])
    ulp = 2.0 ** -7 if name.startswith("tokens") else 0
    for dtype, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, ulp)):
        args = (jnp.asarray(rng.normal(size=(_T, _D)), dtype),
                jnp.asarray(rng.normal(size=(_T * _K, _D)), dtype),
                jnp.asarray(rng.uniform(size=(_T, _K)), jnp.float32),
                jnp.argsort(order).astype(jnp.int32).reshape(_T, _K), order,
                jnp.int32(n))
        got, want = mover(*args), oracle(*args)
        if name == "rows_scaled":
            held = np.asarray(args[3]) < n
            # a weight's gradient is a sum over the row's 1536 columns
            np.testing.assert_allclose(np.where(held, got[1], 0), want[1],
                                       rtol=1e-4, atol=1e-4)
            got, want = got[0][:n], want[0][:n]
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=tol,
                                   atol=tol * 4)
