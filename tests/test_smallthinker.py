"""The ``smallthinker`` decoder family (``gluon.model_zoo.text``: a router
that reads the block's input before the attention, softmax over the chosen
logits, ReLU-gated experts, no shared expert, a full layer without rotary
embedding before the window layers) at its tiny preset against the plain
reference of the benchmark (``perfbench/references/smallthinker_21b.py``):
logits, loss and every leaf of the gradient in float32; what the family
asks of the shared kernels at its own shapes (the flash kernels at 28 query
heads over 4, the expert layer's ops at 6 of 64 under the second scoring
rule and gate, the eight shares that add up, the row movers at rows of
2,560); the configuration's file against the catalog row.  (The benchmark's
controls are in ``test_smallthinker_controls.py``.)  Pallas runs in
interpret mode here; the file takes about a minute."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon
from incubator_mxnet_tpu.gluon.block import pure_forward
from incubator_mxnet_tpu.gluon.model_zoo import text
from incubator_mxnet_tpu.gluon.parameter import shape_only_init
from incubator_mxnet_tpu.ndarray import NDArray
from incubator_mxnet_tpu.parallel import flash_attention, moe
from incubator_mxnet_tpu.parallel.ring_attention import attention_reference
from perfbench.references import smallthinker_21b as ref
from perfbench.runners import train_tokens as tt

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DATA = os.path.join(_ROOT, "tests", "benchmark_tests", "data_smallthinker")
_SEQ, _ROWS, _HELD = 32, 48, (2, 4)
#: the tiny preset's numbers, as ``ref.model_cfg`` wants them
_CONFIG = json.load(open(os.path.join(_DATA, "bench", "configs",
                                      "tiny_smallthinker.json")))
#: the published router; the tiny cell itself centres the selection
_CFG = dict(ref.model_cfg(_CONFIG), centred_selection=0)


# ---------------------------------------------------------------------------
# the zoo's model against the plain reference
# ---------------------------------------------------------------------------

def _resolved(net, batch, seq):
    net.initialize(init=mx.init.Xavier())
    with shape_only_init():
        out = jax.eval_shape(lambda x: pure_forward(net, [], [], x)[0],
                             jax.ShapeDtypeStruct((batch, seq), "int32"))
    return net, out


@pytest.fixture(scope="module", params=[0, 4], ids=["published", "centred"])
def tiny(request):
    """The tiny net with its shapes resolved abstractly, seeded weights by
    the reference's names (norm scales away from one, so that their
    gradients mean something; a selection bias that moves some choices),
    one batch of two sequences, and the reference's ``cfg``: the published
    router, and the one whose selection is centred over blocks of 4 tokens."""
    net, _ = _resolved(text.smallthinker_tiny(
        experts_held=_HELD, vocab_rows=_ROWS, recompute=True,
        centred_selection=request.param), 2, _SEQ)
    weights = tt.Weights(net, 3).by_name()
    key = jax.random.PRNGKey(5)
    for i, name in enumerate(sorted(weights)):
        if name.endswith("_gamma"):
            weights[name] = 1.0 + 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), weights[name].shape)
        if name.endswith("_bias"):
            weights[name] = 0.002 * jax.random.normal(
                jax.random.fold_in(key, i), weights[name].shape)
    ids = np.random.RandomState(1).randint(0, _ROWS, (2, _SEQ + 1))
    return net, weights, jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:]), \
        dict(_CFG, centred_selection=request.param)


def _reference_params(weights):
    return {k: v for k, v in weights.items() if not k.endswith("_counts")}


def _model_logits(net, weights, x, vals=None):
    names = tt.short_names(net)
    trained = [p for p in names if p.grad_req != "null"]
    fixed = [p for p in names if p.grad_req == "null"]
    if vals is None:
        vals = [weights[names[p]] for p in trained]
    return pure_forward(
        net, trained + fixed, vals + [weights[names[p]] for p in fixed], x,
        training=True)[0]


def _model_loss(net, weights, x, y, vals=None):
    logits = _model_logits(net, weights, x, vals)
    return gluon.loss.SoftmaxCrossEntropyLoss()(
        NDArray(logits), NDArray(y)).mean()._data


def _worst(grads, want):
    errs = {}
    for name, g in grads.items():
        errs.update(tt._leaf_errors(name, g, want[name]))
    return max(errs.values()), max(errs, key=errs.get)


def test_building_the_family_allocates_nothing_and_counts_the_issues_numbers():
    net = text.smallthinker_tiny()
    net.initialize(init=mx.init.Xavier())
    pending = [p.name for p in net.collect_params().values()
               if p._data is None]
    # all but the three expert layers' selection bias and counters
    assert len(pending) == len(net.collect_params()) - 3 * 2
    names = sorted(tt.short_names(net).values())
    # two norms and four projections a block, no shared expert, no gate
    assert [n for n in names if n.startswith("layer1_")] == [
        "layer1_attn_k_weight", "layer1_attn_o_weight",
        "layer1_attn_q_weight", "layer1_attn_v_weight", "layer1_moe_bias",
        "layer1_moe_counts", "layer1_moe_router_weight", "layer1_moe_w1",
        "layer1_moe_w2", "layer1_moe_w3", "layer1_norm1_gamma",
        "layer1_norm2_gamma"]

    def trained(net):
        return sum(int(np.prod(p.shape))
                   for p in net.collect_params().values()
                   if p.grad_req != "null")

    cut, out = _resolved(text.smallthinker_21b(
        num_layers=4, experts_held=(0, 8), vocab_rows=18992), 1, 16384)
    assert out.shape == (1, 16384, 18992) and out.dtype == jnp.float32
    assert trained(cut) == 370547200
    assert all(p._data is None or p.shape == (64,)
               for p in cut.collect_params().values())
    whole, _ = _resolved(text.smallthinker_21b(), 1, 128)
    assert trained(whole) == 21506562560 \
        == 52 * 398627840 + 2 * 151936 * 2560 + 2560
    # the full layer first in the period, rotary exactly on the window layers
    assert [(a._window, a._theta) for a in
            (layer.attn for layer in cut.layers)] == [
                (None, None)] + [(4096, 1500000)] * 3
    with pytest.raises(TypeError, match="config.json"):
        text.smallthinker_21b(no_such_key=1)
    with pytest.raises(ValueError, match="experts_held"):
        text.smallthinker_tiny(experts_held=(6, 4))
    with pytest.raises(ValueError, match="names 4 layers"):
        text.smallthinker_tiny(num_layers=5)


def test_only_the_published_scoring_rule_is_built():
    """The family builds the softmax over the chosen logits and nothing
    else: the zoo and the plain reference both refuse a configuration
    without it, rather than hold a second rule that nothing runs."""
    with pytest.raises(ValueError, match="softmax over the chosen"):
        text.smallthinker_tiny(moe_primary_router_apply_softmax=False)
    config = json.load(open(os.path.join(
        _ROOT, "perfbench", "configs", "smallthinker_21b.json")))
    with pytest.raises(ValueError, match="softmax over the chosen"):
        ref.model_cfg(dict(config, moe_primary_router_apply_softmax=False))


def test_tiny_model_matches_the_plain_reference_in_float32(tiny):
    net, weights, x, y, cfg = tiny
    p = _reference_params(weights)
    names = tt.short_names(net)
    trained = [names[q] for q in names if q.grad_req != "null"]
    value, grads = jax.jit(jax.value_and_grad(
        lambda vals: _model_loss(net, weights, x, y, vals)))(
            [weights[name] for name in trained])
    grads = dict(zip(trained, grads))
    want, want_grads, _ = jax.jit(
        lambda p: ref.loss_and_grads(p, x, y, cfg))(p)
    assert abs(float(value) - float(want)) <= 1e-5 * float(want)
    assert set(grads) == set(want_grads)
    worst, leaf = _worst(grads, want_grads)
    # float32 on both sides: rounding and the order of sums
    assert worst < 2e-5, (leaf, worst)

    @jax.jit
    def reference_logits(p):
        outer, layers = ref._split(p, cfg)
        out = []
        for n in range(x.shape[0]):
            h = outer["embed_weight"][x[n]]
            for kind, pl in zip(cfg["layer_types"], layers):
                h, _ = ref.layer(pl, h, kind == "sliding_attention", cfg=cfg)
            out.append(ref.rms_norm(h, outer["norm_gamma"], 1e-6)
                       @ outer["head_weight"].T)
        return jnp.stack(out)

    logits = jax.jit(lambda: _model_logits(net, weights, x))()
    assert logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, reference_logits(p), rtol=2e-4,
                               atol=2e-6)


def test_reference_block_by_block_agrees_with_its_loss_differentiated_whole(
        tiny):
    _, weights, x, y, cfg = tiny
    p = _reference_params(weights)
    trained = {k: v for k, v in p.items() if not k.endswith("_bias")}
    whole, grads = jax.jit(jax.value_and_grad(
        lambda t: ref.loss(dict(p, **t), x, y, cfg)))(trained)
    want, want_grads, _ = jax.jit(
        lambda p: ref.loss_and_grads(p, x, y, cfg))(p)
    assert abs(float(whole) - float(want)) <= 1e-6 * float(want)
    assert set(grads) == set(want_grads)
    worst, leaf = _worst(grads, want_grads)
    assert worst < 2e-5, (leaf, worst)


def test_a_recomputed_block_keeps_its_routers_choice_and_flash_forward(tiny):
    net, weights, x, y, cfg = tiny
    names = tt.short_names(net)
    trained = [names[p] for p in names if p.grad_req != "null"]
    text_ = str(jax.make_jaxpr(jax.grad(
        lambda vals: _model_loss(net, weights, x, y, vals)))(
            [weights[name] for name in trained]))
    # three blocks: one forward kernel and ONE backward kernel each, and no
    # second top-k in the recomputed forward
    assert text_.count("name=flash_fwd") == 3
    assert len(re.findall(r"name=flash_bwd\b", text_)) == 3
    assert len(re.findall(r"\btop_k\[", text_)) == 3


def test_the_reference_follows_another_routers_choice_within_a_relative_margin():
    """The margin is in units of a token's own spread of logits, so the same
    ``eps`` means the same for a router that reads embedding rows of rms
    0.01 and for one that reads a stream of rms 1."""
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    for scale in (1.0, 0.01):
        xs = x * scale
        scores = xs @ w.T
        _, own = jax.lax.top_k(scores, 2)
        order = jnp.argsort(-scores, -1)
        # every token's second choice swapped for its third
        forced = jnp.stack([order[:, 0], order[:, 2]], -1)
        gap = (jnp.take_along_axis(scores, order[:, 1:2], -1)
               - jnp.take_along_axis(scores, order[:, 2:3], -1))[:, 0] \
            / jnp.std(scores, -1)
        eps = float(jnp.median(gap))
        weights, sel, facts = ref.route(xs, w, jnp.zeros(8), _CFG, forced,
                                        eps)
        followed = np.asarray(gap <= eps)
        assert 0 < followed.sum() < 40
        np.testing.assert_array_equal(
            np.sort(sel, -1), np.sort(np.where(followed[:, None], forced,
                                               own), -1))
        np.testing.assert_allclose(facts["refused"][2], 1 - followed.mean(),
                                   rtol=1e-6)
        np.testing.assert_allclose(facts["moved"], 0.5)
        # the weights: a softmax over the logits of the experts taken
        np.testing.assert_allclose(weights, jax.nn.softmax(
            jnp.take_along_axis(scores, sel, -1), -1), rtol=1e-6)


# ---------------------------------------------------------------------------
# the flash kernels at this family's shape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [24, None], ids=["window", "full"])
def test_flash_kernels_at_28_query_heads_over_4(window):
    """A group of seven: the kernels' head arithmetic divides by a number
    that is no power of two, and dk, dv outlive seven query heads."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.normal(size=(1, 28, 64, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 4, 64, 16)), jnp.float32)
            for _ in range(2))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=16, block_k=16)

    def dense(q, k, v):
        return attention_reference(q, k, v, causal=True, window=window)

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


# ---------------------------------------------------------------------------
# the expert layer at 6 of 64: softmax over the chosen, ReLU gates
# ---------------------------------------------------------------------------

def _expert_weights(e, d, f, seed=0):
    rng = np.random.RandomState(seed)
    return {"router_weight": rng.normal(size=(e, d)),
            "bias": rng.normal(size=(e,)) * 0.1,
            "w1": rng.normal(size=(e, d, f)) * 0.3,
            "w3": rng.normal(size=(e, d, f)) * 0.3,
            "w2": rng.normal(size=(e, f, d)) * 0.3}


def _token_loop(routed_on, x, w, held, top_k):
    """The layer written out a token at a time, in float64."""
    first, count = held
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        logits = w["router_weight"] @ routed_on[t]
        sel = np.argsort(-(logits + w["bias"]), kind="stable")[:top_k]
        weights = np.exp(logits[sel] - logits[sel].max())
        weights /= weights.sum()
        for e, weight in zip(sel, weights):
            if first <= e < first + count:
                gate = np.maximum(x[t] @ w["w1"][e], 0.0)
                out[t] += weight * ((gate * (x[t] @ w["w3"][e]))
                                    @ w["w2"][e])
    return out


@pytest.mark.parametrize("held", [(0, 8), (24, 8), (0, 64)])
def test_expert_ops_at_6_of_64_match_a_loop_over_tokens(held):
    w = _expert_weights(64, 16, 24)
    rng = np.random.RandomState(1)
    x, routed_on = rng.normal(size=(48, 16)), rng.normal(size=(48, 16))
    first, count = held
    as32 = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    weights, sel, counts = moe.moe_route(
        jnp.asarray(routed_on, jnp.float32), as32["router_weight"],
        as32["bias"], top_k=6, score="softmax")
    rows, sizes, row, order = moe.moe_dispatch(
        jnp.asarray(x, jnp.float32), sel, experts_held=held)
    assert rows.shape[0] == 48 * 6      # room for every assignment
    ys = moe.moe_experts(rows, *(as32[k][first:first + count]
                                 for k in ("w1", "w3", "w2")), sizes,
                         act="relu")
    got = moe.moe_combine(ys, weights, sizes, row, order)
    np.testing.assert_allclose(got, _token_loop(routed_on, x, w, held, 6),
                               rtol=1e-4, atol=1e-5)
    assert float(counts.sum()) == 48 * 6
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)
    with pytest.raises(ValueError, match="score"):
        moe.moe_route(as32["w1"][0], as32["router_weight"], as32["bias"],
                      score="tanh")


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_centred_selection_leaves_out_what_neighbouring_tokens_share(score):
    """``centred`` = n: the choice reads each expert's score less its mean
    over the token's block of n consecutive tokens, so a part of the
    router's input that a block's tokens share chooses nothing, while the
    weights are the chosen scores' as ever.  Four blocks of 12, each with a
    shared part of its own."""
    rng = np.random.RandomState(2)
    w = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    bias = jnp.asarray(0.05 * rng.normal(size=64), jnp.float32)
    apart = jnp.asarray(0.3 * rng.normal(size=(48, 16)), jnp.float32)
    shared = jnp.repeat(jnp.asarray(rng.normal(size=(4, 16)), jnp.float32),
                        12, 0)
    x = apart + shared
    weights, sel, counts = moe.moe_route(x, w, bias, top_k=6, score=score,
                                         centred=12)
    scores = np.asarray(x, np.float64) @ np.asarray(w, np.float64).T
    if score == "sigmoid":
        scores = 1 / (1 + np.exp(-scores))
    blocks = scores.reshape(4, 12, 64)
    select = (blocks - blocks.mean(1, keepdims=True)).reshape(48, 64) \
        + np.asarray(bias)
    want = np.argsort(-select, -1, kind="stable")[:, :6]
    np.testing.assert_array_equal(np.sort(sel, -1), np.sort(want, -1))
    taken = np.take_along_axis(scores, np.asarray(sel), -1)
    np.testing.assert_allclose(
        weights, np.exp(taken) / np.exp(taken).sum(-1, keepdims=True)
        if score == "softmax" else taken / taken.sum(-1, keepdims=True),
        rtol=1e-5)
    assert float(counts.sum()) == 48 * 6
    # a block alone chooses as it does among the others
    _, alone, _ = moe.moe_route(x[12:24], w, bias, top_k=6, score=score,
                                centred=12)
    np.testing.assert_array_equal(alone, sel[12:24])
    with pytest.raises(ValueError, match="blocks of centred=10"):
        moe.moe_route(x, w, bias, top_k=6, score=score, centred=10)
    if score == "softmax":
        # logits are linear: what a block shares moves every token's logit
        # of an expert alike, the centred choice not at all, the plain one a
        # lot
        _, apart_sel, _ = moe.moe_route(apart, w, bias, top_k=6,
                                        score=score, centred=12)
        np.testing.assert_array_equal(np.sort(apart_sel, -1),
                                      np.sort(sel, -1))
        _, plain, _ = moe.moe_route(x, w, bias, top_k=6, score=score)
        moved = np.mean([len(set(a) - set(b)) / 6 for a, b in
                         zip(np.asarray(plain), np.asarray(sel))])
        assert moved > 0.3
        # the plain reference's route does the same
        cfg = dict(_CFG, num_experts_per_tok=6, centred_selection=12)
        _, own, _ = ref.route(x, w, bias, cfg)
        np.testing.assert_array_equal(np.sort(own, -1), np.sort(sel, -1))


def test_the_reference_reads_centred_selection_from_the_factorys_keywords():
    kwargs = dict(_CONFIG["factory_kwargs"])
    assert kwargs.pop("centred_selection") == 4
    assert ref.model_cfg(_CONFIG)["centred_selection"] == 4
    assert ref.model_cfg(dict(_CONFIG, factory_kwargs=kwargs))[
        "centred_selection"] == 0
    # off unless asked for: the zoo builds the published router
    net = text.smallthinker_tiny()
    assert all("centred" not in layer.ffn._route for layer in net.layers)
    net = text.smallthinker_tiny(centred_selection=4)
    assert all(layer.ffn._route["centred"] == 4 for layer in net.layers)


def test_the_eight_shares_add_up_to_the_uncut_layer_at_6_of_64():
    """What each of eight chips computes of one expert layer (its own 8 of
    the 64 experts' part under the routing of the block's input; there is no
    shared expert to count once) adds up to the uncut reference's layer."""
    d, f = 32, 24
    cfg = dict(_CFG, num_experts_per_tok=6, experts_held=(0, 64))
    whole = {k: jnp.asarray(v, jnp.float32)
             for k, v in _expert_weights(64, d, f, seed=5).items()}
    rng = np.random.RandomState(4)
    x, routed_on = (mx.nd.array(rng.normal(size=(2, 12, d)))
                    for _ in range(2))
    total = 0.0
    for chip in range(8):
        first, count = 8 * chip, 8
        block = text.ExpertFFN(d, 64, 6, f, experts_held=(first, count),
                               shared=False, score="softmax", act="relu",
                               prefix="moe_")
        block.initialize(init=mx.init.Xavier())
        block(x, routed_on)    # resolves the deferred shapes
        assert sorted(p.name for p in block.collect_params().values()) == [
            "moe_bias", "moe_counts", "moe_router_weight", "moe_w1",
            "moe_w2", "moe_w3"]
        for p in block.collect_params().values():
            name = p.name[len(block.prefix):]
            if name != "counts":
                value = whole[name]
                p.set_data(value[first:first + count]
                           if name in ("w1", "w3", "w2") else value)
        total = total + block(x, routed_on).asnumpy()
    want, _ = ref.expert_ffn(whole, "", routed_on._data.reshape(-1, d),
                             x._data.reshape(-1, d), cfg)
    np.testing.assert_allclose(total, np.asarray(want).reshape(2, 12, d),
                               rtol=1e-4, atol=1e-5)
    # and the router really read the other tensor
    flat = x._data.reshape(-1, d)
    same = np.asarray(ref.expert_ffn(whole, "", flat, flat, cfg)[0])
    assert np.abs(total - same.reshape(2, 12, d)).max() > 1e-2


# ---------------------------------------------------------------------------
# the row movers at 6 choices and rows of 2,560
# ---------------------------------------------------------------------------

_T, _K, _D = 64, 6, 2560    # 384 rows of 2560: a bf16 slab has room for 4096


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
def test_a_row_mover_at_6_choices_and_rows_of_2560_is_the_form_it_replaced(
        name, n):
    """Rows of 2,560 values are no multiple of the 2,048 bfloat16 values
    (1,024 float32) that fill a slab of whole (8, 128) tiles of words: a
    row travels in a slab of 16 word-rows (24), room for 4,096 (3,072),
    and the arrays the kernels take and give stay 2,560 wide."""
    from incubator_mxnet_tpu.parallel import moe_rows

    assert moe_rows._geometry(_D, jnp.bfloat16) == (2, 2560, 16)
    assert moe_rows._geometry(_D, jnp.float32) == (1, 2560, 24)
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
            # a weight's gradient is a sum over the row's 2560 columns
            np.testing.assert_allclose(np.where(held, got[1], 0), want[1],
                                       rtol=1e-4, atol=2e-4)
            got, want = got[0][:n], want[0][:n]
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=tol,
                                   atol=tol * 4)


# ---------------------------------------------------------------------------
# the configuration's file against the catalog row
# ---------------------------------------------------------------------------

#: ``config`` of the catalog row SmallThinker-21BA3B-Instruct (the
#: model-configs guide): ``config.json`` of
#: PowerInfer/SmallThinker-21BA3B-Instruct
_PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def test_configuration_file_states_the_published_numbers_and_the_cut():
    config = json.load(open(os.path.join(
        _ROOT, "perfbench", "configs", "smallthinker_21b.json")))
    bench = json.load(open(os.path.join(_ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["smallthinker_21b"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert config["reduced"] == entry["reduced"] == [
        "num_layers", "experts_held", "vocab_rows"]
    # every key of the catalog row's config, unchanged: no width is cut
    for key, value in _PUBLISHED.items():
        assert config[key] == value, key
    assert (config["num_layers"], config["experts_held"],
            config["vocab_rows"], config["seq_len"]) == (4, [0, 8], 18992,
                                                         16384)
    assert config["seq_len"] == _PUBLISHED["max_position_embeddings"]
    assert config["vocab_rows"] * 8 == _PUBLISHED["vocab_size"]
    assert config["experts_held"][1] * 8 == \
        _PUBLISHED["moe_num_primary_experts"]
    assert config["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936, "layers": config["published"]["layers"]}
    assert "eight chips share each layer" in config["deployment"]
    assert "pipeline stages" in config["deployment"]
    # what the factory is given is the cut, and its defaults the rest
    kwargs = config["factory_kwargs"]
    for key in ("num_layers", "experts_held", "vocab_rows"):
        assert kwargs[key] == config[key], key
    assert kwargs["recompute"] is True and kwargs["keep_choices"] is True
    for key, value in text.smallthinker._SMALLTHINKER_21B.items():
        assert config[key] == value, key
    for item in ("router_input", "block_layout", "attention", "rotary",
                 "expert_layers", "secondary_experts", "selection_bias",
                 "learning_rate", "warm_up", "clip", "initializer", "packing",
                 "data", "weight_decay", "precision"):
        assert len(config["assumed"][item]) > 20, item
    assert "N1(x)" in config["assumed"]["router_input"]
    assert config["loss"] == "SoftmaxCrossEntropyLoss"
    recipe, prec = config["recipe"], config["precision"]
    assert (recipe["optimizer"], recipe["beta1"], recipe["beta2"],
            recipe["epsilon"], recipe["wd"], recipe["learning_rate"],
            recipe["per_chip_batch"]) == ("adamw", 0.9, 0.95, 1e-8, 0.1,
                                          1e-6, 1)
    # the departure says what it departs from, and that it is asked of the
    # factory; the rate is ISSUE 36's
    assert "DEPARTURE from the published router" in \
        config["assumed"]["selection_bias"]
    assert "centred_selection" in config["assumed"]["selection_bias"]
    # a block is half the window's length
    assert kwargs["centred_selection"] == config["sliding_window_size"] // 2
    assert config["seq_len"] % kwargs["centred_selection"] == 0
    assert prec == dict(prec, compute_dtype="bfloat16",
                        multi_precision=False, loss_scale=None)
    # each limit of the comparison has its reason in the file
    why = config["reference"]["why"]
    for word in sorted(ref.CONTROLS) + ["seeds"]:
        assert word in why, word
    assert config["reference"]["module"] == "smallthinker_21b"
    assert set(config["reference"]["grad_rel"]) == {
        g for g, _ in ref.GRAD_GROUPS}
    # the parameters here: ISSUE 36's arithmetic from the published keys
    layer = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560 + 64 * 2560 \
        + 2 * 2560 + 8 * 3 * 2560 * 768
    assert layer == 68326400
    assert 4 * layer + 2 * 18992 * 2560 + 2560 == 370547200
    # and the forward's dense products a token
    assert config["fwd_macs_per_sample"] == 16384 * (
        4 * (20971520 + 163840) + 2560 * 18992)
