"""The ``glm4_moe_lite`` decoder family (``gluon.model_zoo.text``: latent
attention, a prediction module, two loss terms) at its tiny preset against
the plain reference of the benchmark
(``perfbench/references/glm47_flash.py``): both loss terms and every leaf of
the gradient, float32 tight and bf16 under a stated tolerance; the module's
shift and mask; the configuration's file against the catalog row.  (Latent
attention, the kernels and the expert layer at this family's shapes are in
``test_glm4_moe_lite_kernels.py``, the benchmark's controls in
``test_glm4_moe_lite_controls.py``.)  Pallas runs in interpret mode here;
the file takes about a minute."""
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
from perfbench.references import glm47_flash as ref
from perfbench.runners import train_decoder as td
from perfbench.runners import train_tokens as tt

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DATA = os.path.join(_ROOT, "tests", "benchmark_tests", "data_decoder")
_SEQ, _ROWS, _HELD, _LAMBDA = 32, 48, (2, 4), 0.3
#: the tiny preset's numbers, as ``ref.model_cfg`` wants them
_CONFIG = dict(
    json.load(open(os.path.join(_DATA, "bench", "configs",
                                "tiny_glm4.json"))), experts_held=_HELD)
_CFG = ref.model_cfg(_CONFIG)


# ---------------------------------------------------------------------------
# the zoo's model against the plain reference
# ---------------------------------------------------------------------------

def _tiny_net(**kwargs):
    net = text.glm4_moe_lite_tiny(experts_held=_HELD, vocab_rows=_ROWS,
                                  **kwargs)
    net.initialize(init=mx.init.Xavier())
    with shape_only_init():
        jax.eval_shape(lambda x: pure_forward(net, [], [], x)[0],
                       jax.ShapeDtypeStruct((2, _SEQ), "int32"))
    return net


@pytest.fixture(scope="module")
def tiny():
    """The tiny net with its shapes resolved abstractly, seeded weights by
    the reference's names (norm scales away from one, so that their
    gradients mean something), and one batch."""
    net = _tiny_net(recompute=True)
    weights = tt.Weights(net, 3).by_name()
    key = jax.random.PRNGKey(5)
    for i, name in enumerate(sorted(weights)):
        if name.endswith("_gamma"):
            weights[name] = 1.0 + 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), weights[name].shape)
    ids = np.random.RandomState(1).randint(0, _ROWS, (2, _SEQ + 1))
    return net, weights, jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def _reference_params(weights):
    return {k: v for k, v in weights.items() if not k.endswith("_counts")}


@pytest.fixture(scope="module")
def tiny_reference(tiny):
    _, weights, x, y = tiny
    p = _reference_params(weights)
    loss, grads, _ = jax.jit(lambda p: ref.loss_and_grads(p, x, y, _CFG))(p)
    terms = jax.jit(lambda p: ref.loss_terms(p, x, y, _CFG))(p)
    return float(loss), grads, tuple(map(float, terms))


def _model_terms(net, weights, x, y, vals=None, dtype=None):
    """``(the net's two loss terms through the Gluon loss's two children,
    the Gluon loss)`` at ``vals`` for the trained parameters."""
    names = tt.short_names(net)
    trained = [p for p in names if p.grad_req != "null"]
    fixed = [p for p in names if p.grad_req == "null"]
    if vals is None:
        vals = [weights[names[p]] for p in trained]
    if dtype is not None:
        vals = [v.astype(dtype) for v in vals]
    out, _ = pure_forward(
        net, trained + fixed, vals + [weights[names[p]] for p in fixed], x,
        training=True)
    assert all(o.dtype == jnp.float32 for o in out)
    loss = gluon.loss.MultiTokenCrossEntropyLoss(_LAMBDA)
    whole = loss(tuple(map(NDArray, out)), NDArray(y)).mean()._data
    first = loss.next_token(NDArray(out[0]), NDArray(y)).mean()._data
    return first, (whole - first) / _LAMBDA, whole


def _model_loss_and_grads(net, weights, x, y, dtype=None):
    names = tt.short_names(net)
    trained = [names[p] for p in names if p.grad_req != "null"]
    value, grads = jax.jit(jax.value_and_grad(
        lambda vals: _model_terms(net, weights, x, y, vals, dtype)[2]))(
            [weights[name] for name in trained])
    return float(value), dict(zip(trained, grads))


def _worst(grads, want):
    errs = {}
    for name, g in grads.items():
        errs.update(tt._leaf_errors(name, g, want[name]))
    return max(errs.values()), max(errs, key=errs.get)


def test_building_the_family_allocates_nothing_until_it_is_given_weights():
    net = text.glm4_moe_lite_tiny()
    net.initialize(init=mx.init.Xavier())
    pending = [p.name for p in net.collect_params().values()
               if p._data is None]
    # all but the three expert layers' selection bias and counters
    assert len(pending) == len(net.collect_params()) - 3 * 2
    # the module's embedding and head are the model's own: one of each
    names = list(tt.short_names(net).values())
    assert names.count("embed_weight") == names.count("head_weight") == 1
    assert not [n for n in names if n.startswith("layer3_") and
                n.endswith(("embed_weight", "head_weight"))]
    published = text.glm47_flash(num_layers=5, experts_held=(0, 8),
                                 vocab_rows=19360)
    assert all(p._data is None or p.shape == (64,)
               for p in published.collect_params().values())
    with pytest.raises(TypeError, match="config.json"):
        text.glm47_flash(no_such_key=1)
    with pytest.raises(ValueError, match="experts_held"):
        text.glm4_moe_lite_tiny(experts_held=(6, 4))
    # the flash kernels take a value head narrower than the query/key head
    narrow = text.glm4_moe_lite_tiny(v_head_dim=8)
    assert narrow.layers[0].attn.kv_b._units == 4 * (8 + 8)


def test_tiny_model_matches_the_plain_reference_in_float32(tiny,
                                                           tiny_reference):
    net, weights, x, y = tiny
    loss, grads = _model_loss_and_grads(net, weights, x, y)
    want, want_grads, want_terms = tiny_reference
    assert abs(loss - want) <= 1e-5 * want
    first, second, _ = jax.jit(
        lambda: _model_terms(net, weights, x, y))()
    np.testing.assert_allclose([float(first), float(second)], want_terms,
                               rtol=1e-5)
    assert set(grads) == set(want_grads)
    worst, leaf = _worst(grads, want_grads)
    # float32 on both sides: rounding and the order of sums
    assert worst < 2e-5, (leaf, worst)


def test_tiny_model_in_bf16_stays_near_the_float32_reference(tiny):
    """Under the step's own routing, as the benchmark compares
    (``train_tokens.SetUp.reference``): 64 tokens over 8 experts, two a
    token, and bf16 activations move a few tokens' second choice across to
    another expert, one token of an expert's ten.  Which tokens follows the
    rounding (the expert op's gate rounds once where XLA's fusions round at
    every op), so the net keeps its choices and the float32 reference
    follows each where it is a top-2 within 0.01 of its own scores.  None
    may be refused and at most four of the 128 assignments a layer may have
    moved; then every leaf is held to a tenth (0.053 read), the loss to
    2 % and the head, which is above every router, to a fifth.  The
    published widths are held on the chip
    (perfbench/configs/glm47_flash.json)."""
    _, weights, x, y = tiny
    net = _tiny_net(recompute=True, keep_choices=True)
    names = tt.short_names(net)
    weights = dict(weights, **{
        name: jnp.zeros(p.shape, jnp.float32) for p, name in names.items()
        if name.endswith("_moe_chosen")})
    trained = [p for p in names if p.grad_req != "null"]

    def whole(vals):
        # _model_terms' third term, and what the routers wrote beside it
        fixed = [p for p in names if p.grad_req == "null"]
        out, tc = pure_forward(
            net, trained + fixed, [v.astype(jnp.bfloat16) for v in vals]
            + [weights[names[p]] for p in fixed], x, training=True)
        chosen = {tt._layer_of(names[p]): v.astype(jnp.int32)
                  for p, v in zip(*tc.collect_aux())
                  if names[p].endswith("_moe_chosen")}
        loss = gluon.loss.MultiTokenCrossEntropyLoss(_LAMBDA)
        return loss(tuple(map(NDArray, out)), NDArray(y)).mean()._data, chosen

    (loss, chosen), grads = jax.jit(jax.value_and_grad(whole, has_aux=True))(
        [weights[names[p]] for p in trained])
    grads = dict(zip([names[p] for p in trained], grads))
    assert sorted(chosen) == [1, 2, 3]
    want, want_grads, facts = ref.loss_and_grads(
        _reference_params(tiny[1]), x, y, _CFG, chosen, 0.01)
    assert max(float(r[2]) for r in facts["refused"]) == 0
    assert max(map(float, facts["moved"])) <= 4 / 128
    assert abs(float(loss) - float(want)) <= 0.02 * float(want)
    worst, leaf = _worst(grads, want_grads)
    assert worst < 0.1, (leaf, worst)
    head, _ = _worst({"head_weight": grads["head_weight"]}, want_grads)
    assert head < 0.2, head


def test_reference_block_by_block_agrees_with_its_loss_differentiated_whole(
        tiny, tiny_reference):
    _, weights, x, y = tiny
    p = _reference_params(weights)
    trained = {k: v for k, v in p.items() if not k.endswith("_bias")}
    whole, grads = jax.jit(jax.value_and_grad(
        lambda t: ref.loss(dict(p, **t), x, y, _CFG)))(trained)
    want, want_grads, terms = tiny_reference
    assert abs(float(whole) - want) <= 1e-6 * want
    assert abs(terms[0] + _LAMBDA * terms[1] - want) <= 1e-6 * want
    assert set(grads) == set(want_grads)
    worst, leaf = _worst(grads, want_grads)
    assert worst < 2e-5, (leaf, worst)
    # the embedding and the head are each used twice: their gradients are
    # sums over both uses, so neither is what the first term alone gives
    alone = jax.jit(jax.grad(lambda t: ref.loss_terms(
        dict(p, **t), x, y, _CFG)[0]))(trained)
    for name in ("embed_weight", "head_weight"):
        assert _worst({name: alone[name]}, want_grads)[0] > 0.05, name


# ---------------------------------------------------------------------------
# the prediction module's shift and mask
# ---------------------------------------------------------------------------

def test_the_modules_last_position_reaches_no_loss_term(tiny):
    """The module is fed ``E[t_{i+1}]``, ``ids`` rolled left by one: its
    last position wraps around to the first token and is masked out, so
    what stands there changes nothing; a position before it does."""
    net, weights, x, y = tiny
    terms = jax.jit(lambda x: _model_terms(net, weights, x, y)[:2])
    first, second = map(float, terms(x))
    # the first token enters the module only through the wrap (position
    # S - 1 of the rolled ids) but the main path at position 0: compare the
    # module on ids whose FIRST token differs, position by position
    names = tt.short_names(net)
    params = list(names)

    def logits(ids):
        return pure_forward(net, params, [weights[names[p]] for p in params],
                            ids, training=True)[0]

    other = x.at[:, 0].set((x[:, 0] + 1) % _ROWS)
    a, b = jax.jit(logits)(x), jax.jit(logits)(other)
    # position 0 of the main path saw the change; the module's last
    # position saw it through the wrap, and that is the only one of the
    # module's positions the loss leaves out
    assert float(jnp.abs(a[1][:, -1] - b[1][:, -1]).max()) > 1e-3
    loss = gluon.loss.MultiTokenCrossEntropyLoss(_LAMBDA)

    def second_term(out):
        whole = loss(tuple(map(NDArray, out)), NDArray(y)).mean()._data
        return float(whole - loss.next_token(NDArray(out[0]),
                                             NDArray(y)).mean()._data)

    moved = (a[0], a[1].at[:, -1].set(b[1][:, -1]).at[:, -1, 0].add(5.0))
    assert second_term(moved) == second_term(a)
    moved = (a[0], a[1].at[:, -2, 0].add(5.0))
    assert second_term(moved) != second_term(a)
    # and the term is the plain formula: position i against label i + 1
    logp = jax.nn.log_softmax(a[1], -1)
    want = -jnp.take_along_axis(logp[:, :-1], y[:, 1:, None], -1).mean()
    np.testing.assert_allclose(second, float(want), rtol=1e-6)
    assert first > 0


def test_feeding_the_module_this_tokens_embedding_changes_the_second_term(
        tiny):
    net, weights, x, y = tiny
    sound = jax.jit(lambda: _model_terms(net, weights, x, y)[:2])()
    with td.control(ref.CONTROLS, "mtp_shift"):
        broken = jax.jit(lambda: _model_terms(net, weights, x, y)[:2])()
    assert float(broken[0]) == float(sound[0])
    assert abs(float(broken[1]) - float(sound[1])) > 1e-3


def test_a_recomputed_block_of_the_family_runs_flash_forward_once(tiny):
    net, weights, x, y = tiny
    names = tt.short_names(net)
    trained = [names[p] for p in names if p.grad_req != "null"]
    text_ = str(jax.make_jaxpr(jax.grad(
        lambda vals: _model_terms(net, weights, x, y, vals)[2]))(
            [weights[name] for name in trained]))
    # three blocks and the module's: one forward kernel each, no second one
    # in the recomputed forward
    assert text_.count("name=flash_fwd") == 4
    # and ONE backward kernel each, which makes dq, dk and dv
    assert len(re.findall(r"name=flash_bwd\b", text_)) == 4
    assert "name=flash_bwd_d" not in text_


# ---------------------------------------------------------------------------
# the configuration's file against the catalog row
# ---------------------------------------------------------------------------

#: ``config`` of the catalog row GLM-4.7-Flash (the model-configs guide):
#: ``config.json`` of zai-org/GLM-4.7-Flash
_PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def test_configuration_file_states_the_published_numbers_and_the_cut():
    config = json.load(open(os.path.join(
        _ROOT, "perfbench", "configs", "glm47_flash.json")))
    bench = json.load(open(os.path.join(_ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["glm47_flash"]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    assert config["reduced"] == entry["reduced"] == [
        "num_layers", "experts_held", "vocab_rows"]
    # every key of the catalog row's config, unchanged: no width is cut
    for key, value in _PUBLISHED.items():
        assert config[key] == value, key
    assert (config["num_layers"], config["experts_held"],
            config["vocab_rows"], config["seq_len"]) == (5, [0, 8], 19360,
                                                         8192)
    assert config["vocab_rows"] * 8 == _PUBLISHED["vocab_size"]
    assert config["experts_held"][1] * 8 == _PUBLISHED["n_routed_experts"]
    assert config["published"]["num_hidden_layers"] == 47
    assert config["published"]["n_routed_experts"] == 64
    assert config["published"]["vocab_size"] == 154880
    assert "eight chips share each layer" in config["deployment"]
    # what the factory is given is the cut, and its defaults the rest
    kwargs = config["factory_kwargs"]
    for key in ("num_layers", "experts_held", "vocab_rows"):
        assert kwargs[key] == config[key], key
    for key, value in text.glm4_moe_lite._GLM47_FLASH.items():
        assert config[key] == value, key
    for item in ("block_layout", "latent_norms", "mtp_form", "mtp_input",
                 "mtp_halves", "mtp_shared", "mtp_weight", "rotary_pairing",
                 "softmax_scale", "selection_bias", "learning_rate",
                 "warm_up", "clip", "initializer", "packing", "data"):
        assert len(config["assumed"][item]) > 20, item
    assert config["loss"] == "MultiTokenCrossEntropyLoss"
    assert config["loss_kwargs"] == {"mtp_weight": 0.3}
    recipe, prec = config["recipe"], config["precision"]
    assert (recipe["optimizer"], recipe["beta1"], recipe["beta2"],
            recipe["epsilon"], recipe["wd"], recipe["learning_rate"],
            recipe["per_chip_batch"]) == ("adamw", 0.9, 0.95, 1e-8, 0.1,
                                          1e-6, 1)
    assert prec == dict(prec, compute_dtype="bfloat16",
                        multi_precision=False, loss_scale=None)
    # each limit of the comparison has its reason in the file
    why = config["reference"]["why"]
    for word in ("rope", "expert", "float8", "mtp_shift", "seeds"):
        assert word in why, word
    assert set(config["reference"]["grad_rel"]) == {
        g for g, _ in ref.GRAD_GROUPS}
    # the parameters here: ISSUE 34's arithmetic from the published keys
    attention = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 \
        + 5120 * 2048
    expert = 3 * 2048 * 1536
    block = attention + expert + 64 * 2048 + 8 * expert + 2 * 2048
    matrices = (attention + 3 * 2048 * 10240 + 2 * 2048) + 5 * block \
        + 4096 * 2048 + 2 * 19360 * 2048
    norms = 6 * (768 + 512) + 3 * 2048 + 2048
    assert abs(matrices + norms - 706.5e6) < 0.1e6
