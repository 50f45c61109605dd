"""The ``kimi_linear`` decoder family (``gluon.model_zoo.text``: Kimi Delta
Attention beside latent attention without position, a dense layer and then
experts) at its tiny preset against the plain reference of the benchmark
(``perfbench/references/kimi_linear.py``, whose recurrence runs token by
token): the loss and every leaf of the gradient in float32; the reference's
walk over the blocks against its loss differentiated whole; the 32 shares of
an expert layer that add up to the uncut layer; each of the benchmark's
controls moves the model; the configuration's file against the catalog row.
Pallas runs in interpret mode here."""
import json
import os

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
from perfbench.references import kimi_linear as ref
from perfbench.runners import train_decoder as td
from perfbench.runners import train_tokens as tt

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DATA = os.path.join(_ROOT, "tests", "benchmark_tests", "data_kimi")
#: two chunks of the recurrence and a part of a third
_SEQ, _ROWS, _HELD = 150, 48, (2, 4)
_CONFIG = dict(
    json.load(open(os.path.join(_DATA, "bench", "configs",
                                "tiny_kimi.json"))), experts_held=_HELD)
_CFG = ref.model_cfg(_CONFIG)


def _tiny_net(**kwargs):
    net = text.kimi_linear_tiny(experts_held=_HELD, vocab_rows=_ROWS,
                                **kwargs)
    net.initialize(init=mx.init.Xavier())
    with shape_only_init():
        jax.eval_shape(lambda x: pure_forward(net, [], [], x)[0],
                       jax.ShapeDtypeStruct((2, _SEQ), "int32"))
    return net


@pytest.fixture(scope="module")
def tiny():
    """The tiny net with its shapes resolved abstractly, seeded weights by
    the reference's names (norm scales away from one and decay rates of
    their own, so that their gradients mean something), and one batch."""
    net = _tiny_net(recompute=True)
    weights = tt.Weights(net, 3).by_name()
    key = jax.random.PRNGKey(5)
    for i, name in enumerate(sorted(weights)):
        k = jax.random.fold_in(key, i)
        if name.endswith("_gamma"):
            weights[name] = 1.0 + 0.1 * jax.random.normal(
                k, weights[name].shape)
        elif name.endswith("_dt_bias"):
            weights[name] = -1.0 + 0.5 * jax.random.normal(
                k, weights[name].shape)
    ids = np.random.RandomState(1).randint(0, _ROWS, (2, _SEQ + 1))
    return net, weights, jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def _reference_params(weights):
    return {k: v for k, v in weights.items()
            if not k.endswith(("_counts", "_chosen"))}


def _model_loss(net, weights, x, y, vals=None):
    names = tt.short_names(net)
    trained = [p for p in names if p.grad_req != "null"]
    fixed = [p for p in names if p.grad_req == "null"]
    if vals is None:
        vals = [weights[names[p]] for p in trained]
    out, _ = pure_forward(
        net, trained + fixed, vals + [weights[names[p]] for p in fixed], x,
        training=True)
    assert out.dtype == jnp.float32
    return gluon.loss.SoftmaxCrossEntropyLoss()(
        NDArray(out), NDArray(y)).mean()._data


def _model_loss_and_grads(net, weights, x, y):
    names = tt.short_names(net)
    trained = [names[p] for p in names if p.grad_req != "null"]
    value, grads = jax.jit(jax.value_and_grad(
        lambda vals: _model_loss(net, weights, x, y, vals)))(
            [weights[name] for name in trained])
    return float(value), dict(zip(trained, grads))


def _worst(grads, want):
    errs = {}
    for name, g in grads.items():
        errs.update(tt._leaf_errors(name, g, want[name]))
    return max(errs.values()), max(errs, key=errs.get)


@pytest.fixture(scope="module")
def tiny_reference(tiny):
    _, weights, x, y = tiny
    loss, grads, _ = ref.loss_and_grads(_reference_params(weights), x, y,
                                        _CFG)
    return float(loss), grads


def test_building_the_family_allocates_only_what_is_a_few_values_wide():
    net = text.kimi_linear_tiny()
    net.initialize(init=mx.init.Xavier())
    pending = [p.name for p in net.collect_params().values()
               if p._data is None]
    # all but the two expert layers' selection bias and counters, and the
    # two KDA layers' three convolutions' taps, A_log, dt_bias and output
    # norm's scale, whose shapes the configuration gives
    assert len(pending) == len(net.collect_params()) - 2 * 2 - 2 * 6
    published = text.kimi_linear_48b(num_layers=5, experts_held=(0, 8),
                                     vocab_rows=20480)
    kinds = [type(layer.attn).__name__ for layer in published.layers]
    # the published layers 1-5: three KDA layers, latent attention, KDA
    assert kinds == ["KimiDeltaAttention"] * 3 + ["LatentAttention",
                                                  "KimiDeltaAttention"]
    assert type(published.layers[0].ffn).__name__ == "GatedFFN"
    assert {type(layer.ffn).__name__ for layer in published.layers[1:]} == \
        {"ExpertFFN"}
    assert ref.layer_kinds(dict(json.load(open(os.path.join(
        _ROOT, "perfbench", "configs", "kimi_linear_48b.json"))))) == \
        ["kda", "kda", "kda", "mla", "kda"]
    with pytest.raises(TypeError, match="config.json"):
        text.kimi_linear_48b(no_such_key=1)
    with pytest.raises(ValueError, match="layer kind"):
        text.kimi_linear_tiny(layer_kinds=["kda", "swa"])


def test_tiny_model_matches_the_plain_reference_in_float32(tiny,
                                                           tiny_reference):
    net, weights, x, y = tiny
    loss, grads = _model_loss_and_grads(net, weights, x, y)
    want, want_grads = tiny_reference
    assert abs(loss - want) <= 1e-5 * want
    assert set(grads) == set(want_grads)
    worst, leaf = _worst(grads, want_grads)
    # float32 on both sides, the recurrence in chunks against token by
    # token: rounding and the order of sums
    assert worst < 2e-4, (leaf, worst)


def test_reference_block_by_block_agrees_with_its_loss_differentiated_whole(
        tiny, tiny_reference):
    _, weights, x, y = tiny
    p = _reference_params(weights)
    trained = {k: v for k, v in p.items() if not k.endswith("_moe_bias")}
    whole, grads = jax.jit(jax.value_and_grad(
        lambda t: ref.loss(dict(p, **t), x, y, _CFG)))(trained)
    want, want_grads = tiny_reference
    assert abs(float(whole) - want) <= 1e-6 * want
    assert set(grads) == set(want_grads)
    worst, leaf = _worst(grads, want_grads)
    assert worst < 2e-5, (leaf, worst)


@pytest.mark.parametrize("name", sorted(ref.CONTROLS))
def test_each_control_of_the_benchmark_moves_the_model(tiny, name):
    """Each way the benchmark breaks the step on purpose, applied to the
    registered op it names while the net is traced, moves the logits by far
    more than rounding."""
    net, weights, x, _ = tiny
    names = tt.short_names(net)
    params = list(names)

    def logits():
        return jax.jit(lambda vals: pure_forward(
            net, params, vals, x, training=True)[0])(
                [weights[names[p]] for p in params])

    sound = logits()
    with td.control(ref.CONTROLS, name):
        broken = logits()
    moved = float(jnp.max(jnp.abs(broken - sound)) / jnp.max(jnp.abs(sound)))
    assert moved > 1e-4, moved


def _expert_weights(n, d, f, seed):
    rng = np.random.RandomState(seed)
    return {"router_weight": rng.normal(size=(n, d)),
            "bias": rng.normal(size=n) * 0.01,
            "w1": rng.normal(size=(n, d, f)) * 0.3,
            "w3": rng.normal(size=(n, d, f)) * 0.3,
            "w2": rng.normal(size=(n, f, d)) * 0.3,
            "shared_w1_weight": rng.normal(size=(f, d)) * 0.3,
            "shared_w3_weight": rng.normal(size=(f, d)) * 0.3,
            "shared_w2_weight": rng.normal(size=(d, f)) * 0.3}


def test_the_32_shares_add_up_to_the_uncut_layer_at_8_of_256():
    """What each of 32 chips computes of one expert layer (its own 8 of the
    256 experts' part, plus the shared expert that every chip computes
    alike), with the shared expert counted once, is the uncut reference's
    layer: 8 of 256 chosen by sigmoid scores, renormalised, x 2.446."""
    d, f, scale = 16, 8, 2.446
    cfg = dict(num_experts_per_tok=8, route_norm=True, route_scale=scale,
               experts_held=(0, 256))
    whole = {k: jnp.asarray(v, jnp.float32)
             for k, v in _expert_weights(256, d, f, seed=5).items()}
    x = mx.nd.array(np.random.RandomState(4).normal(size=(2, 12, d)))
    total = 0.0
    for chip in range(32):
        first, count = 8 * chip, 8
        block = text.ExpertFFN(d, 256, 8, f, experts_held=(first, count),
                               route_scale=scale, prefix="moe_")
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
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(whole, "", flat, cfg)
    np.testing.assert_allclose(total - 31 * np.asarray(shared),
                               np.asarray(want).reshape(2, 12, d),
                               rtol=1e-4, atol=1e-5)


#: ``config`` of the catalog row Kimi-Linear-48B-A3B-Instruct (the
#: model-configs guide): ``config.json`` of moonshotai/Kimi-Linear-48B-A3B
_PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


def test_configuration_file_states_the_published_numbers_and_the_cut():
    config = json.load(open(os.path.join(
        _ROOT, "perfbench", "configs", "kimi_linear_48b.json")))
    bench = json.load(open(os.path.join(_ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["kimi_linear_48b"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    assert config["reduced"] == entry["reduced"] == [
        "num_layers", "experts_held", "vocab_rows"]
    # every key of the catalog row's config, unchanged: no width is cut
    for key, value in _PUBLISHED.items():
        assert config[key] == value, key
    assert (config["num_layers"], config["experts_held"],
            config["vocab_rows"], config["seq_len"]) == (5, [0, 8], 20480,
                                                         16384)
    assert config["vocab_rows"] * 8 == _PUBLISHED["vocab_size"]
    assert config["experts_held"][1] * 32 == _PUBLISHED["num_experts"]
    assert config["published"]["num_hidden_layers"] == 27
    assert "32 chips share each layer" in config["deployment"]
    kwargs = config["factory_kwargs"]
    for key in ("num_layers", "experts_held", "vocab_rows"):
        assert kwargs[key] == config[key], key
    for key, value in text.kimi_linear._KIMI_LINEAR_48B.items():
        assert config[key] == value, key
    for item in ("block_layout", "kda_gate_rank", "kda_decay", "kda_conv",
                 "kda_qk", "kda_output_gate", "kda_chunks", "mla_nope",
                 "softmax_scale", "selection_bias", "learning_rate",
                 "initializer", "packing", "data", "precision"):
        assert len(config["assumed"][item]) > 20, item
    recipe, prec = config["recipe"], config["precision"]
    assert (recipe["optimizer"], recipe["beta1"], recipe["beta2"],
            recipe["epsilon"], recipe["wd"], recipe["learning_rate"],
            recipe["per_chip_batch"]) == ("adamw", 0.9, 0.95, 1e-8, 0.1,
                                          1e-6, 1)
    assert prec == dict(prec, compute_dtype="bfloat16",
                        multi_precision=False, loss_scale=None)


def test_the_decay_and_the_taps_are_drawn_as_the_familys_code_draws_them():
    """The harness draws ``A_log`` and the taps Xavier and ``dt_bias`` zero
    by name; the reference's ``balance`` hands back, from those draws, A
    uniform over 1 to 16 a head, dt log-uniform over 1e-3 to 1e-1 a channel
    and taps uniform over +-1/2 (K = 4), the same for the same seed and
    another for another seed or layer."""
    net = _tiny_net()
    draws = [tt.Weights(net, seed).by_name() for seed in (3, 3, 4)]
    got = [ref.kda_init(w, _CFG) for w in draws]
    names = sorted(got[0])
    assert names == ["layer%d_kda_%s" % (i, n) for i in (0, 2) for n in (
        "A_log", "dt_bias", "k_conv_weight", "q_conv_weight",
        "v_conv_weight")]
    for name in names:
        assert got[0][name].shape == draws[0][name].shape
        np.testing.assert_array_equal(got[0][name], got[1][name])
        assert not np.array_equal(got[0][name], got[2][name])
    assert not np.array_equal(got[0]["layer0_kda_dt_bias"],
                              got[0]["layer2_kda_dt_bias"])

    def every(end):
        return np.concatenate([g[n].ravel() for g in got for n in names
                               if n.endswith(end)])

    a, dt, taps = np.exp(every("A_log")), np.logaddexp(0.0, every(
        "dt_bias")), every("conv_weight")
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 1e-3 * (1 - 1e-5) <= dt.min() and dt.max() <= 0.1 * (1 + 1e-5)
    # log-uniform: about half the channels below the geometric middle
    assert 0.4 < np.mean(dt < 1e-2) < 0.6
    assert 0.45 < np.abs(taps).max() <= 0.5 and abs(taps.mean()) < 0.02
    held = ref.balance(draws[0], jnp.zeros((1, 8), jnp.int32), _CFG, 2, 0.1,
                       0.9)
    assert set(names) < set(held)
    assert {n for n in held if n not in names} == {"layer1_moe_bias",
                                                   "layer2_moe_bias"}


def test_reference_kda_by_groups_of_heads_is_kda_over_all_of_them(
        tiny, monkeypatch):
    """The reference computes KDA a group of heads at a time: with one head
    a group (two groups at the tiny preset) its output and its gradients
    are those of both heads at once."""
    _, weights, x, _ = tiny
    pre = "layer0_kda_"
    p = {k[len("layer0_"):]: jnp.asarray(v, jnp.float32)
         for k, v in weights.items() if k.startswith(pre)}
    y = jax.random.normal(jax.random.PRNGKey(2), (_SEQ, 32), jnp.float32)

    def run():
        f = jax.jit(jax.value_and_grad(lambda p, y: jnp.sum(
            ref.kda_attention(p, "kda_", y, _CFG) ** 2), (0, 1)))
        return f(p, y)

    whole = run()
    monkeypatch.setattr(ref, "_KDA_HEADS", 1)
    grouped = run()
    np.testing.assert_allclose(grouped[0], whole[0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grouped[1]), jax.tree.leaves(whole[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))
