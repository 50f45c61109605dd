"""The ``afmoe`` decoder family (``gluon.model_zoo.text``) at its tiny preset
against the plain reference of the benchmark
(``perfbench/references/trinity_mini.py``); the three controls that the
benchmark's comparison must fail; AdamW in the fused step; the
configuration's file against the published numbers.  (The kernels' own tests
are ``tests/test_moe_window_kernels.py``.)  Pallas runs in interpret mode
here; the file takes about a minute and a half, a third of it the bf16
cell that tells float8 inputs apart."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.gluon.block import pure_forward
from incubator_mxnet_tpu.gluon.model_zoo import text
from incubator_mxnet_tpu.gluon.parameter import shape_only_init
from perfbench.references import trinity_mini as ref
from perfbench.runners import train_tokens as tt

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SEQ, _ROWS, _HELD = 32, 48, (2, 4)
_LAYERS = ["sliding_attention"] * 2 + ["full_attention"]
#: the tiny preset's numbers, as the plain reference wants them
_CFG = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, sliding_window=8, rope_theta=10000.0,
            rms_norm_eps=1e-5, num_experts=8, num_experts_per_tok=2,
            route_norm=True, route_scale=2.826, mup_enabled=True,
            layer_types=_LAYERS, num_dense_layers=1, experts_held=_HELD)


# ---------------------------------------------------------------------------
# the zoo's model against the plain reference
# ---------------------------------------------------------------------------

def _tiny_net(recompute):
    net = text.afmoe_tiny(experts_held=_HELD, vocab_rows=_ROWS,
                          recompute=recompute)
    net.initialize(init=mx.init.Xavier())
    with shape_only_init():
        jax.eval_shape(lambda x: pure_forward(net, [], [], x)[0],
                       jax.ShapeDtypeStruct((2, _SEQ), "int32"))
    return net


@pytest.fixture(scope="module")
def tiny():
    """The tiny net with its shapes resolved abstractly, seeded weights by
    the reference's names, and one batch."""
    net = _tiny_net(recompute=True)
    weights = tt.Weights(net, 3).by_name()
    # norm scales away from one, so that their gradients mean something
    key = jax.random.PRNGKey(5)
    for i, name in enumerate(sorted(weights)):
        if name.endswith("_gamma"):
            weights[name] = 1.0 + 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), weights[name].shape)
    ids = np.random.RandomState(1).randint(0, _ROWS, (2, _SEQ + 1))
    return net, weights, jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


@pytest.fixture(scope="module")
def tiny_reference(tiny):
    _, weights, x, y = tiny
    loss, grads, _ = jax.jit(lambda p: ref.loss_and_grads(p, x, y, _CFG))(
        {k: v for k, v in weights.items() if not k.endswith("_counts")})
    return float(loss), grads


def _model_loss_and_grads(net, weights, x, y, dtype=None):
    names = tt.short_names(net)
    trained = [p for p in names if p.grad_req != "null"]
    fixed = [p for p in names if p.grad_req == "null"]

    def loss(vals):
        if dtype is not None:
            vals = [v.astype(dtype) for v in vals]
        logits, _ = pure_forward(
            net, trained + fixed, vals + [weights[names[p]] for p in fixed],
            x, training=True)
        assert logits.dtype == jnp.float32
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))

    value, grads = jax.jit(jax.value_and_grad(loss))(
        [weights[names[p]] for p in trained])
    return float(value), {names[p]: g for p, g in zip(trained, grads)}


def _worst(grads, want):
    errs = {}
    for name, g in grads.items():
        errs.update(tt._leaf_errors(name, g, want[name]))
    return max(errs.values()), max(errs, key=errs.get)


def test_building_the_family_allocates_nothing_until_it_is_given_weights():
    net = text.afmoe_tiny()
    net.initialize(init=mx.init.Xavier())
    pending = [p.name for p in net.collect_params().values()
               if p._data is None]
    # all but the expert layers' selection bias and counters (8 numbers each)
    assert len(pending) == len(net.collect_params()) - 2 * 2
    with pytest.raises(TypeError, match="config.json"):
        text.afmoe_tiny(no_such_key=1)
    with pytest.raises(ValueError, match="experts_held"):
        text.afmoe_tiny(experts_held=(6, 4))


def test_tiny_model_matches_the_plain_reference_in_float32(tiny,
                                                           tiny_reference):
    net, weights, x, y = tiny
    loss, grads = _model_loss_and_grads(net, weights, x, y)
    want, want_grads = tiny_reference
    assert abs(loss - float(want)) <= 1e-5 * float(want)
    assert set(grads) == set(want_grads)
    worst, leaf = _worst(grads, want_grads)
    # float32 on both sides: rounding and the order of sums
    assert worst < 2e-5, (leaf, worst)


def test_tiny_model_in_bf16_stays_near_the_float32_reference(tiny,
                                                              tiny_reference):
    """Tolerance: 64 tokens over 8 experts, two a token.  bf16 activations
    move a few tokens' second choice across to another expert, and one such
    token is a thirtieth of an expert's gradient; the head, which is above
    every router, stays within a fifth (64 tokens; 0.09 read).  The published widths are held to tighter limits on the chip
    (perfbench/configs/trinity_mini.json)."""
    net, weights, x, y = tiny
    loss, grads = _model_loss_and_grads(net, weights, x, y, jnp.bfloat16)
    want, want_grads = tiny_reference
    assert abs(loss - float(want)) <= 1e-2 * float(want)
    worst, leaf = _worst(grads, want_grads)
    assert worst < 0.6, (leaf, worst)
    head = tt._leaf_errors("head_weight", grads["head_weight"],
                           want_grads["head_weight"])["head_weight"]
    assert head < 0.2, head


def test_reference_block_by_block_agrees_with_its_loss_differentiated_whole(
        tiny, tiny_reference):
    """``gradients`` walks jitted blocks and their vector-Jacobian products
    from Python; ``jax.grad`` of the straightforward composition must give
    the same, and ``step`` the same parameters as AdamW over them."""
    _, weights, x, y = tiny
    p = {k: v for k, v in weights.items()
         if not k.endswith(("_counts", "_chosen"))}
    trained = {k: v for k, v in p.items() if not k.endswith("moe_bias")}
    fixed = {k: v for k, v in p.items() if k.endswith("moe_bias")}
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda t: ref.loss(dict(t, **fixed), x, y, _CFG)))(trained)
    got, got_grads = tiny_reference
    assert abs(got - float(want)) <= 1e-6 * float(want)
    assert set(got_grads) == set(want_grads)
    worst, leaf = _worst(got_grads, want_grads)
    assert worst < 1e-5, (leaf, worst)
    recipe = dict(beta1=0.9, beta2=0.95, epsilon=1e-8, wd=0.1,
                  learning_rate=1e-3)
    blocks = ref.Blocks(_CFG, recipe)
    stepped, m, v = {k: jnp.array(a) for k, a in p.items()}, {}, {}
    loss = ref.step(stepped, m, v, 1, x, y, _CFG, blocks)
    assert abs(float(loss) - got) <= 1e-6 * got
    zeros = {k: jnp.zeros_like(a) for k, a in trained.items()}
    whole, m_whole, _ = ref.adamw(trained, want_grads, zeros, zeros, 1,
                                  recipe)
    for k in trained:
        # a first Adam step is lr * sign(g) wherever |g| >> eps: an element
        # whose gradient is rounding itself may land anywhere within it
        np.testing.assert_allclose(stepped[k], whole[k], rtol=1e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(m[k], m_whole[k], rtol=1e-4, atol=1e-7)
    np.testing.assert_array_equal(stepped["layer1_moe_bias"],
                                  p["layer1_moe_bias"])


@pytest.fixture(scope="module")
def gradient_program(tiny):
    """The text of the gradient's program of the tiny net, every block of
    which is a remat region."""
    net, weights, x, _ = tiny
    names = tt.short_names(net)
    params = list(names)

    def loss(vals):
        logits, _ = pure_forward(net, params, vals, x, training=True)
        return jnp.sum(logits)

    assert all(layer._flags.get("remat") for layer in net.layers)
    text_of = str(jax.make_jaxpr(jax.grad(loss))(
        [weights[names[p]] for p in params]))
    assert "checkpoint" in text_of or "remat" in text_of
    return text_of


def test_a_recomputed_block_keeps_its_routers_choice(gradient_program):
    """Inside a remat region the router's choice is kept, not made again:
    made again from recomputed scores (another fusion, other roundings) a
    near-tie falls the other way, and the backward pass differentiates a
    routing the forward never ran (on the chip the worst expert matrix read
    0.15-0.18 from the reference for that, PERF.md section 6, PR 30).  So
    the gradient's program holds ONE top-k an expert layer."""
    assert gradient_program.count("top_k[") == 2


def test_an_expert_layer_moves_the_row_buffer_whole_only_into_expert_order(
        gradient_program):
    """Combine, and the transposes of dispatch and combine, move rows through
    the Pallas kernels whose work stops at the last held row
    (``parallel/moe_rows.py``): of the gathers and broadcasts whose result
    has tokens x top-k rows of the hidden width, in either of the forms they
    had, (T * K, d) and (T, K, d), the gradient's program holds only the
    rows into expert order, forward and recomputed, of its two expert
    layers.  Each layer calls, forward and recomputed, the weighted sum back
    to tokens, and backward its transpose and the row gather's; the three
    are jitted, so the program holds each ONCE and calls it (a
    ``pallas_call`` is traced and lowered anew at every call site)."""
    tokens, top_k, hidden = 2 * _SEQ, _CFG["num_experts_per_tok"], \
        _CFG["hidden_size"]
    whole = r"\[(?:%d,%d|%d,%d,%d)\] = (gather|broadcast_in_dim)\b" % (
        tokens * top_k, hidden, tokens, top_k, hidden)
    assert re.findall(whole, gradient_program) == ["gather"] * 4
    calls = {mover: len(re.findall(r"jit\[\s*name=%s\b" % mover,
                                   gradient_program))
             for mover in ("_weighted_rows_to_tokens",
                           "_weighted_tokens_to_rows", "_rows_to_tokens")}
    assert list(calls.values()) == [4, 2, 2], calls


def test_a_recomputed_block_runs_attentions_forward_kernel_once(
        gradient_program):
    """The flash kernels name their two results ``tracing.REMAT_KEEP``, so a
    block's remat region keeps ``out`` and ``lse`` and the recomputed forward,
    left with no reader for them, drops the kernel: the gradient's program
    holds ONE ``flash_fwd`` an attention layer (two before: a twentieth of
    the step on the chip, PERF.md section 6, PR 31) and ONE backward kernel,
    ``flash_bwd``, which makes dq, dk and dv from one softmax a tile (the
    two it replaced serve only sequences whose sums do not fit VMEM)."""
    calls = {kernel: len(re.findall(r"name=%s\b" % kernel, gradient_program))
             for kernel in ("flash_fwd", "flash_bwd", "flash_bwd_dq",
                            "flash_bwd_dkv")}
    assert list(calls.values()) == [len(_LAYERS), len(_LAYERS), 0, 0], calls


def test_a_recomputed_net_has_the_gradients_of_the_one_that_keeps_everything(
        tiny):
    """The backward kernels of a recomputed block read the forward pass's own
    ``out`` and ``lse`` beside a recomputed q, k and v: in float32 that is
    the gradient of the net that recomputes nothing, to rounding."""
    net, weights, x, y = tiny
    plain = _tiny_net(recompute=False)
    assert not any(layer._flags.get("remat") for layer in plain.layers)
    loss, grads = _model_loss_and_grads(net, weights, x, y)
    want, want_grads = _model_loss_and_grads(plain, weights, x, y)
    assert abs(loss - want) <= 1e-6 * want
    assert set(grads) == set(want_grads)
    worst, leaf = _worst(grads, want_grads)
    assert worst < 2e-5, (leaf, worst)


def test_recompute_survives_a_plain_hybridize(tiny):
    net = tiny[0]      # built with recompute=True, as the benchmark's cell is
    assert all(layer._flags.get("remat") for layer in net.layers)
    net.hybridize()    # a plain hybridize() keeps the blocks' regions
    assert all(layer._flags.get("remat") for layer in net.layers)
    net.hybridize(False)
    plain = text.afmoe_tiny()
    plain.hybridize()
    assert not any(layer._flags.get("remat") for layer in plain.layers)


def test_adamw_of_the_fused_step_is_adamw():
    """One parameter, two steps, by hand: bias-corrected Adam plus decoupled
    decay, and no trust ratio (which is lamb's)."""
    from incubator_mxnet_tpu.parallel.train_step import FunctionalOptimizer

    opt = FunctionalOptimizer("adamw", learning_rate=0.1, wd=0.5, beta1=0.9,
                              beta2=0.5, epsilon=1e-8)
    p = jnp.asarray([1.0, -2.0, 4.0])
    state = opt.init([p])[0]
    m = v = np.zeros(3)
    want = np.asarray(p, np.float64)
    for t, g in enumerate(([0.5, 0.25, -1.0], [1.0, -0.5, 0.125]), 1):
        g = np.asarray(g)
        p, state = opt.apply_single(p, jnp.asarray(g, jnp.float32), state, t)
        m, v = 0.9 * m + 0.1 * g, 0.5 * v + 0.5 * g * g
        want = want - 0.1 * (m / (1 - 0.9 ** t)
                             / (np.sqrt(v / (1 - 0.5 ** t)) + 1e-8)
                             + 0.5 * want)
        np.testing.assert_allclose(p, want, rtol=1e-5)


# ---------------------------------------------------------------------------
# the controls of the benchmark's comparison, at the tiny size
# ---------------------------------------------------------------------------

_COMPARED = {
    "ref_loss0_rel", "ref_loss1_rel", "ref_loss2_rel", "route_refused_share",
    "route_moved_share", "grad_worst_attention", "grad_worst_experts",
    "grad_worst_router", "grad_worst_other"}


def _verdict(setup, broken):
    """The runner's comparison of a step built under ``control(broken)``."""
    step, _, losses, applied, chosen = setup.first_steps(broken)
    del step
    setup.release()
    compared, problems = setup.reference(losses, applied, chosen)
    over = [k for k, (value, limit) in compared.items() if not value <= limit]
    assert bool(problems) == bool(over), compared
    # every number that was compared stands beside its limit
    assert set(compared) == _COMPARED
    return compared, over


@pytest.fixture(scope="module")
def tiny_setup():
    """The tiny cell's set-up (float32 on both sides)."""
    import time

    from perfbench import run

    cell = run.load_cell(os.path.join(_ROOT, "tests", "benchmark_tests",
                                      "data_tokens"), "tiny_afmoe_train")
    return tt.SetUp(cell, 11, time.monotonic())


@pytest.mark.parametrize("broken", [None, "window", "expert", "float8"])
def test_a_broken_step_fails_the_comparison_with_the_reference(tiny_setup,
                                                               broken):
    compared, over = _verdict(tiny_setup, broken)
    assert bool(over) == (broken is not None), compared
    if broken is None:
        # the step wrote its routing counts through the aux channel, and the
        # reference, routing for itself, made every one of its choices
        assert compared["route_moved_share"][0] == 0.0
    if broken == "expert":
        # the expert that was left out shows in ITS matrices, not the loss
        assert compared["grad_worst_experts"][0] > 0.99
        assert compared["ref_loss0_rel"][0] < 0.05


def test_the_selection_biases_even_out_the_routers_of_the_step(tiny_setup):
    """The biases come from ONE forward pass of the plain reference; the
    step's own routers, under them, load their experts more evenly than
    with none (2.1 and 1.9 largest over mean on this seed, third step)."""
    fixed = tiny_setup.weights.fixed
    assert sorted(fixed) == ["layer1_moe_bias", "layer2_moe_bias"]
    assert all(float(abs(b).max()) > 0 for b in fixed.values())
    step, *_ = tiny_setup.first_steps()
    del step
    counts = [c for _, c in tt.routing_counts(tiny_setup.net)]
    tiny_setup.release()
    assert [c.sum() for c in counts] == [2 * _SEQ * 2] * 2
    assert all(c.shape == (8,) for c in counts)
    assert max(float(c.max() / c.mean()) for c in counts) < 1.7
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0),
                                              (512, 8)) + jnp.arange(8) / 4)
    bias = ref.balanced_bias(scores, 2, 40, 0.1, 0.9)
    load = np.bincount(np.asarray(
        jax.lax.top_k(scores + bias, 2)[1]).ravel(), minlength=8)
    free = np.bincount(np.asarray(jax.lax.top_k(scores, 2)[1]).ravel(),
                       minlength=8)
    assert load.max() / load.mean() < 1.1 < free.max() / free.mean()


#: a cell of the family small enough for the CPU and large enough that bf16
#: rounding is a few per cent of every gradient leaf: 256 tokens over 8
#: experts of which 4 are held, two a token
_SMALL = dict(hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
              num_attention_heads=4, num_key_value_heads=2, head_dim=32,
              num_experts=8, num_experts_per_tok=2, sliding_window=64)
_SMALL_SHARE = dict(layer_types=["sliding_attention"] * 2
                    + ["full_attention"], num_dense_layers=1,
                    experts_held=[0, 4], vocab_rows=256)


def test_a_bf16_step_passes_where_float8_inputs_to_the_experts_fail():
    """What the chip's limits rest on, at a size the CPU runs: the sound
    bf16 step reads 0.02-0.03 on every group of gradient leaves against the
    float32 reference (rounding alone, because the recomputed blocks keep
    their routers' choices), and the same step with the expert product's
    inputs rounded to float8 in the forward pass 0.05-0.08; the expert
    limit between them tells the two apart."""
    import time

    config = dict(
        _SMALL, **_SMALL_SHARE, name="small_afmoe", rope_theta=10000.0,
        rms_norm_eps=1e-5, route_norm=True, route_scale=2.826,
        mup_enabled=True, seq_len=256, loss="SoftmaxCrossEntropyLoss",
        factory="incubator_mxnet_tpu.gluon.model_zoo.text:trinity_mini",
        factory_kwargs=dict(_SMALL, **_SMALL_SHARE, recompute=True,
                            keep_choices=True),
        recipe=dict(optimizer="adamw", learning_rate=1e-6, beta1=0.9,
                    beta2=0.95, epsilon=1e-8, wd=0.1, per_chip_batch=1),
        precision=dict(compute_dtype="bfloat16", multi_precision=False,
                       loss_scale=None),
        reference=dict(module="trinity_mini", steps=3,
                       loss_rtol=[1e-3, 1e-2, 1e-2], route_eps=0.02,
                       route_refused_share=0.004, route_moved_share=0.05,
                       grad_rel=dict(attention=0.1, experts=0.04, router=0.1,
                                     other=0.1)))
    setup = tt.SetUp(dict(config=config, chips=1, name="small_afmoe_train"),
                     4, time.monotonic())
    sound, over = _verdict(setup, None)
    assert not over, sound
    assert 0 < sound["route_moved_share"][0]      # bf16 moves near-ties
    rounded, over = _verdict(setup, "float8")
    assert "grad_worst_experts" in over, rounded
    assert rounded["grad_worst_experts"][0] > 2 * sound[
        "grad_worst_experts"][0]


# ---------------------------------------------------------------------------
# the configuration's file against the published numbers
# ---------------------------------------------------------------------------

#: the numbers of ``config.json`` of arcee-ai/Trinity-Mini (the catalog row
#: of the model-configs guide)
_PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_size": 2048,
    "intermediate_size": 6144, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "moe_intermediate_size": 1024,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "route_scale": 2.826, "sliding_window": 2048,
    "topk_group": 1, "vocab_size": 200192}


def test_configuration_file_states_the_published_numbers_and_the_cut():
    config = json.load(open(os.path.join(
        _ROOT, "perfbench", "configs", "trinity_mini.json")))
    bench = json.load(open(os.path.join(_ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["trinity_mini"]
    reduced = config["reduced"]
    assert reduced == entry["reduced"] == [
        "num_layers", "num_dense_layers", "layer_types", "experts_held",
        "vocab_rows"]
    for key, value in _PUBLISHED.items():
        if key not in reduced:
            assert config[key] == value, key
    assert config["mup_enabled"] is True and config["route_norm"] is True
    assert config["score_func"] == "sigmoid" and config["model_type"] == "afmoe"
    # the cut: one dense layer and one whole period, 16 of 128 experts, an
    # eighth of the vocabulary; the published values stand beside it
    assert config["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"] and config["num_layers"] == 5
    assert config["num_dense_layers"] == 1
    assert config["experts_held"] == [0, 16] and config["vocab_rows"] == 25024
    assert config["vocab_rows"] * 8 == _PUBLISHED["vocab_size"]
    assert config["published"]["num_hidden_layers"] == 32
    assert config["published"]["num_dense_layers"] == 2
    assert config["published"]["num_experts"] == 128
    assert config["published"]["vocab_size"] == 200192
    assert "eight chips share each layer" in config["deployment"]
    # what the factory is given is the cut, and its defaults the rest
    kwargs = config["factory_kwargs"]
    for key in ("layer_types", "num_dense_layers", "experts_held",
                "vocab_rows"):
        assert kwargs[key] == config[key], key
    for key, value in text.afmoe._TRINITY_MINI.items():
        assert config[key] == value or key in reduced, key
    for item in ("learning_rate", "warm_up", "clip", "initializer",
                 "selection_bias", "load_balance", "packing", "output_gate",
                 "sandwich_norms", "rotary", "embedding_multiplier",
                 "weight_decay"):
        assert item in config["assumed"], item
    recipe, prec = config["recipe"], config["precision"]
    assert (recipe["optimizer"], recipe["beta1"], recipe["beta2"],
            recipe["epsilon"], recipe["wd"], recipe["per_chip_batch"]) == (
                "adamw", 0.9, 0.95, 1e-8, 0.1, 1)
    assert prec == dict(prec, compute_dtype="bfloat16",
                        multi_precision=False, loss_scale=None)
