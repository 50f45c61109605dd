"""Window and grouped heads in the flash kernels (forward, the one backward
kernel that makes dq, dk and dv, and the two-kernel form a sequence falls
back to whose sums do not fit VMEM, against the masked dense
``attention_reference``), and the no-drop
expert layer of a chip that holds a share of the experts
(``parallel/moe.py``): against a loop over tokens, under a router forced onto
one expert, its gradients, and the eight shares that add up to the uncut
layer; its Pallas row movers (``parallel/moe_rows.py``) against the
``jax.numpy`` forms they replaced, kept here as the oracle, at every edge of
``n``, and the layer with the buffer's tail poisoned.  Pallas runs in
interpret mode here; sizes are small enough that the file takes about two
minutes."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.gluon.model_zoo import text
from incubator_mxnet_tpu.parallel import flash_attention, moe, moe_rows
from incubator_mxnet_tpu.parallel.ring_attention import attention_reference
from incubator_mxnet_tpu.tracing import REMAT_KEEP
from perfbench.references import trinity_mini as ref

#: what the plain reference's expert layer reads of a configuration
_CFG = dict(num_experts_per_tok=2, route_norm=True, route_scale=2.826)


# ---------------------------------------------------------------------------
# flash attention: window and grouped heads
# ---------------------------------------------------------------------------

def _qkv(h=4, hkv=2, s=64, sk=None, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.normal(size=(1, h, s, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, hkv, sk or s, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, hkv, sk or s, d)), jnp.float32))


@pytest.mark.parametrize("window", [8, 16, 24, 100],
                         ids=["below_block", "one_block", "above_block",
                              "above_sequence"])
def test_flash_window_and_grouped_heads_forward_and_backward(window):
    q, k, v = _qkv()

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=16, block_k=16)

    def dense(q, k, v):
        return attention_reference(q, k, v, causal=True, window=window)

    want = dense(q, k, v)
    np.testing.assert_allclose(flash(q, k, v), want, rtol=2e-5, atol=2e-5)
    weight = jnp.cos(want)   # some cotangent that is not all ones
    got = jax.grad(lambda *a: (flash(*a) * weight).sum(), (0, 1, 2))(q, k, v)
    exp = jax.grad(lambda *a: (dense(*a) * weight).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, exp):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def _projected(window, weight):
    """Attention behind a projection of q, so that a remat region has
    something to make again before the kernels."""
    def f(q, k, v):
        out = flash_attention(jnp.tanh(q @ weight), k, v, causal=True,
                              window=window, block_q=16, block_k=16)
        return jnp.sin(out).sum()
    return f


def _kernel_calls(fn, *args):
    """How often the gradient's program calls ``flash_fwd``, ``flash_bwd``
    and either of the two kernels ``flash_bwd`` replaced."""
    text_of = str(jax.make_jaxpr(jax.grad(fn, (0, 1, 2)))(*args))
    return tuple(len(re.findall(r"name=%s\b" % kernel, text_of))
                 for kernel in ("flash_fwd", "flash_bwd", r"flash_bwd_d\w+"))


@pytest.mark.parametrize("window,hkv", [(8, 2), (None, 2), (24, 4)],
                         ids=["window_grouped", "full_grouped",
                              "window_own_heads"])
def test_a_region_that_keeps_the_named_runs_flash_forward_once(window, hkv,
                                                                capsys):
    """Under ``jax.checkpoint`` with the policy of a block's remat region
    (``HybridBlock._forward_remat``) the kernels' ``out`` and ``lse`` are
    kept: the gradients are those of the call outside any region, from one
    ``flash_fwd`` where a region without the policy runs two; and the ``lse``
    kept is the compact (B*H, S) one, not the kernel's (B*H, S, 1), which the
    TPU's tiled layout pads to 128 lanes."""
    q, k, v = _qkv(hkv=hkv)
    f = _projected(window, jnp.asarray(
        np.random.RandomState(1).normal(size=(16, 16)) * 0.3, jnp.float32))
    kept = jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(REMAT_KEEP))
    want = jax.grad(f, (0, 1, 2))(q, k, v)
    got = jax.grad(kept, (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    assert _kernel_calls(f, q, k, v) == (1, 1, 0)
    assert _kernel_calls(kept, q, k, v) == (1, 1, 0)
    assert _kernel_calls(jax.checkpoint(f), q, k, v) == (2, 1, 0)
    jax.ad_checkpoint.print_saved_residuals(kept, q, k, v)
    saved = [line.split()[0] for line in capsys.readouterr().out.splitlines()
             if "flash_attention" in line]
    assert sorted(saved) == ["f32[4,64,16]", "f32[4,64]"], saved


def test_outside_a_region_the_names_are_all_that_changed():
    """Outside a remat region ``checkpoint_name`` is the identity: the
    gradient's program is that of the kernels under an unnamed custom VJP,
    equation for equation, but for the two ``name`` equations, of which the
    second (``lse``) is two-dimensional."""
    from incubator_mxnet_tpu.parallel.flash_attention import (_bwd, _fwd,
                                                              _make_attn)

    static = (0.25, True, 8, 4, 2, 16, 16, True)
    q, k, v = (a[0] for a in _qkv())

    @jax.custom_vjp
    def unnamed(q, k, v):
        return _fwd(q, k, v, *static)[0]

    def unnamed_fwd(q, k, v):
        out, lse = _fwd(q, k, v, *static)
        return out, (q, k, v, out, lse)

    unnamed.defvjp(unnamed_fwd, lambda res, g: _bwd(*static, res, g))
    named = _make_attn(0.25, True, 16, 16, True, window=8, heads=4,
                       kv_heads=2)

    def grad_of(attn):
        return jax.grad(lambda *a: jnp.sin(attn(*a)).sum(), (0, 1, 2))

    def equations(attn):
        return [(e.primitive.name, [str(o.aval) for o in e.outvars])
                for e in jax.make_jaxpr(grad_of(attn))(q, k, v).eqns]

    got, want = equations(named), equations(unnamed)
    names = [e for e in got if e[0] == "name"]
    assert names == [("name", ["float32[4,64,16]"]),
                     ("name", ["float32[4,64]"])], names
    assert [e for e in got if e[0] != "name"] == want
    for a, b in zip(grad_of(named)(q, k, v), grad_of(unnamed)(q, k, v)):
        np.testing.assert_array_equal(a, b)


#: (query heads, key/value heads, queries, keys, head size, causal, window,
#: block_q, block_k): what the callers differ in, which the backward kernel
#: reads from the shapes
_BACKWARD_CASES = {
    "window_grouped": (4, 2, 64, 64, 16, True, 24, 16, 32),
    "full_grouped_wide_query_tile": (8, 2, 64, 64, 16, True, None, 32, 16),
    "own_heads": (4, 4, 64, 64, 16, True, None, 16, 16),
    "longer_keys": (6, 3, 16, 48, 16, True, None, 8, 16),
    "longer_keys_window": (6, 3, 16, 48, 16, True, 20, 8, 16),
    "longer_keys_no_mask": (6, 3, 16, 48, 16, False, None, 8, 16),
    "tiles_that_do_not_divide": (3, 1, 60, 60, 8, True, 7, 16, 25),
    "head_size_256": (2, 2, 32, 32, 256, True, None, 16, 8),
}


def _backward_case(name):
    h, hkv, s, sk, d, causal, window, bq, bk = _BACKWARD_CASES[name]
    q, k, v = _qkv(h=h, hkv=hkv, s=s, sk=sk, d=d, seed=3)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=bq, block_k=bk)

    def dense(q, k, v):
        return attention_reference(q, k, v, causal=causal, window=window)

    weight = jnp.cos(dense(q, k, v))
    return ((q, k, v), lambda *a: (flash(*a) * weight).sum(),
            lambda *a: (dense(*a) * weight).sum())


@pytest.fixture
def no_vmem(monkeypatch):
    """The budget the backward pass holds the fused kernel's VMEM against,
    shrunk to nothing: every sequence is then a long one."""
    import importlib

    module = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    monkeypatch.setattr(module, "_vmem_budget", lambda: 0)
    module._make_attn.cache_clear()
    yield
    module._make_attn.cache_clear()


@pytest.mark.parametrize("case", sorted(_BACKWARD_CASES))
def test_flash_backward_is_one_kernel_with_the_dense_gradients(case):
    args, flash, dense = _backward_case(case)
    assert _kernel_calls(flash, *args) == (1, 1, 0)
    got = jax.grad(flash, (0, 1, 2))(*args)
    want = jax.grad(dense, (0, 1, 2))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("case", ["window_grouped", "own_heads",
                                  "longer_keys_window",
                                  "tiles_that_do_not_divide"])
def test_flash_backward_of_a_sequence_whose_sums_do_not_fit(case, request):
    """Where dq of the sequence (and, under grouped heads, dk and dv) would
    overrun the VMEM budget, dq and dk/dv come from a kernel each, which hold
    tiles only: the same tiles summed in the same order, so in float32 the
    same gradients bit for bit."""
    args, flash, dense = _backward_case(case)
    fused = jax.grad(flash, (0, 1, 2))(*args)
    request.getfixturevalue("no_vmem")
    assert _kernel_calls(flash, *args) == (1, 0, 2)
    by_tiles = jax.grad(flash, (0, 1, 2))(*args)
    for a, b, c in zip(by_tiles, fused, jax.grad(dense, (0, 1, 2))(*args)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, c, rtol=5e-5, atol=5e-5)


def test_what_the_backward_kernel_asks_of_vmem_follows_the_shapes():
    """Float32 sums (dq of the sequence; dk and dv of a tile, or of the
    sequence under grouped heads), every block twice, six score tiles: both
    decoder cells' shapes ask for about half the budget.  At tiles of
    1024 x 1024 the longest power of two that still fits is 65,536 tokens at
    head size 128 (32,768 where eight query heads share a key/value head)
    and 16,384 at head size 256; the next falls back to the two tile
    kernels, which ask for what the fused one would for one tile."""
    from incubator_mxnet_tpu.parallel.flash_attention import (
        _VMEM_BYTES, _fused_bwd_vmem, _vmem_budget)

    mib = 2 ** 20
    assert _vmem_budget() == 96 * mib == _VMEM_BYTES * 3 // 4
    # 8,192 x (32 over 4 heads of 128), tiles 1024 x 1024: three sums of
    # 4 MiB, blocks 2 x 4.5 MiB, tiles 24 MiB
    assert _fused_bwd_vmem(8192, 8192, 128, 1024, 1024, 8, 2, 128) == 45 * mib
    # 8,192 x 20 heads of 256, tiles 1024 x 1024: 8 + 2 x 1, 2 x 8, 24 MiB
    assert _fused_bwd_vmem(8192, 8192, 256, 1024, 1024, 1, 2, 256) == 50 * mib
    longest = {(d, group): max(
        s for s in (2 ** n for n in range(10, 22))
        if _fused_bwd_vmem(s, s, d, 1024, 1024, group, 2, d)
        <= _vmem_budget())
        for d, group in ((128, 1), (128, 8), (256, 1))}
    assert longest == {(128, 1): 65536, (128, 8): 32768,
                       (256, 1): 16384}, longest
    # the first that falls back at head size 256, and its tile kernels
    assert _fused_bwd_vmem(32768, 32768, 256, 1024, 1024, 1, 2,
                           256) == 98 * mib
    assert _fused_bwd_vmem(1024, 1024, 256, 1024, 1024, 1, 2, 256) == 36 * mib


def test_flash_grouped_heads_without_a_mask_and_with_longer_keys():
    q, k, v = _qkv(h=6, hkv=3, s=16, sk=48)
    for causal, window in ((False, None), (True, None), (True, 20)):
        got = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=8, block_k=16)
        want = attention_reference(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_window_walks_only_the_tiles_the_mask_admits():
    from incubator_mxnet_tpu.parallel.flash_attention import _steps

    # 8,192 keys in tiles of 512, queries in tiles of 256: a window of 2,048
    # touches 6 key tiles at most, where the causal mask alone walks all 16
    assert _steps(16, 256, 512, 2048) == 6 and _steps(16, 256, 512, None) == 16
    assert _steps(4, 16, 16, 100) == 4
    with pytest.raises(ValueError, match="window"):
        flash_attention(*_qkv(), causal=False, window=8)
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(*_qkv(h=4, hkv=3))


def test_flash_attention_op_takes_window_and_grouped_heads():
    q, k, v = _qkv(s=32)
    got = mx.nd.contrib.flash_attention(
        mx.nd.NDArray(q), mx.nd.NDArray(k), mx.nd.NDArray(v), causal=True,
        window=8, block_q=16, block_k=16)
    want = attention_reference(q, k, v, causal=True, window=8)
    np.testing.assert_allclose(got.asnumpy(), want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _expert_weights(e=8, d=16, f=8, seed=0):
    rng = np.random.RandomState(seed)
    return dict(
        router=jnp.asarray(rng.normal(size=(e, d)) * 0.5, jnp.float32),
        bias=jnp.asarray(rng.normal(size=(e,)) * 0.01, jnp.float32),
        w1=jnp.asarray(rng.normal(size=(e, d, f)) * 0.3, jnp.float32),
        w3=jnp.asarray(rng.normal(size=(e, d, f)) * 0.3, jnp.float32),
        w2=jnp.asarray(rng.normal(size=(e, f, d)) * 0.3, jnp.float32))


def _layer(x, w, held, top_k=2):
    """Router, then the held experts' part."""
    first, count = held
    mine = [w[k][first:first + count] for k in ("w1", "w3", "w2")]
    weights, sel, counts = moe.moe_route(
        x, w["router"], w["bias"], top_k=top_k, route_norm=True,
        route_scale=2.826)
    rows, sizes, row, order = moe.moe_dispatch(x, sel, experts_held=held)
    assert rows.shape[0] == x.shape[0] * top_k   # room for every assignment
    ys = moe.moe_experts(rows, *mine, sizes)
    return moe.moe_combine(ys, weights, sizes, row, order), counts, sizes


def _token_loop(x, w, held, top_k=2):
    """Token by token, choice by choice: the held experts' part."""
    first, count = held
    x_, w_ = np.asarray(x, np.float64), {k: np.asarray(v, np.float64)
                                         for k, v in w.items()}
    out = np.zeros_like(x_)
    for t, row in enumerate(x_):
        scores = 1.0 / (1.0 + np.exp(-(w_["router"] @ row)))
        chosen = np.argsort(-(scores + w_["bias"]), kind="stable")[:top_k]
        weight = scores[chosen] / (scores[chosen].sum() + 1e-20) * 2.826
        for e, c in zip(chosen, weight):
            if first <= e < first + count:
                gate = row @ w_["w1"][e]
                h = gate / (1.0 + np.exp(-gate)) * (row @ w_["w3"][e])
                out[t] += c * (h @ w_["w2"][e])
    return out


@pytest.mark.parametrize("held", [(0, 8), (2, 4), (7, 1)])
def test_expert_layer_matches_a_loop_over_tokens(held):
    w = _expert_weights()
    x = jnp.asarray(np.random.RandomState(1).normal(size=(40, 16)),
                    jnp.float32)
    got, counts, sizes = _layer(x, w, held)
    np.testing.assert_allclose(got, _token_loop(x, w, held), rtol=1e-4,
                               atol=1e-5)
    assert float(counts.sum()) == 40 * 2
    np.testing.assert_array_equal(
        np.asarray(sizes), np.asarray(counts)[held[0]:held[0] + held[1]])


def test_expert_layer_drops_nothing_when_every_token_goes_to_one_expert():
    w = _expert_weights()
    # the selection bias forces experts 3 and 5 on every token
    w["bias"] = w["bias"].at[jnp.array([3, 5])].set(10.0)
    x = jnp.asarray(np.random.RandomState(2).normal(size=(40, 16)),
                    jnp.float32)
    got, counts, sizes = _layer(x, w, (2, 4))
    assert np.asarray(counts).tolist() == [0, 0, 0, 40, 0, 40, 0, 0]
    assert np.asarray(sizes).tolist() == [0, 40, 0, 40]
    np.testing.assert_allclose(got, _token_loop(x, w, (2, 4)), rtol=1e-4,
                               atol=1e-5)
    # the bias chose, the scores weigh: the weights do not hold the 10.0
    weights, _, _ = moe.moe_route(x, w["router"], w["bias"], top_k=2,
                                  route_norm=True, route_scale=1.0)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)


def test_expert_layer_gradients_match_dense_autodiff():
    w = _expert_weights()
    x = jnp.asarray(np.random.RandomState(3).normal(size=(24, 16)),
                    jnp.float32)

    def dense(x, w):
        p = {"router_weight": w["router"], "bias": w["bias"],
             "w1": w["w1"][2:6], "w3": w["w3"][2:6], "w2": w["w2"][2:6],
             "shared_w1_weight": jnp.zeros((8, 16)),
             "shared_w3_weight": jnp.zeros((8, 16)),
             "shared_w2_weight": jnp.zeros((16, 8))}
        return ref.expert_ffn(p, "", x, dict(_CFG, experts_held=(2, 4)))[0]

    weight = jnp.cos(dense(x, w))
    want = jax.grad(lambda x, w: (dense(x, w) * weight).sum(), (0, 1))(x, w)
    got = jax.grad(lambda x, w: (_layer(x, w, (2, 4))[0] * weight).sum(),
                   (0, 1))(x, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """What each of eight chips computes of one expert layer (its own
    experts' part, plus the shared expert that every chip computes alike),
    with the shared expert counted once, is the uncut reference's layer."""
    net, full = [], None
    cfg = dict(_CFG, experts_held=(0, 16))
    for chip in range(8):
        held = (2 * chip, 2)
        block = text.ExpertFFN(32, 16, 2, 16, experts_held=held,
                               route_scale=2.826, prefix="moe_")
        block.initialize(init=mx.init.Xavier())
        net.append((held, block))
    x = mx.nd.array(np.random.RandomState(4).normal(size=(2, 12, 32)))
    rng = np.random.RandomState(5)
    whole = {"router_weight": rng.normal(size=(16, 32)) * 0.3,
             "bias": rng.normal(size=(16,)) * 0.01,
             "w1": rng.normal(size=(16, 32, 16)) * 0.3,
             "w3": rng.normal(size=(16, 32, 16)) * 0.3,
             "w2": rng.normal(size=(16, 16, 32)) * 0.3,
             "shared_w1_weight": rng.normal(size=(16, 32)) * 0.3,
             "shared_w3_weight": rng.normal(size=(16, 32)) * 0.3,
             "shared_w2_weight": rng.normal(size=(32, 16)) * 0.3}
    whole = {k: jnp.asarray(v, jnp.float32) for k, v in whole.items()}
    total = 0.0
    for (first, count), block in net:
        block(x)    # resolves the deferred shapes
        for p in block.collect_params().values():
            name = p.name[len(block.prefix):]
            if name == "counts":
                continue
            value = whole[name]
            p.set_data(value[first:first + count]
                       if name in ("w1", "w3", "w2") else value)
        total = total + block(x).asnumpy()
    shared = ref.gated_ffn(x._data.reshape(-1, 32), whole["shared_w1_weight"],
                           whole["shared_w3_weight"],
                           whole["shared_w2_weight"]).reshape(2, 12, 32)
    want, _ = ref.expert_ffn(whole, "", x._data.reshape(-1, 32), cfg)
    np.testing.assert_allclose(total - 7 * np.asarray(shared),
                               np.asarray(want).reshape(2, 12, 32),
                               rtol=1e-4, atol=1e-5)




# ---------------------------------------------------------------------------
# the row movers against the jax.numpy forms they replaced
# ---------------------------------------------------------------------------

_T, _K, _D = 64, 8, 16      # a buffer of 512 rows: two blocks of the row kernels


def _pick(ys, row, held):
    return jnp.where(held[..., None], ys[row], 0).astype(jnp.float32)


def _oracle_rows_scaled(x, ys, weights, row, order, n):
    """``_weighted_sum_bwd`` as it was, ``x`` the tokens' cotangent."""
    held = row < n
    w_row = jnp.where(held, weights, 0.0).reshape(-1)[order]
    dys = w_row[:, None] * x[order // _K].astype(jnp.float32)
    dw = jnp.sum(x[:, None, :].astype(jnp.float32) * _pick(ys, row, held), -1)
    return dys.astype(ys.dtype), dw


def _oracle_tokens_weighted(x, ys, weights, row, order, n):
    """``_weighted_sum`` as it was."""
    return jnp.sum(weights[..., None] * _pick(ys, row, row < n),
                   1).astype(ys.dtype)


def _oracle_tokens_plain(x, ys, weights, row, order, n):
    """``_gather_rows_bwd`` as it was, ``ys`` the rows' cotangent."""
    return jnp.sum(_pick(ys, row, row < n), 1).astype(ys.dtype)


def _mover_rows_scaled(x, ys, weights, row, order, n):
    return moe._weighted_sum_bwd((ys, weights, row, order, n), x)[:2]


def _mover_tokens_weighted(x, ys, weights, row, order, n):
    return moe._weighted_sum(ys, weights, row, order, n)


def _mover_tokens_plain(x, ys, weights, row, order, n):
    return moe._gather_rows_bwd((row, n), ys)[0]


#: each mover beside the form it replaced, compiled once for every n
_MOVERS = {"rows_scaled": (_mover_rows_scaled, _oracle_rows_scaled),
           "tokens_weighted": (_mover_tokens_weighted,
                               _oracle_tokens_weighted),
           "tokens_plain": (_mover_tokens_plain, _oracle_tokens_plain)}
_MOVERS = {name: tuple(map(jax.jit, pair)) for name, pair in _MOVERS.items()}


@functools.lru_cache(maxsize=None)
def _buffers(dtype, d=_D):
    rng = np.random.RandomState(6)
    order = jnp.asarray(rng.permutation(_T * _K), jnp.int32)
    return (jnp.asarray(rng.normal(size=(_T, d)), dtype),
            jnp.asarray(rng.normal(size=(_T * _K, d)), dtype),
            jnp.asarray(rng.uniform(size=(_T, _K)), jnp.float32),
            jnp.argsort(order).astype(jnp.int32).reshape(_T, _K), order)


@pytest.mark.parametrize("n", [0, 1, 255, 256, 257, _T * _K])
@pytest.mark.parametrize("name", sorted(_MOVERS))
def test_a_row_mover_is_the_form_it_replaced(name, n):
    """At no row, one row, a block less one, a block, a block and one, and
    every row of the buffer.  What a kernel does not write the interpreter
    fills with NaN: the rows are compared below ``n``, the tokens whole.
    bfloat16 rows come out bit for bit; a bfloat16 token is a float32 sum
    rounded once, and may fall to the next value with the order of the
    sum."""
    _a_mover_against_its_oracle(name, n, _D)


@pytest.mark.parametrize("n", [0, 257, _T * _K])
@pytest.mark.parametrize("d", [256, 1536, 2560])
@pytest.mark.parametrize("name", sorted(_MOVERS))
def test_a_row_mover_at_a_width_of_whole_lanes_that_fills_no_slab(name, d, n):
    """The same at widths the kernels take as they are (whole tiles of 128
    lanes) and a slab is wider than: 256 (bfloat16: two of a slab's eight
    word-rows hold a tile, in their low halves), 1,536 and 2,560 (bfloat16:
    four word-rows hold two tiles, the rest one; float32: 12 of 16 and 20
    of 24 word-rows hold one)."""
    _a_mover_against_its_oracle(name, n, d)


def _a_mover_against_its_oracle(name, n, d):
    mover, oracle = _MOVERS[name]
    ulp = 2.0 ** -7 if name.startswith("tokens") else 0
    for dtype, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, ulp)):
        args = _buffers(dtype, d) + (jnp.int32(n),)
        got, want = mover(*args), oracle(*args)
        if name == "rows_scaled":
            held = np.asarray(args[3]) < n
            # a float32 sum of d products, in another order
            np.testing.assert_allclose(np.where(held, got[1], 0), want[1],
                                       rtol=1e-5, atol=1e-6 * d / _D)
            assert np.all(np.asarray(got[1])[~held] == 0)
            got, want = got[0], want[0]
        if name.startswith("rows"):
            edge = min(-(-n // 256) * 256, _T * _K)
            tail = np.asarray(got[n:edge], np.float32)
            assert np.all(tail == 0), "zeros up to the end of n's block"
            got, want = got[:n], want[:n]
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=tol,
                                   atol=tol)


def _slab_values(slabs, rows, dtype):
    """What the slabs hold, (rows, values a slab has room for) as bits: a
    float32 word is a value, a two-byte value lies in the low half of word
    ``c`` or the high half of word ``c - words / 2``."""
    words = slabs.reshape(rows, -1)
    if dtype == jnp.float32:
        return words
    return np.concatenate([words & 0xFFFF, words >> 16], 1).astype(np.uint16)


@pytest.mark.parametrize("dtype,d,sub", [
    (jnp.float32, _D, 8), (jnp.float32, 2560, 24), (jnp.bfloat16, 2560, 16)],
    ids=["float32_16", "float32_2560", "bfloat16_2560"])
def test_slabs_past_the_last_held_row_are_not_written(dtype, d, sub):
    """The grid covers the whole buffer and its steps past ``n`` do nothing:
    their slabs are what the buffer held (the interpreter's fill for words
    never written, 0), the live block's are the rows.  A row of 2,560
    values lies in a slab of 4,096 (3,072 float32): what a slab has past
    its row is zero bits or not written, which the interpreter makes zero
    bits too."""
    x = _buffers(dtype, d)[1]
    slabs = np.asarray(jax.jit(moe_rows.to_slabs)(x, jnp.int32(3)))
    assert slabs.shape == (_T * _K * sub, 128)
    got = _slab_values(slabs, _T * _K, dtype)
    want = np.asarray(x).view(got.dtype)
    np.testing.assert_array_equal(got[:256, :d], want[:256])
    assert want[256:].any(1).all() and not got[256:].any()
    assert not got[:, d:].any()


@pytest.mark.parametrize("dtype,d,loops", [
    (jnp.bfloat16, 2048, 1), (jnp.bfloat16, 4096, 1), (jnp.float32, 1024, 1),
    (jnp.bfloat16, 256, 1), (jnp.bfloat16, 1536, 2), (jnp.bfloat16, 2560, 2),
    (jnp.float32, 2560, 1)])
def test_a_slab_its_row_fills_is_walked_by_one_loop(dtype, d, loops):
    """The word-rows of a slab are walked by one traced loop a RANGE (two
    tiles a word-row, one, none): a width that fills its slab has one
    range, and so has every float32 width; 1,536 and 2,560 bfloat16 values
    have two.  Nothing is unrolled."""
    text = str(jax.make_jaxpr(moe_rows.to_slabs)(
        jax.ShapeDtypeStruct((_T * _K, d), dtype), jnp.int32(3)))
    assert text.count("while[") == loops
    assert "pad" not in text


@jax.custom_vjp
def _poison_tail(a, n):
    return _poisoned(a, n)


def _poisoned(a, n):
    return jnp.where((jnp.arange(a.shape[0]) < n)[:, None], a, jnp.nan)


_poison_tail.defvjp(lambda a, n: (_poisoned(a, n), n),
                    lambda n, g: (_poisoned(g, n), None))


@pytest.mark.parametrize("held", [(2, 4), (0, 8)])
def test_nothing_reads_the_buffers_tail(held):
    """The rows past ``sum(sizes)`` of the row buffer, of the grouped
    product's result and of both their cotangents set to NaN: the layer's
    output and every gradient come out finite and the same."""
    w = _expert_weights()
    x = jnp.asarray(np.random.RandomState(7).normal(size=(40, 16)),
                    jnp.float32)
    first, count = held

    def layer(x, w, poison):
        mine = [w[k][first:first + count] for k in ("w1", "w3", "w2")]
        weights, sel, _ = moe.moe_route(x, w["router"], w["bias"], top_k=2,
                                        route_norm=True, route_scale=2.826)
        rows, sizes, row, order = moe.moe_dispatch(x, sel, experts_held=held)
        rows = poison(rows, jnp.sum(sizes))
        ys = poison(moe.moe_experts(rows, *mine, sizes), jnp.sum(sizes))
        return moe.moe_combine(ys, weights, sizes, row, order)

    def both(poison):
        return jax.value_and_grad(
            lambda x, w: jnp.sum(jnp.sin(layer(x, w, poison))), (0, 1))(x, w)

    got, want = both(_poison_tail), both(lambda a, n: a)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(a, b)


def test_the_reference_follows_another_routers_choice_only_within_its_margin():
    """The reference routes by its own scores; a token's forced set is
    taken only where it is a top-k within ``eps`` of them, else refused."""
    w = _expert_weights()
    x = jnp.asarray(np.random.RandomState(6).normal(size=(24, 16)),
                    jnp.float32)
    cfg = dict(_CFG, experts_held=(0, 8))
    weights, sel, facts = ref.route(x, w["router"], w["bias"], cfg)
    assert float(facts["moved"]) == 0.0 and not facts["refused"].any()
    scores = np.asarray(facts["scores"] + w["bias"])
    # its own choice in another order: taken, nothing moved or refused
    same = ref.route(x, w["router"], w["bias"], cfg, forced=sel[:, ::-1],
                     eps=0.0)
    np.testing.assert_allclose(same[0][:, ::-1], weights, rtol=1e-6)
    assert float(same[2]["moved"]) == 0.0 and not same[2]["refused"].any()
    # the second choice of six tokens swapped for each token's THIRD best:
    # taken where the third is within eps of the second, else refused
    third = np.argsort(-scores, -1)[:, 2]
    other = sel.at[:6, 1].set(jnp.asarray(third[:6], sel.dtype))
    gap = np.sort(scores, -1)[:6, -2] - np.sort(scores, -1)[:6, -3]
    eps = float(np.sort(gap)[2] + np.sort(gap)[3]) / 2     # three within
    _, taken, facts = ref.route(x, w["router"], w["bias"], cfg, forced=other,
                                eps=eps)
    assert float(facts["moved"]) == pytest.approx(6 / 48)
    assert float(facts["refused"][2]) == pytest.approx(3 / 24)
    for t in range(6):
        want = other[t] if gap[t] <= eps else sel[t]
        assert sorted(np.asarray(taken[t])) == sorted(np.asarray(want))
    np.testing.assert_array_equal(taken[6:], other[6:])
    # a margin no set meets: the reference's own choice everywhere
    free = ref.route(x, w["router"], w["bias"], cfg, forced=other,
                     eps=-jnp.inf)
    np.testing.assert_array_equal(free[1], sel)
    np.testing.assert_allclose(free[0], weights, rtol=1e-6)
