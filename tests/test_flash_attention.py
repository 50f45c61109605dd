"""Pallas flash-attention tests (interpret mode on CPU; same code path
compiles on TPU)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.parallel import flash_attention
from incubator_mxnet_tpu.parallel.ring_attention import attention_reference

fa = importlib.import_module("incubator_mxnet_tpu.parallel.flash_attention")


def _qkv(b=2, h=2, s=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.uniform(-1, 1, (b, h, s, d)).astype(np.float32))
            for _ in range(3)]


def test_flash_forward_matches_dense():
    q, k, v = _qkv()
    out = flash_attention(q, k, v)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_flash_causal():
    q, k, v = _qkv(s=32)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_flash_blocking_invariance():
    """Different block sizes give identical results (streaming softmax)."""
    q, k, v = _qkv(s=48)
    a = flash_attention(q, k, v, block_q=16, block_k=16)
    b = flash_attention(q, k, v, block_q=48, block_k=48)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_flash_non_pow2_seq():
    q, k, v = _qkv(s=40)   # 40 % 128 != 0 → block shrinks to a divisor
    out = flash_attention(q, k, v)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_flash_causal_cross_length():
    """kv_len != q_len: causal mask right-aligns (KV-cache decode
    convention, tril(klen-qlen)) matching attention_reference."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.uniform(-1, 1, (1, 2, 4, 8)).astype(np.float32))
    k = jnp.asarray(rng.uniform(-1, 1, (1, 2, 12, 8)).astype(np.float32))
    v = jnp.asarray(rng.uniform(-1, 1, (1, 2, 12, 8)).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, block_q=2, block_k=4)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_dense(causal):
    q, k, v = _qkv(s=32)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, n in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(gf, gr, rtol=1e-4, atol=1e-5,
                                   err_msg="d%s mismatch" % n)


def test_flash_bf16_runs():
    q, k, v = [x.astype(jnp.bfloat16) for x in _qkv()]
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32))
    np.testing.assert_allclose(out.astype(jnp.float32), ref, rtol=5e-2,
                               atol=5e-2)


def test_flash_op_registry_path():
    q, k, v = _qkv(s=32)
    out = nd.contrib.flash_attention(nd.from_jax(q), nd.from_jax(k),
                                     nd.from_jax(v))
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_flash_inside_jit():
    """The kernel composes under jit (one compiled program)."""
    q, k, v = _qkv(s=32)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v).sum()

    val = f(q, k, v)
    ref = attention_reference(q, k, v).sum()
    np.testing.assert_allclose(val, ref, rtol=1e-5)


def test_flash_causal_empty_rows():
    """kv_len < q_len (causal): leading q rows have ZERO unmasked keys.
    Output must be 0 there (not mean(V)) and gradients must stay finite."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.uniform(-1, 1, (1, 2, 8, 8)).astype(np.float32))
    k = jnp.asarray(rng.uniform(-1, 1, (1, 2, 4, 8)).astype(np.float32))
    v = jnp.asarray(rng.uniform(-1, 1, (1, 2, 4, 8)).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, block_q=4, block_k=4)
    # offset = klen - qlen = -4: rows 0..3 see no keys at all
    np.testing.assert_allclose(np.asarray(out[:, :, :4]), 0.0, atol=1e-6)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :, 4:]),
                               np.asarray(ref[:, :, 4:]), rtol=1e-5, atol=1e-5)

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                block_q=4, block_k=4) ** 2).sum()

    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        assert np.isfinite(np.asarray(g)).all()
    # empty rows contribute nothing to dq
    np.testing.assert_allclose(np.asarray(dq[:, :, :4]), 0.0, atol=1e-6)


def test_flash_attention_long_seq_block_heuristic(monkeypatch):
    """seq >= 4096 auto-selects 256x512 blocks on the Pallas path when
    the caller leaves block sizes unset; explicit sizes always win; the
    tiling change never changes semantics."""
    import importlib

    fa = importlib.import_module(
        "incubator_mxnet_tpu.parallel.flash_attention")
    picked = []
    orig = fa._make_attn

    def spy(scale, causal, block_q, block_k, interpret):
        picked.append((block_q, block_k))
        return orig(scale, causal, block_q, block_k, interpret)

    monkeypatch.setattr(fa, "_make_attn", spy)
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, 4096, 8))
                           .astype(np.float32)) * 0.1 for _ in range(3))
    out = fa.flash_attention(q, k, v, causal=True, use_pallas=True)
    assert picked[-1] == (256, 512), picked
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    # explicit block sizes are never overridden (bench.py sweeps them)
    fa.flash_attention(q, k, v, causal=True, use_pallas=True,
                       block_q=128, block_k=128)
    assert picked[-1] == (128, 128), picked


# ---------------------------------------------------------------------------
# the mask on the tiles its edge crosses only
# ---------------------------------------------------------------------------

def _tile_counts(s, block, window):
    """(tiles wholly inside the mask, tiles the kernels compute) of a causal
    self-attention over ``s`` tokens in square tiles of ``block``."""
    n = s // block
    inside = admitted = 0
    for qi in range(n):
        first, last = fa._k_range(qi, block, block, 0, True, window, n)
        for ki in range(int(first), int(last) + 1):
            admitted += 1
            inside += bool(fa._inside(qi, ki, block, block, 0, window))
    return inside, admitted


@pytest.mark.parametrize("s,window,counts", [
    (16384, None, (120, 136)), (16384, 4096, (42, 70)),
    (8192, None, (28, 36)), (8192, 2048, (7, 21))],
    ids=["smallthinker_full", "smallthinker_window", "full_8k",
         "trinity_window"])
def test_tiles_wholly_inside_the_mask_at_the_cells_geometry(s, window,
                                                            counts):
    """Tiles of 1024 x 1024: of the tiles a causal layer computes, those that
    pay for no mask (ISSUE 40's counts)."""
    assert _tile_counts(s, 1024, window) == counts


@pytest.mark.parametrize("s,sk,bq,bk,window", [
    (64, 64, 16, 16, None), (64, 64, 16, 16, 8), (64, 64, 16, 16, 16),
    (64, 64, 16, 8, 24), (64, 64, 8, 16, 40), (64, 64, 16, 16, 80),
    (32, 64, 16, 16, None), (32, 64, 8, 16, 20), (64, 32, 16, 16, None),
    (64, 32, 16, 8, 12)], ids=str)
def test_inside_is_the_brute_force_mask_over_every_tile(s, sk, bq, bk,
                                                        window):
    """``_inside`` against numpy's mask, tile by tile over the whole grid,
    with the key/value sequence as long as, longer and shorter than the
    queries' (rows with no admitted key)."""
    rows = np.arange(s)[:, None] + (sk - s)
    cols = np.arange(sk)[None, :]
    keep = cols <= rows
    if window is not None:
        keep &= cols > rows - window
    for qi in range(s // bq):
        for ki in range(sk // bk):
            want = keep[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].all()
            got = fa._inside(qi, ki, bq, bk, sk - s, window)
            assert bool(got) == want, (qi, ki)


_SPLIT_CASES = {   # s, sk, block_q, block_k, window, heads, kv_heads
    "causal_full": (64, 64, 16, 16, None, 2, 2),
    "window_under_tile": (64, 64, 16, 16, 8, 2, 2),
    "window_is_tile": (64, 64, 16, 16, 16, 2, 2),
    "window_off_tiles": (64, 64, 16, 8, 24, 2, 2),
    "window_past_sequence": (64, 64, 16, 16, 80, 2, 2),
    "keys_past_queries": (32, 64, 16, 16, None, 2, 2),
    "grouped_heads": (64, 64, 16, 16, 24, 4, 2),
}


def _kernel_results(s, sk, bq, bk, window, heads, kv_heads):
    """out, lse, dq, dk, dv of the kernels in bfloat16 (lse float32), as
    bits.  Compiled without XLA:CPU's fusion emitters, which sum a row in
    another order where a select is fused into the sum (an ulp in lse and
    out, the interpreter's host code and not the kernels': the chip's
    compiler is held to the bits by a run on the chip, PERF.md section 5)."""
    rng = np.random.RandomState(40)

    def draw(rows, n):
        return jnp.asarray(rng.normal(size=(n, rows, 16)), jnp.bfloat16)

    q, do = draw(s, heads), draw(s, heads)
    k, v = draw(sk, kv_heads), draw(sk, kv_heads)
    static = (0.25, True, window, heads, kv_heads, bq, bk, True)

    def both(q, k, v, do):
        out, lse = fa._fwd(q, k, v, *static)
        return (out, lse, *fa._bwd(*static, (q, k, v, out, lse), do))

    results = jax.jit(both).lower(q, k, v, do).compile(compiler_options={
        "xla_cpu_use_fusion_emitters": False})(q, k, v, do)
    return [np.asarray(x).view(np.uint16 if x.dtype == jnp.bfloat16
                               else np.uint32) for x in results]


@pytest.mark.parametrize("by_tiles", [False, True],
                         ids=["flash_bwd", "bwd_by_tiles"])
@pytest.mark.parametrize("case", sorted(_SPLIT_CASES))
def test_unmasked_tiles_give_the_masked_kernels_bits(monkeypatch, case,
                                                     by_tiles):
    """The kernels as they are against the same kernels with every tile
    taken for one the mask's edge crosses (``_inside`` always false, the
    form before PR 40): out, lse, dq, dk and dv bit for bit, through the one
    backward kernel and through the two of the long form."""
    if by_tiles:
        monkeypatch.setattr(fa, "_vmem_budget", lambda: 0)
    split = _kernel_results(*_SPLIT_CASES[case])
    monkeypatch.setattr(fa, "_inside", lambda *a: False)
    edge = _kernel_results(*_SPLIT_CASES[case])
    for name, got, want in zip(("out", "lse", "dq", "dk", "dv"), split,
                               edge):
        np.testing.assert_array_equal(got, want, err_msg=name)


def _dense_causal(q, k, v, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    keep = jnp.tril(jnp.ones(s.shape[-2:], bool))
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("by_tiles", [False, True],
                         ids=["fused_bwd", "two_kernel_bwd"])
def test_value_heads_narrower_than_query_key_heads(monkeypatch, by_tiles):
    """Latent attention without rotary has query/key heads of 192 and value
    heads of 128: the kernels take a value width of its own, forward and
    both backward forms, against plain causal attention."""
    rng = np.random.RandomState(7)
    q, k = (jnp.asarray(rng.uniform(-1, 1, (1, 2, 64, 192)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.uniform(-1, 1, (1, 2, 64, 128)), jnp.float32)
    if by_tiles:
        monkeypatch.setattr(fa, "_vmem_budget", lambda: 0)
    fa._make_attn.cache_clear()
    scale = 1.0 / np.sqrt(192)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale, block_q=16,
                               block_k=32, use_pallas=True)

    def dense(q, k, v):
        return _dense_causal(q, k, v, scale)

    out = flash(q, k, v)
    assert out.shape == (1, 2, 64, 128)
    np.testing.assert_allclose(out, dense(q, k, v), rtol=1e-5, atol=1e-5)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), (0, 1, 2))(q, k, v)
    for a, b, n in zip(got, want, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg="d%s mismatch" % n)
    fa._make_attn.cache_clear()
