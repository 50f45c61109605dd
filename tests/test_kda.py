"""Kimi Delta Attention's recurrence (``parallel/delta_rule.py``): the Pallas
kernels (interpret mode here) and the chunked ``jax.numpy`` form against the
recurrence token by token, forward and the gradients of q, k, v, g and beta,
over several chunks and a sequence that is not a whole number of them, with
decays near 0 and near 1, write strengths near 0 and 1, and keys alike
within a chunk; the registered op."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.parallel import delta_rule as dr

#: three chunks and a part of a fourth, two heads of 32
_B, _S, _H, _D = 1, 200, 2, 32

#: (decays as g = log(alpha), write strengths beta): ranges to draw from
_CASES = {
    "mixed": ((-1.0, 0.0), (0.0, 1.0)),
    "decay_near_1": ((-1e-3, 0.0), (0.0, 1.0)),
    "decay_near_0": ((-12.0, -6.0), (0.0, 1.0)),
    "beta_near_0": ((-1.0, 0.0), (0.0, 0.02)),
    "beta_near_1": ((-0.3, 0.0), (0.98, 1.0)),
    # keys alike within a chunk (one direction a head and a little noise)
    # under slow decay: the chunk's triangular system is far from I
    "alike_keys": ((-1e-2, 0.0), (0.9, 1.0)),
}


def _inputs(case, seed=0):
    """q and k L2-normed per head (q scaled as the model scales it), the
    flat (B, S, H * D) layout the op takes."""
    (g_lo, g_hi), (b_lo, b_hi) = _CASES[case]
    r = np.random.RandomState(seed)
    q, k, v = (r.randn(_B, _S, _H, _D) for _ in range(3))
    if case == "alike_keys":
        k = r.randn(_B, 1, _H, _D) + 0.2 * k
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(_D)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = r.uniform(g_lo, g_hi, (_B, _S, _H, _D))
    beta = r.uniform(b_lo, b_hi, (_B, _S, _H))
    flat = [jnp.asarray(x.reshape(_B, _S, -1), jnp.float32)
            for x in (q, k, v, g)]
    return flat + [jnp.asarray(beta, jnp.float32)]


def _recurrent(q, k, v, g, beta):
    """The recurrence token by token (``lax.scan``), float32 at the highest
    precision, shapes as ``dr.kda``'s."""
    f = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    bsz, s, h = beta.shape
    q, k, v, g = (x.reshape(bsz, s, h, -1) for x in (q, k, v, g))

    def token(S, t):
        qt, kt, vt, gt, bt = t
        S = S * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - f("bhkv,bhk->bhv", S, kt))
        S = S + kt[..., None] * u[..., None, :]
        return S, f("bhkv,bhk->bhv", S, qt)

    S0 = jnp.zeros((bsz, h, q.shape[-1], v.shape[-1]), jnp.float32)
    xs = [x.swapaxes(0, 1) for x in (q, k, v, g, beta)]
    return jax.lax.scan(token, S0, xs)[1].swapaxes(0, 1).reshape(bsz, s, -1)


def _grads(f, xs, do):
    return jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * do),
                            argnums=(0, 1, 2, 3, 4)))(*xs)


@functools.lru_cache(maxsize=None)
def _want(case):
    xs = _inputs(case)
    out = jax.jit(_recurrent)(*xs)
    do = jnp.asarray(np.random.RandomState(9).randn(*out.shape), jnp.float32)
    return xs, do, out, _grads(_recurrent, xs, do)


@pytest.mark.parametrize("form", ["pallas", "chunked"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_chunked_forms_are_the_recurrence_token_by_token(case, form):
    f = dr.kda if form == "pallas" else dr.kda_chunked
    xs, do, want, want_grads = _want(case)
    out = jax.jit(f)(*xs)
    assert out.shape == want.shape == (_B, _S, _H * _D)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(out - want))) <= 2e-5 * scale
    for name, got, ref in zip(("q", "k", "v", "g", "beta"),
                              _grads(f, xs, do), want_grads):
        assert got.shape == ref.shape
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 0, name
        err = float(jnp.max(jnp.abs(got - ref)))
        # float32 on both sides; the chunk's products at the highest
        # precision: rounding and the order of sums.  Under decays near 0
        # the gradient of g is a sum of terms of order 0.01 that cancel to
        # 3e-4: its rounding is that of the terms
        assert err <= 2e-5 * max(scale, 0.01), (name, err, scale)


def test_the_state_crosses_every_chunk_boundary():
    """A token's write is read back after three chunk boundaries: with no
    decay, beta 1 at token 0 and 0 after it, the read of token 199 is
    token 0's value."""
    q, k, v, g, beta = _inputs("mixed", seed=3)
    g = jnp.zeros_like(g)
    beta = jnp.zeros_like(beta).at[:, 0].set(1.0)
    k = k.at[:, 1:].set(0.0)
    q = q.at[:, -1].set(k[:, 0])
    out = dr.kda(q, k, v, g, beta)
    # o = S^T q = v_0 (k_0 . k_0) for each head; k_0 has unit norm
    np.testing.assert_allclose(out[:, -1], v[:, 0], rtol=1e-5, atol=1e-6)


def test_the_op_keeps_the_dtype_and_matches_the_kernels_in_bf16():
    q, k, v, g, beta = _inputs("mixed")
    half = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    got = nd.contrib.kda(*(nd.array(x) for x in half + [g, beta]))._data
    assert got.dtype == jnp.bfloat16
    want = dr.kda(*half, g, beta)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


#: the cases above, and decays slower still under keys alike: the chunk's
#: system as far from I as a chunk makes it
_SYSTEMS = dict(_CASES, slowest_decay=((-1e-4, 0.0), (0.99, 1.0)))


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("case", sorted(_SYSTEMS))
def test_the_chunk_inverse_is_the_inverse_in_float64(case, seed):
    """``T = (I + diag(beta) B)^{-1}`` of one chunk of 64 tokens and keys of
    128, ``beta * B`` made as ``_chunk_parts`` makes it, against
    ``numpy.linalg.inv`` in float64."""
    (g_lo, g_hi), (b_lo, b_hi) = _SYSTEMS[case]
    c, d = dr.CHUNK, 128
    r = np.random.RandomState(seed)
    k = r.randn(c, d)
    if case in ("alike_keys", "slowest_decay"):
        k = r.randn(1, d) + 0.2 * k
    k = jnp.asarray(k / np.linalg.norm(k, axis=-1, keepdims=True),
                    jnp.float32)
    g = jnp.asarray(r.uniform(g_lo, g_hi, (c, d)), jnp.float32)
    beta = jnp.asarray(r.uniform(b_lo, b_hi, (c, 1)), jnp.float32)

    @jax.jit
    def system(k, g, beta):
        G = dr._mm(dr._lower(c, False), g, 1, 0)
        return beta * dr._pairs(G, k, [k])[0] * dr._lower(c, True)

    a = system(k, g, beta)
    got = np.asarray(jax.jit(dr._inverse)(a), np.float64)
    want = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-6 * scale


def test_the_chunk_inverse_is_ten_products():
    """Block doubling at C = 64: at most 10 products, so a chain of at most
    10; a chunk that is not a power of two is refused."""
    jaxpr = jax.make_jaxpr(dr._inverse)(
        jnp.zeros((dr.CHUNK,) * 2, jnp.float32))
    names = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert names.count("dot_general") <= 10
    with pytest.raises(ValueError, match="power of two"):
        dr._inverse(jnp.zeros((48, 48), jnp.float32))
