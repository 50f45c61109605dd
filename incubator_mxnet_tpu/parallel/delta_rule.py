"""Kimi Delta Attention (KDA): the gated delta rule with one decay a key
channel, as a chunked Pallas scan with a backward kernel of its own.

For one head, with keys of ``dk`` and values of ``dv`` columns and the state
``S`` (dk x dv, zero before the first token)::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,        alpha_t = exp(g_t),  g_t <= 0 (dk values)

**Chunks.**  The sequence is cut into chunks of ``CHUNK`` = 64 tokens and
the state is carried between them in float32.  Inside a chunk, with ``G``
the cumulative sum of ``g`` from the chunk's start and ``S0`` the state it
starts from, the rule unrolls to (the WY/UT form)::

    u_t = beta_t (v_t - S0^T (e^{G_t} k_t) - sum_{i<t} u_i B_ti)
    B_ti = sum_c k_tc k_ic e^{G_tc - G_ic}            (i < t)
    U = T (beta V) - T (beta e^G K) S0,   T = (I + diag(beta) B)^{-1}
    O = (e^G Q) S0 + A U,   A_ti = sum_c q_tc k_ic e^{G_tc - G_ic}  (i <= t)
    S1 = Diag(e^{G_C}) S0 + (e^{G_C - G} K)^T U

``T`` is a unit lower-triangular inverse, made by block doubling
(``_inverse``): 10 products of C x C in a chain 10 deep.  No factor
that is computed can overflow: ``B`` and ``A`` (whose factor
``e^{G_t - G_i}`` would be ``e^{G_t} e^{-G_i}`` in a plain product, and
``e^{-G_i}`` grows without bound where the decay is strong) are made in
sub-chunks of ``_SUB`` rows.  Columns left of a row block are one product
whose two factors are taken about the block's first row, so each exponent
is at most 0; the block's own ``_SUB`` x ``_SUB`` triangle is made column by
column, elementwise.

**The state is kept transposed** (``Z = S^T``, dv x dk) in the kernels, so
that ``Diag(e^{G_C})`` scales its columns and nothing is transposed inside a
kernel.

**Kernels.**  The forward kernel walks the chunks of a head in order, holds
``Z`` in VMEM and writes ``O`` and the state each chunk STARTS from (float32,
the backward pass's residual).  The backward kernel walks them in reverse,
holds ``dZ`` in VMEM, recomputes a chunk's matrices from its inputs and its
stored start, and writes ``dq, dk, dv, dg, dbeta``.  Everything inside is
float32, products at the highest precision.  On the CPU the same kernels run
through the Pallas interpreter.  :func:`kda_chunked` is the same chunk
mathematics as plain ``jax.numpy`` over a ``lax.scan`` of chunks (with the
same backward, a reverse scan).

The registered op ``_contrib_kda`` takes ``(B, S, H * D)`` arrays as the
model's projections give them (a ``(B, S, H, D)`` view would be another
layout on the chip, and a copy), ``g`` ``(B, S, H * dk)`` and ``beta`` ``(B,
S, H)``; the package runs with x64 on, so every index here is an explicit
int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import _backend

__all__ = ["kda", "kda_chunked", "CHUNK"]

#: tokens a chunk
CHUNK = 64
#: rows of a sub-chunk (a power of two): the triangles made elementwise are
#: _SUB x _SUB
_SUB = 16
_F32 = jnp.float32
_I0 = _np.int32(0)


def _mm(a, b, ca, cb):
    """``a`` times ``b`` contracted over axis ``ca`` of ``a`` and ``cb`` of
    ``b``, float32 at the highest precision."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _eye(c):
    return _iota((c, c), 0) == _iota((c, c), 1)


def _lower(c, strict):
    """``(C, C)`` float32 ones on and below the diagonal (``strict``: below
    it only)."""
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    return jnp.where(col < row if strict else col <= row, _F32(1), _F32(0))


def _pairs(G, b, lefts):
    """For each ``a`` of ``lefts``: ``P_ti = sum_c a_tc b_ic e^{G_tc -
    G_ic}`` for ``i <= t``, zero above the diagonal, ``(C, C)``."""
    c = G.shape[0]
    blocks = [[] for _ in lefts]
    for r0 in range(0, c, _SUB):
        gs = G[r0:r0 + _SUB]
        col = _iota((_SUB, c), 1)
        row = r0 + _iota((_SUB, c), 0)
        if r0:
            ref = G[r0:r0 + 1]
            right = b * jnp.exp(jnp.minimum(ref - G, _F32(0)))
            down = jnp.exp(gs - ref)
            out = [jnp.where(col < r0, _mm(a[r0:r0 + _SUB] * down, right,
                                           1, 1), _F32(0)) for a in lefts]
        else:
            out = [jnp.zeros((_SUB, c), _F32) for _ in lefts]
        for ci in range(r0, r0 + _SUB):
            # rows above ci are masked below: their exponent is cut at 0
            kb = jnp.exp(jnp.minimum(gs - G[ci:ci + 1], _F32(0))) \
                * b[ci:ci + 1]
            for j, a in enumerate(lefts):
                out[j] = jnp.where(col == ci, jnp.sum(
                    a[r0:r0 + _SUB] * kb, 1, keepdims=True), out[j])
        for j in range(len(lefts)):
            blocks[j].append(jnp.where(col <= row, out[j], _F32(0)))
    return [jnp.concatenate(bl, 0) for bl in blocks]


def _pairs_bwd(G, b, lefts):
    """The transpose of :func:`_pairs` for ``lefts`` = ``[(a, dP)]``, ``dP``
    zero above the diagonal: ``([da for each a], db)`` with ``da_t = sum_i
    dP_ti b_i e^{G_t - G_i}`` and ``db_i = sum over the pairs of sum_t dP_ti
    a_t e^{G_t - G_i}``."""
    c, d = G.shape
    das = [[] for _ in lefts]
    db = jnp.zeros((c, d), _F32)
    diag = []
    for r0 in range(0, c, _SUB):
        gs = G[r0:r0 + _SUB]
        col = _iota((_SUB, c), 1)
        rows = [dp[r0:r0 + _SUB] for _, dp in lefts]
        if r0:
            ref = G[r0:r0 + 1]
            up = jnp.exp(jnp.minimum(ref - G, _F32(0)))
            down = jnp.exp(gs - ref)
            right = b * up
            off = [jnp.where(col < r0, dp, _F32(0)) for dp in rows]
            da = [down * _mm(dp, right, 1, 0) for dp in off]
            back = sum(_mm(dp, a[r0:r0 + _SUB] * down, 0, 0)
                       for dp, (a, _) in zip(off, lefts))
            db = db + up * back
        else:
            da = [jnp.zeros((_SUB, d), _F32) for _ in lefts]
        dbs = jnp.zeros((_SUB, d), _F32)
        srow = _iota((_SUB, d), 0)
        for ci in range(r0, r0 + _SUB):
            e = jnp.exp(jnp.minimum(gs - G[ci:ci + 1], _F32(0)))
            z = jnp.zeros((_SUB, d), _F32)
            for j, ((a, _), dp) in enumerate(zip(lefts, rows)):
                dcol = jnp.sum(jnp.where(col == ci, dp, _F32(0)), 1,
                               keepdims=True)
                da[j] = da[j] + dcol * e * b[ci:ci + 1]
                z = z + dcol * a[r0:r0 + _SUB]
            dbs = jnp.where(srow == ci - r0, jnp.sum(z * e, 0, keepdims=True),
                            dbs)
        diag.append(dbs)
        for j in range(len(lefts)):
            das[j].append(da[j])
    db = db + jnp.concatenate(diag, 0)
    return [jnp.concatenate(x, 0) for x in das], db


def _inverse(a):
    """``(I + a)^{-1}`` of a strictly lower-triangular ``(C, C)`` ``a``, C a
    power of two, by block doubling.  With ``T_s`` the inverse of ``I + a``
    kept to its diagonal blocks of ``s`` rows, and ``O_s`` the entries of
    ``a`` in the lower-left ``s x s`` quadrant of each diagonal block of
    ``2s`` rows::

        T_2 = I - O_1,    T_2s = T_s - T_s (O_s T_s)

    (``[[M11, 0], [M21, M22]]^{-1} = [[T11, 0], [-T22 M21 T11, T22]]``, for
    every block at once): ``2 log2(C) - 2`` products, 10 at C = 64, in a
    chain as deep.  Every product holds blocks of an inverse, which are as
    bounded as the recurrence.  (A sum of the powers of ``-a`` by doubling
    holds those powers, whose entries grow as binomials of C: where the
    keys of a chunk are alike and the decay slow they overflow float32.)"""
    c = a.shape[0]
    if c & (c - 1):
        raise ValueError("kda: a chunk of %d rows, not a power of two" % c)
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    # int32 shifts and masks: the kernels' compiler takes no integer
    # division.  Row and column in one block of 2s rows and in different
    # blocks of s: their indices shifted by log2(s) differ in the last bit
    def quadrant(lv):
        return jnp.where(((row >> lv) ^ (col >> lv)) == 1, a, _F32(0))

    t = jnp.where(row == col, _F32(1), _F32(0)) - quadrant(_np.int32(0))
    for lv in range(1, c.bit_length() - 1):
        t = t - _mm(t, _mm(quadrant(_np.int32(lv)), t, 1, 0), 1, 0)
    return t


def _chunk_parts(q, k, v, g, beta, z):
    """What the forward and backward passes of a chunk share."""
    c = q.shape[0]
    G = _mm(_lower(c, False), g, 1, 0)
    B, A = _pairs(G, k, [k, q])
    B = B * _lower(c, True)
    T = _inverse(beta * B)
    gam = jnp.exp(G)
    gl = G[c - 1:c]
    kp, qp = k * gam, q * gam
    x, y = beta * kp, beta * v
    W = _mm(T, x, 1, 0)
    U = _mm(T, y, 1, 0) - _mm(W, z, 1, 1)
    kt = k * jnp.exp(gl - G)
    return dict(G=G, B=B, A=A, T=T, gam=gam, gl=gl, kp=kp, qp=qp, x=x, y=y,
                W=W, U=U, kt=kt)


def _chunk_fwd(q, k, v, g, beta, z):
    """One chunk: ``(O, Z1)`` from float32 ``q, k (C, dk), v (C, dv), g (C,
    dk), beta (C, 1)`` and the transposed state ``z`` (dv, dk) it starts
    from."""
    p = _chunk_parts(q, k, v, g, beta, z)
    o = _mm(p["qp"], z, 1, 1) + _mm(p["A"], p["U"], 1, 0)
    z1 = z * jnp.exp(p["gl"]) + _mm(p["U"], p["kt"], 0, 0)
    return o, z1


def _chunk_bwd(q, k, v, g, beta, z, do, dz1):
    """The gradients of one chunk, ``(dq, dk, dv, dg, dbeta, dz)``, for the
    cotangents of its output ``do`` and of the state it ends with ``dz1``."""
    c = q.shape[0]
    p = _chunk_parts(q, k, v, g, beta, z)
    G, A, B, T, U, W = p["G"], p["A"], p["B"], p["T"], p["U"], p["W"]
    kp, qp, kt, gam, gl = p["kp"], p["qp"], p["kt"], p["gam"], p["gl"]
    du = _mm(A, do, 0, 0) + _mm(kt, dz1, 1, 1)
    dA = _mm(do, U, 1, 1) * _lower(c, False)
    dz = _mm(do, qp, 0, 0) + dz1 * jnp.exp(gl) - _mm(du, W, 0, 0)
    dqp = _mm(do, z, 1, 0)
    dkt = _mm(U, dz1, 1, 0)
    dW = -_mm(du, z, 1, 0)
    dT = _mm(dW, p["x"], 1, 1) + _mm(du, p["y"], 1, 1)
    dx = _mm(T, dW, 0, 0)
    dy = _mm(T, du, 0, 0)
    dAkk = -_mm(_mm(T, dT, 0, 0), T, 1, 1) * _lower(c, True)
    dbeta = jnp.sum(dAkk * B, 1, keepdims=True) \
        + jnp.sum(dy * v, 1, keepdims=True) + jnp.sum(dx * kp, 1,
                                                      keepdims=True)
    dB = beta * dAkk
    dv = beta * dy
    dkp = beta * dx
    (da_q, da_k), db = _pairs_bwd(G, k, [(q, dA), (k, dB)])
    dk = dkp * gam + dkt * jnp.exp(gl - G) + da_k + db
    dq = dqp * gam + da_q
    dG = dkp * kp + dqp * qp - dkt * kt + q * da_q + k * da_k - k * db
    last = jnp.sum(dkt * kt, 0, keepdims=True) \
        + jnp.sum(z * dz1, 0, keepdims=True) * jnp.exp(gl)
    dG = dG + jnp.where(_iota(dG.shape, 0) == c - 1, last, _F32(0))
    dg = _mm(_lower(c, False), dG, 0, 0)
    return dq, dk, dv, dg, dbeta, dz


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _column(row):
    """``(1, C)`` to ``(C, 1)`` without a transpose: the row spread over
    the diagonal and summed across."""
    c = row.shape[-1]
    return jnp.sum(jnp.where(_eye(c), jnp.broadcast_to(row, (c, c)),
                             _F32(0)), 1, keepdims=True)


def _row(col):
    """``(C, 1)`` to ``(1, C)``, as :func:`_column`."""
    c = col.shape[0]
    return jnp.sum(jnp.where(_eye(c), jnp.broadcast_to(col, (c, c)),
                             _F32(0)), 0, keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, zs_ref, z_scr):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        z_scr[...] = jnp.zeros_like(z_scr)

    z = z_scr[...]
    o, z1 = _chunk_fwd(q_ref[0].astype(_F32), k_ref[0].astype(_F32),
                       v_ref[0].astype(_F32), g_ref[0],
                       _column(b_ref[0, 0, 0]), z)
    o_ref[0] = o.astype(o_ref.dtype)
    zs_ref[0, 0, 0] = z
    z_scr[...] = z1


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, zs_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, dz_scr):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        dz_scr[...] = jnp.zeros_like(dz_scr)

    dq, dk, dv, dg, dbeta, dz = _chunk_bwd(
        q_ref[0].astype(_F32), k_ref[0].astype(_F32), v_ref[0].astype(_F32),
        g_ref[0], _column(b_ref[0, 0, 0]), zs_ref[0, 0, 0],
        do_ref[0].astype(_F32), dz_scr[...])
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dg_ref[0] = dg
    db_ref[0, 0, 0] = _row(dbeta)
    dz_scr[...] = dz


def _specs(n, dk, dv, reverse):
    """Block specs over the grid (B, H, N) of the (B, S, H * D) inputs, of
    beta as (B, H, N, 1, C) (a chunk's betas are one row: a (C, 1) block
    would pad every value to 128 lanes) and of the states (B, H, N, dv,
    dk); ``reverse`` walks the chunks from the last."""
    def chunk(j):
        return (_np.int32(n - 1) - j) if reverse else j

    def seq(width):
        return pl.BlockSpec((1, CHUNK, width),
                            lambda b, i, j: (b, chunk(j), i))

    def per_chunk(rows, cols):
        return pl.BlockSpec((1, 1, 1, rows, cols),
                            lambda b, i, j: (b, i, chunk(j), _I0, _I0))

    return dict(qk=seq(dk), v=seq(dv), beta=per_chunk(1, CHUNK),
                state=per_chunk(dv, dk))


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(q, k, v, g, beta):
    bsz, h, n = beta.shape[:3]
    dk, dv = q.shape[-1] // h, v.shape[-1] // h
    sp = _specs(n, dk, dv, False)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, h, n),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["qk"], sp["beta"]],
        out_specs=[sp["v"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((bsz, h, n, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_SEMANTICS,
        interpret=_backend.pallas_interpret(),
        name="kda_fwd",
    )(q, k, v, g, beta)


def _backward(q, k, v, g, beta, states, do):
    bsz, h, n = beta.shape[:3]
    dk, dv = q.shape[-1] // h, v.shape[-1] // h
    sp = _specs(n, dk, dv, True)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, h, n),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["qk"], sp["beta"],
                  sp["state"], sp["v"]],
        out_specs=[sp["qk"], sp["qk"], sp["v"], sp["qk"], sp["beta"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_SEMANTICS,
        interpret=_backend.pallas_interpret(),
        name="kda_bwd",
    )(q, k, v, g, beta, states, do)


# each kernel jitted once for every call site (``_backend.lowered_once``)
_forward_once = _backend.lowered_once(_forward)
_backward_once = _backend.lowered_once(_backward)


@jax.custom_vjp
def _kda_flat(q, k, v, g, beta):
    return _forward_once(q, k, v, g, beta)[0]


def _kda_flat_fwd(q, k, v, g, beta):
    o, states = _forward_once(q, k, v, g, beta)
    return o, (q, k, v, g, beta, states)


def _kda_flat_bwd(res, do):
    return _backward_once(*res, do)


_kda_flat.defvjp(_kda_flat_fwd, _kda_flat_bwd)


def _pad_seq(x, s):
    if x.shape[1] == s:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, s - x.shape[1])
    return jnp.pad(x, pad)


def _checked(q, v, g, beta):
    """``(heads, the sequence padded to whole chunks)``."""
    bsz, s, h = beta.shape
    if q.shape[-1] % h or v.shape[-1] % h or g.shape != q.shape:
        raise ValueError("kda: q %s, v %s, g %s over %d heads"
                         % (q.shape, v.shape, g.shape, h))
    return h, -(-s // CHUNK) * CHUNK


def kda(q, k, v, g, beta):
    """The KDA recurrence through the Pallas kernels.  ``q, k`` are ``(B, S,
    H * dk)`` (head by head, as the projections give them), ``v`` ``(B, S,
    H * dv)``, ``g`` ``(B, S, H * dk)`` (log decays, at most 0), ``beta``
    ``(B, S, H)``; returns ``o`` ``(B, S, H * dv)`` in ``v``'s dtype.  A
    sequence that is not a whole number of chunks is padded with tokens
    that write nothing (their ``k``, ``v``, ``beta`` are zero), which no
    earlier token sees."""
    h, sp = _checked(q, v, g, beta)
    bsz, s = beta.shape[:2]
    b = _pad_seq(beta.astype(_F32), sp).transpose(0, 2, 1).reshape(
        bsz, h, sp // CHUNK, 1, CHUNK)
    o = _kda_flat(_pad_seq(q, sp), _pad_seq(k, sp), _pad_seq(v, sp),
                  _pad_seq(g.astype(_F32), sp), b)
    return o[:, :s]


# ---------------------------------------------------------------------------
# the same chunks in plain jax.numpy
# ---------------------------------------------------------------------------

def _by_chunks(x, h):
    """``(B, S, H * D)`` to ``(N, B, H, C, D)``."""
    bsz, s, hd = x.shape
    return x.reshape(bsz, s // CHUNK, CHUNK, h, hd // h).transpose(
        1, 0, 3, 2, 4)


def _from_chunks(x):
    n, bsz, h, c, d = x.shape
    return x.transpose(1, 0, 3, 2, 4).reshape(bsz, n * c, h * d)


_each = functools.partial(jax.vmap, in_axes=0)


def _scan_fwd(xs):
    """The chunks in order: ``(O, the state each chunk starts from)``."""
    fwd1 = _each(_each(_chunk_fwd))
    q = xs[0]
    z0 = jnp.zeros(q.shape[1:3] + (xs[2].shape[-1], q.shape[-1]), _F32)

    def body(z, chunk):
        o, z1 = fwd1(*chunk, z)
        return z1, (o, z)

    _, (o, states) = jax.lax.scan(body, z0, xs)
    return o, states


@jax.custom_vjp
def _chunked(*xs):
    return _scan_fwd(xs)[0]


def _chunked_fwd(*xs):
    o, states = _scan_fwd(xs)
    return o, (xs, states)


def _chunked_bwd(res, do):
    xs, states = res
    bwd1 = _each(_each(_chunk_bwd))

    def body(dz, chunk):
        grads = bwd1(*chunk[0], chunk[1], chunk[2], dz)
        return grads[-1], grads[:-1]

    dz0 = jnp.zeros_like(states[0])
    _, grads = jax.lax.scan(body, dz0, (xs, states, do), reverse=True)
    return grads


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def kda_chunked(q, k, v, g, beta):
    """:func:`kda` as plain ``jax.numpy``: the same chunk mathematics over a
    ``lax.scan`` of chunks, with the same backward pass as a reverse scan."""
    h, sp = _checked(q, v, g, beta)
    s = beta.shape[1]
    xs = [_by_chunks(_pad_seq(x.astype(_F32), sp), h) for x in (q, k, v, g)]
    b = _by_chunks(_pad_seq(beta.astype(_F32), sp), h)
    return _from_chunks(_chunked(*xs, b))[:, :s].astype(v.dtype)


# op-registry surface: mx.nd.contrib.kda / mx.sym.contrib.kda
from ..ops.registry import register as _register_op  # noqa: E402


@_register_op("_contrib_kda", num_inputs=5)
def _kda_op(q, k, v, g, beta):
    """Kimi Delta Attention's recurrence, shapes as :func:`kda` takes them:
    its Pallas kernels (interpreted on the CPU)."""
    return kda(q, k, v, g, beta)


# the layer's short convolutions, norms and gates: ops of their own, whose
# scopes (op._contrib_kda_*) hold what surrounds the recurrence


@_register_op("_contrib_kda_conv", num_inputs=2)
def _kda_conv(data, weight):
    """``silu`` of a causal depthwise convolution over the sequence, from
    zeros: ``data`` ``(B, S, C)``, ``weight`` ``(C, K)`` (a channel's taps,
    the last one on the current token, as a depthwise ``Conv1d``'s with
    ``K - 1`` zeros of left padding), no bias; float32 inside, the result
    in ``data``'s dtype."""
    taps = weight.shape[-1]
    x = jnp.pad(data.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(_F32)
    s = data.shape[1]
    y = sum(x[:, m:m + s] * w[:, m] for m in range(taps))
    return jax.nn.silu(y).astype(data.dtype)


def _by_heads(x, heads):
    return x.astype(_F32).reshape(x.shape[:-1] + (heads, -1))


@_register_op("_contrib_kda_qk_norm", num_inputs=1)
def _kda_qk_norm(data, heads=1, scale=1.0, eps=1e-6):
    """Each head's columns of ``data`` ``(..., heads * D)`` over their L2
    norm, ``x * rsqrt(sum(x**2) + eps) * scale``; float32 inside."""
    x = _by_heads(data, int(heads))
    y = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps) * scale
    return y.reshape(data.shape).astype(data.dtype)


@_register_op("_contrib_kda_gate", num_inputs=4, num_outputs=2)
def _kda_gate(decay, write, a_log, dt_bias):
    """The decay and the write strength, float32: ``g = -exp(A_log[h]) *
    softplus(decay + dt_bias)`` over ``decay`` ``(B, S, H * dk)`` (``A_log``
    one a head, ``dt_bias`` one a channel), and ``beta = sigmoid(write)``
    over ``write`` ``(B, S, H)``."""
    h = write.shape[-1]
    rate = jnp.exp(a_log.astype(_F32).reshape(h, 1))
    f = _by_heads(decay, h) + dt_bias.astype(_F32).reshape(h, -1)
    g = -rate * jax.nn.softplus(f)
    return g.reshape(decay.shape), jax.nn.sigmoid(write.astype(_F32))


@_register_op("_contrib_kda_out_norm", num_inputs=3)
def _kda_out_norm(data, gate, gamma, eps=1e-5):
    """The gated output norm: each head of ``data`` ``(..., H * D)`` RMS
    normed with the learned scale ``gamma`` ``(D,)``, times
    ``sigmoid(gate)``; float32 inside, the result in ``data``'s dtype."""
    h = data.shape[-1] // gamma.shape[0]
    x = _by_heads(data, h)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(_F32)
    return (y.reshape(data.shape) * jax.nn.sigmoid(gate.astype(_F32))).astype(
        data.dtype)
