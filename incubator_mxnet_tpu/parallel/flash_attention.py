"""Pallas TPU flash attention (fwd + bwd kernels, custom VJP).

Replaces the reference's fused attention matmuls
(``src/operator/contrib/transformer.cc`` interleaved_matmul_selfatt_*)
with a blockwise-softmax kernel that never materializes the (S, S)
score matrix: Q tiles stay resident in VMEM while K/V tiles stream
through, with running max/sum rescaling (the numerics of
``parallel.ring_attention._block_attn_update``, pushed down into one
kernel so the MXU sees back-to-back (block_q × D) @ (D × block_k)
matmuls and HBM traffic is O(S·D) instead of O(S²)).

On the CPU backend the kernels run in interpreter mode so the same code
path is testable there (tests/conftest.py virtual mesh).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as _np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import _backend

__all__ = ["flash_attention"]

_NEG_INF = -1e30

# index-map constant pinned to i32: the package enables jax_enable_x64, and
# a python 0 in a BlockSpec index map lowers as i64, which Mosaic rejects
# (failed to legalize func.return (i32, i32, i64))
_I0 = _np.int32(0)


def _cdiv(a, b):
    return (a + b - 1) // b


def _fit_block(size, block):
    """Largest divisor of ``size`` that is ≤ ``block`` — blocks must tile
    the sequence exactly (no out-of-bounds block reads)."""
    block = min(block, size)
    while size % block:
        block -= 1
    return block


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _causal_mask(s, qi, ki, block_q, block_k, offset):
    """Right-aligned causal mask: query row i attends keys j with
    j <= i + offset, offset = kv_len - q_len (KV-cache decode
    convention, matching attention_reference's tril(klen - qlen))."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    # explicit f32 fill: a python float would enter the kernel as f64 and
    # Mosaic cannot legalize the f64->f32 truncf
    return jnp.where(rows + offset >= cols, s, jnp.float32(_NEG_INF))


def _block_relevant(qi, ki, block_q, block_k, offset):
    """False iff the (qi, ki) tile lies entirely above the causal
    diagonal (its mask would zero everything) — skip ~half the grid."""
    last_row = qi * block_q + block_q - 1
    first_col = ki * block_k
    return first_col <= last_row + offset


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, nk, offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    relevant = _block_relevant(qi, ki, block_q, block_k, offset) \
        if causal else True

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # (bq, D)
        k = k_ref[0].astype(jnp.float32)               # (bk, D)
        v = v_ref[0].astype(jnp.float32)               # (bk, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)

        m_prev = m_scr[:]                              # (bq, 1)
        l_prev = l_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # rows with zero unmasked keys (causal, kv_len < q_len): every score
        # is _NEG_INF, so exp(s - m_new) would be 1 everywhere and emit
        # mean(V); force those rows to contribute nothing (output 0)
        p = jnp.where(m_new > jnp.float32(_NEG_INF / 2), p, jnp.float32(0.0))
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # lse carried as (bq, 1): a trailing unit lane keeps the block shape
        # Mosaic-legal (last dim equals the array dim; (1, bq) blocks are not)
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l, 1e-30))


def _fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    bh, s, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(s, block_q)
    block_k = _fit_block(sk, block_k)
    nq = s // block_q
    nk = sk // block_k
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, nk=nk,
                               offset=sk - s)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, _I0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_k, nk, offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    relevant = _block_relevant(qi, ki, block_q, block_k, offset) \
        if causal else True

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                # (bq, 1)
        delta = delta_ref[0]                            # (bq, 1)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)
        p = jnp.exp(s - lse)
        # rows with zero unmasked keys have lse ~= _NEG_INF, which would
        # blow exp() up instead of zeroing it; mask on the raw scores
        p = jnp.where(s > jnp.float32(_NEG_INF / 2), p, jnp.float32(0.0))
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] = dq_scr[:] + jnp.dot(ds, k,
                                        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, block_q, block_k, nq, offset):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    relevant = _block_relevant(qi, kj, block_q, block_k, offset) \
        if causal else True

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                # (bq, 1)
        delta = delta_ref[0]                            # (bq, 1)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        p = jnp.exp(s - lse)                            # (bq, bk)
        p = jnp.where(s > jnp.float32(_NEG_INF / 2), p, jnp.float32(0.0))
        dv_scr[:] = dv_scr[:] + jnp.dot(p.T, do,
                                        preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[:] = dk_scr[:] + jnp.dot(ds.T, q,
                                        preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    do = g
    bh, s, d = q.shape
    sk = k.shape[1]
    bq = _fit_block(s, block_q)
    bk = _fit_block(sk, block_k)
    nq = s // bq
    nk = sk // bk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)             # (bh, s, 1)
    lse3 = lse[:, :, None]                              # (bh, s, 1)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nk=nk, offset=sk - s),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _I0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, _I0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _I0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse3, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq, offset=sk - s),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _I0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, _I0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, _I0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse3, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _make_attn(scale, causal, block_q, block_k, interpret):
    """One custom_vjp function per static-param tuple — cached so eager
    callers hit JAX's trace cache instead of re-tracing the kernels every
    invocation."""
    @jax.custom_vjp
    def _attn(qf, kf, vf):
        out, _ = _fwd(qf, kf, vf, scale, causal, block_q, block_k,
                      interpret)
        return out

    def _attn_fwd(qf, kf, vf):
        out, lse = _fwd(qf, kf, vf, scale, causal, block_q, block_k,
                        interpret)
        return out, (qf, kf, vf, out, lse)

    def _attn_bwd(res, g):
        return _bwd(scale, causal, block_q, block_k, interpret, res, g)

    _attn.defvjp(_attn_fwd, _attn_bwd)
    return _attn


def flash_attention(q, k, v, causal=False, scale: Optional[float] = None,
                    block_q=None, block_k=None, interpret=None,
                    use_pallas=None):
    """Flash attention over (B, H, S, D) tensors.

    Returns softmax(QKᵀ·scale [+ causal mask]) V without materializing
    the score matrix.  Differentiable.

    Backend policy (round-4 measurement, docs/PERF.md): on TPU the stock
    XLA fused attention (`jax.nn.dot_product_attention`) beat this
    module's Pallas kernels (5.8 vs 6.3 ms at 2048/8/128), so the XLA
    path is the DEFAULT; the Pallas kernels remain behind
    ``use_pallas=True`` (and keep serving ring attention's per-shard
    block compute, where the blockwise-update formulation is required).
    Interpret-mode (non-TPU backends) keeps Pallas so the kernels stay
    CPU-tested.
    """
    b, h, s, d = q.shape
    sk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _backend.pallas_interpret()
    if use_pallas is None:
        use_pallas = interpret  # real-chip default: XLA fused attention
    if not use_pallas:
        # jax.nn.dot_product_attention is (B, S, H, D)
        out = jax.nn.dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=float(scale),
            is_causal=bool(causal))
        return out.transpose(0, 2, 1, 3)

    if block_q is None or block_k is None:
        # defaults: 128x128; at long sequence bigger tiles amortize grid
        # overhead and keep the MXU on larger products.  Explicit
        # block_q/block_k always win (bench.py sweeps them).  _fit_block
        # still clamps to divisors of the actual lengths.
        bq_d, bk_d = ((256, 512) if sk >= 4096 else (128, 128))
        block_q = bq_d if block_q is None else block_q
        block_k = bk_d if block_k is None else block_k
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    _attn = _make_attn(float(scale), bool(causal), int(block_q),
                       int(block_k), bool(interpret))
    return _attn(qf, kf, vf).reshape(b, h, s, d)


# op-registry surface: mx.nd.contrib.flash_attention / mx.sym.contrib...
from ..ops.registry import register as _register_op  # noqa: E402


@_register_op("_contrib_flash_attention", num_inputs=3)
def _flash_attention_op(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None):
    """Fused attention op (the TPU answer to
    _contrib_interleaved_matmul_selfatt_* in transformer.cc)."""
    return flash_attention(
        q, k, v, causal=bool(causal), scale=scale,
        block_q=None if block_q is None else int(block_q),
        block_k=None if block_k is None else int(block_k))
