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
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import _backend
from ..tracing import REMAT_KEEP

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
# which tiles a mask admits
# ---------------------------------------------------------------------------
#
# Right-aligned causal mask with an optional sliding window: query row i
# attends key j iff  i + offset - window < j <= i + offset, with
# offset = kv_len - q_len (KV-cache decode convention, matching
# attention_reference's tril(klen - qlen)).  A grid step whose tile the mask
# rules out entirely is skipped twice over: its compute by pl.when, and its
# DMA because the index maps below walk only the tiles between first and
# last (a step past the last repeats the last tile's index, and Pallas does
# not fetch a block again whose index did not change).  With a window the
# grid's inner axis is as short as the widest run of admitted tiles.  Of the
# tiles a step does compute, only those that the mask's edge crosses pay for
# the mask (_on_admitted): at tiles of 1024 x 1024, 16 of 136 over 16,384
# causal tokens, 28 of 70 under a window of 4,096.


def _mask(s, qi, ki, block_q, block_k, offset, window):
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = rows + offset >= cols
    if window is not None:
        keep &= cols > rows + offset - window
    # explicit f32 fill: a python float would enter the kernel as f64 and
    # Mosaic cannot legalize the f64->f32 truncf
    return jnp.where(keep, s, jnp.float32(_NEG_INF))


def _inside(qi, ki, block_q, block_k, offset, window):
    """Whether tile (qi, ki) lies wholly inside the causal mask and its
    window: its first query row admits its last key, and its last query row
    its first key.  Scalar int32 arithmetic on the grid indices, as
    ``_k_range``'s."""
    inside = ki * block_k + (block_k - 1) <= qi * block_q + offset
    if window is not None:
        inside &= ki * block_k > qi * block_q + (block_q - 1 + offset - window)
    return inside


def _on_admitted(live, qi, ki, tile, body):
    """``body(masked)`` under ``pl.when(live)``, as two bodies: masked on a
    tile the mask's edge crosses, unmasked on one that lies wholly inside it
    (every tile of a call without a mask).  There the mask's select would
    keep every score and every row has an admitted key, so the unmasked body
    gives the masked one's results bit for bit, without its iotas, compares
    and two selects over the tile."""
    if not tile["causal"]:
        pl.when(live)(lambda: body(False))
        return
    inside = _inside(qi, ki, tile["block_q"], tile["block_k"],
                     tile["offset"], tile["window"])
    pl.when(live & inside)(lambda: body(False))
    pl.when(live & jnp.logical_not(inside))(lambda: body(True))


def _steps(n_other, block_self, block_other, window):
    """Length of the inner grid axis: every tile without a window, else the
    most tiles of ``block_other`` that ``window + block_self - 1``
    consecutive positions can touch."""
    if window is None:
        return n_other
    return min(n_other, _cdiv(window + block_self - 1, block_other) + 1)


def _div(a, n):
    """a // n and a % n for a non-negative int32 ``a`` (grid indices): as
    ``lax.div`` / ``lax.rem``, see ``_floordiv``."""
    return jax.lax.div(a, _np.int32(n))


def _rem(a, n):
    return jax.lax.rem(a, _np.int32(n))


def _floordiv(num, den, low):
    """floor(num / den) for an int32 ``num`` that is never below the static
    ``low``: shifted to be non-negative and divided by ``lax.div`` (jnp's
    ``//`` on traced ints does not lower through Mosaic under x64)."""
    shift = _cdiv(max(0, -low), den)
    return jax.lax.div(num + _np.int32(shift * den), _np.int32(den)) \
        - _np.int32(shift)


def _k_range(qi, block_q, block_k, offset, causal, window, nk):
    """(first, last) key tile a query tile needs; last < first: none."""
    first, last = 0, nk - 1
    if causal:
        last = jnp.minimum(_floordiv(
            qi * block_q + (block_q - 1 + offset), block_k, offset), nk - 1)
    if window is not None:
        low = offset - window + 1
        first = jnp.maximum(_floordiv(
            qi * block_q + low, block_k, low), 0)
    return first, last


def _q_range(kj, block_q, block_k, offset, causal, window, nq):
    """(first, last) query tile a key tile is needed by."""
    first, last = 0, nq - 1
    if causal:
        first = jnp.maximum(_floordiv(
            kj * block_k - offset, block_q, -offset), 0)
    if window is not None:
        low = block_k - 1 + window - 1 - offset
        last = jnp.minimum(_floordiv(
            kj * block_k + low, block_q, low), nq - 1)
    return first, last


def _tile(first, last, step):
    """Index-map form of ``first + step``: clamped into [0, last] so that a
    skipped step names a tile that exists (and, past the last, the one
    already resident)."""
    return jnp.maximum(jnp.minimum(first + step, last), 0).astype(jnp.int32)


def _dot(a, b, contract):
    """a·b over the given axes, f32 accumulate, operands in their own dtype
    (bf16 operands keep the MXU on its fast path; for them the precision is
    pinned, since Mosaic refuses a bf16 product under a process-wide
    ``jax_default_matmul_precision`` of "highest")."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, nk, steps, **tile):
    qi = pl.program_id(1)
    step = pl.program_id(2)
    first, last = _k_range(qi, tile["block_q"], tile["block_k"],
                           tile["offset"], tile["causal"], tile["window"], nk)
    ki = first + step

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(masked):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]         # (bq, D), (bk, D) x2
        s = _dot(q, k, (1, 1)) * tile["scale"]
        if masked:
            s = _mask(s, qi, ki, tile["block_q"], tile["block_k"],
                      tile["offset"], tile["window"])

        m_prev = m_scr[:]                              # (bq, 1)
        l_prev = l_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            # rows with zero unmasked keys so far: every score is _NEG_INF,
            # so exp(s - m_new) would be 1 everywhere and emit mean(V); force
            # those rows to contribute nothing (output 0)
            p = jnp.where(m_new > jnp.float32(_NEG_INF / 2), p,
                          jnp.float32(0.0))
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot(p.astype(v.dtype), v, (1, 0))
        m_scr[:] = m_new
        l_scr[:] = l_new

    _on_admitted(ki <= last, qi, ki, tile, _compute)

    @pl.when(step == steps - 1)
    def _finish():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # lse carried as (bq, 1): a trailing unit lane keeps the block shape
        # Mosaic-legal (last dim equals the array dim; (1, bq) blocks are not)
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l, 1e-30))


def _geometry(q, k, block_q, block_k, heads, kv_heads, causal, window):
    s, sk = q.shape[1], k.shape[1]
    bq, bk = _fit_block(s, block_q), _fit_block(sk, block_k)
    nq, nk = s // bq, sk // bk
    group = heads // kv_heads
    g = dict(bq=bq, bk=bk, nq=nq, nk=nk, offset=sk - s, group=group,
             k_steps=_steps(nk, bq, bk, window),
             q_steps=_steps(nq, bk, bq, window))

    def kv_of(b):
        """Row of the flattened (B*Hkv) keys that query row ``b`` of the
        flattened (B*H) queries reads."""
        return _div(b, heads) * kv_heads + _div(_rem(b, heads), group)

    def k_tile(i, j):
        first, last = _k_range(i, bq, bk, sk - s, causal, window, nk)
        return _tile(first, last, j)

    g["q_map"] = lambda b, i, j: (b, i, _I0)
    g["k_map"] = lambda b, i, j: (kv_of(b), k_tile(i, j), _I0)
    return g


def _fwd(q, k, v, scale, causal, window, heads, kv_heads, block_q, block_k,
         interpret):
    bh, s, d = q.shape
    dv = v.shape[-1]
    g = _geometry(q, k, block_q, block_k, heads, kv_heads, causal, window)
    bq, bk = g["bq"], g["bk"]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window, block_q=bq,
        block_k=bk, nk=g["nk"], steps=g["k_steps"], offset=g["offset"])
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, g["nq"], g["k_steps"]),
        in_specs=[
            pl.BlockSpec((1, bq, d), g["q_map"]),
            pl.BlockSpec((1, bk, d), g["k_map"]),
            pl.BlockSpec((1, bk, dv), g["k_map"]),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), g["q_map"]),
            pl.BlockSpec((1, bq, 1), g["q_map"]),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------
#
# One kernel makes dq, dk and dv: a tile's scores, probabilities and score
# gradients are made once and feed all three sums (five products a tile).
# The key tile is the outer loop and the query tiles that see it the inner
# one, so dk and dv of a tile are finished when its walk ends, while dq
# collects a term from every key tile: dq of the whole sequence of the
# current query head waits in VMEM, in float32, and is written once.  Where
# that does not fit (_fused_bwd_vmem against _vmem_budget: at tiles of
# 1024 x 1024 from 131,072 tokens at head size 128, from 65,536 where eight
# query heads share a key/value head, from 32,768 at head size 256), dq and
# dk/dv come from a kernel each, which need only tiles.

#: VMEM of the one core the backward kernels are written for, the TPU v5e's
#: (a core with less refuses what they ask for: they are v5e-only as they
#: stand), and what a kernel gets of it without asking
_VMEM_BYTES = 128 * 2 ** 20
_VMEM_UNASKED = 16 * 2 ** 20


def _vmem_budget():
    """Bytes of VMEM a kernel here may ask for: three quarters of the
    core's, the rest is left to the compiler's own buffers."""
    return _VMEM_BYTES * 3 // 4


def _fused_bwd_vmem(s, sk, d, bq, bk, group, itemsize, dv):
    """Bytes of VMEM ``flash_bwd`` asks for: the float32 sums (dq of the
    sequence; dk and dv of a key tile, or of the sequence where a group of
    query heads shares them), every block twice for the pipeline (lse and
    delta are (bq, 1) float32, which the tiled layout pads to 128 lanes; dq
    leaves as one block of the sequence; v, do and dv are ``dv`` wide, the
    rest ``d``), and six float32 (bq, bk) tiles:
    scores, probabilities, dp, ds, and two for the mask and the copies in
    the compute dtype (the v5e compiler reuses them down to under three at
    8,192 x 128 and 8,192 x 256: it takes 28 MiB at either; the rest is
    the margin).  A head narrower than 128 fills whole lanes all the same,
    and no kernel asks for less than it would get unasked."""
    d = _cdiv(d, 128) * 128
    dv = _cdiv(dv, 128) * 128
    rows = bk if group == 1 else sk
    sums = 4 * (d * (s + rows) + dv * rows)
    blocks = 2 * (itemsize * (d * (bq + 2 * bk + s) + dv * (bq + 2 * bk))
                  + 2 * 4 * 128 * bq)
    return max(_VMEM_UNASKED, sums + blocks + 6 * 4 * bq * bk)


def _p_and_ds(q, k, v, do, lse, delta, qi, ki, masked, *, scale, causal,
              window, block_q, block_k, offset):
    """The tile's probabilities and score gradients, both (bq, bk) f32;
    ``masked`` as ``_on_admitted`` gives it."""
    s = _dot(q, k, (1, 1)) * scale
    if masked:
        s = _mask(s, qi, ki, block_q, block_k, offset, window)
    p = jnp.exp(s - lse)
    if masked:
        # rows with zero unmasked keys have lse ~= _NEG_INF, which would
        # blow exp() up instead of zeroing it; mask on the raw scores
        p = jnp.where(s > jnp.float32(_NEG_INF / 2), p, jnp.float32(0.0))
    dp = _dot(do, v, (1, 1))
    return p, p * (dp - delta) * scale


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *, nq, nk, steps,
                group, **tile):
    """Grid (key/value head, query head of its group, key tile, query tile
    that sees it).  ``dq_scr`` is the sequence's dq of the current query
    head; ``dk_scr`` / ``dv_scr`` are the current key tile's sums when each
    query head has its own keys, else the sequence's, because a key tile's
    sums then outlive the walk over the group's query heads."""
    g, kj, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    bq, bk = tile["block_q"], tile["block_k"]
    first, last = _q_range(kj, bq, bk, tile["offset"], tile["causal"],
                           tile["window"], nq)
    qi = first + step
    k_rows = pl.ds(0, bk) if group == 1 else \
        pl.ds(pl.multiple_of(kj * bk, bk), bk)

    @pl.when((kj == 0) & (step == 0))
    def _new_query_head():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when((g == 0) & (step == 0))
    def _new_key_tile():
        dk_scr[k_rows, :] = jnp.zeros((bk, dk_scr.shape[1]), jnp.float32)
        dv_scr[k_rows, :] = jnp.zeros((bk, dv_scr.shape[1]), jnp.float32)

    def _compute(masked):
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        p, ds = _p_and_ds(q, k, v_ref[0], do, lse_ref[0], delta_ref[0], qi,
                          kj, masked, **tile)
        ds = ds.astype(q.dtype)
        dv_scr[k_rows, :] = dv_scr[k_rows, :] + _dot(
            p.astype(do.dtype), do, (0, 0))
        dk_scr[k_rows, :] = dk_scr[k_rows, :] + _dot(ds, q, (0, 0))
        q_rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)
        dq_scr[q_rows, :] = dq_scr[q_rows, :] + _dot(ds, k, (1, 0))

    _on_admitted(qi <= last, qi, kj, tile, _compute)

    @pl.when((g == group - 1) & (step == steps - 1))
    def _key_tile_done():
        dk_ref[0] = dk_scr[k_rows, :].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[k_rows, :].astype(dv_ref.dtype)

    @pl.when((kj == nk - 1) & (step == steps - 1))
    def _query_head_done():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, nk, steps, **tile):
    qi = pl.program_id(1)
    step = pl.program_id(2)
    first, last = _k_range(qi, tile["block_q"], tile["block_k"],
                           tile["offset"], tile["causal"], tile["window"], nk)
    ki = first + step

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(masked):
        k = k_ref[0]
        _, ds = _p_and_ds(q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0],
                          delta_ref[0], qi, ki, masked, **tile)
        dq_scr[:] = dq_scr[:] + _dot(ds.astype(k.dtype), k, (1, 0))

    _on_admitted(ki <= last, qi, ki, tile, _compute)

    @pl.when(step == steps - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, nq, steps, group,
                    **tile):
    """One key tile of one key/value head: the inner axis walks the query
    heads that share it (``group``) and, for each, the query tiles that see
    it; dk and dv are their sum."""
    kj = pl.program_id(1)
    t = pl.program_id(2)
    first, last = _q_range(kj, tile["block_q"], tile["block_k"],
                           tile["offset"], tile["causal"], tile["window"], nq)
    qi = first + _rem(t, steps)

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked):
        q, do = q_ref[0], do_ref[0]
        p, ds = _p_and_ds(q, k_ref[0], v_ref[0], do, lse_ref[0],
                          delta_ref[0], qi, kj, masked, **tile)
        dv_scr[:] = dv_scr[:] + _dot(p.astype(do.dtype), do, (0, 0))
        dk_scr[:] = dk_scr[:] + _dot(ds.astype(q.dtype), q, (0, 0))

    _on_admitted(qi <= last, qi, kj, tile, _compute)

    @pl.when(t == group * steps - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, window, heads, kv_heads, block_q, block_k, interpret,
         res, do):
    q, k, v, out, lse = res
    bh, s, d = q.shape
    bkv, sk, _ = k.shape
    dv = v.shape[-1]
    g = _geometry(q, k, block_q, block_k, heads, kv_heads, causal, window)
    bq, bk, nq, nk = g["bq"], g["bk"], g["nq"], g["nk"]
    group, q_steps = g["group"], g["q_steps"]
    tile = dict(scale=scale, causal=causal, window=window, block_q=bq,
                block_k=bk, offset=g["offset"])
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)             # (bh, s, 1)
    operands = (q, k, v, do, lse[:, :, None], delta)    # lse as (bh, s, 1)
    vmem = _fused_bwd_vmem(s, sk, d, bq, bk, group, q.dtype.itemsize, dv)
    if vmem > _vmem_budget():
        return _bwd_by_tiles(operands, g, tile, interpret)

    def q_map(b, h, j, t):
        first, last = _q_range(j, bq, bk, g["offset"], causal, window, nq)
        return b * group + h, _tile(first, last, t), _I0

    def k_map(b, h, j, t):
        return b, j, _I0

    def dk_map(b, h, j, t):
        # a key tile's sums are written during the group's last query head;
        # before it the index stands still, so nothing unwritten goes out
        return b, jnp.where(h == group - 1, j, _I0), _I0

    k_rows = bk if group == 1 else sk
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, nq=nq, nk=nk, steps=q_steps,
                          group=group, **tile),
        grid=(bkv, group, nk, q_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), k_map),
            pl.BlockSpec((1, bk, dv), k_map),
            pl.BlockSpec((1, bq, dv), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, s, d), lambda b, h, j, t: (b * group + h, _I0,
                                                        _I0)),
            pl.BlockSpec((1, bk, d), dk_map),
            pl.BlockSpec((1, bk, dv), dk_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, sk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((s, d), jnp.float32),
            pltpu.VMEM((k_rows, d), jnp.float32),
            pltpu.VMEM((k_rows, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
        name="flash_bwd",
    )(*operands))


def _bwd_by_tiles(operands, g, tile, interpret):
    """The long-sequence form: dq from a kernel that walks a query tile's
    key tiles, dk and dv from one that walks a key tile's query tiles.
    Each makes the tile's probabilities for itself (seven products a tile),
    and neither holds more than tiles."""
    q, k, v = operands[:3]
    bh, s, d = q.shape
    bkv, sk, _ = k.shape
    dv = v.shape[-1]
    bq, bk, nq, nk = g["bq"], g["bk"], g["nq"], g["nk"]
    causal, window = tile["causal"], tile["window"]
    # what flash_bwd would ask for a sequence of one tile: at tiles of
    # 1024 x 1024 more than a kernel gets unasked (31 MiB at head size 128,
    # 36 at 256)
    params = pltpu.CompilerParams(vmem_limit_bytes=_fused_bwd_vmem(
        bq, bk, d, bq, bk, 1, q.dtype.itemsize, dv))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, steps=g["k_steps"], **tile),
        grid=(bh, nq, g["k_steps"]),
        in_specs=[
            pl.BlockSpec((1, bq, d), g["q_map"]),
            pl.BlockSpec((1, bk, d), g["k_map"]),
            pl.BlockSpec((1, bk, dv), g["k_map"]),
            pl.BlockSpec((1, bq, dv), g["q_map"]),
            pl.BlockSpec((1, bq, 1), g["q_map"]),
            pl.BlockSpec((1, bq, 1), g["q_map"]),
        ],
        out_specs=pl.BlockSpec((1, bq, d), g["q_map"]),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(*operands)

    group, q_steps = g["group"], g["q_steps"]

    def q_map(b, j, t):
        first, last = _q_range(j, bq, bk, g["offset"], causal, window, nq)
        return b * group + _div(t, q_steps), \
            _tile(first, last, _rem(t, q_steps)), _I0

    def k_map(b, j, t):
        return b, j, _I0

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, steps=q_steps, group=group,
                          **tile),
        grid=(bkv, nk, group * q_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), k_map),
            pl.BlockSpec((1, bk, dv), k_map),
            pl.BlockSpec((1, bq, dv), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), k_map),
            pl.BlockSpec((1, bk, dv), k_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, sk, dv), k.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _make_attn(scale, causal, block_q, block_k, interpret, window=None,
               heads=1, kv_heads=1):
    """One custom_vjp function per static-param tuple — cached so eager
    callers hit JAX's trace cache instead of re-tracing the kernels every
    invocation.  ``heads`` and ``kv_heads`` matter only where they differ
    (equal, each row of the flattened queries reads its own row of keys)."""
    static = (scale, causal, window, heads, kv_heads, block_q, block_k,
              interpret)

    @jax.custom_vjp
    def _attn(qf, kf, vf):
        return _fwd(qf, kf, vf, *static)[0]

    def _attn_fwd(qf, kf, vf):
        # a block's remat region keeps what is named REMAT_KEEP: with the
        # kernel's two results kept (lse in its compact (B*H, S) form), the
        # recomputed forward has no reader for flash_fwd and drops it
        out, lse = _fwd(qf, kf, vf, *static)
        out = checkpoint_name(out, REMAT_KEEP)
        lse = checkpoint_name(lse, REMAT_KEEP)
        return out, (qf, kf, vf, out, lse)

    def _attn_bwd(res, g):
        return _bwd(*static, res, g)

    _attn.defvjp(_attn_fwd, _attn_bwd)
    return _attn


def flash_attention(q, k, v, causal=False, scale: Optional[float] = None,
                    block_q=None, block_k=None, interpret=None,
                    use_pallas=None, window: Optional[int] = None):
    """Flash attention: q and k are (B, H, S, D) and (B, Hkv, Sk, D), v is
    (B, Hkv, Sk, Dv), with Hkv dividing H (each key/value head serves H / Hkv
    consecutive query heads); the result is (B, H, S, Dv).

    Returns softmax(QKᵀ·scale [+ mask]) V without materializing the score
    matrix.  Differentiable.  ``causal`` masks keys j > i + (Sk - S);
    ``window`` (needs ``causal``) also masks keys j <= i + (Sk - S) - window,
    so each query sees at most ``window`` keys, itself included.  Tiles the
    mask rules out are neither fetched nor computed.

    Backend policy (round-4 measurement, docs/PERF.md): on TPU the stock
    XLA fused attention (`jax.nn.dot_product_attention`) beat this
    module's Pallas kernels (5.8 vs 6.3 ms at 2048/8/128), so the XLA
    path is the DEFAULT for plain attention; the Pallas kernels remain
    behind ``use_pallas=True`` (and keep serving ring attention's
    per-shard block compute, where the blockwise-update formulation is
    required).  With a window or grouped heads the kernels ARE the path:
    the XLA form materialises H x S x Sk scores, which a long sequence
    cannot hold, and computes what the window masks out.
    Interpret-mode (non-TPU backends) keeps Pallas so the kernels stay
    CPU-tested.
    """
    b, h, s, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if h % hkv or k.shape[:-1] != v.shape[:-1] or k.shape[-1] != d:
        raise ValueError("flash_attention: %d query heads over key/value of "
                         "shapes %s, %s" % (h, k.shape, v.shape))
    if window is not None and (not causal or window < 1):
        raise ValueError("flash_attention: window=%r needs causal=True and "
                         "at least 1" % (window,))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _backend.pallas_interpret()
    if use_pallas is None:
        # real-chip default: XLA fused attention, where it can express it
        use_pallas = interpret or window is not None or hkv != h
    if not use_pallas:
        # jax.nn.dot_product_attention is (B, S, H, D)
        out = jax.nn.dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=float(scale),
            is_causal=bool(causal),
            local_window_size=None if window is None else (window - 1, 0))
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
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, v.shape[-1])
    extra = {}
    if window is not None:
        extra["window"] = int(window)
    if hkv != h:
        extra.update(heads=h, kv_heads=hkv)
    _attn = _make_attn(float(scale), bool(causal), int(block_q),
                       int(block_k), bool(interpret), **extra)
    return _attn(qf, kf, vf).reshape(b, h, s, v.shape[-1])


# op-registry surface: mx.nd.contrib.flash_attention / mx.sym.contrib...
from ..ops.registry import register as _register_op  # noqa: E402


@_register_op("_contrib_flash_attention", num_inputs=3)
def _flash_attention_op(q, k, v, causal=False, scale=None, block_q=None,
                        block_k=None, window=None, use_pallas=None):
    """Fused attention op (the TPU answer to
    _contrib_interleaved_matmul_selfatt_* in transformer.cc); ``window``,
    fewer key/value heads than query heads and ``use_pallas`` as
    ``flash_attention`` takes them."""
    return flash_attention(
        q, k, v, causal=bool(causal), scale=scale,
        block_q=None if block_q is None else int(block_q),
        block_k=None if block_k is None else int(block_k),
        window=None if window is None else int(window),
        use_pallas=None if use_pallas is None else bool(use_pallas))
