"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO long-context parallelism (SURVEY.md §5.7) — only
bucketing and fused attention matmuls.  Here sequence scaling is a
first-class capability of the sharding layer:

- :func:`ring_attention` — blockwise-softmax (flash-style numerics)
  attention where K/V blocks rotate around the ``sp`` mesh axis via
  ``lax.ppermute`` (ICI neighbor exchange), overlapping compute with
  communication.  Memory per device is O(seq_local²-block), enabling
  sequences sharded across the pod.
- :func:`ulysses_attention` — all-to-all resharding (seq-sharded ->
  head-sharded), dense local attention, then the inverse all-to-all.
- :func:`sharded_self_attention` — host-level wrapper: shard_map over a mesh
  axis for eager arrays.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .collectives import ppermute  # eager GL001-validated collective
from .mesh import shard_map  # version-compat import, one home

__all__ = ["attention_reference", "ring_attention", "ulysses_attention",
           "sharded_self_attention"]


def attention_reference(q, k, v, causal=False, scale=None, window=None):
    """Dense softmax attention (correctness oracle). q: (B,H,S,D); k, v:
    (B,Hkv,Sk,D), each key/value head serving H // Hkv consecutive query
    heads.  ``window`` (with ``causal``) keeps only the last ``window`` keys
    a query may see, itself included."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool), klen - qlen)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((qlen, klen), bool),
                              klen - qlen - window)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _block_attn_update(q, k, v, m, l, o, scale, mask=None):
    """One flash-attention accumulation step with a K/V block."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (exp(-inf - -inf))
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - safe_m))
    alpha = jnp.where(jnp.isneginf(m), 0.0, alpha)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Ring attention over a shard_map axis.

    Inside ``shard_map``: q,k,v are the LOCAL sequence shards
    (B,H,S_local,D).  K/V rotate around the ring; each device accumulates
    its queries' attention over every block with streaming-softmax state.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    s_local = q.shape[-2]

    b, h, sq, _ = q.shape
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros(q.shape, jnp.float32)
    # constants must carry the 'varying over sp' type to sit in the scan carry
    try:
        m0, l0, o0 = (lax.pcast(x, (axis_name,), to="varying")
                      for x in (m0, l0, o0))
    except AttributeError:  # older jax without the VMA system
        pass
    qf = q.astype(jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        m, l, o, k_blk, v_blk = carry
        # source shard of the current block after `step` rotations
        src = (my_idx - step) % n
        if causal:
            q_pos = my_idx * s_local + jnp.arange(s_local)[:, None]
            k_pos = src * s_local + jnp.arange(s_local)[None, :]
            mask = (k_pos <= q_pos)[None, None]
        else:
            mask = None
        m, l, o = _block_attn_update(qf, k_blk.astype(jnp.float32),
                                     v_blk.astype(jnp.float32),
                                     m, l, o, scale, mask)
        k_blk = ppermute(k_blk, axis_name, perm)
        v_blk = ppermute(v_blk, axis_name, perm)
        return m, l, o, k_blk, v_blk

    m, l, o, _, _ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    out = o / jnp.maximum(l, 1e-38)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Ulysses-style SP: all-to-all heads<->sequence, dense local attention.

    Inside shard_map with seq-sharded q,k,v (B,H,S_local,D) and H divisible
    by the axis size: reshards to (B,H_local,S_full,D), attends densely,
    reshards back.
    """
    n = lax.psum(1, axis_name)
    # split heads across devices, gather sequence: (B,H,S_l,D)->(B,H/n,S,D)
    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    q2, k2, v2 = to_seq(q), to_seq(k), to_seq(v)
    from .flash_attention import flash_attention
    out = flash_attention(q2, k2, v2, causal=causal, scale=scale)
    return to_heads(out)


def sharded_self_attention(q, k, v, mesh: Mesh, seq_axis="sp", causal=False,
                           impl="ring", scale=None):
    """Host-level entry: shard q,k,v over ``seq_axis`` on dim 2 and run the
    chosen SP attention as one compiled SPMD program."""
    fn = ring_attention if impl == "ring" else ulysses_attention
    spec = P(None, None, seq_axis, None)
    # pallas_call (flash kernel in the ulysses path) doesn't carry
    # varying-mesh-axis metadata; skip the vma check
    mapped = shard_map(
        functools.partial(fn, axis_name=seq_axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return jax.jit(mapped)(q, k, v)
