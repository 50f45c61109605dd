"""Ops of a decoder block that the vision and RNN families had no use for:
RMS norm and rotary position embedding.  (Attention is
``_contrib_flash_attention``, the expert layer's ops are in
``parallel/moe.py``.)"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from .registry import register


@register("_contrib_rms_norm", num_inputs=2)
def _rms_norm(data, gamma, eps=1e-5):
    """``data * rsqrt(mean(data**2, -1) + eps) * gamma`` over the last axis;
    the statistics in float32, the result in ``data``'s dtype."""
    x = data.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


@register("_contrib_rotary", num_inputs=1)
def _rotary(data, theta=10000.0):
    """Rotary position embedding of ``(..., S, D)`` over the whole last axis,
    positions 0..S-1, the two halves of D paired (``rotate_half``); the
    rotation in float32, the result in ``data``'s dtype.  The cosines and
    sines are constants of the program (numpy, at trace time): left to the
    compiler they are computed again for every head."""
    s, d = data.shape[-2:]
    inv = 1.0 / float(theta) ** (_np.arange(0, d, 2, dtype=_np.float64) / d)
    ang = _np.arange(s, dtype=_np.float64)[:, None] * inv[None, :]
    cos = _np.concatenate([_np.cos(ang)] * 2, -1).astype(_np.float32)
    sin = _np.concatenate([_np.sin(ang)] * 2, -1).astype(_np.float32)
    x = data.astype(jnp.float32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    out = x * cos + jnp.concatenate([-x2, x1], -1) * sin
    return out.astype(data.dtype)
