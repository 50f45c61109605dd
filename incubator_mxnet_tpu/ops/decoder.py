"""Ops of a decoder block that the vision and RNN families had no use for:
RMS norm and rotary position embedding.  (Attention is
``_contrib_flash_attention``, the expert layer's ops are in
``parallel/moe.py``.)"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import _backend
from .registry import register

_LANES = 128
#: the index maps' zero (under x64 a Python 0 lowers as int64)
_I0 = _np.int32(0)
#: values of a block of the rotary kernel: 2,048 positions of 128 are 512
#: KiB of bf16 in and as much out, and the tables' blocks 1 MiB each
_BLOCK = 2048 * 128


@register("_contrib_rms_norm", num_inputs=2)
def _rms_norm(data, gamma, eps=1e-5):
    """``data * rsqrt(mean(data**2, -1) + eps) * gamma`` over the last axis;
    the statistics in float32, the result in ``data``'s dtype."""
    x = data.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


@functools.lru_cache(maxsize=8)
def _tables(s, d, theta):
    """The cosines and sines of positions 0..S-1, ``(S, D)`` float32, each
    half of D the same: computed in float64 by numpy at trace time and
    passed as constants of the program (left to the compiler they are
    computed again for every head).  Made once a shape and kept, read-only:
    every layer, each way, asks for the same (48 ms at 16,384 x 128)."""
    inv = 1.0 / float(theta) ** (_np.arange(0, d, 2, dtype=_np.float64) / d)
    ang = _np.arange(s, dtype=_np.float64)[:, None] * inv[None, :]
    cos = _np.concatenate([_np.cos(ang)] * 2, -1).astype(_np.float32)
    sin = _np.concatenate([_np.sin(ang)] * 2, -1).astype(_np.float32)
    cos.flags.writeable = sin.flags.writeable = False
    return cos, sin


def _rotary_xla(data, theta):
    """The rotation as XLA's own ops: ``x * cos + [-x2, x1] * sin``."""
    s, d = data.shape[-2:]
    cos, sin = _tables(s, d, theta)
    x = data.astype(jnp.float32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    out = x * cos + jnp.concatenate([-x2, x1], -1) * sin
    return out.astype(data.dtype)


def _rotate_kernel(x_ref, cos_ref, sin_ref, o_ref, *, sign):
    """``x * cos + sign * [-x2, x1] * sin`` over a block of positions:
    ``sin_ref`` holds the sines with the first half of D negated, so the
    second term is ``x`` rolled by D / 2 lanes times that table, and no
    slice splits a vreg.  The backward (``sign`` -1) subtracts the same
    product, which is the bits of the transpose of ``[-x2, x1]`` applied to
    ``g * sin``."""
    x = x_ref[0].astype(jnp.float32)
    xc = x * cos_ref[...]
    rs = pltpu.roll(x, _np.int32(x.shape[-1] // 2), 1) * sin_ref[...]
    o_ref[0] = (xc + rs if sign > 0 else xc - rs).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _rotation(sign):
    """The kernel over ``(heads, S, D)`` with the tables ``(S, D)``, jitted
    once for every call site (``_backend.lowered_once``).  The grid is
    (position blocks, heads), the heads innermost: the tables' block keeps
    its index while the heads go by, and is fetched once a position
    block."""
    name = "rotary" if sign > 0 else "rotary_bwd"

    def rotate(x, cos, sin):
        n, s, d = x.shape
        block = min(s, _BLOCK // d)
        return pl.pallas_call(
            functools.partial(_rotate_kernel, sign=sign),
            grid=(pl.cdiv(s, block), n),
            in_specs=[pl.BlockSpec((1, block, d), lambda i, h: (h, i, _I0)),
                      pl.BlockSpec((block, d), lambda i, h: (i, _I0)),
                      pl.BlockSpec((block, d), lambda i, h: (i, _I0))],
            out_specs=pl.BlockSpec((1, block, d), lambda i, h: (h, i, _I0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=_backend.pallas_interpret(),
            name=name,
        )(x, cos, sin)

    rotate.__name__ = name
    return _backend.lowered_once(rotate)


def _rotate(data, theta, sign):
    s, d = data.shape[-2:]
    cos, sin = _tables(s, d, theta)
    sin = _np.concatenate([-sin[:, :d // 2], sin[:, d // 2:]], -1)
    x = data.reshape((-1, s, d))
    return _rotation(sign)(x, cos, sin).reshape(data.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rotary_pallas(data, theta):
    return _rotate(data, theta, 1)


def _rotary_pallas_fwd(data, theta):
    return _rotate(data, theta, 1), None


def _rotary_pallas_bwd(theta, _, g):
    # the rotation is linear and the transpose of [-x2, x1] is its negation
    # (the halves of sin are equal): the cotangent is the same kernel with
    # the sign turned
    return (_rotate(g, theta, -1),)


_rotary_pallas.defvjp(_rotary_pallas_fwd, _rotary_pallas_bwd)


@register("_contrib_rotary", num_inputs=1)
def _rotary(data, theta=10000.0):
    """Rotary position embedding of ``(..., S, D)`` over the whole last axis,
    positions 0..S-1, the two halves of D paired (``rotate_half``); the
    rotation in float32, the result in ``data``'s dtype.  Where D fills
    whole vregs of 128 lanes, bfloat16 or float32, it is one Pallas pass
    each way, which reads ``data`` once and writes the result once.
    Elsewhere XLA's own ops; at D = 64 they are faster alone than a kernel
    whose rows hold two positions (0.09 against 0.27 ms for 20 heads of
    8,192, PERF.md section 6, PR 41)."""
    if data.shape[-1] % _LANES or data.dtype not in (jnp.bfloat16,
                                                      jnp.float32):
        return _rotary_xla(data, theta)
    return _rotary_pallas(data, float(theta))
