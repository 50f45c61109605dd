"""Pallas fused ghost batch norm (+ReLU, +residual-add) for TPU.

The north-star ResNet-50 train step is HBM-bound (docs/PERF.md): XLA runs
BatchNorm as separate full passes over each conv output — a stats
reduction read, a normalize+activation read+write in fwd, and a reduce
pass plus an elementwise pass in bwd (23 ms/step of
`convert_reduce_fusion` at batch 256).  These kernels keep a slab of the
activation resident in VMEM and do

* fwd:  statistics + normalize + (residual add) + ReLU in ONE read of X,
* bwd:  the dgamma/dbeta reductions AND dX (+ residual grad) in one
        read of (dY, X),

cutting ~2 full HBM passes per BatchNorm layer.

The price is *ghost* statistics: mean/var are computed per group of
images (the slab must fit VMEM), not over the whole local batch.  This
matches the per-device semantics of the distributed north-star row
(`dist_sync_device` computes BN stats per worker over batch/N_workers in
the reference — `src/operator/nn/batch_norm.cc` never reduces stats
across devices), and ghost/sub-batch BN is a standard, documented
technique; it is exposed as an explicit opt-in (`ghost_bn` on the model
zoo / `group` here), never a silent default.

Layout (the whole game — a wrong view forces XLA to insert full-tensor
transposes around the custom call):

* C >= 128: X viewed as (L, N, C), L = H*W.  The conv's TPU layout for
  these tensors is {1,0,3,2} (minor dims C, N) == row-major (H, W, N, C)
  — a bitcast.  Channels ride the 128 lanes; the ghost group is a
  sublane block of N (multiples of 16 for bf16, so windows don't pad).
* C < 128: X viewed as (L, C, N).  XLA lays small-C tensors out as
  {0,1,3,2} (minor dims N, C) == row-major (H, W, C, N) — also a
  bitcast.  Channels ride sublanes; the ghost group is the lane block
  of N (=128): an even larger statistics group.

Layers whose whole-L windows can't fit VMEM do not all fall back to jnp
(round 20, docs/PERF.md; re-planned in PR 23 against the v5e compiler,
which gives EVERY operand of a call its own double-buffered window,
aliased or not — see ``_plan``):

* **lane-fold** (C < 128): the C lanes pad to 128 anyway, so k = 128/C
  rows of L are packed into the padded lane dimension — the view is
  (L/k, N, k*C) and the per-window footprint shrinks by k.  Stats
  fold-reduce the k lane copies in-kernel by rotating lanes
  (``_fold_lanes``); the ghost group stays the sublane image block, so
  ``bn_group`` semantics are unchanged.  This puts the FORWARD of the
  112x112x64 stem at bf16 on Pallas (51.4 -> 25.7 MB windows).  It is a
  forward-only form: the stem's backward needs three such windows, which
  overrun VMEM even folded, so a lane-folded layer's backward is jnp by
  plan.
* **spatial-tiled** (cross-tile stat accumulation): a two-phase kernel
  pair — phase 1 accumulates per-tile partial sums over a sequential
  tile grid dimension into revisited (G, 1, C) blocks, the moments
  finalize on the tiny partials in jnp, and a parallel phase-2 kernel
  re-reads X to normalize (fwd) / write dX (bwd).  The window covers an
  L-tile instead of whole L, at the honest price of ONE extra read of
  the operands (its own pallas_call, so graftcost charges it).  At batch
  256 this carries the 56x56x256 exits both ways and the backward of the
  56x56x256 shortcut BN and of the 28x28x512 exits.

Only layers (or directions) that fit none of the forms use the equivalent
jnp formulation with the same ghost statistics — chosen by ``_plan`` from
shapes, the same on every backend.  ``tests/test_chip_compile.py`` compiles
every ResNet-50 batch-256 site for a described v5e.

Interpret mode runs the same kernels on CPU for tests, like
parallel/flash_attention.py.
"""
from __future__ import annotations

import contextlib
import functools
import os
import sys
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import _backend

_I0 = np.int32(0)  # index-map literal pinned to i32 (package enables x64)

__all__ = ["ghost_bn_act", "ghost_bn_stats_merge", "plan_describe", "Plan",
           "record_sites"]

_VMEM_KERNEL_LIMIT = 120 * 1024 * 1024
_WINDOW_BUDGET = 104 * 1024 * 1024

#: spatial-tiling cap: beyond this many tiles the sequential stats grid
#: and the extra finalize pass stop paying for the reclaimed window
_MAX_TILES = 16

#: in-place output aliasing (dX over gY etc. — see _call_bwd).  A
#: debugging escape hatch; it changes HBM buffers, not the plan.
_IO_ALIASES = True


def _aliases(d):
    return d if _IO_ALIASES else {}


def _rup(x, m):
    return -(-x // m) * m


def _sublane(itemsize):
    return 16 if itemsize == 2 else 8


# NB round-5 rewrite: the round-4 kernels split C >= 256 into 128-wide
# lane blocks, which turned every window DMA into cb*itemsize-byte
# strided runs (256 B at 512 B stride for the stage-2 exits) — exactly
# the measured ~55 % of the BW roofline.  The channel dim is now NEVER
# split in the LNC view: a (L, ng, C) block reads ng*C*itemsize
# CONTIGUOUS runs (4-16 KB on the ResNet-50 shapes).


# ---------------------------------------------------------------------------
# kernels (parameterized by which block axis carries channels)
# ---------------------------------------------------------------------------
# Block shape is (L, A, B); ch_axis 2 means channels on B (lanes, LNC
# view), ch_axis 1 means channels on A (sublanes, LCN view).  Reductions
# run over the other two axes; scoped-VMEM stack limits (~16 MB) force
# chunked loops over L instead of whole-slab f32 temps.


def _chunk(l, a, b, budget=1536 * 1024):
    """Largest divisor of L within the f32-temp budget; a slightly
    over-budget divisor beats degenerating to many 1-row loop iterations
    (L=49 at the 7x7 stages has divisors {1,7,49} only).  The bwd kernel
    keeps ~3 chunk-sized f32 temps live at once, so the over-budget
    stretch is capped at 2x (3 x 3 MB = 9 MB, under the ~16 MB scoped-
    VMEM stack limit); when even 2x can't reach a divisor (tiny caps
    from very large A*B blocks) the degenerate small chunk stands —
    slow-ish but VMEM-safe."""
    cap = max(1, min(budget // (a * b * 4), l))
    divs = [d for d in range(1, l + 1) if l % d == 0]
    best = max((d for d in divs if d <= cap), default=1)
    if best * 2 <= cap:
        over = [d for d in divs if cap < d <= 2 * cap]
        if over:
            return min(over)
    return best


def _bshape(vec, ch_axis):
    return vec[None, :, None] if ch_axis == 1 else vec[None, None, :]


def _fold_lanes(vec, fold):
    """Sum the ``fold`` lane copies of a (fold*C,) vector.  Every lane
    ends up holding its channel's total, so the result is already tiled
    across the copies — the form the normalize/dX loops broadcast.
    Rotate-and-add, log2(fold) steps (C divides 128, so fold is a power
    of two): Mosaic has no lane-splitting reshape — the v5e compiler
    refused ``sm.reshape(fold, -1)`` with ``infer-vector-layout:
    unsupported shape cast`` (``vector<128xf32> -> vector<2x64xf32>``).
    The shift is pinned to i32 like the index-map literals."""
    row = vec.reshape(1, -1)
    shift = row.shape[1] // fold
    while shift < row.shape[1]:
        row = row + pltpu.roll(row, np.int32(shift), 1)
        shift *= 2
    return row.reshape(-1)


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, m_ref, v_ref, *, eps, act, lc,
                ch_axis, r_ref=None, fold=1):
    l, a, b = x_ref.shape
    k = l // lc
    cnt = l * (b if ch_axis == 1 else a) * fold

    # per-chunk reduce only over the major (L) axis into an (A, B) f32
    # accumulator — cross-sublane/lane reduction happens ONCE at the end
    # (per-chunk cross reduces were the VPU bottleneck)
    def red(i, acc):
        s, ss = acc
        xc = x_ref[pl.ds(i * jnp.int32(lc), lc)].astype(jnp.float32)
        return s + jnp.sum(xc, axis=0), ss + jnp.sum(xc * xc, axis=0)
    zero = jnp.zeros((a, b), jnp.float32)
    sm, ssq = jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), red,
                                (zero, zero))
    cross = 1 if ch_axis == 1 else 0
    sm = jnp.sum(sm, axis=cross)
    ssq = jnp.sum(ssq, axis=cross)
    if fold > 1:
        # lane-fold: the lane dim carries (fold, C).  Params and stats
        # ride at the same fold*C width (tiled by the caller), so after
        # the fold-reduce everything below is lane-wide
        sm = _fold_lanes(sm, fold)
        ssq = _fold_lanes(ssq, fold)
    m = sm / cnt
    v = jnp.maximum(ssq / cnt - m * m, 0.0)
    rstd = jax.lax.rsqrt(v + eps)
    g = g_ref[...].reshape(-1).astype(jnp.float32)
    bb = b_ref[...].reshape(-1).astype(jnp.float32)
    scale = _bshape(g * rstd, ch_axis)
    shift = _bshape(bb - m * g * rstd, ch_axis)

    def norm(i, _):
        sl = pl.ds(i * jnp.int32(lc), lc)
        y = x_ref[sl].astype(jnp.float32) * scale + shift
        if r_ref is not None:
            y = y + r_ref[sl].astype(jnp.float32)
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        y_ref[sl] = y.astype(y_ref.dtype)
        return jnp.int32(0)
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), norm, jnp.int32(0))
    m_ref[...] = m.reshape(m_ref.shape)
    v_ref[...] = v.reshape(v_ref.shape)


def _fwd_kernel_res(x_ref, r_ref, g_ref, b_ref, y_ref, m_ref, v_ref, *,
                    eps, act, lc, ch_axis, fold=1):
    _fwd_kernel(x_ref, g_ref, b_ref, y_ref, m_ref, v_ref, eps=eps, act=act,
                lc=lc, ch_axis=ch_axis, r_ref=r_ref, fold=fold)


def _bwd_kernel(gy_ref, x_ref, g_ref, b_ref, m_ref, v_ref, dx_ref, dg_ref,
                db_ref, *, eps, act, lc, ch_axis, y_ref=None, dr_ref=None,
                gy2_ref=None):
    l, a, b = x_ref.shape
    k = l // lc
    cnt = l * (b if ch_axis == 1 else a)
    m = m_ref[...].reshape(-1)
    v = v_ref[...].reshape(-1)
    rstd = jax.lax.rsqrt(v + eps)
    g = g_ref[...].reshape(-1).astype(jnp.float32)
    bb = b_ref[...].reshape(-1).astype(jnp.float32) if b_ref is not None \
        else None
    mb = _bshape(m, ch_axis)
    rb = _bshape(rstd, ch_axis)
    gb = _bshape(g, ch_axis)

    def gyld(sl):
        # dual-output join absorption: the block exit's two cotangents
        # (conv path + shortcut) sum on the VMEM window load, so the
        # surrounding program never materializes an add_any join
        gyc = gy_ref[sl].astype(jnp.float32)
        if gy2_ref is not None:
            gyc = gyc + gy2_ref[sl].astype(jnp.float32)
        return gyc

    def masked(sl, gyc, xhat):
        if act != "relu":
            return gyc
        if y_ref is not None:
            return jnp.where(y_ref[sl].astype(jnp.float32) > 0, gyc, 0.0)
        pre = xhat * gb + _bshape(bb, ch_axis)
        return jnp.where(pre > 0, gyc, 0.0)

    def red(i, acc):
        sdb, sdg = acc
        sl = pl.ds(i * jnp.int32(lc), lc)
        xhat = (x_ref[sl].astype(jnp.float32) - mb) * rb
        gp = masked(sl, gyld(sl), xhat)
        return sdb + jnp.sum(gp, axis=0), sdg + jnp.sum(gp * xhat, axis=0)
    zero = jnp.zeros((a, b), jnp.float32)
    db, dg = jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), red, (zero, zero))
    cross = 1 if ch_axis == 1 else 0
    db = jnp.sum(db, axis=cross)
    dg = jnp.sum(dg, axis=cross)
    dbb = _bshape(db, ch_axis)
    dgb = _bshape(dg, ch_axis)

    def wr(i, _):
        sl = pl.ds(i * jnp.int32(lc), lc)
        xhat = (x_ref[sl].astype(jnp.float32) - mb) * rb
        gp = masked(sl, gyld(sl), xhat)
        dx = gb * rb * (gp - (dbb + xhat * dgb) / cnt)
        dx_ref[sl] = dx.astype(dx_ref.dtype)
        if dr_ref is not None:
            dr_ref[sl] = gp.astype(dr_ref.dtype)
        return jnp.int32(0)
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), wr, jnp.int32(0))
    dg_ref[...] = dg.reshape(dg_ref.shape)
    db_ref[...] = db.reshape(db_ref.shape)


def _bwd_kernel_res(gy_ref, x_ref, y_ref, g_ref, m_ref, v_ref, dx_ref,
                    dg_ref, db_ref, dr_ref, *, eps, act, lc, ch_axis):
    # residual variant: the post-add ReLU mask comes from the saved OUTPUT
    # (y > 0 iff pre+res > 0), so the residual tensor itself is not re-read
    _bwd_kernel(gy_ref, x_ref, g_ref, None, m_ref, v_ref, dx_ref, dg_ref,
                db_ref, eps=eps, act=act, lc=lc, ch_axis=ch_axis,
                y_ref=y_ref, dr_ref=dr_ref)


def _bwd_kernel_res_dual(gy_ref, gy2_ref, x_ref, y_ref, g_ref, m_ref, v_ref,
                         dx_ref, dg_ref, db_ref, dr_ref, *, eps, act, lc,
                         ch_axis):
    # dual-cotangent residual variant (the block-exit join absorption):
    # gy1 (conv path) + gy2 (shortcut) sum on the window load
    _bwd_kernel(gy_ref, x_ref, g_ref, None, m_ref, v_ref, dx_ref, dg_ref,
                db_ref, eps=eps, act=act, lc=lc, ch_axis=ch_axis,
                y_ref=y_ref, dr_ref=dr_ref, gy2_ref=gy2_ref)


# ---------------------------------------------------------------------------
# spatial-tiled kernels (LNC only; cross-tile stat accumulation)
# ---------------------------------------------------------------------------
# The tile grid dim is SEQUENTIAL ("arbitrary" semantics, innermost), and
# the per-(group, channel) partial-sum blocks are revisited across it —
# the flash_attention.py accumulation idiom: init at tile 0, add after.


def _tile_acc(ref, val, t):
    @pl.when(t == 0)
    def _init():
        ref[...] = val.reshape(ref.shape)

    @pl.when(t != 0)
    def _add():
        ref[...] = ref[...] + val.reshape(ref.shape)


def _stats_tile_kernel(x_ref, s_ref, ss_ref, *, lc):
    """Phase-1 fwd: per-tile partial sum/sumsq over (L-tile, ng),
    accumulated across the sequential tile dim into (1, 1, C) blocks."""
    t = pl.program_id(1)
    l, a, b = x_ref.shape
    k = l // lc

    def red(i, acc):
        s, ss = acc
        xc = x_ref[pl.ds(i * jnp.int32(lc), lc)].astype(jnp.float32)
        return s + jnp.sum(xc, axis=0), ss + jnp.sum(xc * xc, axis=0)
    zero = jnp.zeros((a, b), jnp.float32)
    sm, ssq = jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), red,
                                (zero, zero))
    _tile_acc(s_ref, jnp.sum(sm, axis=0), t)
    _tile_acc(ss_ref, jnp.sum(ssq, axis=0), t)


def _norm_tile_kernel(x_ref, g_ref, b_ref, m_ref, v_ref, y_ref, *, eps,
                      act, lc, r_ref=None):
    """Phase-2 fwd: normalize one tile with the finalized stats (the
    extra read of X the plan charges for)."""
    l, a, b = x_ref.shape
    k = l // lc
    m = m_ref[...].reshape(-1)
    rstd = jax.lax.rsqrt(v_ref[...].reshape(-1) + eps)
    g = g_ref[...].reshape(-1).astype(jnp.float32)
    bb = b_ref[...].reshape(-1).astype(jnp.float32)
    scale = (g * rstd)[None, None, :]
    shift = (bb - m * g * rstd)[None, None, :]

    def norm(i, _):
        sl = pl.ds(i * jnp.int32(lc), lc)
        y = x_ref[sl].astype(jnp.float32) * scale + shift
        if r_ref is not None:
            y = y + r_ref[sl].astype(jnp.float32)
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        y_ref[sl] = y.astype(y_ref.dtype)
        return jnp.int32(0)
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), norm, jnp.int32(0))


def _norm_tile_kernel_res(x_ref, r_ref, g_ref, b_ref, m_ref, v_ref, y_ref,
                          *, eps, act, lc):
    _norm_tile_kernel(x_ref, g_ref, b_ref, m_ref, v_ref, y_ref, eps=eps,
                      act=act, lc=lc, r_ref=r_ref)


def _tile_masked(gy_ref, y_ref, gb, bbv, act):
    """The shared ReLU cotangent mask: from the saved output when a
    residual was added (y > 0 iff pre+res > 0), else from the pre-act."""
    def masked(sl, gyc, xhat):
        if act != "relu":
            return gyc
        if y_ref is not None:
            return jnp.where(y_ref[sl].astype(jnp.float32) > 0, gyc, 0.0)
        pre = xhat * gb + bbv[None, None, :]
        return jnp.where(pre > 0, gyc, 0.0)
    return masked


def _bwd_red_tile_kernel(gy_ref, x_ref, g_ref, b_ref, m_ref, v_ref,
                         db_ref, dg_ref, *, eps, act, lc, y_ref=None):
    """Phase-1 bwd: per-tile partial dbeta/dgamma reductions, accumulated
    across the sequential tile dim."""
    t = pl.program_id(1)
    l, a, b = x_ref.shape
    k = l // lc
    m = m_ref[...].reshape(-1)
    rstd = jax.lax.rsqrt(v_ref[...].reshape(-1) + eps)
    mb, rb = m[None, None, :], rstd[None, None, :]
    gb = g_ref[...].reshape(-1).astype(jnp.float32)[None, None, :] \
        if g_ref is not None else None
    bbv = b_ref[...].reshape(-1).astype(jnp.float32) \
        if b_ref is not None else None
    masked = _tile_masked(gy_ref, y_ref, gb, bbv, act)

    def red(i, acc):
        sdb, sdg = acc
        sl = pl.ds(i * jnp.int32(lc), lc)
        xhat = (x_ref[sl].astype(jnp.float32) - mb) * rb
        gp = masked(sl, gy_ref[sl].astype(jnp.float32), xhat)
        return sdb + jnp.sum(gp, axis=0), sdg + jnp.sum(gp * xhat, axis=0)
    zero = jnp.zeros((a, b), jnp.float32)
    db, dg = jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), red,
                               (zero, zero))
    _tile_acc(db_ref, jnp.sum(db, axis=0), t)
    _tile_acc(dg_ref, jnp.sum(dg, axis=0), t)


def _bwd_red_tile_kernel_res(gy_ref, x_ref, y_ref, m_ref, v_ref, db_ref,
                             dg_ref, dr_ref, *, eps, act, lc, gy2_ref=None):
    """Phase-1 residual bwd: the partial dbeta/dgamma reductions AND the
    masked cotangent dR (= gp) in the same read — gY (and the dual
    shortcut cotangent gy2) is consumed HERE, so phase 2 never re-reads
    it (the gY-read-once protocol; dR aliases gY's dead window)."""
    t = pl.program_id(1)
    l, a, b = x_ref.shape
    k = l // lc
    m = m_ref[...].reshape(-1)
    rstd = jax.lax.rsqrt(v_ref[...].reshape(-1) + eps)
    mb, rb = m[None, None, :], rstd[None, None, :]
    masked = _tile_masked(gy_ref, y_ref, None, None, act)

    def red(i, acc):
        sdb, sdg = acc
        sl = pl.ds(i * jnp.int32(lc), lc)
        xhat = (x_ref[sl].astype(jnp.float32) - mb) * rb
        gyc = gy_ref[sl].astype(jnp.float32)
        if gy2_ref is not None:
            gyc = gyc + gy2_ref[sl].astype(jnp.float32)
        gp = masked(sl, gyc, xhat)
        dr_ref[sl] = gp.astype(dr_ref.dtype)
        return sdb + jnp.sum(gp, axis=0), sdg + jnp.sum(gp * xhat, axis=0)
    zero = jnp.zeros((a, b), jnp.float32)
    db, dg = jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), red,
                               (zero, zero))
    _tile_acc(db_ref, jnp.sum(db, axis=0), t)
    _tile_acc(dg_ref, jnp.sum(dg, axis=0), t)


def _bwd_red_tile_kernel_res_dual(gy_ref, gy2_ref, x_ref, y_ref, m_ref,
                                  v_ref, db_ref, dg_ref, dr_ref, *, eps,
                                  act, lc):
    _bwd_red_tile_kernel_res(gy_ref, x_ref, y_ref, m_ref, v_ref, db_ref,
                             dg_ref, dr_ref, eps=eps, act=act, lc=lc,
                             gy2_ref=gy2_ref)


def _bwd_dx_tile_kernel(gy_ref, x_ref, g_ref, b_ref, m_ref, v_ref, db_ref,
                        dg_ref, dx_ref, *, eps, act, lc, cnt):
    """Phase-2 bwd (no residual): dX for one tile from the cross-tile-
    reduced dbeta/dgamma totals; dX aliases the dead gY window."""
    l, a, b = x_ref.shape
    k = l // lc
    m = m_ref[...].reshape(-1)
    rstd = jax.lax.rsqrt(v_ref[...].reshape(-1) + eps)
    g = g_ref[...].reshape(-1).astype(jnp.float32)
    bbv = b_ref[...].reshape(-1).astype(jnp.float32) \
        if b_ref is not None else None
    mb, rb, gb = m[None, None, :], rstd[None, None, :], g[None, None, :]
    dbb = db_ref[...].reshape(-1)[None, None, :]
    dgb = dg_ref[...].reshape(-1)[None, None, :]
    masked = _tile_masked(gy_ref, None, gb, bbv, act)

    def wr(i, _):
        sl = pl.ds(i * jnp.int32(lc), lc)
        xhat = (x_ref[sl].astype(jnp.float32) - mb) * rb
        gp = masked(sl, gy_ref[sl].astype(jnp.float32), xhat)
        dx = gb * rb * (gp - (dbb + xhat * dgb) / cnt)
        dx_ref[sl] = dx.astype(dx_ref.dtype)
        return jnp.int32(0)
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), wr, jnp.int32(0))


def _bwd_dx_from_dr_tile_kernel(dr_ref, x_ref, g_ref, m_ref, v_ref, db_ref,
                                dg_ref, dx_ref, *, eps, lc, cnt):
    """Phase-2 residual bwd: dX for one tile from the phase-1 masked
    cotangent dR and the cross-tile totals — reads (dR, X) only (no gY,
    no Y: the mask is already applied inside dR); dX aliases X's dead
    window."""
    l, a, b = x_ref.shape
    k = l // lc
    m = m_ref[...].reshape(-1)
    rstd = jax.lax.rsqrt(v_ref[...].reshape(-1) + eps)
    g = g_ref[...].reshape(-1).astype(jnp.float32)
    mb, rb, gb = m[None, None, :], rstd[None, None, :], g[None, None, :]
    dbb = db_ref[...].reshape(-1)[None, None, :]
    dgb = dg_ref[...].reshape(-1)[None, None, :]

    def wr(i, _):
        sl = pl.ds(i * jnp.int32(lc), lc)
        xhat = (x_ref[sl].astype(jnp.float32) - mb) * rb
        gp = dr_ref[sl].astype(jnp.float32)
        dx = gb * rb * (gp - (dbb + xhat * dgb) / cnt)
        dx_ref[sl] = dx.astype(dx_ref.dtype)
        return jnp.int32(0)
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), wr, jnp.int32(0))


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _specs(l, n, c, ab, ch_axis):
    """Block specs for the (L, A, B) view.  ab = (A-block, B-block).
    Grid is (groups, channel-blocks); channel params/stats use the
    'equal-dim trick' shapes so small channel blocks stay legal.  The
    lane-fold forward calls this with its folded width (``_lane_width``):
    X, params and stats all carry fold*C lanes there."""
    a_blk, b_blk = ab
    if ch_axis == 2:   # LNC: A=N (groups on sublanes), B=C
        xspec = pl.BlockSpec((l, a_blk, b_blk),
                             lambda g, ci: (_I0, g, ci))
        pspec = pl.BlockSpec((1, b_blk), lambda g, ci: (_I0, ci))
        sspec = pl.BlockSpec((1, 1, b_blk), lambda g, ci: (g, _I0, ci))
        n_groups = n // a_blk
        pshape = (1, c)
        sshape = (n_groups, 1, c)
    else:              # LCN: A=C (channels on sublanes), B=N (groups)
        xspec = pl.BlockSpec((l, a_blk, b_blk), lambda g, ci: (_I0, ci, g))
        pspec = pl.BlockSpec((a_blk, 1), lambda g, ci: (ci, _I0))
        sspec = pl.BlockSpec((1, a_blk, 1), lambda g, ci: (g, ci, _I0))
        n_groups = n // b_blk
        pshape = (c, 1)
        sshape = (n_groups, c, 1)
    return xspec, pspec, sspec, n_groups, pshape, sshape


def _lane_width(x_v, ab, ch_axis, fold):
    """(true C, kernel-visible C, kernel-visible ab) of a view.  Under
    lane-fold (LNC only) the kernel sees fold*C channels: the caller
    tiles params/stats up to that width and slices the kernel's stat
    outputs back to the first C lanes (every copy holds the total)."""
    if ch_axis == 2:
        cw = x_v.shape[2]
        return cw // fold, cw, (ab[0], fold * ab[1])
    return x_v.shape[1], x_v.shape[1], ab


def _call_fwd(x_v, gamma, beta, residual, eps, act, ab, ch_axis,
              donate_res=False, fold=1):
    l = x_v.shape[0]
    n = x_v.shape[1] if ch_axis == 2 else x_v.shape[2]
    c, cw, ab = _lane_width(x_v, ab, ch_axis, fold)
    xspec, pspec, sspec, ngroups, pshape, sshape = _specs(l, n, cw, ab,
                                                          ch_axis)
    grid = (ngroups, cw // (ab[1] if ch_axis == 2 else ab[0]))
    lc = _chunk(l, ab[0], ab[1])
    if fold > 1:
        gamma, beta = jnp.tile(gamma, fold), jnp.tile(beta, fold)
    out_shape = [jax.ShapeDtypeStruct(x_v.shape, x_v.dtype),
                 jax.ShapeDtypeStruct(sshape, jnp.float32),
                 jax.ShapeDtypeStruct(sshape, jnp.float32)]
    aliases = {}
    if residual is None:
        kern = functools.partial(_fwd_kernel, eps=eps, act=act, lc=lc,
                                 ch_axis=ch_axis, fold=fold)
        in_specs = [xspec, pspec, pspec]
        args = (x_v, gamma.reshape(pshape), beta.reshape(pshape))
    else:
        kern = functools.partial(_fwd_kernel_res, eps=eps, act=act, lc=lc,
                                 ch_axis=ch_axis, fold=fold)
        in_specs = [xspec, xspec, pspec, pspec]
        args = (x_v, residual, gamma.reshape(pshape), beta.reshape(pshape))
        if donate_res:
            # the caller declared the residual dead after this layer
            # (the downsample-shortcut case): Y writes into its window
            # — the norm loop reads r[sl] strictly before y[sl] lands,
            # so the in-place chunk update is race-free
            aliases = {1: 0}
    y, m, v = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs,
        out_specs=[xspec, sspec, sspec], out_shape=out_shape,
        input_output_aliases=_aliases(aliases),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_KERNEL_LIMIT),
        interpret=_backend.pallas_interpret())(*args)
    return y, m.reshape(ngroups, cw)[:, :c], v.reshape(ngroups, cw)[:, :c]


def _call_bwd(gy, x_v, y_v, gamma, beta, m, v, eps, act, ab, ch_axis,
              gy2=None):
    """One-read backward.  The cotangent gY and the saved X are both
    dead after this call (gY's only consumer is this vjp; X was saved
    exactly for it), so the kernels write their outputs in place:
    dX over gY (non-residual) / dR over gY and dX over X (residual) via
    ``input_output_aliases`` — each grid step reads and writes the same
    block index, so the in-place update is race-free.  The alias saves
    the HBM buffer only: Mosaic still gives every operand, input or
    output, its own double-buffered VMEM window, and ``_plan`` counts
    them all.  ``gy2`` is the dual-output shortcut
    cotangent (round 20): a block exit returning its tensor in TWO
    output positions receives the conv-path and shortcut cotangents
    separately, and the kernel sums them on the window load instead of
    the program paying a materialized add_any join."""
    l = x_v.shape[0]
    n = x_v.shape[1] if ch_axis == 2 else x_v.shape[2]
    c = x_v.shape[2] if ch_axis == 2 else x_v.shape[1]
    xspec, pspec, sspec, ngroups, pshape, sshape = _specs(l, n, c, ab,
                                                          ch_axis)
    grid = (ngroups, c // (ab[1] if ch_axis == 2 else ab[0]))
    lc = _chunk(l, ab[0], ab[1])
    dstat = jax.ShapeDtypeStruct(sshape, jnp.float32)
    m_s = m.reshape(sshape)
    v_s = v.reshape(sshape)
    if y_v is None:
        if gy2 is not None:
            # no dual non-residual kernel form (the model only marks
            # residual block exits dual) — merge upfront, stay correct
            gy = gy + gy2
        kern = functools.partial(_bwd_kernel, eps=eps, act=act, lc=lc,
                                 ch_axis=ch_axis)
        dx, dg, db = pl.pallas_call(
            kern, grid=grid,
            in_specs=[xspec, xspec, pspec, pspec, sspec, sspec],
            out_specs=[xspec, sspec, sspec],
            out_shape=[jax.ShapeDtypeStruct(x_v.shape, x_v.dtype), dstat,
                       dstat],
            input_output_aliases=_aliases({0: 0}),  # dX over dead gY
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_VMEM_KERNEL_LIMIT),
            interpret=_backend.pallas_interpret())(
            gy, x_v, gamma.reshape(pshape), beta.reshape(pshape), m_s, v_s)
        dr = None
    else:
        if gy2 is None:
            kern = functools.partial(_bwd_kernel_res, eps=eps, act=act,
                                     lc=lc, ch_axis=ch_axis)
            in_specs = [xspec, xspec, xspec, pspec, sspec, sspec]
            args = (gy, x_v, y_v, gamma.reshape(pshape), m_s, v_s)
            aliases = {0: 3, 1: 0}  # dR/gY, dX/X
        else:
            kern = functools.partial(_bwd_kernel_res_dual, eps=eps,
                                     act=act, lc=lc, ch_axis=ch_axis)
            in_specs = [xspec, xspec, xspec, xspec, pspec, sspec, sspec]
            args = (gy, gy2, x_v, y_v, gamma.reshape(pshape), m_s, v_s)
            aliases = {0: 3, 2: 0}  # dR/gY1, dX/X
        dx, dg, db, dr = pl.pallas_call(
            kern, grid=grid, in_specs=in_specs,
            out_specs=[xspec, sspec, sspec, xspec],
            out_shape=[jax.ShapeDtypeStruct(x_v.shape, x_v.dtype), dstat,
                       dstat, jax.ShapeDtypeStruct(x_v.shape, x_v.dtype)],
            input_output_aliases=_aliases(aliases),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_VMEM_KERNEL_LIMIT),
            interpret=_backend.pallas_interpret())(*args)
    return (dx, dg.reshape(ngroups, c).sum(0), db.reshape(ngroups, c).sum(0),
            dr)


def _tile_specs(lt, ng, c):
    """Block specs for the spatial-tiled (LNC) grid (groups, tiles)."""
    xspec = pl.BlockSpec((lt, ng, c), lambda g, t: (t, g, _I0))
    pspec = pl.BlockSpec((1, c), lambda g, t: (_I0, _I0))
    sspec = pl.BlockSpec((1, 1, c), lambda g, t: (g, _I0, _I0))
    return xspec, pspec, sspec


def _tile_params(sequential):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",
                             "arbitrary" if sequential else "parallel"),
        vmem_limit_bytes=_VMEM_KERNEL_LIMIT)


def _call_fwd_tiled(x_v, gamma, beta, residual, eps, act, ab, lt,
                    donate_res=False):
    """Spatial-tiled forward (LNC only).  Phase 1 walks the L-tiles
    sequentially accumulating (G, 1, C) partial sums, the moments
    finalize on the tiny partials in plain jnp, and the fully-parallel
    phase-2 kernel re-reads X to normalize — one extra read of X vs the
    whole-L fused form, charged honestly as its own pallas_call."""
    l, n, c = x_v.shape
    ng = ab[0]
    ngroups, ntiles = n // ng, l // lt
    lc = _chunk(lt, ng, c)
    xspec, pspec, sspec = _tile_specs(lt, ng, c)
    sshape = (ngroups, 1, c)
    s, ss = pl.pallas_call(
        functools.partial(_stats_tile_kernel, lc=lc),
        grid=(ngroups, ntiles), in_specs=[xspec],
        out_specs=[sspec, sspec],
        out_shape=[jax.ShapeDtypeStruct(sshape, jnp.float32)] * 2,
        compiler_params=_tile_params(True),
        interpret=_backend.pallas_interpret())(x_v)
    cnt = l * ng
    m = (s / cnt).reshape(ngroups, c)
    v = jnp.maximum((ss / cnt).reshape(ngroups, c) - m * m, 0.0)
    m_s, v_s = m.reshape(sshape), v.reshape(sshape)
    aliases = {}
    if residual is None:
        kern = functools.partial(_norm_tile_kernel, eps=eps, act=act, lc=lc)
        in_specs = [xspec, pspec, pspec, sspec, sspec]
        args = (x_v, gamma.reshape(1, c), beta.reshape(1, c), m_s, v_s)
    else:
        kern = functools.partial(_norm_tile_kernel_res, eps=eps, act=act,
                                 lc=lc)
        in_specs = [xspec, xspec, pspec, pspec, sspec, sspec]
        args = (x_v, residual, gamma.reshape(1, c), beta.reshape(1, c),
                m_s, v_s)
        if donate_res:
            aliases = {1: 0}  # Y over the dead (donated) residual window
    y = pl.pallas_call(
        kern, grid=(ngroups, ntiles), in_specs=in_specs, out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct(x_v.shape, x_v.dtype),
        input_output_aliases=_aliases(aliases),
        compiler_params=_tile_params(False),
        interpret=_backend.pallas_interpret())(*args)
    return y, m, v


def _call_bwd_tiled(gy, x_v, y_v, gamma, beta, m, v, eps, act, ab, lt,
                    gy2=None):
    """Spatial-tiled backward (LNC only).  No residual: sequential
    phase-1 dbeta/dgamma partial reductions, then a fully-parallel
    phase-2 dX with the cross-tile totals (dX over the dead gY window).
    Residual (round 20, the gY-read-once protocol): phase 1 reads
    (gY[, gY2], X, Y) ONCE, producing the stat partials AND the masked
    cotangent dR (aliasing gY's window); phase 2 reads only (dR, X) —
    the mask is baked into dR, so gY and Y are never re-read — and dX
    aliases X.  That is 5 operand-tile reads instead of 6 (8 dual)."""
    l, n, c = x_v.shape
    ng = ab[0]
    ngroups, ntiles = n // ng, l // lt
    lc = _chunk(lt, ng, c)
    xspec, pspec, sspec = _tile_specs(lt, ng, c)
    sshape = (ngroups, 1, c)
    dstat = jax.ShapeDtypeStruct(sshape, jnp.float32)
    m_s, v_s = m.reshape(sshape), v.reshape(sshape)
    cnt = l * ng
    if y_v is None:
        if gy2 is not None:
            gy = gy + gy2  # no dual non-residual form (see _call_bwd)
        red = functools.partial(_bwd_red_tile_kernel, eps=eps, act=act,
                                lc=lc)
        db, dg = pl.pallas_call(
            red, grid=(ngroups, ntiles),
            in_specs=[xspec, xspec, pspec, pspec, sspec, sspec],
            out_specs=[sspec, sspec], out_shape=[dstat, dstat],
            compiler_params=_tile_params(True),
            interpret=_backend.pallas_interpret())(
            gy, x_v, gamma.reshape(1, c), beta.reshape(1, c), m_s, v_s)
        kern = functools.partial(_bwd_dx_tile_kernel, eps=eps, act=act,
                                 lc=lc, cnt=cnt)
        dx = pl.pallas_call(
            kern, grid=(ngroups, ntiles),
            in_specs=[xspec, xspec, pspec, pspec, sspec, sspec, sspec,
                      sspec],
            out_specs=xspec,
            out_shape=jax.ShapeDtypeStruct(x_v.shape, x_v.dtype),
            input_output_aliases=_aliases({0: 0}),  # dX over dead gY
            compiler_params=_tile_params(False),
            interpret=_backend.pallas_interpret())(
            gy, x_v, gamma.reshape(1, c), beta.reshape(1, c), m_s, v_s,
            db, dg)
        dr = None
    else:
        if gy2 is None:
            red = functools.partial(_bwd_red_tile_kernel_res, eps=eps,
                                    act=act, lc=lc)
            in_specs = [xspec, xspec, xspec, sspec, sspec]
            args = (gy, x_v, y_v, m_s, v_s)
        else:
            red = functools.partial(_bwd_red_tile_kernel_res_dual, eps=eps,
                                    act=act, lc=lc)
            in_specs = [xspec, xspec, xspec, xspec, sspec, sspec]
            args = (gy, gy2, x_v, y_v, m_s, v_s)
        db, dg, dr = pl.pallas_call(
            red, grid=(ngroups, ntiles), in_specs=in_specs,
            out_specs=[sspec, sspec, xspec],
            out_shape=[dstat, dstat,
                       jax.ShapeDtypeStruct(x_v.shape, x_v.dtype)],
            input_output_aliases=_aliases({0: 2}),  # dR over dead gY
            compiler_params=_tile_params(True),
            interpret=_backend.pallas_interpret())(*args)
        kern = functools.partial(_bwd_dx_from_dr_tile_kernel, eps=eps,
                                 lc=lc, cnt=cnt)
        dx = pl.pallas_call(
            kern, grid=(ngroups, ntiles),
            in_specs=[xspec, xspec, pspec, sspec, sspec, sspec, sspec],
            out_specs=xspec,
            out_shape=jax.ShapeDtypeStruct(x_v.shape, x_v.dtype),
            input_output_aliases=_aliases({1: 0}),  # dX over dead X
            compiler_params=_tile_params(False),
            interpret=_backend.pallas_interpret())(
            dr, x_v, gamma.reshape(1, c), m_s, v_s, db, dg)
    return (dx, dg.reshape(ngroups, c).sum(0), db.reshape(ngroups, c).sum(0),
            dr)


# ---------------------------------------------------------------------------
# plan selection + views
# ---------------------------------------------------------------------------


class Plan(NamedTuple):
    """One BN layer's kernel selection.  Field ORDER is load-bearing:
    older callers index ``plan[0..2]`` as ``(ch_axis, ab, bwd_pallas)``.
    ``variant``/``bwd_variant`` name the kernel form per direction
    (``fused`` = whole-L one-read, ``lanefold`` = L-rows folded into the
    padded lanes, ``tiled`` = two-phase spatial tiles, ``jnp`` = the
    ghost fallback for that direction)."""
    ch_axis: int
    ab: Tuple[int, int]
    bwd_pallas: bool
    variant: str = "fused"
    bwd_variant: str = "fused"
    fold: int = 1        # lane-fold factor k = 128/C (lanefold only)
    l_tile: int = 0      # fwd L-tile rows (tiled fwd only)
    l_tile_bwd: int = 0  # bwd L-tile rows (tiled bwd only)
    window_bytes: int = 0  # padded per-window bytes of the fwd form


def _plan(n, c, l, itemsize, group, has_res, dual=False):
    """Choose a :class:`Plan` or None for the full-jnp fallback.

    Feasibility is per DIRECTION and counts what the chip's compiler
    counts: EVERY operand of a ``pallas_call``, input or output, gets
    its own double-buffered (x2) VMEM window, padded to the dtype tile.
    ``input_output_aliases`` shares the HBM buffer, not the window —
    the v5e compiler charged the aliased 28x28x512 residual backward
    "Scoped allocation with size 122.50M", which is 5 windows x 2 x
    12.25 MiB.  So a donated residual does not change the plan (it
    only lets ``_call_fwd`` write Y over the dead residual in HBM).  Whole-L
    fwd: X [R] -> Y is 2 (+1 residual); bwd: gY X -> dX is 3, the
    residual form gY X Y -> dX dR is 5, +1 for a dual exit's gY2.  The
    two-phase tiled forms peak in the phase with most operands: fwd
    phase 2 (X [R] -> Y), non-residual bwd phase 2 (gY X -> dX, 3),
    residual bwd phase 1 (gY [gY2] X Y -> dR, 4 or 5; its phase 2,
    dR X -> dX, is 3).  What ``_WINDOW_BUDGET`` leaves under
    ``_VMEM_KERNEL_LIMIT`` is for the kernels' stack temporaries (the
    f32 chunk temps ``_chunk`` sizes, the accumulators).

    Selection order on the LNC path (round 20): whole-L fused both
    directions > lane-fold fwd + jnp bwd (C < 128: the window shrinks by
    k = 128/C, same one-read fwd kernel) > whole-L fused fwd + spatial-
    tiled bwd > spatial-tiled both > whole-L fused fwd + jnp bwd (the
    legacy hybrid) > None.  Earlier forms read each operand once; the
    tiled forms pay one extra read of the operands (the stats phase).
    """
    sub = _sublane(itemsize)

    def padded(a_blk, b_blk, rows=l):
        return rows * _rup(a_blk, sub) * _rup(b_blk, 128) * itemsize

    def fits(nwin, a_blk, b_blk, rows=l):
        return nwin * 2 * padded(a_blk, b_blk, rows) <= _WINDOW_BUDGET

    fw = 2 + has_res
    bw = (5 + dual) if has_res else 3
    bw_tiled = (4 + dual) if has_res else 3
    if c >= 128 or n > 128:
        # LNC: full C on lanes, ghost group on sublanes.  Prefer
        # tile-multiple groups (a sub-tile group pads VMEM to the tile
        # without shrinking it), largest first; the user group is a CAP.
        cap = min(group if group else 32, n)
        ngs = sorted((g for g in range(1, cap + 1) if n % g == 0),
                     key=lambda g: (g % sub == 0, g), reverse=True)
        # prefer the largest group for which BOTH directions fuse (group
        # size doesn't change the bytes saved, a fused bwd does); fall
        # back to the largest fwd-only group
        best_fwd = None
        for ng in ngs:
            if fits(fw, ng, c):
                if fits(bw, ng, c):
                    return Plan(2, (ng, c), True,
                                window_bytes=padded(ng, c))
                if best_fwd is None:
                    best_fwd = ng
        # lane-fold: C < 128 pads its lanes to 128 anyway — pack
        # k = 128/C rows of L into the padding so the window shrinks by
        # k.  The ghost group stays the sublane image block (bn_group
        # cap semantics unchanged); stats fold-reduce in-kernel.
        # Forward only: where the whole-L backward does not fit, its 3+
        # windows rarely fit folded either (never at a ResNet-50
        # batch-256 site), so the backward of a lane-folded layer is jnp.
        fold = 128 // c if (c < 128 and 128 % c == 0) else 1
        if fold > 1 and l % fold == 0:
            lf = l // fold
            for ng in ngs:
                if fits(fw, ng, fold * c, lf):
                    return Plan(2, (ng, c), False, "lanefold", "jnp",
                                fold=fold,
                                window_bytes=padded(ng, fold * c, lf))

        def tile_rows(nwin, ng):
            # largest L-divisor tile whose nwin windows fit, capped at
            # _MAX_TILES tiles (whole-L itself is the nt=1 case the
            # callers above already rejected)
            for nt in range(2, _MAX_TILES + 1):
                if l % nt == 0 and fits(nwin, ng, c, l // nt):
                    return l // nt
            return 0

        # whole-L fused fwd + spatial-tiled bwd: keeps the one-read fwd
        # and still retires the bwd multi-pass (the 56x56x256
        # downsample-shortcut BN at batch 256)
        if best_fwd is not None:
            ltb = tile_rows(bw_tiled, best_fwd)
            if ltb:
                return Plan(2, (best_fwd, c), True, "fused", "tiled",
                            l_tile_bwd=ltb,
                            window_bytes=padded(best_fwd, c))
        # spatial-tiled both directions (the 56x56x256 identity exits)
        for ng in ngs:
            ltf = tile_rows(fw, ng)
            if ltf:
                ltb = tile_rows(bw_tiled, ng)
                return Plan(2, (ng, c), bool(ltb), "tiled",
                            "tiled" if ltb else "jnp",
                            l_tile=ltf, l_tile_bwd=ltb,
                            window_bytes=padded(ng, c, ltf))
        # whole-L fused fwd + jnp bwd (the legacy hybrid)
        if best_fwd is not None:
            return Plan(2, (best_fwd, c), False, "fused", "jnp",
                        window_bytes=padded(best_fwd, c))
        return None
    # small-N path (N <= 128, C < 128): channels on sublanes, the WHOLE
    # batch on lanes — exact full-batch statistics, contiguous
    # cb*N*itemsize runs (the block covers full N and a dense C-slice).
    # This kernel's ghost group IS the full lane block (= N): when the
    # caller capped the group below that, honoring the declared
    # bn_group semantics outranks the kernel — fall back to the jnp
    # formulation, which computes the capped per-group statistics.
    if group and group < n:
        return None
    cb = c
    while cb > 0 and not fits(fw, cb, n):
        cb -= sub
        while cb > 0 and c % cb:
            cb -= 1
    if cb <= 0:
        return None
    bwd_ok = fits(bw, cb, n)
    return Plan(1, (cb, n), bwd_ok, "fused", "fused" if bwd_ok else "jnp",
                window_bytes=padded(cb, n))


def _to_view(x, ch_axis, fold=1):
    n, c, h, w = x.shape
    if ch_axis == 2:   # (L, N, C): bitcast of layout {1,0,3,2}
        v = jnp.transpose(x, (2, 3, 0, 1)).reshape(h * w, n, c)
        if fold > 1:
            # lane-fold view (L/k, N, k*C): k consecutive L rows move
            # into the padded lane dim; feeds a custom kernel, so the
            # layout chain folds into the window DMA (cost_model.py)
            lf = h * w // fold
            v = jnp.transpose(v.reshape(lf, fold, n, c),
                              (0, 2, 1, 3)).reshape(lf, n, fold * c)
        return v
    # (L, C, N): bitcast of layout {0,1,3,2}
    return jnp.transpose(x, (2, 3, 1, 0)).reshape(h * w, c, n)


def _from_view(x_v, shape, ch_axis, fold=1):
    n, c, h, w = shape
    if ch_axis == 2:
        if fold > 1:
            lf = h * w // fold
            x_v = jnp.transpose(x_v.reshape(lf, n, fold, c),
                                (0, 2, 1, 3)).reshape(h * w, n, c)
        return jnp.transpose(x_v.reshape(h, w, n, c), (2, 3, 0, 1))
    return jnp.transpose(x_v.reshape(h, w, c, n), (3, 2, 0, 1))


# ---------------------------------------------------------------------------
# custom-vjp public entry
# ---------------------------------------------------------------------------


def _gbn_fwd(x, gamma, beta, residual, eps, act, group, donate_res=False,
             dual=False):
    n, c, h, w = x.shape
    plan = _plan(n, c, h * w, x.dtype.itemsize, group,
                 residual is not None, dual)
    ch_axis = plan.ch_axis
    fold = plan.fold if plan.variant == "lanefold" else 1
    x_v = _to_view(x, ch_axis, fold)
    r_v = None if residual is None else _to_view(residual, ch_axis, fold)
    if plan.variant == "tiled":
        y_v, m, v = _call_fwd_tiled(x_v, gamma, beta, r_v, eps, act,
                                    plan.ab, plan.l_tile,
                                    donate_res=donate_res)
    else:
        y_v, m, v = _call_fwd(x_v, gamma, beta, r_v, eps, act, plan.ab,
                              ch_axis, donate_res=donate_res, fold=fold)
    y = _from_view(y_v, x.shape, ch_axis, fold)
    res = (x_v, y_v if residual is not None else None, gamma, beta, m, v,
           x.shape)
    return ((y, m, v), res)


def _gbn_bwd_jnp(gy, x, y, gamma, beta, m, v, eps, act, ng):
    """Ghost-BN backward in plain jnp over the SAME ghost groups as the
    kernels — the hybrid path for layers whose bwd windows don't fit
    VMEM but whose fwd does (the fwd still saves its stats read)."""
    n, c, h, w = x.shape
    g = n // ng
    f32 = jnp.float32
    x5 = x.astype(f32).reshape(g, ng, c, h, w)
    gy5 = gy.astype(f32).reshape(g, ng, c, h, w)
    mb = m.reshape(g, 1, c, 1, 1)
    rstd = jax.lax.rsqrt(v + eps).reshape(g, 1, c, 1, 1)
    gam = gamma.astype(f32).reshape(1, 1, c, 1, 1)
    xhat = (x5 - mb) * rstd
    if act == "relu":
        if y is not None:
            keep = y.astype(f32).reshape(g, ng, c, h, w) > 0
        else:
            keep = (xhat * gam
                    + beta.astype(f32).reshape(1, 1, c, 1, 1)) > 0
        gp = jnp.where(keep, gy5, 0.0)
    else:
        gp = gy5
    cnt = ng * h * w
    db = gp.sum(axis=(1, 3, 4))
    dg = (gp * xhat).sum(axis=(1, 3, 4))
    dx = (gam * rstd
          * (gp - (db.reshape(g, 1, c, 1, 1)
                   + xhat * dg.reshape(g, 1, c, 1, 1)) / cnt))
    dr = gp.reshape(n, c, h, w).astype(x.dtype) if y is not None else None
    return (dx.reshape(n, c, h, w).astype(x.dtype), dg.sum(0), db.sum(0),
            dr)


def _gbn_bwd_impl(eps, act, group, dual, res, gy, gy2):
    x_v, y_v, gamma, beta, m, v, shape = res
    n, c, h, w = shape
    plan = _plan(n, c, h * w, x_v.dtype.itemsize, group, y_v is not None,
                 dual)
    ch_axis = plan.ch_axis
    if plan.bwd_pallas:
        # (never a lane-folded layer: its backward is jnp by plan)
        gy_v = _to_view(gy, ch_axis)
        gy2_v = None if gy2 is None else _to_view(gy2, ch_axis)
        if plan.bwd_variant == "tiled":
            dx, dg, db, dr = _call_bwd_tiled(gy_v, x_v, y_v, gamma, beta,
                                             m, v, eps, act, plan.ab,
                                             plan.l_tile_bwd, gy2=gy2_v)
        else:
            dx, dg, db, dr = _call_bwd(gy_v, x_v, y_v, gamma, beta, m, v,
                                       eps, act, plan.ab, ch_axis,
                                       gy2=gy2_v)
        dx = _from_view(dx, shape, ch_axis)
        dr = None if dr is None else _from_view(dr, shape, ch_axis)
    else:
        if gy2 is not None:
            gy = gy + gy2
        fold = plan.fold if plan.variant == "lanefold" else 1
        x = _from_view(x_v, shape, ch_axis, fold)
        y = None if y_v is None else _from_view(y_v, shape, ch_axis, fold)
        ng = plan.ab[0] if ch_axis == 2 else plan.ab[1]
        dx, dg, db, dr = _gbn_bwd_jnp(gy, x, y, gamma, beta, m, v, eps,
                                      act, ng)
    return (dx, dg.astype(gamma.dtype), db.astype(beta.dtype), dr)


def _gbn_bwd(eps, act, group, donate_res, res, ct):
    gy, _, _ = ct  # cotangents for the stat outputs are not propagated
    return _gbn_bwd_impl(eps, act, group, False, res, gy, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gbn_full(x, gamma, beta, residual, eps, act, group, donate_res):
    """Returns (y, group_mean, group_var) — stat outputs get zero vjp."""
    return _gbn_fwd(x, gamma, beta, residual, eps, act, group, donate_res)[0]


_gbn_full.defvjp(_gbn_fwd, _gbn_bwd)


def _gbn_fwd_dual(x, gamma, beta, residual, eps, act, group, donate_res):
    (y, m, v), res = _gbn_fwd(x, gamma, beta, residual, eps, act, group,
                              donate_res, dual=True)
    return ((y, y, m, v), res)


def _gbn_bwd_dual(eps, act, group, donate_res, res, ct):
    gy, gy2, _, _ = ct
    return _gbn_bwd_impl(eps, act, group, True, res, gy, gy2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gbn_full_dual(x, gamma, beta, residual, eps, act, group, donate_res):
    """Dual-output form: returns (y, y, group_mean, group_var) — the SAME
    tensor exposed in two output positions so a residual block exit can
    route its conv path through one and its shortcut through the other.
    Autodiff then delivers the two cotangents separately and the fused
    bwd sums them on the VMEM window load, absorbing the add_any join
    the program would otherwise materialize (docs/PERF.md round 20)."""
    (y, m, v), _ = _gbn_fwd(x, gamma, beta, residual, eps, act, group,
                            donate_res, dual=True)
    return (y, y, m, v)


_gbn_full_dual.defvjp(_gbn_fwd_dual, _gbn_bwd_dual)


def ghost_bn_stats_merge(m, v):
    """(G, C) group stats -> (C,) whole-batch population stats via the law
    of total variance (for running-average updates)."""
    bm = jnp.mean(m, axis=0)
    bv = jnp.mean(v + m * m, axis=0) - bm * bm
    return bm, jnp.maximum(bv, 0.0)


def _gbn_ref(x, gamma, beta, residual, eps, act, group):
    """Pure-jnp ghost BN (same semantics, standard XLA passes) — the
    fallback for layers whose slab cannot fit the VMEM window budget
    (e.g. the 112x112 stem at batch 256)."""
    n, c, h, w = x.shape
    ng = min(n, group or 32)
    while n % ng:
        ng -= 1
    g = n // ng
    x32 = x.astype(jnp.float32).reshape(g, ng, c, h, w)
    m = jnp.mean(x32, axis=(1, 3, 4))
    v = jnp.maximum(jnp.mean(x32 * x32, axis=(1, 3, 4)) - m * m, 0.0)
    rstd = jax.lax.rsqrt(v + eps)
    g32 = gamma.astype(jnp.float32)
    scale = (g32[None] * rstd)[:, None, :, None, None]
    shift = (beta.astype(jnp.float32)[None]
             - m * g32[None] * rstd)[:, None, :, None, None]
    y = (x32 * scale + shift).reshape(n, c, h, w)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype), m, v


def plan_describe(n, c, h, w, itemsize=2, group=0, has_res=False,
                  dual=False):
    """One layer's kernel-plan decision as a plain dict — the inspectable
    face of :func:`_plan` (``tools/graftcost.py``'s per-layer table, the
    ``MXTPU_BN_PLAN`` trace log).  ``variant``/``bwd`` name the per-
    direction kernel form; ``window_mb`` is the padded per-window VMEM
    footprint the feasibility check charged; ``fold``/``l_tile`` are the
    lane-fold factor and spatial tile rows where those forms apply;
    ``dual`` marks a dual-cotangent block exit (one extra bwd window)."""
    plan = _plan(int(n), int(c), int(h) * int(w), int(itemsize),
                 int(group), bool(has_res), bool(dual))
    if plan is None:
        return {"variant": "jnp", "bwd": "jnp", "fold": 1, "l_tile": 0,
                "l_tile_bwd": 0, "window_mb": 0.0, "group": 0,
                "dual": bool(dual)}
    return {"variant": plan.variant,
            "bwd": plan.bwd_variant if plan.bwd_pallas else "jnp",
            "fold": plan.fold,
            "l_tile": plan.l_tile,
            "l_tile_bwd": plan.l_tile_bwd,
            "window_mb": round(plan.window_bytes / 1e6, 1),
            "group": plan.ab[0] if plan.ch_axis == 2 else plan.ab[1],
            "dual": bool(dual)}


_PLAN_LOGGED = set()
_SITE_RECORDERS = []


@contextlib.contextmanager
def record_sites():
    """Collect the distinct BN sites traced inside the ``with`` block, in
    first-seen order, as ``(shape, dtype_name, group, has_res, donate,
    dual)`` tuples — what ``chip_smoke.py`` reads to print each site's
    plan and to check the Pallas ones against the jnp reference."""
    sites = []
    _SITE_RECORDERS.append(sites)
    try:
        yield sites
    finally:
        # by identity: two recorders with equal contents are not the same
        _SITE_RECORDERS[:] = [s for s in _SITE_RECORDERS if s is not sites]


def _log_plan(shape, dtype, group, has_res, donate, dual=False):
    """Once-per-distinct-layer plan trace (MXTPU_BN_PLAN=1): the layer
    selection is automatic, this makes it visible without a debugger.
    Also feeds :func:`record_sites`."""
    key = (tuple(shape), np.dtype(dtype).name, int(group), bool(has_res),
           bool(donate), bool(dual))
    for sites in _SITE_RECORDERS:
        if key not in sites:
            sites.append(key)
    if not os.environ.get("MXTPU_BN_PLAN"):
        return
    if key in _PLAN_LOGGED:
        return
    _PLAN_LOGGED.add(key)
    n, c, h, w = shape
    d = plan_describe(n, c, h, w, np.dtype(dtype).itemsize, group,
                      has_res, dual)
    print("[ghost-bn] %dx%dx%dx%d %s group<=%d res=%d donate=%d dual=%d "
          "-> fwd=%s bwd=%s fold=%d l_tile=%d/%d window=%.1fMB group=%d"
          % (n, c, h, w, np.dtype(dtype).name, int(group), bool(has_res),
             bool(donate), bool(dual), d["variant"], d["bwd"], d["fold"],
             d["l_tile"], d["l_tile_bwd"], d["window_mb"], d["group"]),
          file=sys.stderr, flush=True)


def ghost_bn_act(x, gamma, beta, residual=None, eps=1e-3, act="relu",
                 group=0, donate_residual=False, dual_out=False):
    """Fused ghost-BN(+residual)+activation.

    x: (N, C, H, W).  Returns ``(y, group_mean, group_var)`` with stats of
    shape (G, C).  The ``group`` argument is a CAP on the ghost group:
    the sublane path picks the largest fitting divisor under it, the
    small-C lane path (whose group is the whole lane block) and the jnp
    fallback honor it exactly — deterministic per shape.  ``act`` is
    ``"relu"`` or ``"none"`` (the downsample-BN case).
    ``donate_residual=True`` declares the residual tensor dead after
    this layer (the downsample-shortcut case — NEVER an identity
    shortcut, which the surrounding program still reads): the fwd
    kernel then writes Y over the residual's HBM buffer (its VMEM
    window stays, so the plan is the same).  ``dual_out=True`` (residual
    block exits feeding both the next block's conv path and its
    shortcut) returns ``(y, y, group_mean, group_var)`` — the same
    tensor in two output positions, so autodiff delivers the two
    downstream cotangents separately and the fused bwd sums them on the
    VMEM window load instead of the program materializing an add_any
    join (one extra bwd window; the plan accounts for it).
    Differentiable in x, gamma, beta and residual (stat outputs carry
    zero gradient — they feed running-stat updates, which the reference
    likewise excludes from autograd, ``src/operator/nn/batch_norm.cc``
    aux states).  Layers whose windows can't fit the VMEM budget use an
    equivalent jnp formulation with the same ghost-group statistics.
    """
    n, c, h, w = x.shape
    donate = bool(donate_residual) and residual is not None
    dual = bool(dual_out)
    _log_plan(x.shape, x.dtype, int(group), residual is not None, donate,
              dual)
    if _plan(n, c, h * w, x.dtype.itemsize, int(group),
             residual is not None, dual) is None:
        y, m, v = _gbn_ref(x, gamma, beta, residual, float(eps), act,
                           int(group))
        return (y, y, m, v) if dual else (y, m, v)
    if dual:
        return _gbn_full_dual(x, gamma, beta, residual, float(eps), act,
                              int(group), donate)
    return _gbn_full(x, gamma, beta, residual, float(eps), act, int(group),
                     donate)
