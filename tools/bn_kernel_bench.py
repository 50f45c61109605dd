#!/usr/bin/env python
"""Per-kernel DMA-efficiency benchmark for the fused ghost-BN kernels.

For every ResNet-50 BN shape (batch 256) this measures, on the chip:

* ``copy``   — a Pallas copy kernel using the SAME view, BlockSpec
  blocks and grid as the selected fwd kernel (whole-L, lane-fold or
  spatial-tiled): the pure-DMA ceiling for that plan.  If ``copy``
  sustains ~roofline but ``fwd`` doesn't, compute (VPU) binds; if
  ``copy`` itself is slow, the window DMA pattern binds (strided runs
  / padding) — the measurement the round-4 review asked for ("prove
  which Mosaic limit binds").
* ``fwd``    — the planned forward variant, one read of X per pass
  (the tiled form pays its extra stats pass and says so in the bytes).
* ``bwd``    — the planned backward variant (one-read whole-L /
  lane-fold, or the two-phase tiled gY-read-once protocol).
* ``stock_xla`` — the plain-jnp ghost BN (XLA's own fusions) on the
  same shape, fwd and fwd+bwd: the reference column every variant row
  is judged against.

One row per (shape, residual[, dual]) with the plan columns
(variant / bwd / fold / l_tile / window MB) so a chip log directly
shows WHICH kernel form produced each number.  ``--format json``
prints machine-readable JSON lines (the chip-queue artifact);
``--out`` appends the same rows to a file.

Reference bar: docs/PERF.md roofline (819 GB/s HBM peak on v5e);
the round-4 kernels sustained ~55 % — the round-5 full-C blocks must
show >= 85 % on ``copy`` for the fused path to be viable.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax
import jax.numpy as jnp
import numpy as np

from incubator_mxnet_tpu.parallel import fused_bn as fb

HBM_PEAK_GBS = 819.0
GROUP = 16

SHAPES = [
    # (N, C, H, W) — every distinct BN shape in ResNet-50 v1 at batch 256
    (256, 64, 112, 112),
    (256, 64, 56, 56),
    (256, 256, 56, 56),
    (256, 128, 28, 28),
    (256, 512, 28, 28),
    (256, 256, 14, 14),
    (256, 1024, 14, 14),
    (256, 512, 7, 7),
    (256, 2048, 7, 7),
]

# interpret-mode shapes sized so the 104 MB-budget selection logic is
# reproduced at a small budget: one lane-fold row (C=32 < 128 at
# N=256), one spatial-tiled row, one whole-L fused row
DRY_BUDGET = 200000
DRY_SHAPES = [
    (256, 32, 4, 4),    # lane-fold (fold 4)
    (32, 128, 6, 6),    # spatial-tiled fwd+bwd
    (32, 128, 2, 2),    # whole-L fused
]


def _time(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    jax.tree_util.tree_map(
        lambda a: a.block_until_ready() if hasattr(a, "block_until_ready")
        else a, out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.tree_util.tree_map(
        lambda a: a.block_until_ready() if hasattr(a, "block_until_ready")
        else a, out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def _copy_kernel(x_ref, y_ref, *, lc):
    l = x_ref.shape[0]

    def body(i, _):
        sl = fb.pl.ds(i * jnp.int32(lc), lc)
        y_ref[sl] = x_ref[sl]
        return jnp.int32(0)
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(l // lc), body, jnp.int32(0))


def _call_copy(x_v, plan):
    """Pure-DMA ceiling with the selected variant's exact blocks/grid."""
    l = x_v.shape[0]
    if plan.ch_axis == 2:
        n, c = x_v.shape[1], x_v.shape[2] // plan.fold
    else:
        n, c = x_v.shape[2], x_v.shape[1]
    if plan.variant == "tiled":
        ng = plan.ab[0]
        xspec, _, _ = fb._tile_specs(plan.l_tile, ng, c)
        grid = (n // ng, l // plan.l_tile)
        lc = fb._chunk(plan.l_tile, ng, c)
    else:
        _, cw, ab = fb._lane_width(x_v, plan.ab, plan.ch_axis, plan.fold)
        xspec, _, _, ngroups, _, _ = fb._specs(l, n, cw, ab, plan.ch_axis)
        grid = (ngroups, cw // (ab[1] if plan.ch_axis == 2 else ab[0]))
        lc = fb._chunk(l, ab[0], ab[1])
    kern = functools.partial(_copy_kernel, lc=lc)
    return fb.pl.pallas_call(
        kern, grid=grid, in_specs=[xspec], out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct(x_v.shape, x_v.dtype),
        compiler_params=fb.pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=fb._VMEM_KERNEL_LIMIT),
        interpret=fb._backend.pallas_interpret())(x_v)


def bench_shape(n, c, h, w, dtype, residual, dual, emit, iters, warmup):
    itemsize = jnp.dtype(dtype).itemsize
    tensor_gb = n * c * h * w * itemsize / 1e9
    plan = fb._plan(n, c, h * w, itemsize, GROUP, residual, dual)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(size=(n, c, h, w)).astype(np.float32),
                    dtype=dtype)
    gamma = jnp.ones((c,), jnp.float32)
    beta = jnp.zeros((c,), jnp.float32)
    res = x * 0.5 if residual else None

    row = {"shape": "%dx%dx%dx%d" % (n, c, h, w), "dtype": str(dtype),
           "residual": bool(residual), "dual": bool(dual),
           "variant": "jnp-fallback" if plan is None else plan.variant,
           "bwd_variant": "jnp" if plan is None else plan.bwd_variant,
           "fold": 0 if plan is None else plan.fold,
           "l_tile": 0 if plan is None else (plan.l_tile or 0),
           "l_tile_bwd": 0 if plan is None else (plan.l_tile_bwd or 0),
           "window_mb": 0.0 if plan is None
           else round(plan.window_bytes / 1e6, 2)}

    def gbs(key, ms, nbytes_gb):
        row[key + "_ms"] = round(ms, 3)
        row[key + "_gbs"] = round(nbytes_gb / (ms / 1e3), 1)
        row[key + "_pct_peak"] = round(
            100 * (nbytes_gb / (ms / 1e3)) / HBM_PEAK_GBS, 1)

    # stock-XLA reference columns (always measured)
    ref = jax.jit(functools.partial(fb._gbn_ref, eps=1e-3, act="relu",
                                    group=GROUP))
    ms = _time(ref, x, gamma, beta, res, iters=iters, warmup=warmup)
    gbs("stock_xla", ms, tensor_gb * (3 if residual else 2) + tensor_gb)

    def loss(xx, rr):
        y, _, _ = fb._gbn_ref(xx, gamma, beta, rr, 1e-3, "relu", GROUP)
        return (y.astype(jnp.float32) ** 2).sum()
    gref = jax.jit(jax.grad(loss, argnums=(0, 1) if residual else (0,)))
    ms = (_time(gref, x, res, iters=iters, warmup=warmup) if residual
          else _time(lambda a: gref(a, None), x, iters=iters,
                     warmup=warmup))
    gbs("stock_xla_fwd_bwd", ms, tensor_gb * (8 if residual else 6))

    if plan is None:
        emit(row)
        return

    x_v = fb._to_view(x, plan.ch_axis, plan.fold)
    res_v = None if res is None else fb._to_view(res, plan.ch_axis,
                                                 plan.fold)

    # pure-copy ceiling with the identical view/blocks/grid
    cp = jax.jit(functools.partial(_call_copy, plan=plan))
    ms = _time(cp, x_v, iters=iters, warmup=warmup)
    gbs("copy", ms, 2 * tensor_gb)

    # planned forward variant.  Tiled pays one extra read of X for the
    # cross-tile stats pass — charged in its bytes, exactly as
    # analysis/cost_model.py prices the two pallas_calls.
    if plan.variant == "tiled":
        fwd = jax.jit(functools.partial(
            fb._call_fwd_tiled, eps=1e-3, act="relu", ab=plan.ab,
            lt=plan.l_tile))
        fwd_gb = tensor_gb * (4 if residual else 3)
    else:
        fwd = jax.jit(functools.partial(
            fb._call_fwd, eps=1e-3, act="relu", ab=plan.ab,
            ch_axis=plan.ch_axis, fold=plan.fold))
        fwd_gb = tensor_gb * (3 if residual else 2)
    ms = _time(lambda a, r: fwd(a, gamma, beta, r), x_v, res_v,
               iters=iters, warmup=warmup)
    gbs("fwd", ms, fwd_gb)

    if plan.bwd_variant == "jnp":
        emit(row)
        return
    y_v, m, v = fwd(x_v, gamma, beta, res_v)
    gy_v = x_v * 0.1
    gy2_v = x_v * 0.3 if dual else None
    if plan.bwd_variant == "tiled":
        bwd = jax.jit(functools.partial(
            fb._call_bwd_tiled, eps=1e-3, act="relu", ab=plan.ab,
            lt=plan.l_tile_bwd))
        bwd_gb = tensor_gb * ((8 if dual else 7) if residual else 5)
    else:
        bwd = jax.jit(functools.partial(
            fb._call_bwd, eps=1e-3, act="relu", ab=plan.ab,
            ch_axis=plan.ch_axis))
        bwd_gb = tensor_gb * ((6 if dual else 5) if residual else 3)
    ms = _time(lambda: bwd(gy_v, x_v, y_v if residual else None,
                           gamma, beta, m, v, gy2=gy2_v),
               iters=iters, warmup=warmup)
    gbs("bwd", ms, bwd_gb)
    emit(row)


COLS = ("shape", "residual", "dual", "variant", "bwd_variant", "fold",
        "l_tile", "window_mb", "copy_ms", "fwd_ms", "bwd_ms",
        "stock_xla_ms", "stock_xla_fwd_bwd_ms")


def _table_line(row):
    return " ".join("%*s" % (max(len(k), 8), row.get(k, "-"))
                    for k in COLS)


def main():
    global SHAPES
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--out", default=None, help="also append JSON rows here")
    ap.add_argument("--residual", action="store_true",
                    help="bench the residual variants too")
    ap.add_argument("--variants", action="store_true",
                    help="round-20 kernel-variant sweep: adds the "
                         "dual-cotangent residual rows (the tuple-"
                         "threaded block exits), so every kernel form — "
                         "whole-L, lane-fold, spatial-tiled, dual — "
                         "lands in the artifact")
    ap.add_argument("--format", dest="fmt", default="table",
                    choices=["table", "json"],
                    help="json prints one JSON object per row (the "
                         "chip-queue artifact format)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny shapes + a scaled-down VMEM budget in "
                         "interpret mode on CPU: exercises the lane-fold "
                         "/ tiled / fused selection and every kernel "
                         "call end-to-end (timings meaningless) — what "
                         "CHIP_QUEUE_DRY_RUN runs in tier-1")
    ap.add_argument("--self-test", action="store_true",
                    help="alias of --dry-run (kept for older queue logs)")
    args = ap.parse_args()
    iters, warmup = args.iters, 3
    if args.dry_run or args.self_test:
        SHAPES = DRY_SHAPES
        fb._WINDOW_BUDGET = DRY_BUDGET
        # a dry run never takes the chip: pin the cpu backend, where
        # every kernel goes through the interpreter
        jax.config.update("jax_platforms", "cpu")
        iters, warmup = 1, 1
    sink = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        if args.fmt == "json":
            print(line, flush=True)
        else:
            print(_table_line(row), flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    backend = jax.default_backend()
    note = ("interpret mode (numbers are NOT kernel perf)"
            if backend != "tpu" else "on-chip")
    print("# backend=%s %s" % (backend, note), file=sys.stderr)
    if args.fmt == "table":
        print(" ".join("%*s" % (max(len(k), 8), k) for k in COLS),
              flush=True)
    dtype = jnp.dtype(args.dtype)
    want_res = args.residual or args.variants or args.dry_run
    for (n, c, h, w) in SHAPES:
        legs = [(False, False)]
        if want_res and c >= 128:
            legs.append((True, False))
            if args.variants or args.dry_run:
                legs.append((True, True))
        for residual, dual in legs:
            try:
                bench_shape(n, c, h, w, dtype, residual, dual, emit,
                            iters, warmup)
            except Exception as e:  # keep the sweep going; record why
                emit({"shape": "%dx%dx%dx%d" % (n, c, h, w),
                      "variant": "error", "stock_xla_ms": -1.0,
                      "residual": residual, "dual": dual,
                      "error": repr(e)[:300]})
    if sink:
        sink.close()


if __name__ == "__main__":
    main()
