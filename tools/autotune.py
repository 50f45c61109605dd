#!/usr/bin/env python
"""autotune CLI — search-based tuner on graftcost + the compile cache.

Closes the graftcost loop (``analysis/autotune.py``, docs/PERF.md
§Autotuning): enumerates the knob space for a target workload, ranks
every candidate by the trace-time CostReport roofline, eagerly rejects
GL201-infeasible configs with ZERO compiles spent, measures only the
top-K on the real backend (each compile routed through the persistent
compile cache, ``MXTPU_COMPILE_CACHE``), fits a learned residual on
predicted-vs-measured drift and re-ranks the remainder.  Emits a JSON
tuning log accounting for 100 % of candidates and a winner config
consumable by ``bench.py`` / ``Trainer.make_fused_step``.

When no TPU is reachable the measurements are *relative* CPU-mesh
numbers: the log is stamped ``backend`` / ``tpu_unavailable`` /
``relative_only`` — never silent zeros (the BENCH r04/r05 failure
mode).

Exit status: 0 — winner found; 1 — every candidate infeasible/invalid
(nothing measurable); 2 — usage errors.

Usage::

    python tools/autotune.py --target train --model dense --mesh dp=8 \
        --budget-compiles 5 --format json --out tuning.json \
        --winner-out winner.json
    python tools/autotune.py --target serve --budget-compiles 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _range_arg(s):
    """argparse type for --input-range (shared grammar:
    analysis.value_range.parse_range_arg)."""
    from incubator_mxnet_tpu.analysis.value_range import parse_range_arg

    try:
        return parse_range_arg(s)
    except ValueError as e:
        raise argparse.ArgumentTypeError("--input-range %s" % e)


def _parse_mesh(spec):
    axes = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition("=")
        if not size:
            raise SystemExit("--mesh entries are axis=size, got %r" % part)
        axes[name.strip()] = int(size)
    return axes


def _parse_bytes(s):
    if s is None:
        return None
    units = {"kib": 2**10, "mib": 2**20, "gib": 2**30, "tib": 2**40,
             "kb": 10**3, "mb": 10**6, "gb": 10**9, "tb": 10**12, "b": 1}
    low = str(s).strip().lower()
    for u in sorted(units, key=len, reverse=True):
        if low.endswith(u):
            return float(low[: -len(u)]) * units[u]
    return float(s)


def _conv_bn_workload():
    """The graftcost-CLI conv-bn net as an autotune workload."""
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.gluon import nn

    def make_net(knobs):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Conv2D(16, 3, padding=1, in_channels=3))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.Conv2D(16, 3, padding=1, in_channels=16))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.initialize(init=mx.init.Xavier())
        net(nd.ones((2, 3, 16, 16)))
        return net

    def make_batch(knobs):
        rng = np.random.RandomState(0)
        b = int(knobs.get("batch", 16))
        x = nd.array(rng.rand(b, 3, 16, 16).astype(np.float32))
        y = nd.array(rng.rand(b, 16, 16, 16).astype(np.float32))
        return x, y

    return make_net, make_batch, gluon.loss.L2Loss()


def _resnet50_workload(image_size=224, classes=1000):
    """The headline bench workload (heavy — measured legs want a TPU)."""
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    def make_net(knobs):
        mx.random.seed(0)
        net = vision.resnet50_v1(classes=classes)
        net.initialize(init=mx.init.Xavier())
        net.shape_init((1, 3, image_size, image_size))
        return net

    def make_batch(knobs):
        rng = np.random.RandomState(0)
        b = int(knobs.get("batch", 32))
        x = nd.array(rng.rand(b, 3, image_size, image_size)
                     .astype(np.float32))
        y = nd.array(rng.randint(0, classes, b).astype(np.float32))
        return x, y

    return make_net, make_batch, gluon.loss.SoftmaxCrossEntropyLoss()


def _format_table(res):
    lines = ["autotune[%s] backend=%s%s — %d candidates, %d measured "
             "(%d compiles), %.1fs"
             % (res.target, res.backend,
                " (TPU UNAVAILABLE: relative numbers)"
                if res.tpu_unavailable else "",
                len(res.candidates),
                sum(1 for c in res.candidates if c.status == "measured"),
                res.compiles_spent, res.wall_s),
             "%-10s %14s %14s %14s  %s"
             % ("status", "pred s/sample", "corr s/sample",
                "meas s/sample", "knobs")]
    for c in sorted(res.candidates,
                    key=lambda c: (c.measured_sps
                                   if c.measured_sps is not None
                                   else float("inf"),
                                   c.pred_sps if c.pred_sps is not None
                                   else float("inf"))):
        def fmt(v):
            return "%.3e" % v if v is not None else "-"

        def show(k, v):
            if k in ("batch", "zero"):
                return True
            if k == "num_micro":
                return v > 1
            if k == "passes":
                return bool(v)
            if k == "schedule":
                return False  # the hash stands in for the full dict
            return v not in (None, False)

        knobs = " ".join("%s=%s" % (k, v)
                         for k, v in sorted(c.knobs.items()) if show(k, v))
        lines.append("%-10s %14s %14s %14s  %s"
                     % (c.status.replace("rejected-", "rej-"),
                        fmt(c.pred_sps), fmt(c.corrected_sps),
                        fmt(c.measured_sps), knobs))
        if c.reason:
            lines.append("           reason: %s" % c.reason[:120])
    if res.residual:
        lines.append("residual: spearman %.3f -> %.3f over %d pairs"
                     % (res.residual.get("spearman_predicted", 0.0),
                        res.residual.get("spearman_corrected", 0.0),
                        res.residual.get("n_pairs", 0)))
    if res.winner is not None:
        lines.append("winner: %s" % json.dumps(res.winner.knobs))
    else:
        best = res.best_predicted()
        if best is not None and res.budget_compiles == 0:
            lines.append("winner (predicted, budget 0): schedule_hash=%s"
                         % best.knobs.get("schedule_hash", "-"))
        else:
            lines.append("winner: NONE (no candidate was measurable)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="autotune", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--target", default="train",
                    choices=["train", "train-schedule", "serve"],
                    help="train-schedule: the graftsched per-site "
                         "search — ranks PassSchedule candidates over "
                         "--passes from ONE abstract site table "
                         "(analysis/autotune.py::"
                         "autotune_train_schedules)")
    ap.add_argument("--model", default="dense",
                    choices=["dense", "conv-bn", "resnet50"],
                    help="train-target workload; the serve target "
                         "always tunes its fixed MLP (ignores --model)")
    ap.add_argument("--mesh", default="",
                    help="mesh axes, e.g. dp=8 or dp=2,pp=4 (CPU devices "
                         "are forged off-chip)")
    ap.add_argument("--batches", default="8,16,32",
                    help="train-target batch sizes to search")
    ap.add_argument("--passes", default="",
                    help="comma-separated graftpass names (tools/"
                         "graftpass.py --list): each becomes an on/off "
                         "knob in the train search space, ranked by the "
                         "post-pass CostReport; GL201/GL301-rejected "
                         "candidates cost zero compiles.  NOTE: under "
                         "graftsched the on/off crossing is sugar for "
                         "the all-sites/no-sites schedule pair of each "
                         "pass (kept so existing tuning logs stay "
                         "comparable); per-site search is "
                         "--target train-schedule, which deprecates "
                         "this whole-program mode")
    ap.add_argument("--numerics", default="off",
                    choices=["off", "warn", "error"],
                    help="graftrange value-range gate per candidate "
                         "(analysis/value_range.py): 'error' rejects "
                         "GL4xx-infeasible configs (amp_bf16 on an "
                         "out-of-bf16-range edge, provably-overflowing "
                         "loss_scale) with zero compiles, like GL201")
    ap.add_argument("--input-range", default=None, type=_range_arg,
                    help="declared batch value range 'lo,hi' (e.g. "
                         "'0,1' for normalized images) seeding the "
                         "graftrange analysis")
    ap.add_argument("--budget-compiles", type=int, default=5,
                    help="how many candidates reach the real backend "
                         "(each costs at most one XLA compile; a warm "
                         "MXTPU_COMPILE_CACHE makes re-measures "
                         "trace-only)")
    ap.add_argument("--hbm-budget", default=None,
                    help="peak-memory budget (16GiB / 8GB / bytes) — the "
                         "GL201 eager-rejection gate")
    ap.add_argument("--device", default="cpu-proxy",
                    help="roofline device-spec registry key")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--qps", type=float, default=300.0,
                    help="serve-target offered Poisson rate")
    ap.add_argument("--requests", type=int, default=60,
                    help="serve-target requests per measured policy")
    ap.add_argument("--format", dest="fmt", default="table",
                    choices=["table", "json"])
    ap.add_argument("--out", default=None,
                    help="write the full JSON tuning log here (atomic)")
    ap.add_argument("--winner-out", default=None,
                    help="write the winner config JSON here (the shape "
                         "bench.py / Trainer.make_fused_step consume)")
    args = ap.parse_args(argv)

    mesh_axes = _parse_mesh(args.mesh)
    ndev = 1
    for v in mesh_axes.values():
        ndev *= v
    if mesh_axes and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=%d" % max(ndev, 2)

    import jax

    from incubator_mxnet_tpu.analysis import DEVICE_SPECS
    from incubator_mxnet_tpu.analysis.autotune import (
        autotune_serve, autotune_train, autotune_train_schedules,
        default_train_space, dense_workload)

    if args.device not in DEVICE_SPECS:
        raise SystemExit("unknown --device %r (registry: %s)"
                         % (args.device, sorted(DEVICE_SPECS)))
    budget = _parse_bytes(args.hbm_budget)
    mesh = None
    if mesh_axes:
        from incubator_mxnet_tpu.parallel import make_mesh

        mesh = make_mesh(mesh_axes, devices=jax.devices()[:ndev])

    if args.target in ("train", "train-schedule"):
        if args.model == "dense":
            make_net, make_batch, loss_fn = dense_workload()
        elif args.model == "conv-bn":
            make_net, make_batch, loss_fn = _conv_bn_workload()
        else:
            make_net, make_batch, loss_fn = _resnet50_workload()
        pass_names = tuple(s.strip() for s in args.passes.split(",")
                           if s.strip())
        if pass_names:
            from incubator_mxnet_tpu.analysis.passes import get_pass

            for n in pass_names:
                get_pass(n)  # fail fast on unknown names
        batches = tuple(int(b) for b in args.batches.split(",") if b)
        if args.target == "train-schedule":
            if not pass_names:
                raise SystemExit("--target train-schedule needs "
                                 "--passes to build the site table")
            res = autotune_train_schedules(
                make_net, make_batch, loss_fn, passes=pass_names,
                knobs={"batch": batches[0]}, mesh=mesh,
                device=args.device, hbm_budget=budget,
                budget_compiles=args.budget_compiles,
                warmup=args.warmup, iters=args.iters,
                numerics=args.numerics, input_range=args.input_range,
                log_path=args.out)
        else:
            space = default_train_space(mesh_axes, batches=batches,
                                        passes=pass_names)
            res = autotune_train(make_net, make_batch, loss_fn,
                                 space=space,
                                 mesh=mesh, device=args.device,
                                 hbm_budget=budget,
                                 budget_compiles=args.budget_compiles,
                                 warmup=args.warmup, iters=args.iters,
                                 numerics=args.numerics,
                                 input_range=args.input_range,
                                 log_path=args.out)
    else:
        import incubator_mxnet_tpu as mx
        from incubator_mxnet_tpu import nd
        from incubator_mxnet_tpu.gluon import nn

        mx.random.seed(8)
        net = nn.HybridSequential()
        net.add(nn.Dense(64, activation="relu"), nn.Dense(16))
        net.initialize(init=mx.init.Xavier())
        net(nd.ones((2, 32)))
        res = autotune_serve(net, (32,), mesh=mesh, device=args.device,
                             hbm_budget=budget,
                             budget_compiles=args.budget_compiles,
                             qps=args.qps, n_requests=args.requests,
                             log_path=args.out)

    if args.fmt == "json":
        print(res.to_json(indent=2))
    else:
        print(_format_table(res))

    # schedule searches at --budget-compiles 0 are pure zero-compile
    # ranking: the best PREDICTED schedule is the (hash-stamped) winner
    winner_cfg = res.winner_config()
    if args.winner_out and winner_cfg is not None:
        tmp = args.winner_out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(winner_cfg, f, indent=2)
        os.replace(tmp, args.winner_out)
        print("winner config -> %s" % args.winner_out, file=sys.stderr)

    if not res.accounted():
        print("autotune: tuning log does not account for every candidate",
              file=sys.stderr)
    if res.winner is not None:
        return 0
    if args.target == "train-schedule" and args.budget_compiles == 0:
        return 0 if winner_cfg is not None else 1
    return 1


if __name__ == "__main__":
    sys.exit(main())
