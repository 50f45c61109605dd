#!/usr/bin/env python
"""graftcost CLI — trace-time cost report for a model + mesh + knob set.

Builds the requested model, constructs the fused train step with the
given parallelism knobs, and costs its traced program WITHOUT compiling
or running a step (``analysis/cost_model.py``; catalog and field
reference in docs/ANALYSIS.md): per-category FLOPs / fusion-aware HBM
bytes, peak live-buffer memory (donation-, remat- and ZeRO-sharding-
aware), per-mesh-axis collective volume, and the roofline step-time
estimate for a registry device (``tpu-v5e`` default, ``cpu-proxy`` for
off-chip relative numbers).

Exit status 1 when any error-severity GL2xx diagnostic fires — with
``--hbm-budget`` this is the eager infeasibility gate (GL201) the
autotuner (ROADMAP item 4) uses to reject configs before paying a
compile.

``--diff profile.json`` diffs the prediction against a measured
category breakdown (``{"categories": {<hlo_stats category>:
{"ms_per_step": ...}}}``, reduced from a device trace): a
per-category predicted/measured/drift table (the standalone form of
the autotuner's residual-fit input).  Measured hlo_stats categories
are folded into the prediction's category space (fusion kinds →
elementwise, all-reduce/-gather → collective).  Exit status 2 when the
worst per-category drift exceeds ``--drift-threshold`` (default 0.5 =
50 %).

Usage::

    python tools/graftcost.py --model dense --batch 16
    python tools/graftcost.py --model resnet50 --batch 256 --compute-dtype
        bfloat16 --format json
    python tools/graftcost.py --model dense --mesh dp=8 --zero 1
        --hbm-budget 16GiB
    python tools/graftcost.py --model resnet50 --batch 256 --compute-dtype
        bfloat16 --diff profile.json --drift-threshold 0.3
"""
from __future__ import annotations

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _parse_mesh(spec):
    """'dp=8' / 'dp=2,pp=4' -> ordered dict of axis sizes."""
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
    """'16GiB' / '8GB' / '1048576' -> bytes."""
    if s is None:
        return None
    s = str(s).strip()
    units = {"kib": 2**10, "mib": 2**20, "gib": 2**30, "tib": 2**40,
             "kb": 10**3, "mb": 10**6, "gb": 10**9, "tb": 10**12,
             "b": 1}
    low = s.lower()
    for u in sorted(units, key=len, reverse=True):
        if low.endswith(u):
            return float(low[: -len(u)]) * units[u]
    return float(s)


def _build_model(name, feat=16, layers=4):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.gluon import nn

    mx.random.seed(0)
    if name == "dense":
        # the tests/test_zero_sharding.py net: 4 x Dense(16)
        net = nn.HybridSequential()
        for _ in range(layers):
            net.add(nn.Dense(feat, activation="tanh"))
        net.initialize(init=mx.init.Xavier())
        net(nd.ones((2, feat)))
        return net, (feat,), "dense"
    if name == "conv-bn":
        net = nn.HybridSequential()
        net.add(nn.Conv2D(16, 3, padding=1, in_channels=3))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.Conv2D(16, 3, padding=1, in_channels=16))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.initialize(init=mx.init.Xavier())
        net(nd.ones((2, 3, 16, 16)))
        return net, (3, 16, 16), "conv"
    if name == "resnet50":
        from incubator_mxnet_tpu.gluon.model_zoo import vision

        net = vision.resnet50_v1(classes=1000)
        net.initialize(init=mx.init.Zero())
        net.shape_init((1, 3, 224, 224))
        return net, (3, 224, 224), "conv"
    raise SystemExit("unknown --model %r (dense, conv-bn, resnet50)" % name)


#: measured hlo_stats category (the ``--diff`` file's) -> predicted
#: CostReport category.  XLA reports fused elementwise/reduction work
#: as "fusion" kinds, so those fold into elementwise — reduction time
#: inside a convert_reduce_fusion is indistinguishable in the measured
#: breakdown.  Unmatched categories fold into "other" (copies, infeed).
def _map_measured_category(name: str) -> str:
    n = str(name).lower()
    if "conv" in n:
        return "conv"
    if any(k in n for k in ("all-reduce", "allreduce", "all-gather",
                            "allgather", "reduce-scatter", "collective",
                            "all-to-all", "permute")):
        return "collective"
    if "scatter" in n or "gather" in n:
        return "scatter_gather"
    if any(k in n for k in ("fusion", "elementwise", "loop", "convert",
                            "reduce")):
        return "elementwise"
    return "other"


def _pred_category_ms(report, n_dev):
    """Per-category lower-bound milliseconds from a CostReport: each
    category's max of its compute and HBM roofline (comm handled by the
    collective row's wire bytes)."""
    sp = report.spec()
    out = {}
    for k, c in report.categories.items():
        hbm_s = c.hbm_bytes / (sp.hbm_bytes_per_s * n_dev)
        fl_s = c.flops / (sp.flops_per_s * n_dev)
        out[k] = 1e3 * max(hbm_s, fl_s)
    comm_s = max((c.wire_bytes / sp.ici_bytes_per_s
                  for c in report.comm.values()), default=0.0)
    if comm_s:
        out["collective"] = out.get("collective", 0.0) + 1e3 * comm_s
    return out


def _diff_profile(report, profile_path, threshold, fmt):
    """The --diff leg: per-category predicted vs measured ms table.
    Returns (max_abs_drift, rows) and prints; drift = (measured -
    predicted) / measured.  The measured side folds into the predicted
    category space first (elementwise absorbs reduction in BOTH: the
    fusion kinds are not separable in hlo_stats)."""
    import json as _json

    with open(profile_path) as f:
        prof = _json.load(f)
    measured = {}
    for name, row in prof.get("categories", {}).items():
        cat = _map_measured_category(name)
        measured[cat] = measured.get(cat, 0.0) + float(row["ms_per_step"])
    n_dev = max(report.n_devices, 1)
    pred = _pred_category_ms(report, n_dev)
    # reduction folds into elementwise on the predicted side too
    # (measured fusions lump them)
    pred["elementwise"] = pred.get("elementwise", 0.0) \
        + pred.pop("reduction", 0.0)
    cats = sorted(set(pred) | set(measured))
    rows = []
    worst = 0.0
    for cat in cats:
        p = pred.get(cat, 0.0)
        m = measured.get(cat, 0.0)
        if p < 0.01 and m < 0.01:  # both under 10 us: noise, not drift
            drift = 0.0
        elif m > 0:
            drift = (m - p) / m
        else:
            drift = -1.0  # predicted cost the profile never saw
        # "other" (copies, infeed) has no predicted counterpart by
        # design — report it but keep it out of the gate
        if cat != "other":
            worst = max(worst, abs(drift))
        rows.append({"category": cat, "predicted_ms": round(p, 3),
                     "measured_ms": round(m, 3),
                     "drift": round(drift, 4)})
    total_p, total_m = sum(pred.values()), sum(measured.values())
    total_drift = (total_m - total_p) / total_m if total_m > 0 else 0.0
    payload = {"version": 1, "profile": profile_path,
               "threshold": threshold, "rows": rows,
               "total": {"predicted_ms": round(total_p, 3),
                         "measured_ms": round(total_m, 3),
                         "drift": round(total_drift, 4)},
               "max_abs_drift": round(worst, 4),
               "over_threshold": worst > threshold}
    if fmt == "json":
        print(_json.dumps(payload, indent=2))
    else:
        print("%-16s %12s %12s %9s" % ("category", "pred ms", "meas ms",
                                       "drift"))
        for r in rows:
            print("%-16s %12.3f %12.3f %8.1f%%"
                  % (r["category"], r["predicted_ms"], r["measured_ms"],
                     100 * r["drift"]))
        print("%-16s %12.3f %12.3f %8.1f%%" % ("TOTAL", total_p, total_m,
                                               100 * total_drift))
        if worst > threshold:
            print("graftcost --diff: max per-category drift %.1f%% "
                  "exceeds threshold %.1f%%"
                  % (100 * worst, 100 * threshold), file=sys.stderr)
    return worst, payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftcost", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--model", default="dense",
                    choices=["dense", "conv-bn", "resnet50"])
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--mesh", default="",
                    help="mesh axes, e.g. dp=8 or dp=2,pp=4 (devices are "
                         "CPU-forged off-chip)")
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "adam"])
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--zero", type=int, default=0, choices=[0, 1])
    ap.add_argument("--multi-precision", action="store_true")
    ap.add_argument("--pipeline-stages", type=int, default=None)
    ap.add_argument("--num-micro", type=int, default=1)
    ap.add_argument("--pipeline-remat", action="store_true")
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--compute-dtype", default=None,
                    help="e.g. bfloat16 (default: f32)")
    ap.add_argument("--passes", default=None,
                    help="comma-separated graftpass names applied to the "
                         "step before costing (the autotune post-pass "
                         "analyze_cost path), e.g. "
                         "space_to_depth,cse_dead_aux")
    ap.add_argument("--device", default="tpu-v5e",
                    help="roofline device-spec registry key")
    ap.add_argument("--hbm-budget", default=None,
                    help="peak-memory budget (bytes; 16GiB / 8GB forms "
                         "accepted) — GL201 errors over it, exit 1")
    ap.add_argument("--format", dest="fmt", default="table",
                    choices=["table", "json"])
    ap.add_argument("--diff", default=None, metavar="PROFILE_JSON",
                    help="diff the prediction against a measured "
                         "category breakdown ({\"categories\": {name: "
                         "{\"ms_per_step\": ...}}}); exit 2 when the "
                         "worst per-category drift exceeds "
                         "--drift-threshold")
    ap.add_argument("--drift-threshold", type=float, default=0.5,
                    help="--diff gate: max acceptable |measured - "
                         "predicted| / measured per category "
                         "(default 0.5)")
    args = ap.parse_args(argv)

    mesh_axes = _parse_mesh(args.mesh)
    ndev = 1
    for v in mesh_axes.values():
        ndev *= v
    if mesh_axes and "XLA_FLAGS" not in os.environ:
        # forge enough host devices for the mesh BEFORE jax initializes
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=%d" % max(ndev, 2)

    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.analysis import DEVICE_SPECS, Severity
    from incubator_mxnet_tpu.parallel import make_train_step
    from incubator_mxnet_tpu import gluon

    if args.device not in DEVICE_SPECS:
        raise SystemExit("unknown --device %r (registry: %s)"
                         % (args.device, sorted(DEVICE_SPECS)))
    net, in_shape, kind = _build_model(args.model)
    budget = _parse_bytes(args.hbm_budget)

    mesh = None
    if mesh_axes:
        from incubator_mxnet_tpu.parallel import make_mesh

        mesh = make_mesh(mesh_axes, devices=jax.devices()[:ndev])
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss() if kind == "dense" \
        or args.model == "resnet50" else gluon.loss.L2Loss()
    kw = dict(optimizer=args.optimizer, learning_rate=0.1)
    if args.optimizer == "sgd":
        kw["momentum"] = args.momentum
    if args.multi_precision:
        kw["multi_precision"] = True
    step = make_train_step(
        net, loss_fn, mesh=mesh, zero=args.zero,
        pipeline_stages=args.pipeline_stages, num_micro=args.num_micro,
        pipeline_remat=args.pipeline_remat, donate=not args.no_donate,
        compute_dtype=args.compute_dtype, lint="off", cost="off",
        hbm_budget=budget, cost_device=args.device,
        # resolve_passes accepts the raw comma string; () = explicitly
        # none (an absent flag must not absorb MXTPU_PASSES here — the
        # CLI's output should reflect its own arguments only)
        passes=args.passes if args.passes else (), **kw)

    x = jax.ShapeDtypeStruct((args.batch,) + in_shape, jnp.float32)
    if args.model == "conv-bn":
        y = jax.ShapeDtypeStruct((args.batch, 16, 16, 16), jnp.float32)
    else:
        y = jax.ShapeDtypeStruct((args.batch,), jnp.float32)
    report = step.analyze_cost(x, y, device=args.device, hbm_budget=budget)

    if args.diff:
        worst, _ = _diff_profile(report, args.diff, args.drift_threshold,
                                 args.fmt)
        return 2 if worst > args.drift_threshold else 0

    if args.fmt == "json":
        print(report.to_json(indent=2))
    else:
        print(report.format())
    errors = [d for d in report.diagnostics
              if d.severity >= Severity.ERROR]
    if errors and args.fmt != "json":
        print("graftcost: %d error(s) — infeasible config" % len(errors),
              file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
