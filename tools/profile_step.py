#!/usr/bin/env python
"""Profile the fused ResNet-50 train step on the TPU and print a per-op
time breakdown (the `jax.profiler` trace -> xplane -> hlo_stats path).

Answers "where do the 115 ms go?" for the north-star push: groups HLO ops
by category (conv, fusion kinds, all-reduce, copy, ...) and prints the
top individual ops.  Writes the raw trace under .profile/ (git-ignored)
and the summary to stdout; `--doc` rewrites docs/PERF.md.

Reference analog: MXNet's profiler dump of per-op GPU lanes
(src/profiler/profiler.cc); here XLA gives one fused program so the
interesting unit is the HLO fusion, not the framework op.
"""
import argparse
import glob
import gzip
import json
import os
import sys
import time
from collections import defaultdict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_step(batch, image_size=224, compute_dtype="bfloat16"):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.parallel import make_train_step

    mx.random.seed(0)
    net = vision.resnet50_v1(classes=1000)
    net.initialize(init=mx.init.Xavier())
    net.shape_init((1, 3, image_size, image_size))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = make_train_step(net, loss_fn, optimizer="sgd", learning_rate=0.1,
                           momentum=0.9, wd=1e-4, compute_dtype=compute_dtype)
    x = nd.random.uniform(shape=(batch, 3, image_size, image_size))
    import numpy as np
    y = nd.array(np.random.randint(0, 1000, batch).astype(np.float32))
    return step, x, y


def capture(step, x, y, logdir, iters=5):
    import jax

    t = step.aot_compile(x, y)
    print("trace %.1fs compile %.1fs" % (t["trace"], t["compile"]),
          file=sys.stderr)
    loss = step(x, y)
    loss.wait_to_read()
    with jax.profiler.trace(logdir):
        for _ in range(iters):
            loss = step(x, y)
        loss.wait_to_read()


def find_xplane(logdir):
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise SystemExit("no xplane.pb under %s" % logdir)
    return max(paths, key=os.path.getmtime)


def hlo_stats(xplane_path):
    """Parse the xplane with tensorboard_plugin_profile into per-HLO rows."""
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [xplane_path], "hlo_stats", {})
    if isinstance(data, bytes):
        data = data.decode()
    obj = json.loads(data)
    return obj


def categorize(name, category):
    n = name.lower()
    c = (category or "").lower()
    if "convolution" in c or n.startswith("%convolution") or "conv" in c:
        return "convolution"
    if "all-reduce" in n or "allreduce" in c:
        return "all-reduce"
    if c:
        return c
    return "other"


def summarize(obj, total_steps):
    # hlo_stats JSON: {"p": cols meta, "d"/rows}; format is a GViz table.
    cols = [c.get("label") or c.get("id") for c in obj["cols"]]
    rows = [[(cell or {}).get("v") for cell in r["c"]] for r in obj["rows"]]

    def col(label_sub):
        for i, c in enumerate(cols):
            if label_sub.lower() in str(c).lower():
                return i
        return None

    i_cat = col("category")
    i_name = col("HLO op name") or col("hlo op")
    i_time = col("Total time") or col("occurrences")  # fallback probed later
    # prefer self time in us
    for cand in ("Total self time (us)", "total self time"):
        j = col(cand)
        if j is not None:
            i_time = j
            break
    by_cat = defaultdict(float)
    by_op = defaultdict(float)
    total = 0.0
    for r in rows:
        t = float(r[i_time] or 0.0)
        cat = categorize(str(r[i_name]), str(r[i_cat]) if i_cat is not None
                         else "")
        by_cat[cat] += t
        by_op[str(r[i_name])[:110]] += t
        total += t
    return cols, by_cat, by_op, total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--xplane", help="skip capture; parse this xplane.pb")
    ap.add_argument("--out", help="also write the hlo_stats category "
                                  "breakdown as JSON to this path — the "
                                  "measured ground truth graftcost's "
                                  "fusion heuristics diff against "
                                  "(analysis/cost_model.py)")
    args = ap.parse_args()

    if args.xplane:
        xp = args.xplane
    else:
        import jax

        from incubator_mxnet_tpu import _backend

        _backend.use_compile_cache()
        logdir = os.path.join(REPO, ".profile",
                              time.strftime("%Y%m%d-%H%M%S"))
        os.makedirs(logdir, exist_ok=True)
        step, x, y = build_step(args.batch, compute_dtype=args.dtype)
        capture(step, x, y, logdir, iters=args.iters)
        xp = find_xplane(logdir)
        print("xplane: %s" % xp, file=sys.stderr)

    obj = hlo_stats(xp)
    cols, by_cat, by_op, total = summarize(obj, args.iters)
    print("== columns: %s" % cols, file=sys.stderr)
    per_step_us = total / args.iters
    print("\n== by category (total self time, %d steps) ==" % args.iters)
    for cat, t in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        print("  %-28s %10.0f us  (%5.1f%%)  %7.2f ms/step"
              % (cat, t, 100 * t / total, t / args.iters / 1e3))
    print("  %-28s %10.0f us            %7.2f ms/step"
          % ("TOTAL", total, per_step_us / 1e3))
    print("\n== top %d ops ==" % args.top)
    for name, t in sorted(by_op.items(), key=lambda kv: -kv[1])[:args.top]:
        print("  %7.2f ms/step  %5.1f%%  %s"
              % (t / args.iters / 1e3, 100 * t / total, name))

    if args.out:
        # machine-readable category breakdown: the measured counterpart
        # of graftcost's predicted CostReport categories (same
        # "category -> time" shape PERF.md tables use), so the cost
        # model's fusion heuristics can be diffed against reality
        payload = {
            "version": 1,
            "tool": "profile_step",
            "iters": args.iters,
            "batch": args.batch,
            "dtype": args.dtype,
            "xplane": xp,
            "total_self_us": total,
            "per_step_ms": round(per_step_us / 1e3, 3),
            "categories": {
                cat: {"total_self_us": round(t, 1),
                      "ms_per_step": round(t / args.iters / 1e3, 3),
                      "fraction": round(t / total, 4) if total else 0.0}
                for cat, t in sorted(by_cat.items(),
                                     key=lambda kv: -kv[1])},
            "top_ops": [
                {"name": name, "ms_per_step":
                 round(t / args.iters / 1e3, 3)}
                for name, t in sorted(by_op.items(),
                                      key=lambda kv: -kv[1])[:args.top]],
        }
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2)
        os.replace(tmp, args.out)
        print("wrote %s" % args.out, file=sys.stderr)


if __name__ == "__main__":
    main()
