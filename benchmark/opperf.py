#!/usr/bin/env python
"""Per-operator micro-benchmark harness (``benchmark/opperf`` parity).

Reference: ``benchmark/opperf/`` — runs individual operators over
representative shapes and reports per-op latency.  Here each op executes
through the eager dispatch path (per-op compiled executable, warm cache),
so the numbers measure exactly what imperative user code sees.

Usage:
  python benchmark/opperf.py                      # default op set
  python benchmark/opperf.py --ops dot,relu,sum   # subset
  python benchmark/opperf.py --json results.json  # machine-readable dump
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def default_cases():
    r = np.random.RandomState(0)

    def f(*shape):
        return r.normal(0, 1, shape).astype(np.float32)

    b = 32
    return [
        # (op, inputs, attrs)
        ("broadcast_add", [f(b, 256), f(b, 256)], {}),
        ("broadcast_mul", [f(b, 256), f(b, 256)], {}),
        ("relu", [f(b, 1024)], {}),
        ("sigmoid", [f(b, 1024)], {}),
        ("tanh", [f(b, 1024)], {}),
        ("exp", [f(b, 1024)], {}),
        ("sum", [f(b, 64, 64)], {"axis": (1, 2)}),
        ("mean", [f(b, 64, 64)], {"axis": 1}),
        ("softmax", [f(b, 1000)], {}),
        ("log_softmax", [f(b, 1000)], {}),
        ("dot", [f(256, 256), f(256, 256)], {}),
        ("batch_dot", [f(b, 64, 64), f(b, 64, 64)], {}),
        ("FullyConnected", [f(b, 512), f(256, 512), f(256)],
         {"num_hidden": 256}),
        ("Convolution", [f(8, 32, 28, 28), f(64, 32, 3, 3), f(64)],
         {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1)}),
        ("Pooling", [f(8, 32, 28, 28)],
         {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"}),
        ("BatchNorm", [f(8, 32, 28, 28), np.abs(f(32)) + .5, f(32), f(32),
                       np.abs(f(32)) + .5], {"fix_gamma": False}),
        ("LayerNorm", [f(b, 512), np.abs(f(512)) + .5, f(512)], {}),
        ("transpose", [f(b, 64, 64)], {"axes": (2, 0, 1)}),
        ("take", [f(1000, 64), r.randint(0, 1000, 128).astype(np.float32)],
         {}),
        ("topk", [f(b, 1000)], {"k": 10, "ret_typ": "value"}),
        ("sort", [f(b, 1024)], {}),
        ("argmax", [f(b, 1000)], {"axis": 1}),
        ("one_hot", [r.randint(0, 100, b).astype(np.float32)],
         {"depth": 100}),
        ("where", [(f(b, 256) > 0).astype(np.float32), f(b, 256),
                   f(b, 256)], {}),
        ("_contrib_interleaved_matmul_selfatt_qk", [f(128, 4, 192)],
         {"heads": 4}),
    ]


def resnet_cases(batch=64):
    """The hot ResNet-50 ops at representative stage shapes, in bfloat16
    — the dtype the headline bench actually computes in (2x fewer HBM
    bytes and the native MXU path; f32 numbers here would be evidence
    about the wrong configuration).  Per-op TPU latency evidence between
    macro-bench rounds (asked for in the round-4 review; reference
    benchmark/opperf/ runs the same op/shape matrix)."""
    import ml_dtypes

    r = np.random.RandomState(0)

    def f(*shape):
        return r.normal(0, 1, shape).astype(ml_dtypes.bfloat16)

    def conv(n, cin, cout, hw, k, s=1):
        pad = (k // 2, k // 2)
        return ("Convolution",
                [f(n, cin, hw, hw), f(cout, cin, k, k), f(cout)],
                {"kernel": (k, k), "num_filter": cout, "pad": pad,
                 "stride": (s, s)})

    b = batch
    return [
        conv(b, 3, 64, 224, 7, 2),      # stem
        conv(b, 64, 64, 56, 3),         # stage2 3x3
        conv(b, 64, 256, 56, 1),        # stage2 expand
        conv(b, 128, 128, 28, 3),       # stage3 3x3
        conv(b, 256, 512, 28, 1, 2),    # stage3 downsample
        conv(b, 256, 256, 14, 3),       # stage4 3x3
        conv(b, 512, 512, 7, 3),        # stage5 3x3
        ("BatchNorm", [f(b, 256, 56, 56), np.abs(f(256)) + .5, f(256),
                       f(256), np.abs(f(256)) + .5], {"fix_gamma": False}),
        ("BatchNorm", [f(b, 512, 28, 28), np.abs(f(512)) + .5, f(512),
                       f(512), np.abs(f(512)) + .5], {"fix_gamma": False}),
        ("Activation", [f(b, 256, 56, 56)], {"act_type": "relu"}),
        ("elemwise_add", [f(b, 256, 56, 56), f(b, 256, 56, 56)], {}),
        ("Pooling", [f(b, 64, 112, 112)],
         {"kernel": (3, 3), "stride": (2, 2), "pad": (1, 1),
          "pool_type": "max"}),
        ("Pooling", [f(b, 2048, 7, 7)],
         {"global_pool": True, "pool_type": "avg"}),
        ("FullyConnected", [f(b, 2048), f(1000, 2048), f(1000)],
         {"num_hidden": 1000}),
        ("softmax", [f(b, 1000)], {}),
    ]


def bench_op(name, arrays, attrs, warmup=3, iters=50):
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.ops import registry as reg

    ins = [nd.array(a) for a in arrays]
    for _ in range(warmup):
        out = reg.invoke(name, ins, **attrs)
    _wait(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = reg.invoke(name, ins, **attrs)
    _wait(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _wait(out):
    (out[0] if isinstance(out, list) else out).wait_to_read()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default="", help="comma-separated subset")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--json", default="", help="write results to file")
    ap.add_argument("--resnet", action="store_true",
                    help="hot ResNet-50 ops at stage shapes")
    ap.add_argument("--batch", type=int, default=64,
                    help="batch for --resnet cases")
    args = ap.parse_args()

    cases = (resnet_cases(args.batch) if args.resnet else default_cases())
    if args.ops:
        wanted = set(args.ops.split(","))
        cases = [c for c in cases if c[0] in wanted]

    results = []
    print("%-45s %12s" % ("op", "latency(us)"))
    print("-" * 58)
    for name, arrays, attrs in cases:
        try:
            us = bench_op(name, arrays, attrs, iters=args.iters)
            results.append({"op": name, "latency_us": round(us, 1),
                            "attrs": {k: str(v) for k, v in attrs.items()}})
            print("%-45s %12.1f" % (name, us))
        except Exception as e:  # noqa: BLE001
            results.append({"op": name, "error": str(e)})
            print("%-45s %12s  (%s)" % (name, "ERROR", e))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
        print("wrote %s" % args.json)


if __name__ == "__main__":
    main()
