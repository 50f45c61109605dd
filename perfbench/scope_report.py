#!/usr/bin/env python3
"""Where a cell's traced step spends its device time, by the names the
program gives itself (``hlo_scope``).

    python3 perfbench/scope_report.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does and reads the same per-layer
metrics the same way (``layer_metrics.read``); what it adds is below the
metrics: the step by direction, family and kind, its heaviest ops with their
scope and the heaviest ops that carry no name.  The last line of stdout is
one JSON object: ``metrics``, ``families``, ``ops``, ``unscoped`` (ms per
traced step), ``device`` and ``correct``.  Like ``run.py`` it needs the
cell's chips.
"""
import time

T_START = time.monotonic()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import checks, hlo_scope, layer_metrics, run
from perfbench.readers import scope_op


def family(path: str) -> str:
    """``<fwd|bwd|-> <leaf without its instance name>``: every BatchNorm of
    the backward pass is ``bwd op.BatchNorm``."""
    direction = ("bwd" if "transpose(" in path
                 else "fwd" if "jvp(" in path else "-")
    leaf = hlo_scope.leaf(path)
    if not leaf.startswith(("op.", "step.")):
        leaf = leaf.split(".", 1)[0]
    return "%s %s" % (direction, leaf)


def tables(facts: dict, n_ops: int = 15) -> dict:
    """The traced step in ms per step: by family and kind (``families``),
    its ``n_ops`` heaviest ops (``ops``) and heaviest ops that carry no name
    (``unscoped``), each as ``[tag, ms, scope, what else the fusion
    holds]``; empty without a device trace."""
    trace = facts.get("trace")
    if not trace or not trace["n_modules"]:
        return {"families": [], "ops": [], "unscoped": []}
    table = scope_op.scope_table(facts)
    per_step = 1e-6 / trace["n_modules"]
    by_family, by_op = {}, {}
    for tag, _, dur in trace["ops"]:
        key = "%s %s" % (family(table[tag][0]), tag.split(".", 1)[0])
        by_family[key] = by_family.get(key, 0.0) + dur * per_step
        by_op[tag] = by_op.get(tag, 0.0) + dur * per_step
    rows = [[tag, ms, *table[tag]]
            for tag, ms in sorted(by_op.items(), key=lambda kv: -kv[1])]
    return {
        "families": sorted(by_family.items(), key=lambda kv: -kv[1]),
        "ops": rows[:n_ops],
        "unscoped": [r for r in rows if r[2] == hlo_scope.UNSCOPED][:n_ops],
    }


def report(root, name, platform, seed, seconds, t_start, n_ops=15) -> dict:
    """One traced run of the cell; what the module docstring lists."""
    cell, facts = run.cell_facts(root, name, platform, seed, seconds, True,
                                 t_start)
    t0 = time.perf_counter()
    metrics = {m["name"]: layer_metrics.read(m["spec"], facts)
               for m in cell["per_layer"]}
    out = dict(tables(facts, n_ops), metrics=metrics)
    print("scope_report: reading the metrics and the scopes took %.2fs"
          % (time.perf_counter() - t0), flush=True)
    first = facts["devices"][0]
    out["device"] = {"platform": first.platform, "kind": first.device_kind,
                     "count": len(facts["devices"])}
    out["correct"] = not facts["problems"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--ops", type=int, default=15,
                    help="how many of the heaviest ops to list")
    args = ap.parse_args(argv)
    try:
        out = report(ROOT, args.workload, "tpu", args.seed, args.seconds,
                     T_START, args.ops)
    except checks.NoChip as e:
        print("scope_report: %s" % e, file=sys.stderr)
        return 1
    for key, ms in out["families"][:40]:
        print("%9.3f ms  %s" % (ms, key))
    for tag, ms, scope, inside in out["ops"] + out["unscoped"]:
        print("%9.3f ms  %s | %s | %s" % (ms, tag, scope,
                                          " ".join(inside) or "-"))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
