#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout, finds the cell's
configuration, its traffic file and its layer metrics by name, hands them to
the runner the traffic file names (``perfbench/runners/<runner>.py``) and
prints, as the last line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, traced ``breakdown``,
and last ``compared``: every number ``correct`` was decided from as
``[value, limit]``, which are also the last lines of stderr.  Without the
chips the cell asks for it exits non-zero and prints no such line: there is
no CPU continuation.
"""
import time

T_START = time.monotonic()  # set-up is counted from here

import argparse
import importlib
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import checks, layer_metrics


def _json(path):
    with open(path) as f:
        return json.load(f)


def _in_cell(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(root: str, name: str) -> dict:
    """Everything one cell is made of, found by the names in
    ``<root>/BENCHMARK.json``: its configuration (the entry's ``file``), its
    traffic (``<paths[0]>/mixes/<traffic>.json``) and, for each per-layer
    metric the cell reports, ``<paths[0]>/layer_metrics/<metric>.json``."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError("no workload %r in BENCHMARK.json; it has %s"
                       % (name, sorted(cells)))
    entry = cells[name]
    data = os.path.join(root, bench["paths"][0])
    config_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = _json(os.path.join(root, config_entry["file"]))
    config["name"] = entry["config"]
    per_layer = [dict(m, spec=_json(os.path.join(
        data, "layer_metrics", m["name"] + ".json")))
        for m in bench["per_layer"] if _in_cell(m, name)]
    return {
        "name": name,
        "chips": entry["chips"],
        "config": config,
        "traffic": _json(os.path.join(data, "mixes",
                                      entry["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if _in_cell(m, name)],
        "per_layer": per_layer,
    }


def result_line(cell: dict, facts: dict, traced: bool) -> dict:
    """The contract's last line, from what the runner returned."""
    if traced:
        values = {m["name"]: layer_metrics.read(m["spec"], facts)
                  for m in cell["per_layer"]}
        declared = cell["per_layer"]
    else:
        values = dict(facts["end_to_end"], setup_s=facts["setup_s"])
        declared = cell["end_to_end"]
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in declared if values.get(m["name"]) is not None}
    first = facts["devices"][0]
    device = {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": facts["device_count"],
        "memory_peak_bytes": max(facts["memory"].values()),
    }
    line = {"correct": not facts["problems"],
            "attempted": facts["attempted"], "failed": facts["failed"],
            "metrics": metrics, "device": device}
    trace = facts.get("trace")
    if traced and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    # what ``correct`` compared, each number beside its limit; last in the
    # line, and a number that is not finite as a string, so the line is JSON
    line["compared"] = {
        name: [v if math.isfinite(v) else repr(v) for v in pair]
        for name, pair in facts.get("compared", {}).items()}
    return line


def cell_facts(root, name, platform, seed, seconds, traced, t_start,
               counter=None):
    """Load the cell and run its runner: ``(cell, facts)``.  ``platform`` is
    what the devices must be; only ``main()`` insists on ``tpu``."""
    import jax

    cell = load_cell(root, name)
    devices = checks.require_devices(platform, cell["chips"])
    print("[run %6.1fs] jax has %d %s device(s)"
          % (time.monotonic() - t_start, len(devices), platform), flush=True)
    cell["peaks"] = (checks.peaks(devices[0].device_kind)
                     if platform == "tpu" else None)
    runner = importlib.import_module(
        "perfbench.runners." + cell["traffic"]["runner"])
    facts = runner.run(cell, platform, seed, seconds, traced, t_start,
                       counter or checks.CompileCounter())
    facts["device_count"] = len(jax.devices())
    facts["memory"] = checks.memory_peaks(facts["devices"], facts["programs"])
    print("memory on the fullest chip: allocator's peak %.3f GB, compiler's "
          "count for the largest program %.3f GB"
          % tuple(facts["memory"][k] / 1e9 for k in ("allocator", "compiler")),
          flush=True)
    facts["peaks"] = cell["peaks"]
    for problem in facts["problems"]:
        print("NOT CORRECT: " + problem, flush=True)
    return cell, facts


def run_cell(root, name, platform, seed, seconds, traced, t_start,
             counter=None):
    """One run of the cell: the contract's last line."""
    cell, facts = cell_facts(root, name, platform, seed, seconds, traced,
                             t_start, counter)
    return result_line(cell, facts, traced)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the window (default: run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        seconds = _json(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]
    try:
        # not through run_cell(): one Python frame more under the runner
        # moves trace_s.train by half a second on the chip's host (PERF.md
        # section 6, PR 29), so main() keeps the depth it always had
        cell, facts = cell_facts(ROOT, args.workload, "tpu", args.seed,
                                 seconds, bool(args.trace), T_START)
    except checks.NoChip as e:
        print("perfbench: %s" % e, file=sys.stderr)
        return 1
    line = result_line(cell, facts, bool(args.trace))
    for name, (value, limit) in line["compared"].items():
        print("compared: %s %r limit %r" % (name, value, limit),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
