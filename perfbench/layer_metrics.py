"""Per-layer metrics as data: ``layer_metrics/<metric>.json`` names a reader
and its parameters, so a later kernel's metric is a file with a pattern and
no code.  A reader is one of those below or a module
``perfbench/readers/<reader>.py`` with ``read(spec, facts)`` (``scope_op``).
A reader that finds nothing to read returns None, and the harness leaves the
metric out of the line.

``facts`` is what a runner returns: ``setup_parts`` and ``counters`` (dicts
of numbers), ``trace`` (``trace_reduce.summarize``'s dict, or None in a run
that was not traced), ``programs`` (the compiled step first) and ``peaks``
(the device kind's row of peaks.json).

Readers:

``setup_part``  ``key``: seconds of that part of set-up.
``trace_op``    ``pattern`` over the tagged op names (``hlo_tag.tag``) of the
                traced window on device 0; ``reduce`` is ``sum`` or ``union``
                of their device time, in ms per traced module (a train step,
                a served batch).
``roofline``    a kernel's share of its roofline, in %: the least time the
                ops matching ``pattern`` could take, as a share of the union
                of their device time per module.  The least time is counter
                ``work_counter`` (operations per module per chip) over peak
                ``peak``; with ``bytes_counter`` (bytes the kernel has to
                move per module per chip) and ``bytes_peak`` it is the
                larger of that and bytes over ``bytes_peak``, and the log
                says which side bounds it.  Without them it is the
                compute-bound side alone, and a program bound by HBM reads
                low by nature.  A share over 100 % is returned as it is: the
                counters are then too high or the pattern leaves out part of
                the work, and clipping would hide it.

``mfu``         the whole step's share of the chip's peak, in %: end-to-end
                metric ``rate`` (work items per second of the timed window)
                times counter ``work_counter`` (operations per work item,
                recomputed ones not counted) over peak ``peak`` times the
                chips the cell used.  It bounds every kernel's roofline: a
                kernel taken off the path leaves its own metric silent, and
                a gain shows here or nowhere.

Both trace readers take an optional ``scope``, a regex over the op's scope
path (``readers/scope_op.scope_table``) that narrows ``pattern`` to the ops
a part of the program built.  The counters are what the cell's runner
returns in ``facts["counters"]``: the functions that compute a kernel's
operations and bytes live beside the runner that knows the shapes.
"""
from __future__ import annotations

import importlib
import re

from perfbench import trace_reduce
from perfbench.readers import scope_op


def _per_module(spec, facts, union):
    trace = facts.get("trace")
    if not trace or not trace["n_modules"]:
        return None
    if "scope" in spec:
        table, scope = scope_op.scope_table(facts), re.compile(spec["scope"])
        trace = dict(trace, ops=[op for op in trace["ops"]
                                 if scope.search(table[op[0]][0])])
    ms = trace_reduce.op_ms(trace, spec["pattern"], union=union)
    return ms / trace["n_modules"] if ms > 0 else None


def _roofline(spec, facts):
    busy_ms = _per_module(spec, facts, union=True)
    if not busy_ms or not facts.get("peaks"):
        return None
    sides = [("compute", "work_counter", "peak")]
    if "bytes_counter" in spec:
        sides.append(("bytes", "bytes_counter", "bytes_peak"))
    least_ms = {}
    for side, counter, peak in sides:
        count = facts["counters"].get(spec[counter])
        if not count:
            return None
        least_ms[side] = 1e3 * count / facts["peaks"][spec[peak]]
    bound = max(least_ms, key=least_ms.get)
    if len(sides) > 1:
        print("roofline: %s bound, least %s of %.4f ms busy (%s)" % (
            bound, " ".join("%s %.4f ms" % kv for kv in least_ms.items()),
            busy_ms, spec["pattern"]), flush=True)
    return 100.0 * least_ms[bound] / busy_ms


def _mfu(spec, facts):
    rate = facts["end_to_end"].get(spec["rate"])
    work = facts["counters"].get(spec["work_counter"])
    if not rate or not work or not facts.get("peaks"):
        return None
    return 100.0 * rate * work / (len(facts["devices"])
                                  * facts["peaks"][spec["peak"]])


def read(spec: dict, facts: dict):
    """The metric's value from ``facts``, or None."""
    reader = spec["reader"]
    if reader == "setup_part":
        return facts["setup_parts"].get(spec["key"])
    if reader == "mfu":
        return _mfu(spec, facts)
    if reader == "trace_op":
        return _per_module(spec, facts, spec["reduce"] == "union")
    if reader == "roofline":
        return _roofline(spec, facts)
    name = "perfbench.readers." + reader
    try:
        module = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError("unknown reader %r" % reader) from None
    return module.read(spec, facts)
