"""Per-layer metrics as data: ``layer_metrics/<metric>.json`` names one of
the readers below and its parameters, so a later kernel's metric is a file
with a pattern and no code.  A reader that finds nothing to read returns
None, and the harness leaves the metric out of the line.

``facts`` is what a runner returns: ``setup_parts`` and ``counters`` (dicts
of numbers), ``trace`` (``trace_reduce.summarize``'s dict, or None in a run
that was not traced) and ``peaks`` (the device kind's row of peaks.json).

Readers:

``setup_part``  ``key``: seconds of that part of set-up.
``trace_op``    ``pattern`` over the tagged op names (``hlo_tag.tag``) of the
                traced window on device 0; ``reduce`` is ``sum`` or ``union``
                of their device time, in ms per traced module (a train step,
                a served batch).
``roofline``    the compute-bound side of the roofline, in %: the least time
                the ops matching ``pattern`` could take, which is counter
                ``work_counter`` (operations per module per chip) over peak
                ``peak``, as a share of the union of their device time per
                module.  It says nothing of bytes: a program bound by HBM
                reads low here by nature.
"""
from __future__ import annotations

from perfbench import trace_reduce


def _per_module(spec, trace, union):
    if not trace or not trace["n_modules"]:
        return None
    ms = trace_reduce.op_ms(trace, spec["pattern"], union=union)
    return ms / trace["n_modules"] if ms > 0 else None


def read(spec: dict, facts: dict):
    """The metric's value from ``facts``, or None."""
    reader = spec["reader"]
    if reader == "setup_part":
        return facts["setup_parts"].get(spec["key"])
    trace = facts.get("trace")
    if reader == "trace_op":
        return _per_module(spec, trace, spec["reduce"] == "union")
    if reader == "roofline":
        busy_ms = _per_module(spec, trace, union=True)
        work = facts["counters"].get(spec["work_counter"])
        if not busy_ms or not work or not facts.get("peaks"):
            return None
        least_ms = 1e3 * work / facts["peaks"][spec["peak"]]
        return 100.0 * least_ms / busy_ms
    raise ValueError("unknown reader %r" % reader)
