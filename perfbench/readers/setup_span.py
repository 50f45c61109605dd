"""Reader ``setup_span``: the parts of ``setup_s``, from the set-up timeline
the program keeps of itself (``incubator_mxnet_tpu.profiler``: ``Setup``
spans opened where the start-up work happens, and one record for every XLA
program jax traced, lowered, compiled or loaded; ``docs/PROFILING.md``).

Parameters, in the metric's file, one of:

``spans``        a list of span names: seconds covered by the union of those
                 spans (0.0 where the process opened none of them).  With
                 ``"timed_step": true`` only the spans of the timed step:
                 those that began no earlier than the last ``mx.step.build``
                 before the window.  With ``"cut": false`` the spans of the
                 whole process (``mx.import``, which is over before any
                 window).
``programs``     a list of keys of a program record's ``args`` (``trace_s``,
                 ``lower_s``, ``compile_s``): their sum in seconds over the
                 programs; the empty list counts the programs.  ``cache``
                 (``hit`` / ``miss`` / ``off``) keeps only the programs that
                 met their persistent cache so.
``unaccounted``  ``setup_s`` less the union of every span and of every
                 program's three intervals: what the timeline cannot see.

All but ``"cut": false`` read what ENDED BEFORE THE WINDOW OPENED, which on
the recorder's clock is ``T_START + facts["setup_s"]``; None where the
process has no ``T_START`` (``_timeline``).

None, and nothing raised, where the program keeps no such timeline, as a
program from before it does.  The timeline before the window is logged once a
run, as ``profiler.setup_report`` prints it.
"""
from __future__ import annotations

import sys


def _timeline(facts: dict) -> dict:
    """``{"records", "start", "cut"}`` (microseconds on the recorder's
    clock; ``cut`` None without ``T_START``), made on first use and kept in
    ``facts``; ``records`` None where the program keeps no timeline."""
    if "setup_span" not in facts:
        from incubator_mxnet_tpu import profiler

        kept = {"records": None, "start": None, "cut": None}
        if hasattr(profiler, "setup_records"):
            kept["records"] = profiler.setup_records()
            # facts carries setup_s and not T_START; run.py run as the
            # command is sys.modules["__main__"] and has it, the tests set
            # it there, and where there is none every metric that needs the
            # cut is None (PERF.md section 7: t_start belongs in facts)
            t_start = getattr(sys.modules.get("__main__"), "T_START", None)
            if t_start is not None:
                kept["start"] = profiler.clock_us(t_start)
                kept["cut"] = profiler.clock_us(t_start + facts["setup_s"])
                print("set-up timeline before the window:\n"
                      + profiler.setup_report(programs=10,
                                              before=kept["cut"]),
                      flush=True)
        facts["setup_span"] = kept
    return facts["setup_span"]


def _end(record: dict) -> float:
    return record["ts"] + record["dur"]


def _covered_s(intervals, lo=float("-inf"), hi=float("inf")) -> float:
    """Seconds covered by the union of ``(start, end)`` intervals in
    microseconds, clipped to ``lo .. hi``."""
    total, reach = 0.0, lo
    for start, end in sorted(intervals):
        end = min(end, hi)
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total * 1e-6


def _program_intervals(program: dict):
    args = program["args"]
    yield program["ts"], _end(program)
    for part in ("trace", "lower"):
        if part + "_ts" in args:
            yield (args[part + "_ts"],
                   args[part + "_ts"] + args[part + "_s"] * 1e6)


def read(spec: dict, facts: dict):
    """The metric's value from the process's own timeline, or None."""
    timeline = _timeline(facts)
    records, cut = timeline["records"], timeline["cut"]
    if records is None:
        return None
    if spec.get("cut", True):
        if cut is None:
            return None
        records = [r for r in records if _end(r) <= cut]
    spans = [r for r in records if r["cat"] == "setup"]
    programs = [r for r in records if r["cat"] == "setup.program"]
    if "spans" in spec:
        if spec.get("timed_step"):
            builds = [r["ts"] for r in spans if r["name"] == "mx.step.build"]
            spans = [r for r in spans if builds and r["ts"] >= max(builds)]
        return _covered_s([(r["ts"], _end(r)) for r in spans
                           if r["name"] in spec["spans"]])
    if "programs" in spec:
        if "cache" in spec:
            programs = [p for p in programs
                        if p["args"]["cache"] == spec["cache"]]
        if not spec["programs"]:
            return len(programs)
        return sum(p["args"][key] for p in programs
                   for key in spec["programs"])
    if spec.get("unaccounted"):
        intervals = [(r["ts"], _end(r)) for r in spans]
        for p in programs:
            intervals.extend(_program_intervals(p))
        return facts["setup_s"] - _covered_s(intervals, timeline["start"],
                                             cut)
    raise ValueError("a setup_span metric has spans, programs or "
                     "unaccounted: %r" % spec)
