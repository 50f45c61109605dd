"""``mx.profiler`` — Chrome-trace profiler (reference:
python/mxnet/profiler.py:33-404; core src/profiler/profiler.h:251).

Events are collected in-process and dumped as Chrome tracing JSON
(``chrome://tracing`` / Perfetto), like the reference's ``DumpProfile``:
op dispatch, user scopes (Task/Frame/Event/Counter) and markers, on this
module's own monotonic clock.

On TPU the heavy lifting lives inside XLA programs, which only the device
trace sees.  ``set_config(profile_device=True)`` (or ``profile_all``) makes
``set_state("run")`` start a ``jax.profiler`` trace into ``jax_trace_dir``
(default ``<filename without extension>_xplane``) and raise if it cannot.
While that trace runs, every Task/Frame/Event, Marker and op-dispatch event
is ALSO written into it as a ``jax.profiler.TraceAnnotation`` of the same
name, and every ``TrainStep`` call as a step ``mx.train_step``: they land on
the ``/host:CPU`` plane of the one ``*.xplane.pb``, on the clock of the
device ops they caused.  How to take such a trace and read the op names of
the compiled step (``step.*``, ``<Class>.<name>``, ``op.<name>``) is in
``docs/PROFILING.md``.

One category is kept whether or not the profiler runs: ``setup``, the
timeline of what a process does before its first step (the package's
import, parameters, the step's build, placement, trace, lint, lowering and
compile, each a :class:`Setup` span opened where the work happens) and one
record for every XLA program jax traced, lowered, compiled or loaded from
its persistent cache, taken from jax's own monitoring events.
:func:`setup_records` returns them, :func:`setup_report` prints them as a
table, and :func:`dump` writes them with the rest.  It is bounded
(``_SETUP_CAP`` records, overflow counted) and nothing writes to it per
step or per op dispatch.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = ["set_config", "profiler_set_config", "set_state",
           "profiler_set_state", "dump", "dumps", "pause", "resume",
           "Task", "Frame", "Event", "Counter", "Marker", "Setup",
           "setup_span", "setup_records", "setup_report", "clock_us"]

_lock = threading.Lock()
_state = {"running": False, "paused": False, "filename": "profile.json",
          "jax_trace_dir": None, "jax_tracing": False,
          "profile_device": False}
_events: List[Dict[str, Any]] = []
# the clock starts where the package's import did (``mx.import`` starts at 0)
_t0 = getattr(sys.modules.get(__package__), "_IMPORT_T0", None) \
    or time.monotonic()


def _now_us():
    return (time.monotonic() - _t0) * 1e6


def clock_us(monotonic):
    """A ``time.monotonic()`` reading on this module's clock, the ``ts`` of
    every event and record, in microseconds."""
    return (monotonic - _t0) * 1e6


def _emit(ph, name, cat, ts=None, dur=None, args=None, pid=0, tid=None):
    if not _state["running"] or _state["paused"]:
        return
    ev = {"ph": ph, "name": name, "cat": cat, "pid": pid,
          "tid": tid if tid is not None else threading.get_ident() % (1 << 16),
          "ts": ts if ts is not None else _now_us()}
    if dur is not None:
        ev["dur"] = dur
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)


def set_config(**kwargs):
    """Configure (profiler.py:33 set_config).  Accepts the reference kwargs
    (profile_symbolic/profile_imperative/profile_memory/profile_api/
    aggregate_stats ignored where XLA makes them moot) plus ``filename``."""
    _state["filename"] = kwargs.get("filename", _state["filename"])
    if "profile_all" in kwargs or "profile_device" in kwargs:
        _state["profile_device"] = bool(kwargs.get("profile_all", False)
                                        or kwargs.get("profile_device",
                                                      False))
    if "jax_trace_dir" in kwargs:
        _state["jax_trace_dir"] = kwargs["jax_trace_dir"]
    elif _state["jax_trace_dir"] is None or "filename" in kwargs:
        _state["jax_trace_dir"] = \
            os.path.splitext(_state["filename"])[0] + "_xplane"
    return None


profiler_set_config = set_config


def set_state(state="stop"):
    """'run' | 'stop' (profiler.py:89).  With ``profile_device`` set, 'run'
    raises what ``jax.profiler.start_trace`` raises (another trace is
    running, the directory cannot be written) and leaves the profiler
    stopped: whoever asked for a device profile is told there is none."""
    if state == "run":
        if _state["profile_device"] and not _state["jax_tracing"]:
            import jax
            jax.profiler.start_trace(_state["jax_trace_dir"])
            _state["jax_tracing"] = True
        _state["running"] = True
        _state["paused"] = False
    elif state == "stop":
        _state["running"] = False
        if _state["jax_tracing"]:
            import jax
            _state["jax_tracing"] = False
            jax.profiler.stop_trace()
    else:
        raise ValueError("state must be 'run' or 'stop'")


profiler_set_state = set_state


def pause(profile_process="worker"):
    _state["paused"] = True


def resume(profile_process="worker"):
    _state["paused"] = False


def dumps(reset=False):
    """Return aggregate stats as str (profiler.py:151)."""
    with _lock:
        evs = list(_events)
        if reset:
            _events.clear()
    agg: Dict[str, List[float]] = {}
    for e in evs:
        if e["ph"] == "X":
            agg.setdefault(e["name"], []).append(e.get("dur", 0.0))
    lines = ["%-40s %8s %12s %12s" % ("Name", "Calls", "Total(us)",
                                      "Mean(us)")]
    for name, durs in sorted(agg.items()):
        lines.append("%-40s %8d %12.1f %12.1f"
                     % (name[:40], len(durs), sum(durs),
                        sum(durs) / len(durs)))
    return "\n".join(lines)


def dump(finished=True, profile_process="worker"):
    """Write Chrome tracing JSON to the configured filename
    (profiler.py:122; format: src/profiler/profiler.cc DumpProfile)."""
    with _lock:
        evs = _setup + _events
    with open(_state["filename"], "w") as f:
        json.dump({"traceEvents": evs, "displayTimeUnit": "ms"}, f)
    if finished:
        set_state("stop")


# ---------------------------------------------------------------------------
# user scopes (profiler.py:284-404)
# ---------------------------------------------------------------------------

def _annotate(name):
    """An entered ``jax.profiler.TraceAnnotation``, or None unless this
    profiler's own jax trace is recording: the span on the device's clock."""
    if not _state["jax_tracing"] or _state["paused"]:
        return None
    import jax
    annotation = jax.profiler.TraceAnnotation(name)
    annotation.__enter__()
    return annotation


class _Scope:
    _cat = "user"

    def __init__(self, name):
        self.name = name
        self._start: Optional[float] = None
        self._annotation = None

    def start(self):
        self._start = _now_us()
        self._annotation = _annotate(self.name)

    def stop(self):
        if self._start is None:
            return
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        self._keep(self._start, _now_us() - self._start)
        self._start = None

    def _keep(self, ts, dur):
        _emit("X", self.name, self._cat, ts=ts, dur=dur)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Task(_Scope):
    _cat = "task"

    def __init__(self, name, domain=None):
        super().__init__(name)
        self.domain = domain


class Frame(_Scope):
    _cat = "frame"

    def __init__(self, name, domain=None):
        super().__init__(name)
        self.domain = domain


class Event(_Scope):
    _cat = "event"


class Operator(_Scope):
    """Framework op-dispatch event, opened by ``ops.registry.invoke`` (the
    engine's ProfileOperator analog — threaded_engine.h:354)."""
    _cat = "operator"


class Domain:
    """Named grouping for profiler objects (profiler.py:331 Domain)."""

    def __init__(self, name):
        self.name = str(name)

    def __repr__(self):
        return "Domain(%s)" % self.name


class Counter:
    """Numeric counter series (profiler.py:366)."""

    def __init__(self, name, domain=None, value=None):
        self.name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self._value = value
        _emit("C", self.name, "counter", args={"value": value})

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    """Instant marker (profiler.py:404 set_marker)."""

    def __init__(self, name, domain=None):
        self.name = name

    def mark(self, scope="process"):
        _emit("i", self.name, "marker")
        annotation = _annotate(self.name)
        if annotation is not None:
            annotation.__exit__(None, None, None)


def is_running():
    return _state["running"] and not _state["paused"]


# ---------------------------------------------------------------------------
# the set-up timeline: category "setup", kept whether or not the profiler runs
# ---------------------------------------------------------------------------

#: records kept; what comes after is counted in ``_setup_dropped``, not kept
_SETUP_CAP = 4096
_setup: List[Dict[str, Any]] = []
_setup_dropped = 0
_setup_ids = itertools.count(1)


class _SetupThread(threading.local):
    """Per thread: ``stack`` of open Setup spans; ``parts``, the traces and
    lowerings that wait for their compile (jax reports a program's three
    durations one by one); ``cache``, what the compile under way met in
    jax's persistent cache, and ``last_cache``, what the last one did."""

    def __init__(self):
        self.stack, self.parts, self.cache = [], {}, {}
        self.last_cache = "off"


_thread = _SetupThread()
#: ``setup_span``'s default parent: the span open on the calling thread
_OPEN = object()

#: jax's monitoring events (jax/_src/dispatch.py, jax/_src/compiler.py): the
#: three durations of a program, by the key each has in a program record
_PROGRAM_PARTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: fired inside the compile whose duration follows on the same thread
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
#: traces and lowerings that wait for their compile, a thread (a trace that
#: is never compiled, ``jax.eval_shape``'s, waits until it is pushed out)
_PARTS_KEPT = 256


def _keep_setup(name, cat, ts, dur, span_id, parent, args):
    global _setup_dropped
    record = {"ph": "X", "name": name, "cat": cat, "pid": 0,
              "tid": threading.get_ident() % (1 << 16), "ts": ts, "dur": dur,
              "id": span_id, "parent": parent, "args": args}
    with _lock:
        if len(_setup) < _SETUP_CAP:
            _setup.append(record)
        else:
            _setup_dropped += 1


class Setup(_Scope):
    """A span of set-up work: ``with Setup("mx.step.build"): ...`` or
    ``start()`` / ``stop()``, in line where the work happens (never a
    decorator or a wrapper: a Python frame under the step's trace costs
    ``trace_s``).  Kept whether or not the profiler runs, with an ``id`` and,
    as ``parent``, the id of the Setup span that was open on this thread when
    it began; keyword arguments, and what is put into ``args`` before
    ``stop()``, are the record's ``args``."""
    _cat = "setup"

    def __init__(self, name, **args):
        super().__init__(name)
        self.args = args
        self.id = self.parent = None

    def start(self):
        stack = _thread.stack
        self.id = next(_setup_ids)
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        super().start()

    def stop(self):
        stack = _thread.stack
        if self in stack:
            # with it goes whatever an exception left open above it
            del stack[stack.index(self):]
        super().stop()

    def _keep(self, ts, dur):
        _keep_setup(self.name, self._cat, ts, dur, self.id, self.parent,
                    self.args)


def setup_span(name, start, end, parent=_OPEN, **args):
    """A Setup record from two ``time.monotonic()`` readings, for a span
    whose ends are known only afterwards (``mx.import`` starts before this
    module is there; a cache lookup is a span only if it hit).  Its parent is
    the Setup span open on this thread unless given; returns its id."""
    if parent is _OPEN:
        parent = _thread.stack[-1].id if _thread.stack else None
    span_id = next(_setup_ids)
    _keep_setup(name, "setup", clock_us(start), (end - start) * 1e6, span_id,
                parent, args)
    return span_id


def last_program_cache():
    """``hit`` / ``miss`` / ``off``: what the XLA program this thread built
    last met in jax's persistent cache (``off``: there is none)."""
    return _thread.last_cache


def _on_jax_event(event, **_kw):
    state = _CACHE_EVENTS.get(event)
    if state is not None:
        _thread.cache["cache"] = state


def _on_jax_duration(event, seconds, fun_name=None, **_kw):
    part = _PROGRAM_PARTS.get(event)
    if part is None:
        key = _CACHE_SECONDS.get(event)
        if key is not None:
            _thread.cache[key] = seconds
        return
    # jax stamps with time.time(): the end is now, on this module's clock
    end = _now_us()
    start = end - seconds * 1e6
    parts = _thread.parts
    if part != "compile":
        # a program is ``jit(f)`` from its lowering on, ``f`` while traced
        name = "jit(%s)" % fun_name if part == "trace" else fun_name
        parts.pop((part, name), None)  # the newest last: the oldest goes
        parts[part, name] = (start, seconds)
        if len(parts) > _PARTS_KEPT:
            del parts[next(iter(parts))]
        return
    args = dict(_thread.cache, compile_s=seconds)
    _thread.cache.clear()
    if args.setdefault("cache", "off") == "asked":
        # jax asks its cache wherever one could be, also with no directory
        import jax

        args["cache"] = "miss" if jax.config.jax_compilation_cache_dir \
            else "off"
    _thread.last_cache = args["cache"]
    for other in ("trace", "lower"):
        ts, s = parts.pop((other, fun_name), (None, 0.0))
        args[other + "_s"] = s
        if ts is not None:
            args[other + "_ts"] = ts
    stack = _thread.stack
    _keep_setup(fun_name, "setup.program", start, seconds * 1e6,
                next(_setup_ids), stack[-1].id if stack else None, args)


def _listen_to_jax():
    """Register the two listeners; the package's import calls this once."""
    from jax import monitoring

    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)


def setup_records(reset=False):
    """The set-up timeline so far, in the order the records ended: Setup
    spans (``cat`` ``setup``) and one record for every XLA program jax built
    or loaded (``cat`` ``setup.program``; ``name`` is jax's ``fun_name``,
    ``ts`` / ``dur`` its compile or cache load, ``args`` hold ``compile_s``,
    ``trace_s`` and ``lower_s`` with their own starts ``trace_ts`` /
    ``lower_ts``, ``cache`` ``hit`` / ``miss`` / ``off`` and, on a hit,
    ``retrieval_s`` and ``saved_s``).  ``ts`` and ``dur`` are microseconds on
    this module's clock (:func:`clock_us`); ``parent`` is the id of the Setup
    span open on the record's thread when it began, or None.  ``reset``
    empties the timeline and its count of dropped records, as
    ``dumps(reset=True)`` does the events."""
    global _setup_dropped
    with _lock:
        records = [dict(r, args=dict(r["args"])) for r in _setup]
        if reset:
            _setup.clear()
            _setup_dropped = 0
    return records


def _covered_us(intervals):
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def setup_report(programs=20, before=None):
    """The set-up timeline as a table: the span tree with each span's self
    time (its duration less what its child spans cover) and the programs put
    down to it, then the ``programs`` heaviest programs by name.  ``before``
    (microseconds on this module's clock) leaves out what ended later: the
    first step's instant, where set-up alone is asked for."""
    records = [r for r in setup_records()
               if before is None or r["ts"] + r["dur"] <= before]
    spans = [r for r in records if r["cat"] == "setup"]
    progs = [r for r in records if r["cat"] == "setup.program"]

    def seconds(p):
        a = p["args"]
        return a["trace_s"] + a["lower_s"] + a["compile_s"]

    count = {state: sum(p["args"]["cache"] == state for p in progs)
             for state in ("hit", "miss", "off")}
    lines = ["set-up: %d spans, %d XLA programs (%d loaded from the cache, "
             "%d compiled anew, %d with no cache) in %.3f s of trace, %.3f s "
             "of lowering, %.3f s of compile or load; %d records dropped"
             % (len(spans), len(progs), count["hit"], count["miss"],
                count["off"], sum(p["args"]["trace_s"] for p in progs),
                sum(p["args"]["lower_s"] for p in progs),
                sum(p["args"]["compile_s"] for p in progs), _setup_dropped),
             "%-44s %9s %9s %9s %5s %9s  %s" % (
                 "Span", "start_s", "total_s", "self_s", "progs", "prog_s",
                 "args")]
    children: Dict[Any, list] = {}
    for r in records:
        children.setdefault(r["parent"], []).append(r)
    ids = {s["id"] for s in spans}

    def walk(span, depth):
        below = children.get(span["id"], [])
        kids = [c for c in below if c["cat"] == "setup"]
        own = [c for c in below if c["cat"] == "setup.program"]
        covered = _covered_us([(k["ts"], k["ts"] + k["dur"]) for k in kids])
        lines.append("%-44s %9.3f %9.3f %9.3f %5d %9.3f  %s" % (
            ("  " * depth + span["name"])[:44], span["ts"] * 1e-6,
            span["dur"] * 1e-6, (span["dur"] - covered) * 1e-6, len(own),
            sum(seconds(p) for p in own),
            " ".join("%s=%s" % kv for kv in span["args"].items())))
        for kid in sorted(kids, key=lambda k: k["ts"]):
            walk(kid, depth + 1)

    # a span whose parent was dropped at the cap is shown as a root
    for root in sorted((s for s in spans if s["parent"] not in ids),
                       key=lambda s: s["ts"]):
        walk(root, 0)
    loose = [p for p in progs if p["parent"] not in ids]
    lines.append("%-44s %9s %9s %9s %5d %9.3f" % (
        "(under no span)", "", "", "", len(loose),
        sum(seconds(p) for p in loose)))
    names = {s["id"]: s["name"] for s in spans}
    lines.append("%-40s %9s %9s %9s %5s  %s" % (
        "Program (%d heaviest of %d)" % (min(programs, len(progs)),
                                         len(progs)),
        "trace_s", "lower_s", "compile_s", "cache", "under"))
    for p in sorted(progs, key=seconds, reverse=True)[:programs]:
        a = p["args"]
        lines.append("%-40s %9.3f %9.3f %9.3f %5s  %s" % (
            p["name"][:40], a["trace_s"], a["lower_s"], a["compile_s"],
            a["cache"], names.get(p["parent"], "-")))
    return "\n".join(lines)
