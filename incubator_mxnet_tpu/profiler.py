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
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = ["set_config", "profiler_set_config", "set_state",
           "profiler_set_state", "dump", "dumps", "pause", "resume",
           "Task", "Frame", "Event", "Counter", "Marker"]

_lock = threading.Lock()
_state = {"running": False, "paused": False, "filename": "profile.json",
          "jax_trace_dir": None, "jax_tracing": False,
          "profile_device": False}
_events: List[Dict[str, Any]] = []
_t0 = time.monotonic()


def _now_us():
    return (time.monotonic() - _t0) * 1e6


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
        evs = list(_events)
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
        _emit("X", self.name, self._cat, ts=self._start,
              dur=_now_us() - self._start)
        self._start = None

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
