"""From a profiler trace (``*.xplane.pb``) to numbers, with jax alone.

The planes of a TPU trace, as read by hand from the recorded v5e trace under
``.profile/`` (see ``tests/benchmark_tests``): one plane ``/device:TPU:<i>``
per chip with the lines ``XLA Modules`` (one event per executed program,
named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per executed
HLO instruction, named by the instruction's text; asynchronous copies and collectives show
there as their short ``-start`` and ``-done`` ops, and on the line
``Async XLA Ops`` as one span from start to done), and one plane
``/host:CPU`` with a line per thread, where ``jax.profiler.TraceAnnotation``
spans appear under their own names.  Times are nanoseconds from the start of
the trace, on one clock for host and device.

Busy time is the UNION of the op intervals, so an op nested in another (the
body of a ``while``) is not counted twice; a sum over a name pattern is a
plain sum, which is right for the leaf ops a pattern selects.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

from perfbench import hlo_tag

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Device:
    index: int
    modules: list = field(default_factory=list)  # (name, start_ns, dur_ns)
    ops: list = field(default_factory=list)      # (text, start_ns, dur_ns)
    async_ops: list = field(default_factory=list)  # start..done spans, same


@dataclass
class Trace:
    devices: list      # Device, by index
    host_spans: list   # (name, start_ns, dur_ns) whose name has the prefix


def find_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` below ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError("no *.xplane.pb under %s" % trace_dir)
    return max(found, key=os.path.getmtime)


def load(path: str, host_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData

    devices, host = [], []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device(int(m.group(1)))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
                elif line.name == "XLA Ops":
                    dev.ops = [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
                elif line.name == "Async XLA Ops":
                    dev.async_ops = [(e.name, e.start_ns, e.duration_ns)
                                     for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(host_prefix))
    devices.sort(key=lambda d: d.index)
    return Trace(devices, host)


def modules_matching(dev: Device, pattern: str) -> list:
    rx = re.compile(pattern)
    return sorted((m for m in dev.modules if rx.search(m[0])),
                  key=lambda m: m[1])


def merged(intervals, lo, hi) -> list:
    """Sorted, disjoint ``(start, end)`` pieces of the union of
    ``intervals`` clipped to ``[lo, hi]``."""
    out = []
    for start, end in sorted((max(s, lo), min(s + d, hi))
                             for _, s, d in intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _gaps(busy, lo, hi):
    edges = [lo] + [t for piece in busy for t in piece] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _label(gap, host_spans) -> str:
    """The host span that covers most of the gap, or ``unlabelled``."""
    best, best_ns = "unlabelled", 0.0
    for name, start, dur in host_spans:
        cover = min(gap[1], start + dur) - max(gap[0], start)
        if cover > best_ns:
            best, best_ns = name, cover
    return best


def _heaviest(tagged, n=10) -> list:
    """``[[tag, seconds], ...]``: the ``n`` tags with most summed time."""
    totals = {}
    for name, _, d in tagged:
        totals[name] = totals.get(name, 0.0) + d * 1e-9
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def summarize(trace: Trace, module_pattern: str,
              kinds: dict | None = None) -> dict:
    """Everything the layer metrics and the result line read.

    The window runs from the first start to the last end of the modules
    matching ``module_pattern``, on each device.  Returns ``window_s`` and
    ``busy_s`` (means over the devices), the number of matching modules on
    device 0 (``n_modules``), its ops in the window as ``(tag, start_ns,
    dur_ns)`` (``ops``), its ten heaviest tags (``device_ops``) and five
    longest idle gaps (``idle_gaps``), both in seconds."""
    if not trace.devices:
        raise ValueError("the trace holds no /device:TPU:<i> plane")
    windows, busies, first = [], [], None
    for dev in trace.devices:
        mods = modules_matching(dev, module_pattern)
        if not mods:
            raise ValueError("no module matching %r on device %d; it ran %r"
                             % (module_pattern, dev.index,
                                sorted({m[0] for m in dev.modules})[:8]))
        lo, hi = mods[0][1], max(s + d for _, s, d in mods)
        busy = merged(dev.ops, lo, hi)
        windows.append((hi - lo) * 1e-9)
        busies.append(sum(e - s for s, e in busy) * 1e-9)
        if first is None:
            first = (dev, mods, lo, hi, busy)
    dev, mods, lo, hi, busy = first
    tags = {}  # every step repeats the same instruction texts

    def tagged(events):
        out = []
        for text, s, d in events:
            if s + d > lo and s < hi:
                if text not in tags:
                    tags[text] = hlo_tag.tag(text, kinds)
                out.append((tags[text], s, d))
        return out

    ops = tagged(dev.ops)
    gaps = sorted(_gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:5]
    return {
        "n_modules": len(mods),
        "window_s": sum(windows) / len(windows),
        "busy_s": sum(busies) / len(busies),
        "ops": ops,
        "device_ops": _heaviest(ops),
        "idle_gaps": [[_label(g, trace.host_spans), (g[1] - g[0]) * 1e-9]
                      for g in gaps],
        # start-to-done spans of asynchronous ops (copies, collectives):
        # time in flight, which overlaps the ops above; for the log only
        "async_ops": _heaviest(tagged(dev.async_ops)),
    }


def op_ms(summary: dict, pattern: str, union: bool = False) -> float:
    """Milliseconds of the window's ops on device 0 whose tag matches
    ``pattern``: their sum, or the union of their intervals."""
    rx = re.compile(pattern)
    picked = [op for op in summary["ops"] if rx.search(op[0])]
    if union:
        return sum(e - s for s, e in merged(
            picked, float("-inf"), float("inf"))) * 1e-6
    return sum(d for _, _, d in picked) * 1e-6
