"""The yardstick's own checks, copied from ``chip_smoke.py`` where they were
proven on the chip: which device this is, what its peaks are, whether a
program was built where none may be, whether state lives on the device, and
whether a list of losses says the step trains."""
from __future__ import annotations

import json
import math
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

#: what jax reports for every XLA program it builds or loads from its cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """jax found another platform, or fewer chips, than the cell asks for."""


def require_devices(platform: str, count: int) -> list:
    """``jax.devices()`` if they are at least ``count`` of ``platform``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < count:
        raise NoChip("this cell needs %d %s device(s), jax found %r"
                     % (count, platform, devs))
    return devs


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError("no published peaks for device kind %r in "
                       "perfbench/peaks.json; add the kind with its source"
                       % device_kind)
    return table[device_kind]


class CompileCounter:
    """Counts the XLA programs built, or loaded from the persistent cache,
    while it is armed: jax's own monitoring event, so a silent retrace
    behind an AOT executable is seen too.  One per process."""

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if self.armed and name == _COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        self.count, self.armed = 0, True
        return self

    def __exit__(self, *exc):
        self.armed = False


def off_device(arrays, platform: str) -> list:
    """The arrays that do not live on ``platform`` devices alone."""
    return [a for a in arrays
            if {d.platform for d in a.devices()} != {platform}]


def losses_problem(losses, first, last_chunk, must_fall: bool):
    """None if every loss is finite and (where ``must_fall``) the lowest of
    the last whole chunk is below the run's first; else what is wrong."""
    if not all(math.isfinite(v) for v in losses):
        bad = [i for i, v in enumerate(losses) if not math.isfinite(v)]
        return "%d of %d losses are not finite (first at step %d)" % (
            len(bad), len(losses), bad[0])
    if must_fall and not min(last_chunk) < first:
        return ("the loss did not fall: step 0 %r, lowest of the last chunk "
                "%r" % (first, min(last_chunk)))
    return None


def memory_peaks(devices, programs=()) -> dict:
    """Bytes on the fullest chip, from two sources.  ``allocator`` is the
    runtime's own peak (``memory_stats``); it left out a compiled program's
    temporaries when PR 23 read it (0.69 GB against 8.6 GB counted by the
    compiler).  ``compiler`` is the compiler's count for the largest program
    that ran: arguments + outputs + temporaries - aliased, per device.  The
    result line's ``memory_peak_bytes`` is the larger."""
    allocator = compiler = 0
    for d in devices:
        stats = d.memory_stats() or {}
        allocator = max(allocator, int(stats.get("peak_bytes_in_use", 0)))
    for compiled in programs:
        m = compiled.memory_analysis()
        compiler = max(compiler, int(
            m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes))
    return {"allocator": allocator, "compiler": compiler}
