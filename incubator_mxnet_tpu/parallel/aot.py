"""Shared AOT-compile + lint plumbing for compiled-program builders.

Two independent builders assemble long-lived XLA programs from gluon
nets — the fused training step (``parallel/train_step.py``) and the
serving engine (``serve/engine.py``) — and both follow the same ritual:

1. trace the jitted callable ONCE with the GL004 effect hooks active
   (:func:`traced_with_effects` — the very trace jit caches for the
   first call, so the lint costs one jaxpr walk, not an extra trace);
2. assemble a :class:`~..analysis.LintReport` from the effect
   diagnostics + the jaxpr walk + any builder-specific checks and apply
   the ``"error"``/``"warn"``/``"off"`` policy (:func:`finish_lint`);
3. lower + compile with a timed phase split (:func:`compile_timed`) so
   benchmarks can report where startup time goes — the reference's
   analog is cuDNN autotune + InitCachedOps cost at bind
   (``src/executor/graph_executor.cc:1220``).

This module is the ONE copy of that ritual.  The builders keep their
own policy (what counts as an extra diagnostic, when to mark
themselves linted); the mechanics live here.

It also owns the **persistent on-disk compile cache**
(:class:`CompileCache`): every AOT build routed through
:func:`compile_timed` can consult a directory of serialized XLA
executables keyed by (lowered-program hash, mesh shape + axis names,
builder knobs, jax/jaxlib version, backend + device count) before
paying ``lowered.compile()`` — so a retune or a restart pays
trace-but-not-compile across *processes*, not just within one.  Writes
are atomic (temp + fsync + rename, the ``CheckpointManager``
discipline, through the same ``checkpoint._write_bytes`` choke point
``fault_injection.fail_writes`` interposes); corrupt or stale entries
degrade to a recompile with a warning, never a crash and never a wrong
executable; the directory is LRU-swept to a byte cap.  Resolution:
explicit ``cache=`` argument > ``MXTPU_COMPILE_CACHE`` env
(``config.py``) > off.  :data:`XLA_COMPILES` counts real
``lowered.compile()`` invocations — the "0 XLA compiles on a warm
cache" contract the autotuner's tests assert.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

from .. import profiler

__all__ = ["CompileCache", "XLA_COMPILES", "compile_timed",
           "default_compile_cache", "finish_lint", "lint_served_program",
           "resolve_mode", "traced_with_effects"]


class _CompileCounter:
    """Process-wide count of real XLA ``lowered.compile()`` calls made
    through :func:`compile_timed` (cache hits do NOT increment it).
    Incremented under a lock — batcher workers compile post-warmup
    bucket programs concurrently with main-thread builds, and a lost
    increment would let a real compile escape the warm-cache "0 XLA
    compiles" assertions (the same hazard serve/batcher.py's stats
    counters lock against)."""

    __slots__ = ("count", "_lock")

    def __init__(self):
        import threading

        self.count = 0
        self._lock = threading.Lock()

    def bump(self):
        with self._lock:
            self.count += 1


#: the one instance every builder shares
XLA_COMPILES = _CompileCounter()


def resolve_mode(value: Optional[str], env_var: str, default: str,
                 allowed: Sequence[str], what: str) -> str:
    """The shared knob-resolution order: explicit argument > env var
    (``config.py``) > ``default``.  Raises ``ValueError`` naming the
    knob on anything outside ``allowed``."""
    if value is None:
        from .. import config as _cfg

        value = str(_cfg.get(env_var, default) or default).lower()
    if value not in allowed:
        raise ValueError("%s must be one of %s, got %r"
                         % (what, "/".join(repr(a) for a in allowed),
                            value))
    return value


def traced_with_effects(jit_obj, args: tuple, capture: bool = True):
    """Trace ``jit_obj`` (via ``.trace(*args)`` — the trace the first
    call reuses) with the GL004 effect-capture hooks active.  Returns
    ``(traced, effect_diagnostics)``; ``capture=False`` skips the hook
    (an empty diagnostics list comes back)."""
    from contextlib import nullcontext

    from ..analysis.trace_lint import capture_effect_diagnostics

    cm = capture_effect_diagnostics() if capture else nullcontext([])
    with cm as effects:
        traced = jit_obj.trace(*args)
    return traced, list(effects)


def finish_lint(closed_jaxpr, *, mode: str, effects: Iterable = (),
                donated_leaves: Sequence[int] = (), extra: Iterable = (),
                suppress: Tuple[str, ...] = (),
                what: str = "compiled program", stacklevel: int = 5):
    """Assemble and enforce one lint report over a traced program.

    ``effects`` are GL004 diagnostics captured during the trace,
    ``donated_leaves`` flat invar indices for the GL003 walk, ``extra``
    builder-specific diagnostics (GL006/GL007 for the train step,
    GL010 for the serving engine).  ``mode="error"`` raises
    :class:`~..analysis.LintError` on error-severity findings; any
    findings at all are warned (so ``"warn"`` mode surfaces them and
    ``"error"`` mode surfaces the non-fatal ones).  Returns the report.
    """
    from ..analysis import LintReport, Severity, lint_jaxpr

    report = LintReport(suppress=suppress)
    report.extend(effects)
    report.extend(lint_jaxpr(closed_jaxpr,
                             donated_leaves=donated_leaves).diagnostics)
    report.extend(extra)
    if mode == "error":
        report.raise_if_errors()
    if report.errors or report.warnings:
        import warnings as _warnings

        _warnings.warn("graftlint: %s has findings\n%s"
                       % (what, report.format(Severity.WARNING)),
                       stacklevel=stacklevel)
    return report


def lint_served_program(traced, effects, args: tuple,
                        donate_argnums: Sequence[int], *, mode: str,
                        suppress: Tuple[str, ...] = (),
                        what: str = "inference program",
                        param_argnum: int = 0, stacklevel: int = 6):
    """The serving-side lint ritual shared by ``serve/engine.py`` and
    ``serve/cache.py``: GL001–GL004 over the traced program plus GL010
    (``check_inference_param_donation``) against the builder's own
    donation spec — the params argument (``param_argnum``) must never
    be donated.  ONE copy, like :func:`finish_lint` for the generic
    half."""
    import jax

    from ..analysis.trace_lint import (check_inference_param_donation,
                                       donated_leaf_indices)

    donated = donated_leaf_indices(args, donate_argnums)
    off = sum(len(jax.tree_util.tree_leaves(a))
              for a in args[:param_argnum])
    n_param = len(jax.tree_util.tree_leaves(args[param_argnum]))
    extra = check_inference_param_donation(
        donated, range(off, off + n_param), where=what)
    return finish_lint(traced.jaxpr, mode=mode, effects=effects,
                       donated_leaves=donated, extra=extra,
                       suppress=suppress, what=what,
                       stacklevel=stacklevel)


class CompileCache:
    """Persistent on-disk cache of compiled XLA executables.

    Entries are pickled ``jax.experimental.serialize_executable``
    payloads under ``<directory>/<key>.xc``; the key (sha256) covers
    the LOWERED program text (which embeds shapes, dtypes and GSPMD
    shardings), the caller's ``extra`` tuple (mesh shape + axis names,
    builder knobs), the jax + jaxlib versions, and the backend platform
    / device-count / device-kind — anything that could make a stored
    executable wrong for the process loading it.  A key-or-version
    mismatch inside a loaded entry, an unpicklable blob, or a torn file
    all take the same path: warn, drop the entry, recompile.

    Entries are pickles: point the cache only at directories you trust
    (the same standing as ``.jax_cache/`` and checkpoint dirs).
    """

    #: bump to orphan every existing entry on a format change
    VERSION = 1
    _SUFFIX = ".xc"

    def __init__(self, directory: str, max_bytes: int = 512 << 20):
        import threading

        self.directory = str(directory)
        self.max_bytes = int(max_bytes)
        self.hits = 0
        self.misses = 0
        self.dropped = 0       # corrupt/stale entries evicted on load
        self.store_failures = 0
        self._unsupported = False  # backend refused serialization
        # the env-default instance is shared across builder threads
        # (batcher workers compile buckets concurrently)
        self._lock = threading.Lock()

    def _count(self, attr: str):
        with self._lock:
            setattr(self, attr, getattr(self, attr) + 1)

    # -- key -----------------------------------------------------------
    def key_for(self, lowered, extra: Sequence[Any] = ()) -> str:
        """Cache key for one lowered program under the current backend."""
        import hashlib

        import jax
        import jaxlib

        h = hashlib.sha256()
        h.update(lowered.as_text().encode())
        devs = jax.devices()
        h.update(repr((self.VERSION, jax.__version__, jaxlib.__version__,
                       jax.default_backend(), len(devs),
                       getattr(devs[0], "device_kind", "?"),
                       tuple(extra))).encode())
        return h.hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, key + self._SUFFIX)

    # -- load ----------------------------------------------------------
    def load(self, key: str):
        """The compiled executable for ``key``, or None (miss / corrupt
        entry — corrupt entries are warned about and deleted so the
        recompile's store can replace them)."""
        import pickle

        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            self._count("misses")
            return None
        try:
            from jax.experimental import serialize_executable as _se

            payload = pickle.loads(blob)
            if payload.get("key") != key \
                    or payload.get("version") != self.VERSION:
                raise ValueError("entry key/version mismatch")
            compiled = _se.deserialize_and_load(
                payload["exec"], payload["in_tree"], payload["out_tree"])
        except Exception as e:  # noqa: BLE001 — ANY bad entry => recompile
            import warnings

            warnings.warn(
                "compile cache: corrupt or stale entry %s (%s: %s) — "
                "dropping it and recompiling" % (os.path.basename(path),
                                                 type(e).__name__, e),
                stacklevel=3)
            self._count("dropped")
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        try:  # refresh LRU recency
            os.utime(path)
        except OSError:
            pass
        self._count("hits")
        return compiled

    # -- store ---------------------------------------------------------
    def store(self, key: str, compiled) -> bool:
        """Serialize + publish one entry atomically (temp + fsync +
        rename through ``checkpoint._write_bytes`` — the choke point
        ``fault_injection.fail_writes`` interposes).  Best-effort: any
        failure warns and returns False; the caller already holds the
        freshly-compiled executable."""
        import pickle

        if self._unsupported:
            return False
        try:
            import jax
            from jax.experimental import serialize_executable as _se

            payload, in_tree, out_tree = _se.serialize(compiled)
            blob = pickle.dumps({"version": self.VERSION, "key": key,
                                 "jax": jax.__version__,
                                 "exec": payload, "in_tree": in_tree,
                                 "out_tree": out_tree})
        except Exception as e:  # noqa: BLE001 — some backends can't serialize
            import warnings

            self._unsupported = True
            self._count("store_failures")
            warnings.warn("compile cache: this backend cannot serialize "
                          "executables (%s: %s) — cache disabled for "
                          "stores this process" % (type(e).__name__, e),
                          stacklevel=3)
            return False
        from .checkpoint import _write_bytes

        path = self._path(key)
        tmp = path + ".tmp.%d" % os.getpid()
        try:
            os.makedirs(self.directory, exist_ok=True)
            _write_bytes(tmp, blob)
            os.replace(tmp, path)
        except OSError as e:
            import warnings

            self._count("store_failures")
            warnings.warn("compile cache: failed to store %s (%s) — "
                          "continuing uncached" % (os.path.basename(path),
                                                   e), stacklevel=3)
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        self._sweep()
        return True

    def _sweep(self):
        """Size-capped LRU: drop oldest-touched entries (and stray temp
        files) until the directory fits ``max_bytes``."""
        try:
            entries = []
            with os.scandir(self.directory) as it:
                for de in it:
                    if de.name.endswith(self._SUFFIX):
                        st = de.stat()
                        entries.append((st.st_mtime, st.st_size, de.path))
                    elif ".tmp." in de.name:
                        # a crashed writer's stage file: never visible as
                        # an entry, reap it past a grace period
                        st = de.stat()
                        if time.time() - st.st_mtime > 300:
                            os.remove(de.path)
        except OSError:
            return
        total = sum(s for _, s, _ in entries)
        if total <= self.max_bytes:
            return
        for _, size, path in sorted(entries):
            try:
                os.remove(path)
            except OSError:
                continue
            total -= size
            if total <= self.max_bytes:
                break


_DEFAULT_CACHES: Dict[Tuple[str, int], CompileCache] = {}


def default_compile_cache() -> Optional[CompileCache]:
    """The env-configured cache (``MXTPU_COMPILE_CACHE`` directory,
    ``MXTPU_COMPILE_CACHE_MB`` cap), or None when unset.  One
    :class:`CompileCache` instance per (dir, cap) so hit/miss counters
    aggregate across builders."""
    from .. import config as _cfg

    directory = str(_cfg.get("MXTPU_COMPILE_CACHE", "") or "").strip()
    if not directory:
        return None
    cap = int(_cfg.get("MXTPU_COMPILE_CACHE_MB", 512)) << 20
    key = (os.path.abspath(os.path.expanduser(directory)), cap)
    cache = _DEFAULT_CACHES.get(key)
    if cache is None:
        cache = _DEFAULT_CACHES[key] = CompileCache(key[0], max_bytes=cap)
    return cache


def compile_timed(traced, t_trace: float = 0.0, *,
                  cache: Optional[CompileCache] = None,
                  cache_extra: Sequence[Any] = ()) -> Tuple[object,
                                                            Dict[str, Any]]:
    """Lower + compile an already-traced program, returning
    ``(compiled, {"trace": s, "compile": s, "cache": ...})``.
    ``t_trace`` is the wall time the caller already spent tracing
    (lowering is part of the trace phase — it is Python/JAX work, not
    XLA).

    When a :class:`CompileCache` is active (explicit ``cache=`` or the
    ``MXTPU_COMPILE_CACHE`` env), the lowered program is looked up
    first: a hit deserializes the stored executable and reports
    ``compile: 0.0, cache: "hit"`` without touching XLA; a miss
    compiles, bumps :data:`XLA_COMPILES` and stores the result
    (``cache: "stored"``, or ``"store-failed"`` when serialization is
    unavailable).  ``cache_extra`` feeds the key — pass mesh shape +
    axis names and builder knobs so distinct configs can never collide;
    graftsched callers (TrainStep/ServeEngine) include the canonical
    ``PassSchedule`` hash here, so two schedules of the same program
    never share an executable while the SAME schedule cross-process
    hits at zero XLA compiles.
    """
    t0 = time.perf_counter()
    with profiler.Setup("mx.step.lower"):
        lowered = traced.lower()
    t_trace = t_trace + (time.perf_counter() - t0)
    if cache is None:
        cache = default_compile_cache()
    times: Dict[str, Any] = {"trace": t_trace}
    key = None
    if cache is not None:
        key = cache.key_for(lowered, extra=cache_extra)
        times["cache_key"] = key
        t0 = time.monotonic()
        hit = cache.load(key)
        if hit is not None:
            profiler.setup_span("mx.step.compile", t0, time.monotonic(),
                                cache="hit")
            times["cache"] = "hit"
            times["compile"] = 0.0
            return hit, times
    t0 = time.perf_counter()
    with profiler.Setup("mx.step.compile") as span:
        compiled = lowered.compile()
        span.args["cache"] = profiler.last_program_cache()
    XLA_COMPILES.bump()
    times["compile"] = time.perf_counter() - t0
    if cache is not None:
        times["cache"] = "stored" if cache.store(key, compiled) \
            else "store-failed"
    else:
        times["cache"] = "off"
    return compiled, times
