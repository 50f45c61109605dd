"""Search-based autotuner closing the graftcost loop (ROADMAP item 2).

PR 6 built the oracle — a trace-time cost model within ±15 % of measured
ResNet with GL201 eager rejection of infeasible configs *before any
compile* — and this module builds the search that consumes it, the TVM
recipe (arXiv:1802.04799) with a learned twist from value-function
performance models (arXiv:2011.14486):

1. **Enumerate** the knob space for a target workload — the fused train
   step's (``batch``, ``num_micro``, ``pipeline_stages``,
   ``pipeline_remat``, ``zero``, ``multi_precision``, ``loss_scale``)
   grid, optionally crossed with graftpass on/off knobs
   (``default_train_space(passes=...)`` — candidates are then ranked by
   their POST-pass CostReport, and a GL301/GL302-refused pipeline is
   rejected with zero compiles like a GL201 one), or the serving tier's
   (bucket set, flush deadline) grid.
2. **Rank** every candidate by the :class:`~.cost_model.CostReport`
   roofline — one abstract trace each, no compile, no execution — and
   **eagerly drop** anything GL201-infeasible (predicted peak memory
   over budget) with ZERO compiles spent: the rejected candidate's
   step never owned a compiled executable (``step._compiled is None``,
   stamped into the log as ``zero_compile``).
3. **Measure** only the top-K survivors on the real backend (K =
   ``budget_compiles``), each through the persistent compile cache
   (``parallel/aot.py``) so a retune pays trace-but-not-compile.
4. **Fit a learned residual** — a small per-category linear correction
   (compute / HBM / comm roofline seconds → measured seconds, least
   squares) on the measured pairs ``bench.py`` already logs both sides
   of — and **re-rank** the unmeasured remainder with the corrected
   predictions before spending the next measurement.

Every candidate lands in the JSON tuning log with its prediction and
either a measurement or a rejection reason — 100 % accounting, no
silent drops.  When no TPU is reachable the tuner degrades to the
CPU-mesh **proxy mode**: measurements are *relative* step times on the
``cpu-proxy`` device spec, stamped ``backend``/``tpu_unavailable``/
``relative_only`` — never silence (a bare zero reads as a 100 %
regression).

**graftsched** (ROADMAP item 6) extends step 1 from whole-pass on/off
knobs to per-site :class:`~.passes.PassSchedule` candidates, the Relay
move (arXiv:1810.00952): ONE report-everything pipeline run
(``TrainStep.analyze_schedule``) yields a per-site delta table, every
schedule in the space is ranked additively from it with zero further
traces, GL201/GL301/GL403-infeasible schedules are pruned zero-compile,
and the winner persists as a schedule-hash-stamped config that
``bench.py`` and ``ServeEngine(passes=)`` load directly.

Entry points: :func:`autotune_train`, :func:`autotune_serve`,
:func:`autotune_train_schedules`, :func:`schedule_site_table`,
:func:`default_schedule_space`, :func:`fit_residual`,
:func:`spearman`; the CLI is ``tools/autotune.py``; docs in
``docs/PERF.md`` §Autotuning and ``docs/PASSES.md`` §Schedules.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Candidate", "TuningResult", "autotune_train", "autotune_serve",
           "autotune_train_schedules", "backend_status",
           "default_schedule_space", "default_serve_space",
           "default_train_space", "dense_workload", "fit_residual",
           "schedule_site_table", "spearman"]


# ---------------------------------------------------------------------------
# backend status (the never-silence contract)
# ---------------------------------------------------------------------------

def backend_status() -> Tuple[str, bool]:
    """``(backend_name, tpu_unavailable)`` for the active jax backend.

    ``tpu_unavailable=True`` means every measurement below is a
    *relative* CPU-mesh number (proxy mode) — callers must stamp it
    into anything they persist, never record bare numbers that could
    read as a TPU regression."""
    import jax

    backend = jax.default_backend()
    return backend, backend != "tpu"


# ---------------------------------------------------------------------------
# candidates
# ---------------------------------------------------------------------------

@dataclass
class Candidate:
    """One point of the search space, with everything the tuning log
    owes about it: the prediction, and a measurement OR a rejection
    reason."""
    knobs: Dict[str, Any]
    status: str = "pending"  # predicted | rejected-infeasible |
    #                          rejected-invalid | measured | measure-error
    reason: Optional[str] = None
    pred: Dict[str, float] = field(default_factory=dict)
    #: predicted seconds per sample (the ranking score; lower is better)
    pred_sps: Optional[float] = None
    #: residual-corrected prediction (seconds per sample)
    corrected_sps: Optional[float] = None
    #: measured seconds per sample / per step (None until measured)
    measured_sps: Optional[float] = None
    measured_step_s: Optional[float] = None
    #: real XLA compiles this candidate cost (0 for rejected/cache-hit)
    compiles_spent: int = 0
    cache: Optional[str] = None   # compile-cache outcome of the measure
    #: True when the candidate was rejected without ever owning a
    #: compiled executable (``step._compiled is None`` at rejection)
    zero_compile: Optional[bool] = None
    #: measurement detail (e.g. the serve target's LoadReport excerpt)
    detail: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {"knobs": dict(self.knobs), "status": self.status,
                "reason": self.reason, "pred": dict(self.pred),
                "pred_s_per_sample": self.pred_sps,
                "corrected_s_per_sample": self.corrected_sps,
                "measured_s_per_sample": self.measured_sps,
                "measured_step_s": self.measured_step_s,
                "compiles_spent": self.compiles_spent,
                "cache": self.cache,
                "zero_compile": self.zero_compile,
                "detail": dict(self.detail)}


@dataclass
class TuningResult:
    """One tuning run: the full candidate ledger + winner + residual.

    ``accounted()`` is the 100 %-accounting contract: every candidate
    carries a prediction and either a measurement or a rejection
    reason."""
    target: str = "train"
    backend: str = "cpu"
    tpu_unavailable: bool = True
    relative_only: bool = True
    device: str = "cpu-proxy"
    hbm_budget: Optional[float] = None
    budget_compiles: int = 0
    compiles_spent: int = 0
    candidates: List[Candidate] = field(default_factory=list)
    winner: Optional[Candidate] = None
    default: Optional[Candidate] = None
    residual: Optional[Dict[str, Any]] = None
    wall_s: float = 0.0

    def accounted(self) -> bool:
        for c in self.candidates:
            if c.status == "pending":
                return False
            if c.status.startswith("rejected") and not c.reason:
                return False
            if c.status == "measured" and c.measured_sps is None:
                return False
        return True

    def to_dict(self) -> Dict[str, Any]:
        return {
            "version": 1,
            "target": self.target,
            "backend": self.backend,
            "tpu_unavailable": self.tpu_unavailable,
            "relative_only": self.relative_only,
            "device": self.device,
            "hbm_budget": self.hbm_budget,
            "budget_compiles": self.budget_compiles,
            "compiles_spent": self.compiles_spent,
            "space_size": len(self.candidates),
            "accounted": self.accounted(),
            "candidates": [c.to_dict() for c in self.candidates],
            "winner": None if self.winner is None else self.winner.to_dict(),
            "default": None if self.default is None
            else self.default.to_dict(),
            "residual": self.residual,
            "wall_s": self.wall_s,
        }

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def write_log(self, path: str) -> None:
        """Publish the tuning log atomically (temp + replace)."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_json(indent=2))
        os.replace(tmp, path)

    def best_predicted(self) -> Optional["Candidate"]:
        """The best candidate by (residual-corrected, else raw)
        predicted seconds-per-sample among the non-rejected — the
        zero-compile ranking answer when ``budget_compiles=0`` leaves
        no measured winner."""
        pool = [c for c in self.candidates
                if c.status in ("predicted", "measured")
                and c.pred_sps is not None]
        if not pool:
            return None
        return min(pool, key=lambda c: c.corrected_sps
                   if c.corrected_sps is not None else c.pred_sps)

    def winner_config(self) -> Optional[Dict[str, Any]]:
        """The winner's knob dict in the shape ``bench.py`` /
        ``Trainer.make_fused_step`` consume, stamped with provenance
        (backend, relative-only) so a CPU-proxy winner can never be
        mistaken for a measured-on-TPU one.  Schedule-search winners
        carry their canonical ``schedule`` dict and ``schedule_hash``
        inside ``knobs`` — loadable straight into
        ``make_train_step(passes=...)`` / ``ServeEngine(passes=...)``.
        With ``budget_compiles=0`` (pure zero-compile ranking) the
        best *predicted* candidate stands in, ``measured_s_per_sample``
        None."""
        w = self.winner or self.best_predicted()
        if w is None:
            return None
        return {"target": self.target, "knobs": dict(w.knobs),
                "measured_s_per_sample": w.measured_sps,
                "backend": self.backend,
                "tpu_unavailable": self.tpu_unavailable,
                "relative_only": self.relative_only}


# ---------------------------------------------------------------------------
# rank statistics + the learned residual
# ---------------------------------------------------------------------------

def _ranks(xs: Sequence[float]) -> np.ndarray:
    order = np.argsort(np.asarray(xs, dtype=np.float64), kind="stable")
    ranks = np.empty(len(xs), dtype=np.float64)
    ranks[order] = np.arange(len(xs), dtype=np.float64)
    # average ties so equal predictions don't fake correlation
    vals = np.asarray(xs, dtype=np.float64)
    for v in np.unique(vals):
        m = vals == v
        if m.sum() > 1:
            ranks[m] = ranks[m].mean()
    return ranks


def spearman(xs: Sequence[float], ys: Sequence[float]) -> float:
    """Spearman rank correlation (tie-aware; 0.0 when degenerate)."""
    if len(xs) != len(ys) or len(xs) < 2:
        return 0.0
    rx, ry = _ranks(xs), _ranks(ys)
    sx, sy = rx.std(), ry.std()
    if sx == 0 or sy == 0:
        return 0.0
    return float(np.corrcoef(rx, ry)[0, 1])


#: residual feature vector: the per-category roofline seconds the cost
#: model attributes to one candidate (+ intercept)
_RESIDUAL_FEATURES = ("compute_s", "hbm_s", "comm_s")


def _features(pred: Dict[str, float]) -> List[float]:
    return [float(pred.get(k, 0.0)) for k in _RESIDUAL_FEATURES] + [1.0]


def fit_residual(preds: Sequence[Dict[str, float]],
                 measured_s: Sequence[float]) -> Optional[np.ndarray]:
    """Least-squares fit of measured seconds against the per-category
    predicted roofline seconds (compute / HBM / comm + intercept) — the
    learned correction for systematic prediction-vs-measured drift
    (e.g. a backend whose effective HBM bandwidth is half the spec'd
    peak).  Returns the coefficient vector, or None with fewer pairs
    than features (an underdetermined fit would rank on noise)."""
    if len(preds) != len(measured_s) or len(preds) < len(
            _RESIDUAL_FEATURES) + 1:
        return None
    X = np.asarray([_features(p) for p in preds], dtype=np.float64)
    y = np.asarray(measured_s, dtype=np.float64)
    beta, *_ = np.linalg.lstsq(X, y, rcond=None)
    return beta


def apply_residual(beta: Optional[np.ndarray],
                   pred: Dict[str, float]) -> Optional[float]:
    """Corrected step-seconds for one candidate (floored at a nominal
    positive epsilon — a linear fit can extrapolate below zero)."""
    if beta is None:
        return None
    return float(max(np.dot(_features(pred), beta), 1e-9))


# ---------------------------------------------------------------------------
# train target
# ---------------------------------------------------------------------------

def dense_workload(feat: int = 16, layers: int = 4, classes: int = 4,
                   seed: int = 3):
    """The test-net workload (the ``tests/test_zero_sharding.py`` Dense
    stack): returns ``(make_net, make_batch, loss_fn)`` for
    :func:`autotune_train`.  ``make_net(knobs)`` builds a freshly
    seeded net per candidate so measurements never inherit a previous
    candidate's updated weights."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.gluon import nn

    def make_net(knobs):
        mx.random.seed(seed)
        net = nn.HybridSequential()
        for _ in range(layers):
            net.add(nn.Dense(feat, activation="tanh"))
        net.initialize(init=mx.init.Xavier())
        net(nd.ones((2, feat)))
        return net

    def make_batch(knobs):
        rng = np.random.RandomState(0)
        b = int(knobs.get("batch", 16))
        x = nd.array(rng.rand(b, feat).astype(np.float32))
        y = nd.array((np.arange(b) % classes).astype(np.float32))
        return x, y

    return make_net, make_batch, gluon.loss.SoftmaxCrossEntropyLoss()


def default_train_space(mesh_axes: Optional[Dict[str, int]] = None,
                        batches: Sequence[int] = (8, 16, 32),
                        passes: Sequence[Any] = ()
                        ) -> List[Dict[str, Any]]:
    """The default train-step knob grid: ``batch`` × ``zero`` ×
    ``multi_precision`` × ``loss_scale`` (24 candidates on a dp-only
    mesh), plus ``pipeline_stages``/``num_micro``/``pipeline_remat``
    combinations when the mesh has a ``pp`` axis.  ``zero=1`` knobs are
    only emitted when the mesh has a ``dp`` axis (elsewhere they would
    all be rejected-invalid noise, not search space).

    ``passes`` — graftpass names (``analysis/passes.py`` registry):
    each becomes an on/off knob crossed into the grid, so the tuner
    ranks REWRITTEN candidates by their post-pass CostReport (the
    costed program is the one that would compile).  A candidate whose
    pipeline is refused — GL301 contract violation, GL302 re-lint —
    is rejected exactly like a GL201-infeasible one: with its reason
    in the ledger and zero compiles spent."""
    mesh_axes = dict(mesh_axes or {})
    has_dp = "dp" in mesh_axes
    pp = int(mesh_axes.get("pp", 0))
    space: List[Dict[str, Any]] = []
    for batch in batches:
        for zero in ((0, 1) if has_dp else (0,)):
            for mp in (False, True):
                for scale in (None, "dynamic"):
                    space.append({"batch": int(batch), "zero": zero,
                                  "multi_precision": mp,
                                  "loss_scale": scale,
                                  "pipeline_stages": None, "num_micro": 1,
                                  "pipeline_remat": False})
        if pp > 1:
            for num_micro in (2, 4):
                for remat in (False, True):
                    space.append({"batch": int(batch), "zero": 0,
                                  "multi_precision": False,
                                  "loss_scale": None,
                                  "pipeline_stages": pp,
                                  "num_micro": num_micro,
                                  "pipeline_remat": remat})
    if passes:
        import itertools

        names = [p if isinstance(p, str) else getattr(p, "name", str(p))
                 for p in passes]
        expanded = []
        for entry in space:
            for mask in itertools.product((False, True),
                                          repeat=len(names)):
                e = dict(entry)
                e["passes"] = tuple(n for n, on in zip(names, mask) if on)
                expanded.append(e)
        space = expanded
    return space


def _build_train_step(make_net, loss_fn, knobs, mesh, numerics="off",
                      input_range=None):
    from ..parallel import make_train_step

    net = make_net(knobs)
    kw: Dict[str, Any] = {"optimizer": knobs.get("optimizer", "sgd"),
                          "learning_rate": 0.1}
    if kw["optimizer"] == "sgd":
        kw["momentum"] = 0.9
    if knobs.get("multi_precision"):
        kw["multi_precision"] = True
    # explicit () — a candidate without the knob must not inherit
    # MXTPU_PASSES, or every candidate would silently carry it.  A
    # "schedule" knob (the canonical PassSchedule dict graftsched logs)
    # outranks the whole-pass "passes" list.
    pass_cfg = knobs.get("passes", ())
    if knobs.get("schedule") is not None:
        from .passes import PassSchedule

        pass_cfg = PassSchedule.from_dict(knobs["schedule"])
    return make_train_step(
        net, loss_fn, mesh=mesh, zero=int(knobs.get("zero", 0)),
        pipeline_stages=knobs.get("pipeline_stages"),
        num_micro=int(knobs.get("num_micro", 1)),
        pipeline_remat=bool(knobs.get("pipeline_remat", False)),
        loss_scale=knobs.get("loss_scale"),
        compute_dtype=knobs.get("compute_dtype"),
        passes=pass_cfg,
        lint="off", cost="off", numerics=numerics,
        input_range=input_range, **kw)


def _predict_train(c: Candidate, make_net, make_batch, loss_fn, mesh,
                   device: str, hbm_budget: Optional[float],
                   numerics: str = "off", input_range=None) -> None:
    """Phase 2 for one candidate: build + abstract-trace + cost, GL201
    pruning — and, with ``numerics`` on, graftrange GL403/GL405
    pruning: a candidate whose amp_bf16 pipeline is refused on an
    out-of-bf16-range edge, or whose loss-scale config provably
    overflows, is rejected exactly like a GL201 one.  Never compiles —
    the built step is dropped with ``_compiled is None``, recorded as
    ``zero_compile``."""
    from .diagnostics import LintError, Severity

    try:
        step = _build_train_step(make_net, loss_fn, c.knobs, mesh,
                                 numerics=numerics,
                                 input_range=input_range)
        x, y = make_batch(c.knobs)
        report = step.analyze_cost(x, y, device=device,
                                   hbm_budget=hbm_budget)
    except LintError as e:
        # a GL301/GL302/GL403 pipeline refusal: infeasible, not a bug
        # in the knobs — ledger it with the codes, zero compiles spent
        codes = sorted({d.code for d in e.report.diagnostics})
        c.status = "rejected-infeasible"
        c.reason = "%s: %s" % ("/".join(codes) or "lint",
                               str(e).split("\n", 1)[0])
        c.zero_compile = True
        return
    except Exception as e:  # noqa: BLE001 — invalid knob combos are data
        c.status = "rejected-invalid"
        c.reason = "%s: %s" % (type(e).__name__, e)
        c.zero_compile = True
        return
    rf = report.roofline()
    batch = int(c.knobs.get("batch", 1))
    c.pred = {"compute_s": rf["compute_s"], "hbm_s": rf["hbm_s"],
              "comm_s": rf["comm_s"], "step_s": rf["step_s"],
              "hbm_bytes": report.hbm_bytes,
              "peak_bytes": report.peak_bytes,
              "flops": report.total_flops}
    c.pred_sps = rf["step_s"] / max(batch, 1)
    c.zero_compile = step._compiled is None  # invariant: no compile paid
    gl201 = [d for d in report.diagnostics if d.code == "GL201"]
    if gl201:
        c.status = "rejected-infeasible"
        c.reason = "%s: %s" % (gl201[0].code, gl201[0].message)
        return
    if numerics == "error":
        # pruning is the ERROR-mode contract; "warn" keeps the
        # candidate ranked and only surfaces advisories (the step's
        # own warn machinery), exactly like lint="warn" vs "error"
        try:
            nrep = step.analyze_numerics(x, y)
        except LintError as e:
            nerr = list(e.report.diagnostics)
        else:
            nerr = [d for d in nrep.diagnostics
                    if d.severity >= Severity.ERROR]
        if nerr:
            c.status = "rejected-infeasible"
            c.reason = "%s: %s" % (nerr[0].code, nerr[0].message)
            return
    c.status = "predicted"


def _measure_train(c: Candidate, make_net, make_batch, loss_fn, mesh,
                   cache, warmup: int, iters: int,
                   numerics: str = "off", input_range=None) -> None:
    """Phase 3 for one candidate: rebuild fresh (a measured candidate's
    donated params were mutated), AOT-compile through the persistent
    cache, and time ``iters`` real steps."""
    from ..parallel import aot

    try:
        step = _build_train_step(make_net, loss_fn, c.knobs, mesh,
                                 numerics=numerics,
                                 input_range=input_range)
        x, y = make_batch(c.knobs)
        c0 = aot.XLA_COMPILES.count
        times = step.aot_compile(x, y, cache=cache)
        c.compiles_spent = aot.XLA_COMPILES.count - c0
        c.cache = times.get("cache")
        for _ in range(max(warmup, 1)):
            loss = step(x, y)
        loss.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(max(iters, 1)):
            loss = step(x, y)
        loss.wait_to_read()
        dt = (time.perf_counter() - t0) / max(iters, 1)
    except Exception as e:  # noqa: BLE001 — a failed measure is DATA,
        #                     never silence (the r04/r05 lesson)
        c.status = "measure-error"
        c.reason = "%s: %s" % (type(e).__name__, e)
        return
    c.measured_step_s = dt
    c.measured_sps = dt / max(int(c.knobs.get("batch", 1)), 1)
    c.status = "measured"


def _refine_loop(candidates: List[Candidate], measure_fn,
                 budget: int, default_idx: Optional[int],
                 score_of: Callable[[Candidate], float]
                 ) -> Tuple[Optional[np.ndarray], Dict[str, Any]]:
    """The shared measured-refinement loop: spend ``budget``
    measurements best-predicted-first, refitting the residual after
    every measurement (once enough pairs exist) and re-ranking the
    unmeasured remainder with corrected predictions.  The default
    config (``default_idx``) is measured first so the winner always has
    a baseline to beat.  Returns ``(beta, residual_info)``."""
    beta: Optional[np.ndarray] = None
    measured: List[Candidate] = []

    def refit():
        nonlocal beta
        pairs = [(c.pred, c.measured_step_s) for c in measured
                 if c.pred and c.measured_step_s is not None]
        beta = fit_residual([p for p, _ in pairs], [m for _, m in pairs])
        if beta is not None:
            for c in candidates:
                if c.pred:
                    corr = apply_residual(beta, c.pred)
                    c.corrected_sps = corr / max(
                        int(c.knobs.get("batch", 1)), 1)

    spent = 0
    if default_idx is not None and budget > 0:
        c = candidates[default_idx]
        if c.status == "predicted":
            measure_fn(c)
            spent += 1
            if c.status == "measured":
                measured.append(c)
                refit()
    while spent < budget:
        pool = [c for c in candidates if c.status == "predicted"]
        if not pool:
            break
        c = min(pool, key=score_of)
        measure_fn(c)
        spent += 1
        if c.status == "measured":
            measured.append(c)
            refit()
    info: Dict[str, Any] = None
    if measured:
        pred_scores = [c.pred_sps for c in measured]
        meas_scores = [c.measured_sps for c in measured]
        info = {"n_pairs": len(measured),
                "features": list(_RESIDUAL_FEATURES) + ["intercept"],
                "beta": None if beta is None else [float(b) for b in beta],
                "spearman_predicted": spearman(pred_scores, meas_scores)}
        if beta is not None:
            corr_scores = [apply_residual(beta, c.pred) /
                           max(int(c.knobs.get("batch", 1)), 1)
                           for c in measured]
            info["spearman_corrected"] = spearman(corr_scores, meas_scores)
    return beta, info


def autotune_train(make_net=None, make_batch=None, loss_fn=None,
                   space: Optional[List[Dict[str, Any]]] = None,
                   mesh=None, device: str = "cpu-proxy",
                   hbm_budget: Optional[float] = None,
                   budget_compiles: int = 5,
                   default_knobs: Optional[Dict[str, Any]] = None,
                   warmup: int = 1, iters: int = 3,
                   cache=None, numerics: str = "off", input_range=None,
                   log_path: Optional[str] = None) -> TuningResult:
    """Tune the fused train step over ``space`` (default:
    :func:`default_train_space` on the mesh's axes; workload default:
    :func:`dense_workload`).

    Ranking is pure graftcost (one abstract trace per candidate, zero
    compiles); GL201-infeasible and invalid-knob candidates are
    rejected eagerly.  ``budget_compiles`` bounds how many candidates
    reach the real backend — each costs at most one XLA compile, and a
    warm persistent compile cache (``cache=`` /
    ``MXTPU_COMPILE_CACHE``) makes re-measures trace-only.  The
    residual fit re-ranks the unmeasured remainder after every
    measurement.  ``default_knobs`` (default: the first space entry) is
    measured first as the baseline.  The winner is the best *measured*
    seconds-per-sample.  ``log_path`` writes the JSON tuning log
    atomically.

    ``numerics``/``input_range`` switch on the graftrange value-range
    gate per candidate (``analysis/value_range.py``): a candidate whose
    ``amp_bf16`` pipeline is refused on an out-of-bf16-range edge
    (GL403) or whose loss-scale config provably overflows (GL405) is
    rejected with ZERO compiles spent, exactly like GL201/GL301.
    """
    t_start = time.time()
    if make_net is None or make_batch is None or loss_fn is None:
        make_net, make_batch, loss_fn = dense_workload()
    mesh_axes = None if mesh is None else \
        {str(a): int(s) for a, s in dict(mesh.shape).items()}
    if space is None:
        space = default_train_space(mesh_axes)
    if not space:
        raise ValueError("empty search space")
    backend, tpu_unavailable = backend_status()
    result = TuningResult(target="train", backend=backend,
                          tpu_unavailable=tpu_unavailable,
                          relative_only=tpu_unavailable, device=device,
                          hbm_budget=hbm_budget,
                          budget_compiles=int(budget_compiles))
    result.candidates = [Candidate(knobs=dict(k)) for k in space]

    for c in result.candidates:
        _predict_train(c, make_net, make_batch, loss_fn, mesh, device,
                       hbm_budget, numerics=numerics,
                       input_range=input_range)

    default_idx = None
    if default_knobs is None and result.candidates:
        default_idx = 0
    elif default_knobs is not None:
        for i, c in enumerate(result.candidates):
            if c.knobs == default_knobs:
                default_idx = i
                break
        else:
            result.candidates.append(Candidate(knobs=dict(default_knobs)))
            default_idx = len(result.candidates) - 1
            _predict_train(result.candidates[default_idx], make_net,
                           make_batch, loss_fn, mesh, device, hbm_budget,
                           numerics=numerics, input_range=input_range)

    from ..parallel import aot

    c0 = aot.XLA_COMPILES.count
    _, residual_info = _refine_loop(
        result.candidates,
        lambda c: _measure_train(c, make_net, make_batch, loss_fn, mesh,
                                 cache, warmup, iters, numerics=numerics,
                                 input_range=input_range),
        int(budget_compiles), default_idx,
        lambda c: c.corrected_sps if c.corrected_sps is not None
        else (c.pred_sps if c.pred_sps is not None else float("inf")))
    result.compiles_spent = aot.XLA_COMPILES.count - c0
    result.residual = residual_info

    measured = [c for c in result.candidates if c.status == "measured"]
    if measured:
        result.winner = min(measured, key=lambda c: c.measured_sps)
    if default_idx is not None:
        result.default = result.candidates[default_idx]
    result.wall_s = time.time() - t_start
    if log_path:
        result.write_log(log_path)
    return result


# ---------------------------------------------------------------------------
# graftsched: per-site schedule search (train knobs × schedules)
# ---------------------------------------------------------------------------

def schedule_site_table(make_net, make_batch, loss_fn, passes,
                        mesh=None, knobs: Optional[Dict[str, Any]] = None,
                        device: str = "cpu-proxy",
                        hbm_budget: Optional[float] = None,
                        numerics: str = "off", input_range=None
                        ) -> Dict[str, Any]:
    """The per-site delta table behind the schedule search: ONE
    report-everything all-sites pipeline run
    (``TrainStep.analyze_schedule``) plus ONE base (no-pass) cost
    trace, zero compiles.  Returns::

        {"receipts": [PassReceipt...],   # all-sites run, .sites rows
         "base": CostReport,             # the passes=() program
         "pass_names": (...),
         "refused": {pass_name: "GLxxx: ..."}}  # ERROR-refused passes

    Every schedule candidate over ``passes`` is then ranked additively
    from the rows — no per-candidate trace."""
    from .diagnostics import Severity

    knobs = dict(knobs or {})
    names = tuple(p if isinstance(p, str) else getattr(p, "name", str(p))
                  for p in passes)
    sched_knobs = dict(knobs)
    sched_knobs["passes"] = names
    step = _build_train_step(make_net, loss_fn, sched_knobs, mesh,
                             numerics=numerics, input_range=input_range)
    x, y = make_batch(sched_knobs)
    pipeline = step.analyze_schedule(x, y)
    refused: Dict[str, str] = {}
    for r in pipeline.receipts:
        err = [d for d in r.diagnostics if d.severity >= Severity.ERROR]
        if err and not r.installed:
            refused[r.name] = "%s: %s" % (err[0].code,
                                          err[0].message.split("\n")[0])
    base_knobs = dict(knobs)
    base_knobs["passes"] = ()
    base_step = _build_train_step(make_net, loss_fn, base_knobs, mesh,
                                  numerics=numerics,
                                  input_range=input_range)
    base = base_step.analyze_cost(x, y, device=device,
                                  hbm_budget=hbm_budget)
    return {"receipts": list(pipeline.receipts), "base": base,
            "pass_names": names, "refused": refused}


def _schedule_delta(sched, receipts) -> Tuple[float, float, float,
                                              List[str]]:
    """Additive ``(flops, hbm_bytes, peak_bytes)`` delta of one
    schedule, summed from the all-sites run's per-site receipt rows
    (site-aware passes) or whole-receipt deltas (whole-program passes).
    Fourth element: names of enabled-but-ERROR-refused passes — a
    schedule turning one on is infeasible."""
    from .passes import PassSchedule  # noqa: F401  (doc anchor)
    from .diagnostics import Severity

    d_fl = d_by = d_pk = 0.0
    refused: List[str] = []
    by_name = {}
    for r in receipts:
        by_name.setdefault(r.name, r)
    for name, dec in sched.entries:
        r = by_name.get(name)
        if r is None:
            continue
        enabled = any(dec.values()) if isinstance(dec, dict) else bool(dec)
        if not enabled:
            continue
        if any(d.severity >= Severity.ERROR for d in r.diagnostics) \
                and not r.installed:
            refused.append(name)
            continue
        rows = r.sites
        if rows is None:
            # whole-program pass: all-or-nothing
            d_fl += r.flops_after - r.flops_before
            d_by += r.hbm_bytes_after - r.hbm_bytes_before
            d_pk += r.peak_bytes_after - r.peak_bytes_before
            continue
        on = None if dec is True else {s for s, v in dec.items() if v}
        full = True
        for row in rows:
            if not row["installed"]:
                continue
            if on is not None and row["site"] not in on:
                full = False
                continue
            d_fl += row["flops_delta"]
            d_by += row["hbm_bytes_delta"]
        if full:
            # only a full-pass enable may claim the whole peak delta —
            # peak is a max, not a sum, so partial credit would lie
            d_pk += r.peak_bytes_after - r.peak_bytes_before
    return d_fl, d_by, d_pk, refused


def default_schedule_space(table: Dict[str, Any],
                           max_candidates: int = 24) -> List[Any]:
    """The default schedule space over one site table: all-on, all-off,
    each pass solo, beneficial-sites-only (every site whose attributed
    HBM-bytes delta is negative), and per-pass single-site probes —
    deduped by canonical hash, capped at ``max_candidates`` (dropped
    count is the caller's to log).  Returns ``PassSchedule`` objects."""
    from .passes import PassSchedule

    names = list(table["pass_names"])
    rows_of = {r.name: r.sites for r in table["receipts"]}
    out: List[PassSchedule] = []
    out.append(PassSchedule([(n, True) for n in names]))       # all-on
    out.append(PassSchedule([(n, False) for n in names]))      # all-off
    for n in names:                                            # solos
        out.append(PassSchedule([(m, m == n) for m in names]))
    # beneficial-only: keep the sites that predicted a bytes win
    dec = []
    for n in names:
        rows = rows_of.get(n)
        if rows is None:
            r = next(r for r in table["receipts"] if r.name == n)
            dec.append((n, r.hbm_bytes_after < r.hbm_bytes_before
                        or r.installed))
            continue
        good = {row["site"]: True for row in rows
                if row["installed"] and row["hbm_bytes_delta"] < 0}
        dec.append((n, good if good else False))
    out.append(PassSchedule(dec))
    # single-site probes: one site of one pass, everything else off
    for n in names:
        for row in (rows_of.get(n) or []):
            if not row["installed"]:
                continue
            out.append(PassSchedule(
                [(m, {row["site"]: True} if m == n else False)
                 for m in names]))
    seen, deduped = set(), []
    for s in out:
        h = s.hash()
        if h in seen:
            continue
        seen.add(h)
        deduped.append(s)
    return deduped[:max_candidates]


def autotune_train_schedules(make_net=None, make_batch=None, loss_fn=None,
                             passes: Sequence[Any] = (),
                             schedules: Optional[Sequence[Any]] = None,
                             knobs: Optional[Dict[str, Any]] = None,
                             mesh=None, device: str = "cpu-proxy",
                             hbm_budget: Optional[float] = None,
                             budget_compiles: int = 0,
                             warmup: int = 1, iters: int = 3,
                             cache=None, numerics: str = "off",
                             input_range=None,
                             log_path: Optional[str] = None
                             ) -> TuningResult:
    """Search (train knobs × per-site pass schedules) jointly — the
    graftsched closing of the loop.  ``knobs`` pins the train knobs
    (batch etc.); ``schedules`` (default
    :func:`default_schedule_space`) are the
    :class:`~.passes.PassSchedule` candidates over ``passes``.

    Ranking spends ONE all-sites pipeline trace + ONE base cost trace
    total (:func:`schedule_site_table`); every schedule is predicted
    additively from the per-site delta rows — rejected candidates
    never own a trace, let alone a compile (``zero_compile=True`` in
    the ledger).  A schedule enabling an ERROR-refused pass
    (GL301/GL302/GL403) or predicting over ``hbm_budget`` (GL201) is
    pruned eagerly.  ``budget_compiles`` then measures the top
    survivors exactly like :func:`autotune_train` — the compile cache
    keys on the schedule hash, so two schedules never collide and a
    re-tune is trace-only.  The winner's knobs carry
    ``schedule``/``schedule_hash``, loadable by ``bench.py`` and
    ``ServeEngine(passes=)``."""
    t_start = time.time()
    if make_net is None or make_batch is None or loss_fn is None:
        make_net, make_batch, loss_fn = dense_workload()
    backend, tpu_unavailable = backend_status()
    result = TuningResult(target="train-schedule", backend=backend,
                          tpu_unavailable=tpu_unavailable,
                          relative_only=tpu_unavailable, device=device,
                          hbm_budget=hbm_budget,
                          budget_compiles=int(budget_compiles))
    table = schedule_site_table(make_net, make_batch, loss_fn, passes,
                                mesh=mesh, knobs=knobs, device=device,
                                hbm_budget=hbm_budget, numerics=numerics,
                                input_range=input_range)
    if schedules is None:
        schedules = default_schedule_space(table)
    base = table["base"]
    rf = base.roofline()
    knobs = dict(knobs or {})
    batch = int(knobs.get("batch", 16))
    from .passes import PassSchedule

    for sched in schedules:
        if not isinstance(sched, PassSchedule):
            sched = PassSchedule.from_dict(sched)
        c = Candidate(knobs=dict(knobs))
        c.knobs["schedule"] = sched.canonical()
        c.knobs["schedule_hash"] = sched.hash()
        result.candidates.append(c)
        d_fl, d_by, d_pk, refused = _schedule_delta(
            sched, table["receipts"])
        c.zero_compile = True
        if refused:
            c.status = "rejected-infeasible"
            c.reason = "; ".join("%s (%s)" % (table["refused"].get(
                n, "refused"), n) for n in refused)
            continue
        flops = max(base.total_flops + d_fl, 0.0)
        hbm = max(base.hbm_bytes + d_by, 0.0)
        peak = max(base.peak_bytes + d_pk, 0.0)
        compute_s = rf["compute_s"] * (flops / base.total_flops
                                       if base.total_flops else 1.0)
        hbm_s = rf["hbm_s"] * (hbm / base.hbm_bytes
                               if base.hbm_bytes else 1.0)
        step_s = max(compute_s, hbm_s, rf["comm_s"])
        c.pred = {"compute_s": compute_s, "hbm_s": hbm_s,
                  "comm_s": rf["comm_s"], "step_s": step_s,
                  "hbm_bytes": hbm, "peak_bytes": peak, "flops": flops}
        c.pred_sps = step_s / max(batch, 1)
        if hbm_budget is not None and peak > float(hbm_budget):
            c.status = "rejected-infeasible"
            c.reason = ("GL201: predicted peak %.1f MB over the %.1f MB "
                        "budget" % (peak / 1e6, float(hbm_budget) / 1e6))
            continue
        c.status = "predicted"

    from ..parallel import aot

    c0 = aot.XLA_COMPILES.count
    _, residual_info = _refine_loop(
        result.candidates,
        lambda c: _measure_train(c, make_net, make_batch, loss_fn, mesh,
                                 cache, warmup, iters, numerics=numerics,
                                 input_range=input_range),
        int(budget_compiles), None,
        lambda c: c.corrected_sps if c.corrected_sps is not None
        else (c.pred_sps if c.pred_sps is not None else float("inf")))
    result.compiles_spent = aot.XLA_COMPILES.count - c0
    result.residual = residual_info

    measured = [c for c in result.candidates if c.status == "measured"]
    if measured:
        result.winner = min(measured, key=lambda c: c.measured_sps)
    result.wall_s = time.time() - t_start
    if log_path:
        result.write_log(log_path)
    return result


# ---------------------------------------------------------------------------
# serve target: bucket set + flush-deadline policy
# ---------------------------------------------------------------------------

def default_serve_space(max_bucket: int = 16,
                        delays_ms: Sequence[float] = (2.0, 5.0, 10.0)
                        ) -> List[Dict[str, Any]]:
    """The serving policy grid: bucket sets (1-, 2- and 3-point ladders
    up to ``max_bucket``) × flush deadlines.  Deduped — at small
    ``max_bucket`` several ladder formulas collapse to the same set,
    and a duplicate policy would burn a measurement re-measuring it."""
    b = int(max_bucket)
    bucket_sets = [(b,), (max(1, b // 4), b), (max(1, b // 4), b // 2, b),
                   (b // 2, b)]
    seen = set()
    space = []
    for bs in bucket_sets:
        for d in delays_ms:
            key = (tuple(sorted(set(x for x in bs if x >= 1))), float(d))
            if key in seen:
                continue
            seen.add(key)
            space.append({"buckets": key[0], "max_delay_ms": key[1]})
    return space


def _predict_serve(c: Candidate, net, sample_shape, device: str,
                   hbm_budget: Optional[float], report_cache: Dict) -> None:
    """Rank one serving policy without compiling: cost the inference
    program per bucket (abstract trace via ``pure_forward``), predicted
    latency proxy = flush deadline + largest-bucket roofline service
    time.  GL201 on any bucket rejects the whole policy eagerly."""
    import jax

    from .cost_model import analyze_traceable
    from ..gluon.block import pure_forward

    params = list(net.collect_params().values())
    p_vals = [p._data._data for p in params]

    try:
        worst_peak = 0.0
        service_s = 0.0
        hbm_bytes = 0.0
        for b in c.knobs["buckets"]:
            rep = report_cache.get(b)
            if rep is None:
                x = jax.ShapeDtypeStruct((int(b),) + tuple(sample_shape),
                                         np.float32)
                rep = analyze_traceable(
                    lambda xv: pure_forward(net, params, p_vals, (xv,))[0],
                    (x,), device=device, hbm_budget=hbm_budget)
                report_cache[b] = rep
            rf = rep.roofline()
            service_s = max(service_s, rf["step_s"])
            worst_peak = max(worst_peak, rep.peak_bytes)
            hbm_bytes = max(hbm_bytes, rep.hbm_bytes)
            gl201 = [d for d in rep.diagnostics if d.code == "GL201"]
            if gl201:
                c.status = "rejected-infeasible"
                c.reason = "GL201 (bucket %d): %s" % (b, gl201[0].message)
                c.zero_compile = True
                return
        delay_s = c.knobs["max_delay_ms"] / 1e3
        c.pred = {"compute_s": 0.0, "hbm_s": service_s, "comm_s": 0.0,
                  "step_s": service_s, "service_s": service_s,
                  "peak_bytes": worst_peak, "hbm_bytes": hbm_bytes,
                  "latency_proxy_s": delay_s + service_s}
        c.pred_sps = delay_s + service_s
        c.zero_compile = True
        c.status = "predicted"
    except Exception as e:  # noqa: BLE001
        c.status = "rejected-invalid"
        c.reason = "%s: %s" % (type(e).__name__, e)
        c.zero_compile = True


def _measure_serve(c: Candidate, net, sample, qps: float, n_requests: int,
                   mesh, seed: int) -> None:
    """Measure one serving policy against the open-loop Poisson
    loadtest: real engine, real batcher, ``LoadReport.objective()`` as
    the score (seconds, lower is better)."""
    from ..parallel import aot
    from ..serve import ContinuousBatcher, ServeEngine, poisson_loadtest

    try:
        c0 = aot.XLA_COMPILES.count
        eng = ServeEngine(net, buckets=tuple(c.knobs["buckets"]),
                          mesh=mesh, lint="off", cost="off")
        eng.warmup(np.asarray(sample, np.float32))
        c.compiles_spent = aot.XLA_COMPILES.count - c0
        batcher = ContinuousBatcher(
            eng, max_delay=c.knobs["max_delay_ms"] / 1e3)
        try:
            rep = poisson_loadtest(batcher,
                                   lambda i, rng: np.asarray(sample,
                                                             np.float32),
                                   qps=qps, n_requests=n_requests,
                                   seed=seed)
        finally:
            batcher.close()
    except Exception as e:  # noqa: BLE001
        c.status = "measure-error"
        c.reason = "%s: %s" % (type(e).__name__, e)
        return
    c.measured_step_s = rep.p99_ms / 1e3
    c.measured_sps = rep.objective()
    c.detail = {"p50_ms": rep.p50_ms, "p99_ms": rep.p99_ms,
                "qps_sustained": rep.qps_sustained,
                "ok": rep.ok, "errors": rep.errors,
                "shed": rep.shed, "hung": rep.hung,
                "recompiles": rep.recompiles}
    c.status = "measured"


def autotune_serve(net, sample_shape: Sequence[int],
                   space: Optional[List[Dict[str, Any]]] = None,
                   mesh=None, device: str = "cpu-proxy",
                   hbm_budget: Optional[float] = None,
                   budget_compiles: int = 3, qps: float = 300.0,
                   n_requests: int = 60, seed: int = 0,
                   default_knobs: Optional[Dict[str, Any]] = None,
                   log_path: Optional[str] = None) -> TuningResult:
    """Tune the serving tier's (bucket set, flush deadline) policy.

    Same loop as :func:`autotune_train`: rank every policy by a
    zero-compile cost-model proxy (flush deadline + largest-bucket
    roofline service time), reject GL201-infeasible bucket sets
    eagerly, measure the top ``budget_compiles`` policies against the
    open-loop Poisson loadtest (``LoadReport.objective()`` — p99
    seconds with failure penalties), residual-correct, re-rank.
    """
    t_start = time.time()
    if space is None:
        space = default_serve_space()
    if not space:
        raise ValueError("empty search space")
    backend, tpu_unavailable = backend_status()
    result = TuningResult(target="serve", backend=backend,
                          tpu_unavailable=tpu_unavailable,
                          relative_only=tpu_unavailable, device=device,
                          hbm_budget=hbm_budget,
                          budget_compiles=int(budget_compiles))
    result.candidates = [Candidate(knobs=dict(k)) for k in space]
    sample = np.zeros(tuple(sample_shape), np.float32)
    report_cache: Dict[int, Any] = {}
    for c in result.candidates:
        _predict_serve(c, net, sample_shape, device, hbm_budget,
                       report_cache)

    default_idx = None
    if default_knobs is None and result.candidates:
        default_idx = 0
    elif default_knobs is not None:
        for i, c in enumerate(result.candidates):
            if c.knobs == default_knobs:
                default_idx = i
                break
        else:  # baseline outside the grid: predict + measure it too
            result.candidates.append(Candidate(knobs=dict(default_knobs)))
            default_idx = len(result.candidates) - 1
            _predict_serve(result.candidates[default_idx], net,
                           sample_shape, device, hbm_budget, report_cache)

    from ..parallel import aot

    c0 = aot.XLA_COMPILES.count
    _, residual_info = _refine_loop(
        result.candidates,
        lambda c: _measure_serve(c, net, sample, qps, n_requests, mesh,
                                 seed),
        int(budget_compiles), default_idx,
        lambda c: c.corrected_sps if c.corrected_sps is not None
        else (c.pred_sps if c.pred_sps is not None else float("inf")))
    result.compiles_spent = aot.XLA_COMPILES.count - c0
    result.residual = residual_info

    measured = [c for c in result.candidates if c.status == "measured"]
    if measured:
        result.winner = min(measured, key=lambda c: c.measured_sps)
    if default_idx is not None:
        result.default = result.candidates[default_idx]
    result.wall_s = time.time() - t_start
    if log_path:
        result.write_log(log_path)
    return result
