"""Fused training step: forward+backward+optimizer in ONE XLA program.

This is the performance path that replaces the reference's
forward→backward→kvstore-push/pull→optimizer chain (SURVEY.md §3.1/§3.2)
with a single compiled computation: XLA fuses the whole step, donates the
parameter/optimizer buffers (in-place update), and — on a mesh — inserts the
data-parallel gradient all-reduce (the dist_sync_device semantics) as ICI
collectives via GSPMD sharding propagation.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import autograd, profiler, rng, tracing
from ..ndarray import NDArray
from ..ops import optimizer_ops as _oops
from .pipeline import shard_map, spmd_pipeline

__all__ = ["DynamicLossScale", "FunctionalOptimizer", "make_train_step",
           "TrainStep", "STEP_SCOPES"]

#: The ``jax.named_scope`` names of the step program's phases, as they show
#: in the op names of the compiled program (docs/PROFILING.md).  ``step.cast``
#: nests in ``step.forward`` and ``step.update.zero`` in ``step.update``; JAX
#: itself wraps ``step.forward`` as ``jvp(step.forward)`` on the forward pass
#: and ``transpose(jvp(step.forward))`` on the backward pass.  Readers of the
#: device trace (``perfbench/readers/scope_op.py``) rely on these names.
STEP_SCOPES = ("step.forward", "step.cast", "step.guard", "step.unscale",
               "step.update", "step.update.zero", "step.select")
(_FORWARD, _CAST, _GUARD, _UNSCALE, _UPDATE, _UPDATE_ZERO,
 _SELECT) = STEP_SCOPES


#: what ``_ensure_built`` enters once the step is built: no span, no record
_BUILT = contextlib.nullcontext()


def _mesh_axes(mesh):
    """``dp=4,tp=2`` for a set-up span's arguments; ``-`` without a mesh."""
    if mesh is None:
        return "-"
    return ",".join("%s=%d" % kv for kv in dict(mesh.shape).items())


def _nbytes(tree):
    return sum(int(getattr(v, "nbytes", 0)) for v in jax.tree.leaves(tree))


class DynamicLossScale:
    """Functional dynamic loss-scaling policy — the jit-safe analog of
    ``contrib/amp/loss_scaler.py``.

    The mutable ``LossScaler`` adjusts a host float between steps; here
    the scale and its clean-step counter are *carried device state* of
    the fused step (donated, updated inside the program), so scaling
    composes with donation, ``multi_precision`` and ``zero=1`` without
    any per-step host sync.  Semantics match the reference scaler:
    halve (down to ``min_loss_scale``) on an overflowing step, double
    (up to ``max_loss_scale``) after ``scale_window`` consecutive clean
    steps.
    """

    def __init__(self, init_scale=2.**16, scale_factor=2., scale_window=2000,
                 max_loss_scale=2.**24, min_loss_scale=1.0):
        if init_scale <= 0 or scale_factor <= 1:
            raise ValueError("init_scale must be > 0 and scale_factor > 1")
        if int(scale_window) < 1:
            raise ValueError("scale_window must be >= 1")
        self.init_scale = float(init_scale)
        self.scale_factor = float(scale_factor)
        self.scale_window = int(scale_window)
        self.max_loss_scale = float(max_loss_scale)
        self.min_loss_scale = float(min_loss_scale)

    def __repr__(self):
        return ("DynamicLossScale(init=%g, factor=%g, window=%d, max=%g)"
                % (self.init_scale, self.scale_factor, self.scale_window,
                   self.max_loss_scale))


class FunctionalOptimizer:
    """Pure-functional optimizer over parameter pytrees (the reference's
    optimizer update ops composed into the jitted step).

    ``multi_precision=True`` keeps an f32 master copy of every parameter
    in the optimizer state and routes the update through the ``mp_*``
    master-weight ops: gradients are promoted to f32, momentum/mean/var
    accumulate in f32, and only the committed weight is cast back to the
    parameter dtype — fixing the bf16-param path where grads and
    momentum otherwise accumulate in bf16.  Combined with ``zero=1`` on
    the step, the master copy is dp-sharded, so it costs 1/N per device.

    ``rescale_grad`` multiplies gradients before the update (the
    reference update-op semantics), so ``Trainer(rescale_grad=...)``
    parity holds for scaled losses.
    """

    def __init__(self, name="sgd", learning_rate=0.01, momentum=0.9, wd=0.0,
                 beta1=0.9, beta2=0.999, epsilon=1e-8, clip_gradient=-1.0,
                 rescale_grad=1.0, multi_precision=False):
        self.name = name
        self.lr = learning_rate
        self.momentum = momentum
        self.wd = wd
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        # per-element gradient clipping, as in the reference update ops;
        # <= 0 disables
        self.clip_gradient = float(clip_gradient or -1.0)
        self.rescale_grad = float(rescale_grad)
        self.multi_precision = bool(multi_precision)
        if name not in ("sgd", "adam", "lamb", "adamw"):
            raise ValueError("unsupported fused optimizer %r" % name)
        if self.multi_precision and name not in ("sgd", "adam"):
            raise ValueError(
                "multi_precision master weights are implemented for "
                "sgd/adam (the mp_* update ops); got %r" % name)

    @property
    def has_state(self):
        """False only for plain sgd (no momentum, no master weights) —
        the one optimizer whose state pytree is empty."""
        return self.multi_precision or self.name != "sgd" \
            or bool(self.momentum)

    def init(self, param_vals: List[Any]):
        """Fresh per-parameter state.  With ``multi_precision`` every
        parameter gains an f32 master copy as the LAST leaf of its state
        tuple; accumulators are created in f32 regardless of the
        parameter dtype."""
        if self.multi_precision:
            def w32(p):
                # force a DISTINCT buffer: astype is a no-op for f32
                # params, and a master weight aliasing the param buffer
                # makes the donated step execute-fail ("attempt to
                # donate the same buffer twice" — both live in the
                # donated argnums)
                return jnp.array(p, dtype=jnp.float32, copy=True)

            def z32(p):
                return jnp.zeros(p.shape, jnp.float32)

            if self.name == "sgd":
                if self.momentum:
                    return [(z32(p), w32(p)) for p in param_vals]
                return [w32(p) for p in param_vals]
            return [(z32(p), z32(p), w32(p)) for p in param_vals]  # adam
        if self.name == "sgd":
            if self.momentum:
                return [jnp.zeros_like(p) for p in param_vals]
            return []
        return [(jnp.zeros_like(p), jnp.zeros_like(p)) for p in param_vals]

    def state_shardings(self, per_param):
        """Mirror :meth:`init`'s per-parameter state structure with the
        given sharding objects (one entry per parameter) — the single
        place where step builders derive optimizer-state placement."""
        if self.multi_precision:
            if self.name == "sgd" and not self.momentum:
                return list(per_param)
            n = 2 if self.name == "sgd" else 3
            return [(s,) * n for s in per_param]
        if self.name == "sgd":
            return list(per_param) if self.momentum else []
        return [(s, s) for s in per_param]

    def state_range_hints(self):
        """Per-LEAF ``(lo, hi)`` value-range seeds for ONE parameter's
        state tuple, congruent with :meth:`init`'s structure — the
        graftrange analysis' (``analysis/value_range.py``) knowledge of
        optimizer-state invariants: variance accumulators are
        non-negative by construction (they average squared gradients),
        so ``sqrt(var)+eps`` divides clean; momentum/master-weight
        leaves are unknown."""
        var = (0.0, None)
        if self.multi_precision:
            if self.name == "sgd":
                return [None, None] if self.momentum else [None]
            return [None, var, None]       # adam: mean, var, w32
        if self.name == "sgd":
            return [None] if self.momentum else []
        return [None, var]                 # adam/lamb/adamw: mean, var

    def apply_single(self, p, g, s, step_count):
        """One parameter's update: ``(weight, grad, state, step)`` →
        ``(new_weight, new_state)``.

        ``step_count`` is the 1-BASED step number: the fused step
        increments its carried counter BEFORE applying, so adam's
        ``1 - beta**t`` bias correction sees ``t=1`` on the first update
        (``t=0`` would divide by zero — see the regression test in
        tests/test_zero_sharding.py).

        sgd/adam/adamw updates are elementwise, so this applies unchanged
        to ZeRO shards; lamb's trust ratio is a global weight/update norm
        and is excluded from sharded application by the caller.
        """
        mp = self.multi_precision
        if not mp:
            g = g.astype(jnp.float32) if p.dtype == jnp.float32 \
                else g.astype(p.dtype)
        if self.name == "sgd":
            if mp:
                if self.momentum:
                    mom32, w32 = s
                    w, m2, w32n = _oops._mp_sgd_mom_update(
                        p, g, mom32, w32, lr=self.lr,
                        momentum=self.momentum, wd=self.wd,
                        rescale_grad=self.rescale_grad,
                        clip_gradient=self.clip_gradient)
                    return w, (m2, w32n)
                w, w32n = _oops._mp_sgd_update(
                    p, g, s, lr=self.lr, wd=self.wd,
                    rescale_grad=self.rescale_grad,
                    clip_gradient=self.clip_gradient)
                return w, w32n
            if self.momentum:
                w, m = _oops._sgd_mom_update(
                    p, g, s, lr=self.lr, momentum=self.momentum, wd=self.wd,
                    rescale_grad=self.rescale_grad,
                    clip_gradient=self.clip_gradient)
                return w, m
            return _oops._sgd_update(
                p, g, lr=self.lr, wd=self.wd,
                rescale_grad=self.rescale_grad,
                clip_gradient=self.clip_gradient), None
        if self.name == "adam":
            # bias correction in f32: with the package-wide x64 flag on,
            # `beta ** int32_t` promotes to f64 and the corrected lr
            # would silently promote every updated PARAM to float64
            # (defeating donation).  t is 1-based — see the docstring.
            t = jnp.asarray(step_count, jnp.float32)
            lr = self.lr * jnp.sqrt(1 - self.beta2 ** t) / (1 - self.beta1 ** t)
            if mp:
                mean, var, w32 = s
                w, m2, v2, w32n = _oops._mp_adam_update(
                    p, g, mean, var, w32, lr=lr, beta1=self.beta1,
                    beta2=self.beta2, epsilon=self.epsilon, wd=self.wd,
                    rescale_grad=self.rescale_grad,
                    clip_gradient=self.clip_gradient)
                return w, (m2, v2, w32n)
            mean, var = s
            w, m2, v2 = _oops._adam_update(
                p, g, mean, var, lr=lr, beta1=self.beta1, beta2=self.beta2,
                epsilon=self.epsilon, wd=self.wd,
                rescale_grad=self.rescale_grad,
                clip_gradient=self.clip_gradient)
            return w, (m2, v2)
        # lamb / adamw: one direction (bias-corrected Adam plus decoupled
        # weight decay); lamb scales it by its trust ratio, adamw does not
        mean, var = s
        gw, m2, v2 = _oops._lamb_phase1(p, g, mean, var, beta1=self.beta1,
                                        beta2=self.beta2,
                                        epsilon=self.epsilon,
                                        t=step_count, wd=self.wd,
                                        rescale_grad=self.rescale_grad,
                                        clip_gradient=self.clip_gradient)
        if self.name == "adamw":
            return p - self.lr * gw, (m2, v2)
        w = _oops._lamb_phase2(p, gw, None, lr=self.lr)
        return w, (m2, v2)

    def apply(self, param_vals, grads, states, step_count):
        new_p, new_s = [], []
        for i, (p, g) in enumerate(zip(param_vals, grads)):
            s = states[i] if self.has_state else None
            w, s2 = self.apply_single(p, g, s, step_count)
            new_p.append(w)
            if self.has_state:
                new_s.append(s2)
        return new_p, new_s


class TrainStep:
    """Callable train step bound to a gluon net + loss + fused optimizer.

    Usage::

        step = make_train_step(net, loss_fn, optimizer='sgd', learning_rate=.1)
        loss = step(x, y)      # one XLA program: fwd+bwd+allreduce+update
    """

    def __init__(self, net, loss_fn, opt: FunctionalOptimizer,
                 compute_dtype=None, mesh: Optional[Mesh] = None,
                 batch_axis: str = "dp",
                 param_shardings: Optional[Dict[str, Any]] = None,
                 donate: bool = True, pipeline_stages: Optional[int] = None,
                 num_micro: int = 1, pipeline_axis: str = "pp",
                 pipeline_remat: bool = False, zero: int = 0,
                 lint: Optional[str] = None,
                 lint_suppress: Tuple[str, ...] = (),
                 nonfinite: Optional[str] = None,
                 loss_scale=None, cost: Optional[str] = None,
                 hbm_budget: Optional[float] = None,
                 cost_device: str = "tpu-v5e",
                 passes=None, numerics: Optional[str] = None,
                 input_range=None, skip_streak_budget: Optional[int] = None,
                 sync: str = "allreduce",
                 staleness_bound: Optional[int] = None, compression=None):
        self.net = net
        self.loss_fn = loss_fn
        self.opt = opt
        self.compute_dtype = compute_dtype
        self.mesh = mesh
        self.batch_axis = batch_axis
        self.param_shardings = param_shardings or {}
        self.pipeline_stages = pipeline_stages
        self.num_micro = num_micro
        self.pipeline_axis = pipeline_axis
        self.pipeline_remat = pipeline_remat
        # ZeRO-1 weight-update sharding (arXiv:2004.13336): reduce-
        # scatter grads over the dp axis, update 1/N of the weights per
        # replica against dp-sharded optimizer state, all-gather the
        # result.  0 = off (replicated update), 1 = ZeRO stage 1.
        self.zero = int(zero or 0)
        if self.zero not in (0, 1):
            raise ValueError("zero must be 0 (off) or 1 (ZeRO-1 "
                             "weight-update sharding), got %r" % (zero,))
        if self.zero:
            if mesh is None or batch_axis not in mesh.axis_names:
                raise ValueError(
                    "zero=1 shards the weight update over the %r mesh "
                    "axis — pass a mesh that has it" % batch_axis)
            if opt.name not in ("sgd", "adam", "adamw"):
                raise ValueError(
                    "zero=1 needs an elementwise update (sgd/adam/adamw); "
                    "%r's trust ratio is a global norm over the whole "
                    "weight and cannot run on a 1/N shard" % opt.name)
        self._zero_pad0 = None  # per-gp-param padded leading dim, or None
        # ---- resilience: non-finite step containment + loss scaling ----
        # loss_scale: None (off) | float (static) | "dynamic" |
        # DynamicLossScale instance.  The scale and its counters are
        # device-carried step state (see DynamicLossScale).
        if loss_scale is None:
            self._scale_cfg = None
        elif isinstance(loss_scale, DynamicLossScale):
            self._scale_cfg = loss_scale
        elif isinstance(loss_scale, str):
            if loss_scale != "dynamic":
                raise ValueError("loss_scale must be None, a positive "
                                 "number, 'dynamic' or a DynamicLossScale; "
                                 "got %r" % (loss_scale,))
            self._scale_cfg = DynamicLossScale()
        elif isinstance(loss_scale, (int, float)):
            if loss_scale <= 0:
                raise ValueError("static loss_scale must be positive, "
                                 "got %r" % (loss_scale,))
            self._scale_cfg = float(loss_scale)
        else:
            raise ValueError("loss_scale must be None, a positive number, "
                             "'dynamic' or a DynamicLossScale; got %r"
                             % (loss_scale,))
        self._dynamic_scale = isinstance(self._scale_cfg, DynamicLossScale)
        # nonfinite: what a step with any non-finite gradient does.
        # "skip"  — contain it: params, aux state, optimizer state and the
        #           step counter stay bit-identical (one fused all-finite
        #           reduction + a select guard, still one XLA program);
        # "raise" — contain it AND raise FloatingPointError on the host;
        # "off"   — no guard (the pre-resilience program, bit for bit).
        # Default: "skip" when a dynamic scaler is on (its contract
        # REQUIRES skipping overflowed steps), else "off".
        if nonfinite is None:
            nonfinite = "skip" if self._dynamic_scale else "off"
        if nonfinite not in ("skip", "raise", "off"):
            raise ValueError("nonfinite must be 'skip', 'raise' or 'off', "
                             "got %r" % (nonfinite,))
        if self._dynamic_scale and nonfinite == "off":
            raise ValueError(
                "a dynamic loss scale requires skipping overflowed steps "
                "(they are how it detects the scale is too high) — use "
                "nonfinite='skip' or 'raise', not 'off'")
        self.nonfinite = nonfinite
        # skip_streak_budget: DECLARED bound on consecutive skipped
        # steps — enforcement lives in the supervised loop
        # (parallel/supervisor.py reads it as its detector default);
        # declaring it (or a dynamic scale) is what silences GL012,
        # the unbounded-silent-skip-streak lint.
        if skip_streak_budget is not None and int(skip_streak_budget) < 1:
            raise ValueError("skip_streak_budget must be >= 1 or None, "
                             "got %r" % (skip_streak_budget,))
        self.skip_streak_budget = None if skip_streak_budget is None \
            else int(skip_streak_budget)
        # ---- sync→async policy ladder (parallel/param_service.py) ----
        # sync: "allreduce" (the fused collective step, default),
        # "async" (bounded-staleness push/pull through a ParamService),
        # "auto" (start at allreduce; the supervisor's straggler
        # verdicts degrade to async and recover back — SyncPolicy).
        if sync not in ("allreduce", "async", "auto"):
            raise ValueError("sync must be 'allreduce', 'async' or "
                             "'auto', got %r" % (sync,))
        if sync != "allreduce":
            # v1 surface of the async rung: one process-local replica
            # per rank (the ps-worker model — ranks exchange through
            # the service, not through GSPMD collectives), no loss
            # scaling (pushes are unscaled gradients), no pipelining,
            # no ZeRO (optimizer state lives server-side).
            if mesh is not None:
                raise ValueError(
                    "sync=%r exchanges gradients through the parameter "
                    "service, not through mesh collectives — build the "
                    "async-capable step with mesh=None (one replica per "
                    "rank process)" % (sync,))
            if pipeline_stages is not None:
                raise ValueError("sync=%r does not compose with "
                                 "pipeline_stages" % (sync,))
            if self._scale_cfg is not None:
                raise ValueError(
                    "sync=%r pushes unscaled gradients; loss_scale is "
                    "not supported on the async rung" % (sync,))
        if staleness_bound is not None:
            if sync == "allreduce":
                raise ValueError(
                    "staleness_bound only applies to sync='async'/'auto' "
                    "(the bounded-staleness pull clock)")
            if int(staleness_bound) < 0:
                raise ValueError("staleness_bound must be >= 0, got %r"
                                 % (staleness_bound,))
        self.sync = sync
        self.staleness_bound = 4 if staleness_bound is None \
            else int(staleness_bound)
        from ..kvstore.gradient_compression import make_compressor

        self._compression = make_compressor(compression)
        from .param_service import SyncPolicy

        self.sync_policy = SyncPolicy(mode=sync)
        self._applied_sync = "async" if sync == "async" else "allreduce"
        self._svc_client = None
        self._svc_attaching = False
        self._grad_jit = None
        #: bounded wait for an async pull (StalenessTimeout past it) —
        #: the slow-peer deadline, lowered by tests
        self.pull_timeout = 300.0
        self._scaler_dev = None  # (scale f32, unskipped i32, skipped i32)
        # set by Trainer.make_fused_step so the lint pass can flag the
        # legacy save_states path (GL007) still reachable on the object
        self._legacy_state_origin = None
        self._ckpt_manager = None
        self._ckpt_every = None
        self._ckpt_prev_count = 0
        self._ckpt_seen_request = 0
        self._ckpt_data_iter = None
        # graftlint Level 1 runs over the traced step before its first
        # compile (docs/ANALYSIS.md): "error" raises on error-severity
        # findings, "warn" prints them, "off" skips the lint trace.
        # Resolution order: explicit arg > MXTPU_LINT env > "warn".
        from .aot import resolve_mode as _resolve_mode

        self.lint = _resolve_mode(lint, "MXTPU_LINT", "warn",
                                  ("off", "warn", "error"), "lint")
        self.lint_suppress = tuple(lint_suppress)
        self._linted = False
        # graftcost rides the same pre-compile trace (analysis/
        # cost_model.py, docs/ANALYSIS.md): "report" computes the
        # CostReport (surfaced as step.cost_report), "check" additionally
        # raises on GL2xx errors — GL201 rejects an over-budget config
        # at trace time, before any compile.  Resolution order: explicit
        # arg > MXTPU_COST env > "off".
        self.cost = _resolve_mode(cost, "MXTPU_COST", "off",
                                  ("off", "report", "check"), "cost")
        if hbm_budget is not None and float(hbm_budget) <= 0:
            raise ValueError("hbm_budget must be positive bytes, got %r"
                             % (hbm_budget,))
        self.hbm_budget = float(hbm_budget) if hbm_budget else None
        from ..analysis.cost_model import DEVICE_SPECS as _SPECS

        if cost_device not in _SPECS:
            raise ValueError("unknown cost_device %r (registry: %s)"
                             % (cost_device, sorted(_SPECS)))
        self.cost_device = cost_device
        self.cost_report = None  # set by the cost pass (cost != "off")
        # graftrange rides the same pre-compile trace (analysis/
        # value_range.py, docs/ANALYSIS.md GL4xx): an abstract value-
        # range & precision interpreter over the step program.  "warn"
        # surfaces GL401-GL405 findings, "error" raises BEFORE any
        # compile (like cost="check"'s GL201), "off" (default) skips
        # the walk.  Resolution: explicit arg > MXTPU_NUMERICS > "off".
        self.numerics = _resolve_mode(numerics, "MXTPU_NUMERICS", "off",
                                      ("off", "warn", "error"),
                                      "numerics")
        # input_range: declared value range of the batch — a (lo, hi)
        # tuple for x, or a dict {"x": (lo, hi), "y": (lo, hi)}.  Seeds
        # the range analysis; everything unannotated defaults
        # conservatively (floats unknown-finite, ints to dtype range).
        if input_range is not None and not isinstance(input_range,
                                                      (tuple, list, dict)):
            raise ValueError(
                "input_range must be a (lo, hi) tuple for x or a dict "
                "{'x': (lo, hi), 'y': (lo, hi)}; got %r" % (input_range,))
        self.input_range = input_range
        self.range_report = None  # set by the numerics pass
        # graftpass: an ordered jaxpr->jaxpr rewrite pipeline applied to
        # the traced step before its first compile (analysis/passes.py,
        # docs/PASSES.md).  Resolution: explicit arg > MXTPU_PASSES env
        # > ().  Invar-changing passes (quantize) no-op here — a train
        # step's params are donated and updated in place, so the
        # PassContext advertises no quantizable param invars.
        # ``passes=`` also accepts a PassSchedule (or its canonical
        # dict), pinning a per-site decision vector (graftsched); a
        # plain pass list is the all-sites schedule, bitwise-equivalent
        from ..analysis.passes import resolve_schedule as _resolve_schedule

        self._passes, self._schedule = _resolve_schedule(passes)
        #: flat-aval signature -> (rewritten ClosedJaxpr, out treedef,
        #: probe-verified flag)
        self._pass_programs: Dict[tuple, tuple] = {}
        #: (x, y) aval keys whose program is fully verified — the
        #: per-step fast path around the full-args flatten
        self._pass_fast_verified: set = set()
        self._pass_effects: List[Any] = []
        self.pass_receipts = None  # receipts of the last pipeline run
        if pipeline_stages is not None:
            if mesh is None:
                raise ValueError("pipeline_stages requires a mesh with a "
                                 "%r axis" % pipeline_axis)
            if pipeline_axis not in mesh.axis_names:
                raise ValueError("mesh %s has no %r axis for pipelining"
                                 % (mesh, pipeline_axis))
            if mesh.shape[pipeline_axis] != pipeline_stages:
                raise ValueError(
                    "pipeline_stages=%d but mesh axis %r has size %d"
                    % (pipeline_stages, pipeline_axis,
                       mesh.shape[pipeline_axis]))
            if num_micro < 1:
                raise ValueError("num_micro must be >= 1")
        # stage partition: per-stage lists of indices into the gp list,
        # plus the stage-0 blocks used to trace the (uniform) stage program
        self._stage_idx = None
        self._stage0_blocks = None
        self._stage0_gp = None
        self._gp = None
        self._aux = None
        self._opt_state = None
        self._step_count = 0
        self._key_dev = None   # device-carried PRNG key (donated each step)
        self._step_dev = None  # device-carried int32 step counter
        self._key_epoch = None  # rng.epoch() at key draw (reseed detection)
        self._jit = None
        self._compiled = None
        self._compiled_key = None
        self._multihost = False
        self._donate = donate
        # the ONE donation spec: state args of step(p_vals, aux_vals,
        # opt_state, x, y, key, step_count, scaler_state) — jit, the
        # multi-step scan program, and the GL003 lint all key off this
        self._donate_argnums = (0, 1, 2, 5, 6, 7) if donate else ()
        self._placed = False
        self._shardings = None

    # ------------------------------------------------------------------
    def _collect(self):
        params = list(self.net.collect_params().values())
        self._gp = [p for p in params if p.grad_req != "null"]
        self._aux = [p for p in params if p.grad_req == "null"]
        if self.pipeline_stages is not None:
            self._collect_pipeline()
        if self.zero:
            self._build_zero_plan()

    def _build_zero_plan(self):
        """Per-parameter ZeRO layout: the padded leading dim (a multiple
        of the dp axis size — pad-and-slice, never silently replicate),
        or None for params the dp-sharded update does not cover:

        - params already sharded by ``param_shardings`` (tp/ep): their
          optimizer state shards like the parameter, so it is already
          distributed — ZeRO over dp would fight the existing layout;
        - 0-d (scalar) params: nothing to slice.
        """
        n = self.mesh.shape[self.batch_axis]
        plan = []
        for p in self._gp:
            spec = tuple(self.param_shardings.get(p.name, P()))
            sharded = any(e is not None and e != () for e in spec)
            if sharded or len(p.shape) < 1:
                plan.append(None)
            else:
                plan.append(-(-p.shape[0] // n) * n)  # ceil to multiple
        self._zero_pad0 = plan

    @staticmethod
    def _zero_padded(v, pad0):
        """Pad the leading dim up to ``pad0`` (identity when it already
        divides)."""
        if pad0 is None or pad0 == v.shape[0]:
            return v
        return jnp.pad(v, [(0, pad0 - v.shape[0])]
                       + [(0, 0)] * (v.ndim - 1))

    # ------------------------------------------------------------------
    def _finish_step(self, loss_val, grads, p_vals, aux_vals, new_aux,
                     opt_state, key, step_count, scaler):
        """Shared tail of every step program: (un)scale, guard, update.

        One fused global all-finite reduction over the whole grad tree
        (``ops.optimizer_ops.tree_all_finite`` — a single scalar inside
        the program, NOT per-param host syncs), then the optimizer leg,
        then — when containment is on — a select guard: a step with any
        non-finite gradient leaves params, aux state, optimizer state
        (incl. pipeline/ZeRO shards: the select runs on the final,
        full-tree outputs, so sharded layouts pass through untouched)
        and the step counter bit-identical.  The select form is
        donation-safe: both arms alias the same donated buffers and XLA
        lowers it to a predicated copy.  The dynamic scaler (when
        configured) halves on overflow and doubles after
        ``scale_window`` clean steps, functionally, in the carried
        ``(scale, unskipped, skipped)`` state.
        """
        scale, unskipped, skipped = scaler
        scaling = self._scale_cfg is not None
        guard = self.nonfinite != "off"
        if guard:
            # finiteness is checked on the RAW (still scaled) grads:
            # that is where fp16 overflow appears, and unscaling an inf
            # cannot rescue it anyway
            with jax.named_scope(_GUARD):
                ok = _oops.tree_all_finite(grads)
        else:
            ok = jnp.array(True)
        if scaling:
            def unscale(g):
                ct = jnp.promote_types(g.dtype, jnp.float32)
                return (g.astype(ct) * inv.astype(ct)).astype(g.dtype)

            with jax.named_scope(_UNSCALE):
                # powers-of-two scales make the multiply exact; compute in
                # the wider of (grad dtype, f32) so f16/bf16 grads unscale
                # in f32 while f64 grads keep their full mantissa
                inv = (1.0 / scale).astype(jnp.float32)
                grads = [unscale(g) for g in grads]
                loss_val = loss_val * inv
        c1 = step_count + 1
        with jax.named_scope(_UPDATE):
            new_p, new_s = self._apply_update(p_vals, grads, opt_state, c1)
        if guard:
            def sel(n, o):
                return jnp.where(ok, n, o)

            with jax.named_scope(_SELECT):
                new_p = [sel(n, o) for n, o in zip(new_p, p_vals)]
                new_aux = [sel(n, o) for n, o in zip(new_aux, aux_vals)]
                new_s = jax.tree.map(sel, new_s, opt_state)
                c1 = sel(c1, step_count)
                skipped = skipped + jnp.where(ok, jnp.int32(0), jnp.int32(1))
                if self._dynamic_scale:
                    cfg = self._scale_cfg
                    unsk = jnp.where(ok, unskipped + 1, jnp.int32(0))
                    grow = unsk >= cfg.scale_window
                    scale = jnp.where(
                        ok,
                        jnp.where(grow,
                                  jnp.minimum(scale * cfg.scale_factor,
                                              cfg.max_loss_scale),
                                  scale),
                        jnp.maximum(scale / cfg.scale_factor,
                                    cfg.min_loss_scale)).astype(jnp.float32)
                    unskipped = jnp.where(grow, jnp.int32(0), unsk)
        return (loss_val, new_p, list(new_aux), new_s, key, c1,
                (scale, unskipped, skipped), ok)

    def _apply_update(self, p_vals, grads, opt_state, step_count):
        """The optimizer leg of the step program: plain replicated apply,
        or the ZeRO-1 sharded update when ``zero=1``."""
        if not self.zero:
            return self.opt.apply(p_vals, grads, opt_state, step_count)
        return self._apply_zero(p_vals, grads, opt_state, step_count)

    def _apply_zero(self, p_vals, grads, opt_state, step_count):
        """ZeRO-1 weight update over the dp axis (arXiv:2004.13336).

        Inside a ``shard_map`` over the mesh's dp axis: each rank
        consumes only its 1/N gradient and weight shard (sliced by
        ``axis_index``), updates it against its dp-sharded optimizer-
        state shard, and re-materializes the full parameter with
        ``collectives.allgather``.  The grad slice — not an explicit
        collective — is deliberate: on jax 0.4.x the grads reach this
        point dp-replicated (GSPMD has already summed the per-replica
        partials), so slicing is free and exact for ANY axis size, and
        ``all-reduce + per-rank slice`` is precisely the pattern the
        paper's XLA reduce-scatter-creation pass rewrites into a single
        reduce-scatter on TPU; an explicit ``psum_scatter`` here would
        be a REDUNDANT second collective (summing N identical copies,
        with rounding drift for non-power-of-two N) — the waste class
        graftlint GL006 flags for all_gather.  Params/grads enter the
        body replicated and are sliced per rank inside it — also the
        jax 0.4.x-safe pattern (a jit-internal padded operand fed to a
        sharded in_spec risks the GSPMD stacked-operand miscompile,
        graftlint GL002).  Ragged leading dims are padded to a multiple
        of N and the padding is sliced back off after the gather.

        With pipelined grad accumulation (dp×pp), the microbatch grads
        are already summed by the scan transpose, so the grad reduction
        happens ONCE at the end of the step, not per microbatch.
        """
        from . import collectives
        from .mesh import shard_map as _shard_map

        mesh, ax = self.mesh, self.batch_axis
        n = mesh.shape[ax]
        opt = self.opt
        pad0s = self._zero_pad0
        z_idx = [i for i, pad in enumerate(pad0s) if pad is not None]
        r_idx = [i for i, pad in enumerate(pad0s) if pad is None]

        new_p: List[Any] = [None] * len(p_vals)
        new_s: List[Any] = [None] * len(p_vals) if opt.has_state else []
        if r_idx:
            # tp/ep-sharded and scalar params: plain update; their state
            # already shards like the parameter
            rp, rs = opt.apply(
                [p_vals[i] for i in r_idx], [grads[i] for i in r_idx],
                [opt_state[i] for i in r_idx] if opt.has_state else [],
                step_count)
            for j, i in enumerate(r_idx):
                new_p[i] = rp[j]
                if opt.has_state:
                    new_s[i] = rs[j]
        if not z_idx:
            return new_p, new_s

        z_p = [p_vals[i] for i in z_idx]
        z_g = [grads[i] for i in z_idx]
        z_s = [opt_state[i] for i in z_idx] if opt.has_state else []
        z_pad = [pad0s[i] for i in z_idx]
        shard_spec = P(ax)

        # the slices and the all-gather carry their own scope, so a trace
        # tells the sharding's cost from the optimizer's arithmetic
        def body(zp, zg, zs, c):
            with jax.named_scope(_UPDATE_ZERO):
                idx = jax.lax.axis_index(ax)
            out_p, out_s = [], []
            for k, (p, g) in enumerate(zip(zp, zg)):
                pad0 = z_pad[k]
                rows = pad0 // n
                with jax.named_scope(_UPDATE_ZERO):
                    p_pad = self._zero_padded(p, pad0)
                    g_pad = self._zero_padded(g, pad0)
                    g_shard = jax.lax.dynamic_slice_in_dim(
                        g_pad, idx * rows, rows, 0)
                    p_shard = jax.lax.dynamic_slice_in_dim(
                        p_pad, idx * rows, rows, 0)
                s_k = zs[k] if opt.has_state else None
                w_shard, s_new = opt.apply_single(p_shard, g_shard, s_k, c)
                with jax.named_scope(_UPDATE_ZERO):
                    w_full = collectives.allgather(w_shard, ax, axis=0,
                                                   tiled=True)
                    if pad0 != p.shape[0]:
                        w_full = jax.lax.slice_in_dim(w_full, 0, p.shape[0],
                                                      axis=0)
                out_p.append(w_full)
                out_s.append(s_new)
            if opt.has_state:
                return tuple(out_p), tuple(out_s)
            return tuple(out_p)

        repl = P()
        in_specs = (tuple(repl for _ in z_p), tuple(repl for _ in z_g),
                    jax.tree.map(lambda _: shard_spec, z_s), repl)
        if opt.has_state:
            out_specs = (tuple(repl for _ in z_p),
                         tuple(jax.tree.map(lambda _: shard_spec, s)
                               for s in z_s))
        else:
            out_specs = tuple(repl for _ in z_p)
        # per-rank slices/shards differ across dp by construction and
        # re-replicate via the all-gather; skip the conservative
        # replication checker
        mapped = _shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
        res = mapped(tuple(z_p), tuple(z_g), z_s, step_count)
        zp_new, zs_new = res if opt.has_state else (res, None)
        for j, i in enumerate(z_idx):
            new_p[i] = zp_new[j]
            if opt.has_state:
                new_s[i] = zs_new[j]
        return new_p, new_s

    def _collect_pipeline(self):
        """Partition the net's children into ``pipeline_stages`` contiguous,
        structurally congruent stages and map each stage's params back to
        their positions in the flat gp list (so donation/optimizer layout
        is identical to the non-pipelined step)."""
        k = self.pipeline_stages
        try:
            children = list(self.net)
        except TypeError:
            raise ValueError(
                "pipeline_stages needs an iterable stacked net "
                "(e.g. HybridSequential); %r is not iterable"
                % type(self.net).__name__)
        if not children or len(children) % k != 0:
            raise ValueError(
                "cannot split %d child blocks into %d pipeline stages"
                % (len(children), k))
        per = len(children) // k
        groups = [children[s * per:(s + 1) * per] for s in range(k)]
        gp_pos = {id(p): i for i, p in enumerate(self._gp)}
        stage_idx, stage_gp = [], []
        for s, blocks in enumerate(groups):
            ps = [p for b in blocks for p in b.collect_params().values()]
            if any(p.grad_req == "null" for p in ps):
                raise NotImplementedError(
                    "pipeline stage %d carries auxiliary state (BatchNorm "
                    "running stats etc.); aux writes cannot escape the "
                    "pipelined scan — use LayerNorm/GroupNorm inside "
                    "pipeline stages" % s)
            gps = [p for p in ps if id(p) in gp_pos]
            stage_gp.append(gps)
            stage_idx.append([gp_pos[id(p)] for p in gps])
        covered = {i for idx in stage_idx for i in idx}
        if covered != set(range(len(self._gp))):
            raise ValueError(
                "net has trainable parameters outside its child blocks; "
                "the SPMD pipeline owns the full parameter set")
        from .pipeline import stage_congruence_mismatch

        first = stage_gp[0]
        sig0 = [(tuple(p.shape), p.dtype) for p in first]
        for s, ps in enumerate(stage_gp[1:], 1):
            reason = stage_congruence_mismatch(
                sig0, [(tuple(p.shape), p.dtype) for p in ps], s)
            if reason:
                raise ValueError(
                    "pipeline stages must be structurally congruent "
                    "(%s) — uniform-stage SPMD pipelining runs ONE "
                    "stage program with per-rank values" % reason)
        self._stage_idx = stage_idx
        self._stage0_blocks = groups[0]
        self._stage0_gp = first

    @jax.named_scope(_CAST)
    def _cast_inputs(self, pv, x):
        """Shared dtype policy: params re-cast to the compute dtype;
        unsigned-int inputs are raw image bytes (ImageRecordUInt8Iter) —
        promote them so convs run in the compute dtype too."""
        compute_dtype = self.compute_dtype
        if compute_dtype is not None:
            pv_c = [v.astype(compute_dtype)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v
                    for v in pv]
            if jnp.issubdtype(x.dtype, jnp.floating) or \
                    jnp.issubdtype(x.dtype, jnp.unsignedinteger):
                x_c = x.astype(compute_dtype)
            else:
                x_c = x
        else:
            pv_c = pv
            x_c = x.astype(jnp.float32) \
                if jnp.issubdtype(x.dtype, jnp.unsignedinteger) else x
        return pv_c, x_c

    def _loss_closure(self, aux_vals, x, y, use_key, scaler):
        """``pv -> (loss, new_aux)`` — the forward+loss closure both the
        fused allreduce step and the async grads-only program
        differentiate (one definition, so the two rungs of the policy
        ladder train the SAME objective)."""
        gp_list, aux_list = self._gp, self._aux
        net, loss_fn = self.net, self.loss_fn

        @jax.named_scope(_FORWARD)
        def loss_of(pv):
            pv_c, x_c = self._cast_inputs(pv, x)
            tc = tracing.TraceContext(use_key, training=True)
            for p, v in zip(gp_list, pv_c):
                tc.bindings[id(p)] = v
            for p, v in zip(aux_list, aux_vals):
                tc.bindings[id(p)] = v
            tracing.push_trace(tc)
            try:
                with autograd.pause():
                    out = net._forward_impl(NDArray(x_c))
                    loss = loss_fn(out, NDArray(y))
                    loss = loss.mean()
            finally:
                tracing.pop_trace()
            # align aux writes to aux_list positions (functional update:
            # unwritten aux flow through unchanged) — no trace-order
            # side channel between tracing and the caller
            new_aux = []
            for p, bound in zip(aux_list, aux_vals):
                w = tc.aux_writes.get(id(p))
                new_aux.append(bound if w is None
                               else w[1].astype(bound.dtype))
            loss_val = loss._data.astype(jnp.float32)
            # aux losses registered during the forward (MoE load
            # balancing etc.) join the objective here, so their
            # gradients flow through the same fused program
            for al in tc.aux_losses:
                loss_val = loss_val + al.astype(jnp.float32)
            if self._scale_cfg is not None:
                # the SCALED loss feeds the backward pass so fp16
                # grads overflow before they denormalize; the
                # reported loss is unscaled again in _finish_step
                loss_val = loss_val * scaler[0]
            return loss_val, new_aux

        return loss_of

    def _make_plain_step(self):
        def step(p_vals, aux_vals, opt_state, x, y, key, step_count, scaler):
            # key/step_count/scaler are DEVICE-carried state (donated,
            # updated in program): a fresh host scalar or an eager key split
            # per step costs a serialized host->device transfer, which
            # dominated the measured gap on a remote runtime
            key, use_key = jax.random.split(key)
            loss_of = self._loss_closure(aux_vals, x, y, use_key, scaler)
            (loss_val, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(p_vals)
            return self._finish_step(loss_val, grads, p_vals, aux_vals,
                                     new_aux, opt_state, key, step_count,
                                     scaler)

        return step

    def _make_grad_step(self):
        """The async rung's program: forward+backward ONLY — the
        optimizer lives server-side (``ParamService``'s updater applies
        each push, ps-lite's async ApplyUpdates semantics).  Same loss
        closure as the fused step; aux state and the PRNG key stay
        rank-local device-carried state."""
        def gstep(p_vals, aux_vals, x, y, key):
            key, use_key = jax.random.split(key)
            loss_of = self._loss_closure(aux_vals, x, y, use_key, None)
            (loss_val, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(p_vals)
            return loss_val, grads, new_aux, key

        return gstep

    def _make_pipeline_step(self):
        """Pipelined fused step: forward microbatches through the SPMD
        1F1B/GPipe schedule, backward via the scan transpose (cotangents
        hop stage←stage through the inverted ppermute), microbatch
        gradient accumulation on-rank, then the optimizer — ONE jitted,
        donated XLA program, zero per-microbatch Python dispatch."""
        loss_fn, opt = self.loss_fn, self.opt
        mesh = self.mesh
        pp_axis = self.pipeline_axis
        num_micro = self.num_micro
        remat = self.pipeline_remat
        n_stage = self.pipeline_stages
        stage_idx = self._stage_idx
        stage0_blocks = self._stage0_blocks
        stage0_gp = self._stage0_gp
        # microbatches keep the batch sharding on their (second) batch dim
        # when the mesh also has a dp axis — pp composes with dp/tp
        mb_spec = P(None, self.batch_axis) \
            if self.batch_axis in mesh.axis_names else P()

        def stage_fn(sp, h):
            # one uniform stage program, traced through stage 0's blocks
            # with this rank's parameter values bound.  key=None: dropout
            # inside pipeline stages would need per-stage key plumbing
            # through the schedule — fail loudly instead of silently
            # desynchronizing the stream
            tc = tracing.TraceContext(None, training=True)
            for p, v in zip(stage0_gp, sp):
                tc.bindings[id(p)] = v
            tracing.push_trace(tc)
            try:
                with autograd.pause():
                    out = NDArray(h)
                    for b in stage0_blocks:
                        out = b._forward_impl(out)
            finally:
                tracing.pop_trace()
            if tc.aux_losses:
                raise NotImplementedError(
                    "aux losses inside pipeline stages cannot escape the "
                    "pipelined scan; place MoE blocks outside the "
                    "pipelined net or train without pipeline_stages")
            return out._data

        def step(p_vals, aux_vals, opt_state, x, y, key, step_count, scaler):
            key, use_key = jax.random.split(key)

            @jax.named_scope(_FORWARD)
            def loss_of(pv):
                pv_c, x_c = self._cast_inputs(pv, x)
                if x_c.shape[0] % num_micro:
                    raise ValueError(
                        "batch %d not divisible into num_micro=%d"
                        % (x_c.shape[0], num_micro))
                # per-stage params, stacked on a leading pp axis; built
                # from the flat list so grads come back per-parameter
                stacked = tuple(
                    jnp.stack([pv_c[stage_idx[s][i]]
                               for s in range(n_stage)])
                    for i in range(len(stage0_gp)))
                micro = x_c.reshape(
                    (num_micro, x_c.shape[0] // num_micro) + x_c.shape[1:])

                def inner(stk, mb):
                    # stage params enter replicated and each rank slices
                    # its own stage by axis index: feeding a jit-internal
                    # stack into shard_map with a P(pp) in_spec miscompiles
                    # on multi-axis meshes (jax 0.4.x GSPMD resharding);
                    # the dynamic-slice form is exact on pp and dp x pp
                    i = jax.lax.axis_index(pp_axis)
                    local = [s_[i] for s_ in stk]
                    return spmd_pipeline(stage_fn, local, mb,
                                         axis_name=pp_axis, remat=remat)

                # pallas_call (the flash attention kernels a staged
                # block may contain) carries no replication-rule
                # metadata; skip the replication checker like the
                # zero-update leg does
                mapped = shard_map(
                    inner, mesh=mesh,
                    in_specs=(tuple(P() for _ in stacked), mb_spec),
                    out_specs=mb_spec, check_vma=False)
                outs = mapped(stacked, micro)
                flat = outs.reshape((-1,) + outs.shape[2:])
                tc = tracing.TraceContext(use_key, training=True)
                tracing.push_trace(tc)
                try:
                    with autograd.pause():
                        loss = loss_fn(NDArray(flat), NDArray(y))
                        loss = loss.mean()
                finally:
                    tracing.pop_trace()
                loss_val = loss._data.astype(jnp.float32)
                for al in tc.aux_losses:
                    loss_val = loss_val + al.astype(jnp.float32)
                if self._scale_cfg is not None:
                    loss_val = loss_val * scaler[0]
                return loss_val, list(aux_vals)

            (loss_val, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(p_vals)
            # microbatch grads are already accumulated by the scan
            # transpose; under zero=1 they reduce-scatter ONCE here —
            # and the non-finite guard sees the fully-accumulated tree
            return self._finish_step(loss_val, grads, p_vals, aux_vals,
                                     new_aux, opt_state, key, step_count,
                                     scaler)

        return step

    def _build(self):
        step = self._make_pipeline_step() if self.pipeline_stages \
            else self._make_plain_step()
        self._step_fn = step  # shared by the multi-step (scan) program
        return self._jit_for(step)

    def _jit_for(self, step):
        """jit one step-shaped callable under this step's donation and
        sharding specs — shared by the base program and the graftpass-
        rewritten one (same interface by construction: GL301 gates it)."""
        gp_list, aux_list = self._gp, self._aux
        donate = self._donate_argnums
        if self.mesh is None:
            return jax.jit(step, donate_argnums=donate)

        mesh = self.mesh
        repl = NamedSharding(mesh, P())

        def p_shard(p):
            spec = self.param_shardings.get(p.name, P())
            return NamedSharding(mesh, spec)

        p_sh = [p_shard(p) for p in gp_list]
        aux_sh = [repl for _ in aux_list]
        # a pp- or ep-only mesh has no batch axis: batches stay replicated
        batch_sh = NamedSharding(mesh, P(self.batch_axis)) \
            if self.batch_axis in mesh.axis_names else repl
        # opt state shards like its parameter; under zero=1 the state of
        # every dp-covered param is instead dp-sharded on its (padded)
        # leading dim — the 1/N memory the feature exists for
        if self.zero:
            zsh = NamedSharding(mesh, P(self.batch_axis))
            per_param = [zsh if pad is not None else s
                         for s, pad in zip(p_sh, self._zero_pad0)]
        else:
            per_param = p_sh
        state_sh = self.opt.state_shardings(per_param)
        self._shardings = (p_sh, aux_sh, state_sh, batch_sh, repl)
        return jax.jit(step, donate_argnums=donate,
                       in_shardings=(p_sh, aux_sh, state_sh, batch_sh,
                                     batch_sh, repl, repl, repl),
                       out_shardings=(repl, p_sh, aux_sh, state_sh, repl,
                                      repl, repl, repl))

    # ------------------------------------------------------------------
    # graftpass (analysis/passes.py, docs/PASSES.md)
    def _pass_pipeline_inputs(self, example_args, probe=True):
        """The ONE trace-and-context block behind both pipeline
        entrances (`_maybe_apply_passes` installs, `analyze_schedule`
        reports): returns ``(traced, ctx, n_dev, multihost)`` for the
        step's argument signature."""
        from ..analysis.passes import PassContext
        from ..analysis.trace_lint import donated_leaf_indices
        from .aot import traced_with_effects
        from .mesh import spans_processes

        base = getattr(self, "_base_jit", None) or self._jit
        traced, effects = traced_with_effects(
            base, tuple(example_args), capture=self.lint != "off")
        if effects and not self._pass_effects:
            # GL004 effects surface on the BASE trace (the rewritten
            # program replays a finished trace); stash them for the
            # lint report over the rewritten program
            self._pass_effects = list(effects)
        axis_sizes, n_dev, multihost = None, 1, False
        if self.mesh is not None:
            axis_sizes = {k: int(v)
                          for k, v in dict(self.mesh.shape).items()}
            n_dev = int(self.mesh.size)
            multihost = spans_processes(self.mesh)
        num_seeds = None
        if self.numerics != "off":
            num_seeds = self._numerics_seeds(tuple(example_args))[0]
        ctx = PassContext(
            param_invars=frozenset(),  # donated+updated: not quantizable
            allow_invar_change=False,
            donated_leaves=tuple(donated_leaf_indices(
                tuple(example_args), self._donate_argnums)),
            axis_sizes=axis_sizes,
            # a process-spanning program cannot be evaluated eagerly on
            # this host alone; abstract eval + re-lint still gate it
            probe="off" if (multihost or not probe) else "auto",
            # the graftrange hookup: amp_bf16's per-op GL403 gate rides
            # the step's numerics mode and input annotations
            numerics=self.numerics,
            input_ranges=num_seeds,
            where="fused train step")
        return traced, ctx, n_dev, multihost

    def _maybe_apply_passes(self, example_args, probe=True):
        """Run the configured pass pipeline over the traced step for
        this argument signature and install the verified rewrite as the
        program that compiles.  Idempotent per flat-aval signature; the
        contract gates (GL301/GL302) raise BEFORE any compile, so a
        refused rewrite costs zero executables.  The rewritten step
        keeps the exact invar layout, donation spec and shardings —
        invar-changing passes are refused here by construction.

        ``probe=False`` skips the concrete probe (abstract eval,
        re-lint and cost receipts still gate) — the cheap ranking mode
        ``analyze_cost`` uses so the autotuner's zero-compile phase
        never pays two eager step executions per candidate.  A program
        ranked that way is RE-verified with the probe the first time a
        run path (``__call__``/``aot_compile``/``run_steps``) asks for
        it: nothing unprobed ever compiles."""
        if not self._passes:
            return
        # hot-path fast key: only the batch args vary between calls on
        # one step instance (params/opt-state/scaler avals are pinned
        # at build), so a verified (x, y) signature skips the full
        # O(n_leaves) flatten every subsequent step would otherwise pay
        x_ex, y_ex = example_args[3], example_args[4]
        fast = (tuple(x_ex.shape), str(x_ex.dtype),
                tuple(y_ex.shape), str(y_ex.dtype))
        if fast in self._pass_fast_verified:
            return
        flat = jax.tree_util.tree_leaves(tuple(example_args))
        sig = tuple((tuple(v.shape), str(v.dtype)) for v in flat)
        entry = self._pass_programs.get(sig)
        if entry is not None and (entry[2] or not probe):
            if entry[2]:
                self._pass_fast_verified.add(fast)
            return
        from ..analysis.passes import PassManager

        traced, ctx, n_dev, multihost = self._pass_pipeline_inputs(
            example_args, probe=probe)
        mgr = PassManager(self._passes, schedule=self._schedule,
                          device=self.cost_device, n_devices=n_dev)
        result = mgr.run(traced.jaxpr, ctx)
        self.pass_receipts = result.receipts
        out_tree = jax.tree_util.tree_structure(traced.out_info)
        # multihost counts as verified-as-far-as-possible: the probe
        # can never run there, so a False flag would re-run the whole
        # pipeline (trace + lint + cost walks) on every step
        verified = bool(probe) or multihost
        self._pass_programs[sig] = (result.closed_jaxpr, out_tree,
                                    verified)
        if verified:
            self._pass_fast_verified.add(fast)
        if getattr(self, "_base_jit", None) is None:
            self._base_jit = self._jit
            programs = self._pass_programs

            def step2(p_vals, aux_vals, opt_state, x, y, key, step_count,
                      scaler):
                fl = jax.tree_util.tree_leaves(
                    (p_vals, aux_vals, opt_state, x, y, key, step_count,
                     scaler))
                s = tuple((tuple(v.shape), str(v.dtype)) for v in fl)
                entry = programs.get(s)
                if entry is None:
                    raise RuntimeError(
                        "graftpass: no rewritten program for argument "
                        "signature %r — the pass pipeline runs per batch "
                        "signature before trace; this trace bypassed it"
                        % (s[:4],))
                rj, otree = entry[0], entry[1]
                from jax import core as _jcore

                return jax.tree_util.tree_unflatten(
                    otree, _jcore.eval_jaxpr(rj.jaxpr, rj.consts, *fl))

            self._step_fn = step2
            self._jit = self._jit_for(step2)
            self._multi_jit = None  # rebuilt over the rewritten step

    # ------------------------------------------------------------------
    def _maybe_lint(self, example_args):
        """graftlint Level 1 over the step program, BEFORE its first XLA
        compile: checks collective permutations (GL001), partition specs
        incl. the jax 0.4.x stacked-operand GSPMD hazard (GL002),
        donation aliasing against this step's donate_argnums (GL003),
        and aux effects dropped by remat regions (GL004).  The lint
        walks ``self._jit.trace(...)`` — the very trace jit caches for
        the first call — so it costs one jaxpr walk, not an extra
        trace; steady-state steps pay nothing."""
        if self._linted or (self.lint == "off" and self.cost == "off"
                            and self.numerics == "off"):
            return
        self._lint_trace(self._jit, tuple(example_args))

    def _lint_trace(self, jit_obj, args):
        """The one lint ritual: trace ``jit_obj`` (GL004 hooks active),
        lint the jaxpr, and mark this step linted — only after a
        non-raising lint, so in "error" mode a caught/retried LintError
        re-lints (and re-raises) instead of compiling the flagged
        program.  Returns the traced object (shared with the jit's
        trace cache, so the first call/compile reuses it)."""
        from .aot import traced_with_effects

        lint_here = self.lint != "off" and not self._linted
        cost_here = self.cost != "off" and not self._linted
        num_here = self.numerics != "off" and not self._linted
        traced, effects = traced_with_effects(jit_obj, tuple(args),
                                              capture=lint_here)
        if lint_here and self._pass_effects:
            # GL004 effects were captured on the base trace the pass
            # pipeline consumed (the rewritten program replays it)
            effects = list(effects) + list(self._pass_effects)
        if lint_here or cost_here or num_here:
            # the walks over the traced jaxpr alone, as a span of the
            # set-up timeline (opened in line: no frame under the trace)
            with profiler.Setup("mx.step.lint") as span:
                findings = 0
                if lint_here:
                    findings += len(self._finish_lint(
                        traced.jaxpr, effects, args).diagnostics)
                if cost_here:
                    # same trace, one more walk: the cost model's GL201
                    # gate fires HERE — before lower/compile ever run
                    self._finish_cost(traced.jaxpr, args)
                    findings += len(self.cost_report.diagnostics)
                if num_here:
                    # same trace, the graftrange walk: GL401-GL405 fire
                    # HERE, before lower/compile — numerics="error"
                    # rejects the program with zero compiles spent
                    self._finish_numerics(traced.jaxpr, args)
                    findings += len(self.range_report.diagnostics)
                span.args["findings"] = findings
            self._linted = True
        return traced

    def _finish_lint(self, closed_jaxpr, effect_diags, example_args):
        from ..analysis.trace_lint import donated_leaf_indices
        from .aot import finish_lint

        donated = donated_leaf_indices(tuple(example_args),
                                       self._donate_argnums)
        extra = []
        if self.zero and self._shardings is not None:
            # GL006: a zero=1 step whose optimizer state is still
            # replicated over the dp axis keeps the N× memory the
            # feature exists to remove
            from ..analysis.trace_lint import check_zero_state_shardings

            state_sh = self._shardings[2]
            covered = [sh for sh, pad in zip(state_sh, self._zero_pad0)
                       if pad is not None] if state_sh else []
            extra.extend(check_zero_state_shardings(
                covered, self.batch_axis,
                where="TrainStep(zero=1) optimizer state"))
        if self.zero and self._legacy_state_origin:
            # GL007: the Trainer this step was built from still exposes
            # the legacy save_states/load_states path, which cannot
            # represent dp-sharded optimizer state
            from ..analysis.trace_lint import check_legacy_checkpoint_path

            extra.extend(check_legacy_checkpoint_path(
                self._legacy_state_origin,
                where="Trainer.make_fused_step(zero=1)"))
        # GL012: a silently-unbounded skip streak — nonfinite="skip"
        # under a static scale with no declared skip_streak_budget
        from ..analysis.trace_lint import check_unbounded_skip

        extra.extend(check_unbounded_skip(
            self.nonfinite, self._dynamic_scale, self.skip_streak_budget,
            where="TrainStep(nonfinite='skip', loss_scale=static)"))
        # GL013: error-feedback compression whose residual state can
        # never reach the checkpoint save set (sync='allreduce' steps
        # checkpoint no param-service subtree)
        from ..analysis.trace_lint import check_unsaved_compressor_state

        extra.extend(check_unsaved_compressor_state(
            self._compression, self.sync,
            where="TrainStep(compression=..., sync='allreduce')"))
        return finish_lint(closed_jaxpr, mode=self.lint,
                           effects=effect_diags, donated_leaves=donated,
                           extra=extra, suppress=self.lint_suppress,
                           what="fused train step", stacklevel=5)

    # ------------------------------------------------------------------
    # graftcost (analysis/cost_model.py, docs/ANALYSIS.md GL2xx)
    def _cost_shard_factors(self, example_args):
        """Per-flat-invar shard divisors congruent with the step's
        argument pytree — the resident-bytes model's view of the
        in_shardings (a ``P('dp')`` ZeRO state leaf on dp=8 costs 1/8
        per device)."""
        if self.mesh is None or self._shardings is None:
            return None

        from ..analysis.cost_model import shard_factor

        p_sh, aux_sh, state_sh, batch_sh, repl = self._shardings
        sh_args = (list(p_sh), list(aux_sh), state_sh, batch_sh, batch_sh,
                   repl, repl, (repl, repl, repl))
        is_sh = lambda s: hasattr(s, "spec") or hasattr(s, "_partitions")  # noqa: E731
        flat_sh = jax.tree_util.tree_leaves(sh_args, is_leaf=is_sh)
        flat_args = jax.tree_util.tree_leaves(tuple(example_args))
        if len(flat_sh) != len(flat_args):
            return None  # structure drifted; fall back to unsharded bytes
        return [shard_factor(s) for s in flat_sh]

    def _cost_analyze(self, closed_jaxpr, example_args, device=None,
                      hbm_budget=None):
        """One CostReport for the traced step program, with this step's
        donation spec, shardings and knob metadata applied."""
        from ..analysis.cost_model import analyze_jaxpr, shard_factor
        from ..analysis.trace_lint import donated_leaf_indices

        device = device or self.cost_device
        if hbm_budget is None:
            hbm_budget = self.hbm_budget
        donated = donated_leaf_indices(tuple(example_args),
                                       self._donate_argnums)
        factors = self._cost_shard_factors(example_args)
        axis_sizes, n_dev = None, 1
        if self.mesh is not None:
            axis_sizes = {k: int(v) for k, v in dict(self.mesh.shape).items()}
            n_dev = int(self.mesh.size)
        # optimizer-state bytes: exact, from the state leaves and their
        # placements (the ZeRO-1 1/N figures test_zero_sharding measures)
        is_sh = lambda s: hasattr(s, "spec") or hasattr(s, "_partitions")  # noqa: E731
        state_leaves = jax.tree_util.tree_leaves(self._opt_state)
        opt_total = float(sum(
            int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
            for v in state_leaves))
        if self.mesh is not None and self._shardings is not None:
            sh_leaves = jax.tree_util.tree_leaves(self._shardings[2],
                                                  is_leaf=is_sh)
            opt_dev = float(sum(
                int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
                / shard_factor(s)
                for v, s in zip(state_leaves, sh_leaves))) \
                if len(sh_leaves) == len(state_leaves) else opt_total
        else:
            opt_dev = opt_total
        p_bytes = float(sum(
            int(np.prod(p._data._data.shape))
            * np.dtype(p._data._data.dtype).itemsize
            for p in (self._gp or []) + (self._aux or [])))
        report = analyze_jaxpr(
            closed_jaxpr, axis_sizes=axis_sizes, donated_leaves=donated,
            invar_shard_factors=factors, device=device, n_devices=n_dev,
            hbm_budget=hbm_budget,
            meta={"zero": self.zero,
                  "pipeline_stages": self.pipeline_stages,
                  "num_micro": self.num_micro,
                  "pipeline_remat": bool(self.pipeline_remat),
                  "donate": bool(self._donate),
                  "optimizer": self.opt.name,
                  "multi_precision": bool(self.opt.multi_precision),
                  "batch_axis": self.batch_axis})
        report.opt_state_bytes = opt_total
        report.opt_state_bytes_per_device = opt_dev
        report.param_bytes = p_bytes
        if self.sync != "allreduce" or self._compression is not None:
            # trace-time push-volume pricing for the async rung: what
            # one compressed push costs vs its dense f32 wire, priced
            # from shapes alone — zero compiles spent
            from ..analysis.cost_model import push_volume_report

            entries = [(p.name, tuple(p._data._data.shape),
                        str(p._data._data.dtype)) for p in (self._gp or [])]
            report.meta["push_volume"] = push_volume_report(
                entries, self._compression)
        report.diagnostics.extend(self._cost_config_diags(report))
        return report

    def _cost_config_diags(self, report):
        """GL204: knob settings that pay memory or recompute for
        nothing — donation off (peak raised by a full param/state copy,
        zero traffic saved), or pipeline_remat recompute while peak sits
        far under the budget."""
        from ..analysis import Diagnostic, Severity as Sev

        diags = []
        if not self._donate:
            diags.append(Diagnostic(
                "GL204", Sev.WARNING,
                "donate=False: peak memory carries a second full copy of "
                "params and optimizer state (%.1f MB) and saves zero HBM "
                "traffic in exchange"
                % ((report.param_bytes + report.opt_state_bytes_per_device)
                   / 1e6),
                where="TrainStep(donate=False)",
                hint="the knob is make_train_step(donate=True) (the "
                     "default) — leave donation on unless you must "
                     "re-read the old params after the step"))
        if self.pipeline_remat:
            cap = report.hbm_budget or report.spec().hbm_bytes
            if report.peak_bytes < 0.5 * cap:
                diags.append(Diagnostic(
                    "GL204", Sev.WARNING,
                    "pipeline_remat=True pays recompute HBM traffic while "
                    "predicted peak memory (%.1f MB) sits under half the "
                    "budget (%.1f MB) — the stash it avoids would have fit"
                    % (report.peak_bytes / 1e6, cap / 1e6),
                    where="TrainStep(pipeline_remat=True)",
                    hint="the knob is make_train_step(pipeline_remat="
                         "False); drop it (or lower hbm_budget if the "
                         "headroom is intentional) — tools/autotune.py "
                         "searches it as part of the train space"))
        return diags

    def _finish_cost(self, closed_jaxpr, example_args):
        """The in-step cost pass: store the report; ``cost=\"check\"``
        raises :class:`~..analysis.LintError` on error-severity GL2xx
        findings (GL201 over-budget) BEFORE lower/compile, and warns the
        advisory ones.  ``cost=\"report\"`` is silent — read
        ``step.cost_report``."""
        from ..analysis import LintReport, Severity

        report = self._cost_analyze(closed_jaxpr, example_args)
        rep = LintReport(suppress=self.lint_suppress)
        rep.extend(report.diagnostics)
        report.diagnostics = list(rep.diagnostics)
        self.cost_report = report
        if self.cost == "check":
            rep.raise_if_errors()
            if rep.warnings:
                import warnings as _warnings

                _warnings.warn("graftcost: fused train step has findings\n"
                               + rep.format(Severity.WARNING),
                               stacklevel=4)

    def _analysis_args(self, x, y):
        """The step's abstract 8-tuple argument signature for the given
        batch — the zero-compile analysis entrances (`analyze_cost`,
        `analyze_schedule`) share it."""
        self._ensure_built()

        def aval(a):
            if isinstance(a, jax.ShapeDtypeStruct):
                return a
            if isinstance(a, NDArray):
                a = a._data
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        pv = [aval(p._data._data) for p in self._gp]
        av = [aval(p._data._data) for p in self._aux]
        sv = jax.tree_util.tree_map(aval, self._opt_state)
        return (pv, av, sv, aval(x), aval(y), aval(self._key_dev),
                aval(self._step_dev),
                tuple(aval(v) for v in self._scaler_dev))

    def analyze_schedule(self, x, y):
        """Run the configured pass pipeline over the traced step in
        report-everything mode and return the
        :class:`~..analysis.passes.PipelineResult` — per-site receipt
        rows included — WITHOUT installing anything, compiling
        anything, or raising on refusals.  ONE abstract trace; the
        autotuner's site table (``autotune.schedule_site_table``) is
        built from exactly this."""
        from ..analysis.passes import PassManager

        args = self._analysis_args(x, y)
        traced, ctx, n_dev, _multihost = self._pass_pipeline_inputs(
            args, probe=False)
        mgr = PassManager(self._passes, schedule=self._schedule,
                          device=self.cost_device, n_devices=n_dev,
                          raise_on_error=False)
        return mgr.run(traced.jaxpr, ctx)

    def analyze_cost(self, x, y, device=None, hbm_budget=None):
        """Cost the step for the given batch WITHOUT compiling or
        running it: traces abstractly (``jit.trace`` on avals — the
        trace the first real call would reuse) and returns the
        :class:`~..analysis.cost_model.CostReport`.  ``x``/``y`` may be
        arrays, NDArrays or ``jax.ShapeDtypeStruct``s."""
        args = self._analysis_args(x, y)
        # with a pass pipeline configured the costed program is the
        # REWRITTEN one — what would actually compile (post-pass cost,
        # the autotuner's ranking signal for `--passes` candidates).
        # probe=False: ranking a candidate must never pay two eager
        # step executions — the probe runs when a run path installs
        # the program for real (nothing unprobed ever compiles)
        self._maybe_apply_passes(args, probe=False)
        traced = self._jit.trace(*args)
        return self._cost_analyze(traced.jaxpr, args, device=device,
                                  hbm_budget=hbm_budget)

    # ------------------------------------------------------------------
    # graftrange (analysis/value_range.py, docs/ANALYSIS.md GL4xx)
    def _numerics_seeds(self, example_args):
        """``(input_ranges, invar_labels)`` for the step program's flat
        invars: declared batch annotations (``input_range=``),
        optimizer-state invariants (variance accumulators are
        non-negative), the loss-scale config's bounds and the 1-based
        step counter.  Params/aux default to unknown-finite — training
        moves them, so an observed init range would be a lie."""
        (p_vals, aux_vals, opt_state, _x, _y, _key, _step,
         _scaler) = example_args
        seeds: Dict[int, Any] = {}
        labels: Dict[int, str] = {}
        idx = 0
        for p in self._gp:
            labels[idx] = "param:%s" % p.name
            idx += 1
        for p in self._aux:
            labels[idx] = "aux:%s" % p.name
            idx += 1
        state_leaves = len(jax.tree_util.tree_leaves(opt_state))
        hints = self.opt.state_range_hints()
        if hints and self._gp and \
                state_leaves == len(self._gp) * len(hints):
            for i, p in enumerate(self._gp):
                for j, h in enumerate(hints):
                    labels[idx] = "opt:%s[%d]" % (p.name, j)
                    if h is not None:
                        seeds[idx] = h
                    idx += 1
        else:
            idx += state_leaves
        ir = self.input_range
        x_r = y_r = None
        if isinstance(ir, dict):
            x_r, y_r = ir.get("x"), ir.get("y")
        elif ir is not None:
            x_r = tuple(ir)
        labels[idx] = "x"
        if x_r is not None:
            seeds[idx] = tuple(x_r)
        idx += 1
        labels[idx] = "y"
        if y_r is not None:
            seeds[idx] = tuple(y_r)
        idx += 1
        labels[idx] = "rng_key"
        idx += 1
        labels[idx] = "step"
        # the carried counter is incremented BEFORE the update applies,
        # so adam's 1-beta**t bias correction sees t >= 1 (never /0)
        seeds[idx] = (0.0, float(2**31 - 1))
        idx += 1
        if self._dynamic_scale:
            cfg = self._scale_cfg
            scale_seed = (cfg.min_loss_scale, cfg.max_loss_scale, True)
        elif self._scale_cfg is not None:
            s = float(self._scale_cfg)
            scale_seed = (s, s, True)
        else:
            scale_seed = (1.0, 1.0, True)
        for name, seed in (("loss_scale", scale_seed),
                           ("ls_unskipped", (0.0, float(2**31 - 1))),
                           ("ls_skipped", (0.0, float(2**31 - 1)))):
            labels[idx] = name
            seeds[idx] = seed
            idx += 1
        return seeds, labels

    def _numerics_analyze(self, closed_jaxpr, example_args):
        """One RangeReport for the traced step program: the GL401/402/
        403/404 value-range walk seeded with this step's annotations,
        plus the GL405 loss-scale advisory from the step config."""
        from ..analysis.value_range import analyze_ranges, loss_scale_diags

        seeds, labels = self._numerics_seeds(example_args)
        axis_sizes = None
        if self.mesh is not None:
            axis_sizes = {k: int(v)
                          for k, v in dict(self.mesh.shape).items()}
        report = analyze_ranges(
            closed_jaxpr, input_ranges=seeds, invar_labels=labels,
            axis_sizes=axis_sizes,
            meta={"what": "fused train step",
                  "compute_dtype": str(self.compute_dtype),
                  "loss_scale": repr(self._scale_cfg),
                  "input_range": repr(self.input_range)})
        report.diagnostics.extend(loss_scale_diags(
            self.compute_dtype,
            self._scale_cfg if isinstance(self._scale_cfg, float)
            else None,
            self._dynamic_scale,
            where="TrainStep(loss_scale=%r, compute_dtype=%r)"
                  % (self._scale_cfg, self.compute_dtype)))
        # pass-emitted numerics advisories (amp_bf16's GL403 per-op
        # exclusions) belong in the step's numerics report too
        for r in (self.pass_receipts or ()):
            report.diagnostics.extend(
                d for d in r.diagnostics if d.code.startswith("GL4"))
        return report

    def _finish_numerics(self, closed_jaxpr, example_args):
        """The in-step numerics pass: store ``step.range_report``;
        ``numerics="error"`` raises :class:`~..analysis.LintError` on
        error-severity GL4xx findings BEFORE lower/compile (the GL201
        discipline), ``"warn"`` warns them."""
        from ..analysis import LintReport, Severity

        report = self._numerics_analyze(closed_jaxpr, example_args)
        rep = LintReport(suppress=self.lint_suppress)
        rep.extend(report.diagnostics)
        report.diagnostics = list(rep.diagnostics)
        self.range_report = report
        if self.numerics == "error":
            rep.raise_if_errors()
        if rep.diagnostics:
            import warnings as _warnings

            _warnings.warn("graftrange: fused train step has findings\n"
                           + rep.format(Severity.WARNING), stacklevel=5)

    def analyze_numerics(self, x, y, input_range=None):
        """Range-analyze the step for the given batch WITHOUT compiling
        or running it (abstract ``jit.trace`` — the trace the first
        real call reuses; with a pass pipeline configured the analyzed
        program is the REWRITTEN one, so an amp_bf16 demotion shows its
        bf16 edges).  Returns the
        :class:`~..analysis.value_range.RangeReport`; mode policy is
        NOT applied — the caller (the autotuner's GL403/GL405 pruning)
        reads ``report.errors`` itself.  ``input_range`` overrides the
        step's annotation for this analysis."""
        self._ensure_built()
        if input_range is not None:
            prev, self.input_range = self.input_range, input_range
        else:
            prev = self.input_range

        def aval(a):
            if isinstance(a, jax.ShapeDtypeStruct):
                return a
            if isinstance(a, NDArray):
                a = a._data
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        try:
            pv = [aval(p._data._data) for p in self._gp]
            av = [aval(p._data._data) for p in self._aux]
            sv = jax.tree_util.tree_map(aval, self._opt_state)
            args = (pv, av, sv, aval(x), aval(y), aval(self._key_dev),
                    aval(self._step_dev),
                    tuple(aval(v) for v in self._scaler_dev))
            self._maybe_apply_passes(args, probe=False)
            traced = self._jit.trace(*args)
            return self._numerics_analyze(traced.jaxpr, args)
        finally:
            self.input_range = prev

    # ------------------------------------------------------------------
    def _ensure_built(self):
        # the first build is a span of the set-up timeline; every later
        # call (one a step) finds the step built and records nothing
        with (profiler.Setup("mx.step.build") if self._jit is None
              else _BUILT):
            if self._gp is None:
                self._collect()
                if any(p._data is None for p in self._gp + self._aux):
                    raise RuntimeError("initialize() the net before make_train_step")
            if self._opt_state is None:
                pv = [p._data._data for p in self._gp]
                if self.zero:
                    # state is born PADDED (leading dim a multiple of the dp
                    # axis) so device_put onto the P(dp) shardings slices it
                    # evenly; master weights inherit the zero padding
                    pv = [self._zero_padded(v, pad)
                          for v, pad in zip(pv, self._zero_pad0)]
                self._opt_state = self.opt.init(pv)
            if self._jit is None:
                self._jit = self._build()
                from .mesh import spans_processes

                self._multihost = self.mesh is not None \
                    and spans_processes(self.mesh)
            if self._key_dev is None or self._key_epoch != rng.epoch():
                # (re)draw the carried key — also when the user reseeded after
                # steps already ran (mx.random.seed / rng.set_state must keep
                # affecting the training stream)
                self._key_epoch = rng.epoch()
                self._key_dev = rng.next_key()
                if self._placed:
                    if self._multihost:
                        from jax.experimental import multihost_utils as mhu

                        self._key_dev = mhu.host_local_array_to_global_array(
                            self._key_dev, self.mesh, self._shardings[4].spec)
                    else:
                        self._key_dev = jax.device_put(self._key_dev,
                                                       self._shardings[4])
            if self._step_dev is None:
                self._step_dev = jnp.int32(self._step_count)
            if self._scaler_dev is None:
                init_scale = self._scale_cfg.init_scale if self._dynamic_scale \
                    else float(self._scale_cfg or 1.0)
                self._scaler_dev = (jnp.float32(init_scale), jnp.int32(0),
                                    jnp.int32(0))
            # an async-capable step materializes its service client EAGERLY
            # so the checkpoint treedef is identical before and after a
            # policy-ladder degrade (a pre-degrade save must restore into a
            # post-degrade step and vice versa)
            if self.sync != "allreduce" and self._svc_client is None \
                    and not self._svc_attaching:
                self._svc_attaching = True
                try:
                    self.attach_param_service()
                finally:
                    self._svc_attaching = False

    def _place_state(self, p_vals, aux_vals):
        """One-time placement of params/opt-state on their target shardings
        (donation then updates the buffers in place every step).  Multihost:
        host-local replicas (identical after seeded init / broadcast) become
        global arrays — dist_sync_device ≡ one GSPMD program over every
        process's devices (SURVEY §5.8)."""
        p_sh, aux_sh, state_sh, _, repl = self._shardings
        with profiler.Setup(
                "mx.step.place", what="state", mesh=_mesh_axes(self.mesh),
                bytes=_nbytes((p_vals, aux_vals, self._opt_state))):
            if self._multihost:
                # every host holds the FULL state value (identical after
                # seeded init / broadcast); each device fetches its slice of
                # it through the callback.  NOT host_local_array_to_global:
                # that treats the local value as this host's SHARD, which
                # would stack N full copies of a dp-sharded ZeRO-1 state
                # leaf into an N×-too-tall global array.
                def _globalize(v, s):
                    host = np.asarray(v)
                    return jax.make_array_from_callback(
                        host.shape, s, lambda idx: host[idx])

                p_vals = [_globalize(v, s) for v, s in zip(p_vals, p_sh)]
                aux_vals = [_globalize(v, s) for v, s in zip(aux_vals, aux_sh)]
                self._opt_state = jax.tree.map(_globalize, self._opt_state,
                                               state_sh)
                # carried key/step/scaler must be identical across hosts
                # (same seed); promote the host-local replicas too
                self._key_dev = _globalize(self._key_dev, repl)
                self._step_dev = _globalize(self._step_dev, repl)
                self._scaler_dev = tuple(_globalize(v, repl)
                                         for v in self._scaler_dev)
            else:
                p_vals = [jax.device_put(v, s) for v, s in zip(p_vals, p_sh)]
                aux_vals = [jax.device_put(v, s)
                            for v, s in zip(aux_vals, aux_sh)]
                self._opt_state = jax.tree.map(
                    jax.device_put, self._opt_state, state_sh)
                self._key_dev = jax.device_put(self._key_dev, repl)
                self._step_dev = jax.device_put(self._step_dev, repl)
                self._scaler_dev = tuple(jax.device_put(v, repl)
                                         for v in self._scaler_dev)
        self._placed = True
        return p_vals, aux_vals

    def _place_batch(self, xv, yv):
        """Shard the batch over the mesh's batch axis; multihost treats the
        process-local batch as this host's shard of the global batch."""
        batch_sh = self._shardings[3]
        if self._multihost:
            from jax.experimental import multihost_utils as mhu

            return (mhu.host_local_array_to_global_array(
                        xv, self.mesh, batch_sh.spec),
                    mhu.host_local_array_to_global_array(
                        yv, self.mesh, batch_sh.spec))
        return jax.device_put(xv, batch_sh), jax.device_put(yv, batch_sh)

    @property
    def schedule_hash(self):
        """Canonical hash of the active pass schedule (graftsched,
        analysis/passes.py::PassSchedule) — the legacy whole-pass list
        hashes as its all-sites schedule, so the same decisions always
        key the same; None with no passes configured."""
        from ..analysis.passes import PassSchedule

        if self._schedule is not None:
            return self._schedule.hash()
        if not self._passes:
            return None
        return PassSchedule.from_passes(self._passes).hash()

    def _cache_extra(self):
        """This step's contribution to the compile-cache key (beyond the
        lowered program itself): mesh shape + axis names and the knob
        set, so two configs that somehow lower alike still key apart."""
        mesh = None if self.mesh is None else \
            tuple(sorted((str(a), int(s))
                         for a, s in dict(self.mesh.shape).items()))
        return ("train_step", mesh, self.batch_axis, self.zero,
                self.pipeline_stages, self.num_micro,
                bool(self.pipeline_remat), bool(self._donate),
                self.opt.name, bool(self.opt.multi_precision),
                str(self.compute_dtype), self.nonfinite,
                self._dynamic_scale,
                tuple(p.name for p in self._passes),
                # graftsched: two schedules never share an executable
                ("sched", self.schedule_hash))

    def aot_compile(self, x, y, cache=None):
        """Ahead-of-time trace + lower + compile the fused step for the given
        batch, returning per-phase wall seconds ``{"trace": s, "compile": s}``.

        Splits Python/JAX trace time from XLA compile time (the reference's
        analog is cuDNN autotune + InitCachedOps cost at bind,
        ``src/executor/graph_executor.cc:1220``) so benchmarks can report
        where startup time goes.  The compiled executable is installed as
        this step's callable, so subsequent ``step(x, y)`` calls with the
        same shapes skip compilation.

        ``cache`` is an optional :class:`~.aot.CompileCache` (default:
        the ``MXTPU_COMPILE_CACHE`` env) — on a warm cache the XLA
        compile is skipped entirely (``times["cache"] == "hit"``,
        ``times["compile"] == 0.0``), even in a fresh process.
        """
        import time as _time

        self._ensure_built()
        xv = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yv = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        p_vals = [p._data._data for p in self._gp]
        aux_vals = [p._data._data for p in self._aux]
        if self.mesh is not None:
            # compile against the PLACED (global, sharded) avals — the same
            # arrays __call__ will pass — or the executable never matches
            if not self._placed:
                p_vals, aux_vals = self._place_state(p_vals, aux_vals)
                for p, v in zip(self._gp, p_vals):
                    p._data._data = v
                for p, v in zip(self._aux, aux_vals):
                    p._data._data = v
            with profiler.Setup("mx.step.place", what="batch",
                                mesh=_mesh_axes(self.mesh),
                                bytes=_nbytes((xv, yv))):
                xv, yv = self._place_batch(xv, yv)
        # lint rides THIS trace — no separate lint trace, so the trace/
        # compile split below stays honest (the jaxpr walk is ms-scale)
        from .aot import compile_timed

        t0 = _time.perf_counter()
        with profiler.Setup("mx.step.trace"):
            self._maybe_apply_passes((p_vals, aux_vals, self._opt_state, xv,
                                      yv, self._key_dev, self._step_dev,
                                      self._scaler_dev))
            traced = self._lint_trace(self._jit,
                                      (p_vals, aux_vals, self._opt_state,
                                       xv, yv, self._key_dev,
                                       self._step_dev, self._scaler_dev))
        compiled, times = compile_timed(traced, t_trace=_time.perf_counter() - t0,
                                        cache=cache,
                                        cache_extra=self._cache_extra())
        self._compiled = compiled
        self._compiled_key = ((xv.shape, str(xv.dtype)),
                              (yv.shape, str(yv.dtype)))
        return times

    @property
    def compiled(self):
        """The executable :meth:`aot_compile` installed (``as_text()``,
        ``memory_analysis()``), or None before it ran."""
        return self._compiled

    @property
    def opt_state(self):
        """The optimizer-state pytree as it lives on the device(s)."""
        return self._opt_state

    def _build_multi(self):
        """K steps in ONE compiled program: lax.scan over stacked batches.

        Removes per-step dispatch/launch entirely (useful when host
        latency or program-launch overhead matters — e.g. remote or
        congested runtimes) and is the natural carrier for gradient-
        accumulation-style loops.  Params/opt-state/key/step thread
        through the scan carry; returns per-step losses.
        """
        step = self._step_fn

        def multi(p_vals, aux_vals, opt_state, xs, ys, key, step_count,
                  scaler):
            def body(carry, xy):
                p, a, st, k, c, sc = carry
                x, y = xy
                loss, p2, a2, s2, k2, c2, sc2, ok = step(p, a, st, x, y,
                                                         k, c, sc)
                return (p2, a2, s2, k2, c2, sc2), (loss, ok)

            carry, (losses, oks) = jax.lax.scan(
                body, (p_vals, aux_vals, opt_state, key, step_count,
                       scaler), (xs, ys))
            p, a, st, k, c, sc = carry
            return losses, p, a, st, k, c, sc, oks

        donate = self._donate_argnums
        if self.mesh is None:
            return jax.jit(multi, donate_argnums=donate)
        p_sh, aux_sh, state_sh, batch_sh, repl = self._shardings
        stack_sh = NamedSharding(self.mesh, P(None, self.batch_axis)) \
            if self.batch_axis in self.mesh.axis_names else repl
        return jax.jit(multi, donate_argnums=donate,
                       in_shardings=(p_sh, aux_sh, state_sh, stack_sh,
                                     stack_sh, repl, repl, repl),
                       out_shardings=(repl, p_sh, aux_sh, state_sh, repl,
                                      repl, repl, repl))

    def run_steps(self, xs, ys):
        """Run ``K = len(xs)`` steps as one program (see _build_multi).
        ``xs``/``ys``: stacked arrays with a leading K axis, or sequences
        of per-step batches.  Returns the K losses as an NDArray."""
        self._ensure_built()
        if isinstance(xs, (list, tuple)):
            xs = jnp.stack([x._data if isinstance(x, NDArray)
                            else jnp.asarray(x) for x in xs])
        else:
            xs = xs._data if isinstance(xs, NDArray) else jnp.asarray(xs)
        if isinstance(ys, (list, tuple)):
            ys = jnp.stack([y._data if isinstance(y, NDArray)
                            else jnp.asarray(y) for y in ys])
        else:
            ys = ys._data if isinstance(ys, NDArray) else jnp.asarray(ys)
        p_vals = [p._data._data for p in self._gp]
        aux_vals = [p._data._data for p in self._aux]
        if self.mesh is not None:
            if not self._placed:
                p_vals, aux_vals = self._place_state(p_vals, aux_vals)
            from jax.sharding import NamedSharding as _NS

            stack_sh = _NS(self.mesh, P(None, self.batch_axis)) \
                if self.batch_axis in self.mesh.axis_names \
                else _NS(self.mesh, P())
            if self._multihost:
                from jax.experimental import multihost_utils as mhu

                xs = mhu.host_local_array_to_global_array(
                    xs, self.mesh, stack_sh.spec)
                ys = mhu.host_local_array_to_global_array(
                    ys, self.mesh, stack_sh.spec)
            else:
                xs = jax.device_put(xs, stack_sh)
                ys = jax.device_put(ys, stack_sh)
        if self._passes:
            # the scan body is the SINGLE-step program: run the pipeline
            # for the per-step signature before the multi program traces
            # — derived from the PLACED (global on multihost) batch, the
            # shapes the scan body will actually carry
            def sd(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype)

            self._maybe_apply_passes((
                [sd(v) for v in p_vals], [sd(v) for v in aux_vals],
                jax.tree_util.tree_map(sd, self._opt_state),
                jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype),
                jax.ShapeDtypeStruct(ys.shape[1:], ys.dtype),
                sd(self._key_dev), sd(self._step_dev),
                tuple(sd(v) for v in self._scaler_dev)))
        if getattr(self, "_multi_jit", None) is None:
            self._multi_jit = self._build_multi()
        k = xs.shape[0]
        if not self._linted and (self.lint != "off" or self.cost != "off"):
            # lint rides the multi-step program's OWN trace (shared with
            # the compile below via jit's trace cache) — the scan body
            # is the step, so the walker sees the same hazards
            self._lint_trace(self._multi_jit,
                             (p_vals, aux_vals, self._opt_state, xs, ys,
                              self._key_dev, self._step_dev,
                              self._scaler_dev))
        (losses, new_p, new_aux, new_s, self._key_dev, self._step_dev,
         self._scaler_dev, oks) = \
            self._multi_jit(p_vals, aux_vals, self._opt_state, xs, ys,
                            self._key_dev, self._step_dev, self._scaler_dev)
        # host mirror; with nonfinite containment the DEVICE counter is
        # authoritative (skipped steps do not advance it)
        self._step_count += int(k)
        for pp, v in zip(self._gp, new_p):
            pp._data._data = v
        for pp, v in zip(self._aux, new_aux):
            pp._data._data = v
        self._opt_state = new_s
        # boundary checkpoint BEFORE a possible raise: a pending
        # preemption save must not be dropped by an overflowing stack
        self._maybe_checkpoint()
        if self.nonfinite == "raise":
            import numpy as _np

            bad = _np.flatnonzero(~_np.asarray(oks))
            if bad.size:
                raise FloatingPointError(
                    "non-finite gradients in %d of %d scanned steps "
                    "(offsets %s); params/optimizer state were left "
                    "unchanged for those steps"
                    % (bad.size, int(k), bad[:8].tolist()))
        return NDArray(losses)

    # ------------------------------------------------------------------
    # sync→async policy ladder (parallel/param_service.py,
    # docs/RESILIENCE.md §8)
    @property
    def sync_mode(self) -> str:
        """The EFFECTIVE rung right now: ``"allreduce"`` or
        ``"async"`` (``sync="auto"`` moves between them)."""
        return self._applied_sync

    def attach_param_service(self, service=None, rank: int = 0):
        """Bind this step to a :class:`~.param_service.ParamService`
        (created in-process, owned and checkpointed by this step, when
        ``service=None``) and seed it with the current parameters
        (rank-0-wins ``init`` semantics).  Returns the
        :class:`~.param_service.ServiceClient`."""
        from .param_service import (ParamService, ServiceClient,
                                    ServiceUpdater)

        if self.sync == "allreduce":
            raise ValueError(
                "this step was built with sync='allreduce'; rebuild with "
                "make_train_step(sync='async'|'auto') to push/pull "
                "through a parameter service")
        self._ensure_built()
        owns = service is None
        if owns:
            service = ParamService(updater=ServiceUpdater(self.opt),
                                   staleness_bound=self.staleness_bound)
        self._svc_client = ServiceClient(service, rank=int(rank),
                                         compressor=self._compression,
                                         owns_service=owns)
        # positional keys (ps-lite uses int keys too): gluon auto-names
        # drift across rebuilds, positions don't — a resumed process
        # must map its fresh params onto the saved service state
        self._svc_client.init_params(
            {str(i): p._data._data for i, p in enumerate(self._gp)})
        return self._svc_client

    def set_sync_mode(self, mode: str) -> None:
        """Pin the effective rung at a step boundary.  Degrading to
        ``"async"`` starts pushing through the attached service (the
        server holds the authoritative copy from then on); recovering
        to ``"allreduce"`` first adopts the service's parameters so the
        collective rung resumes from the async rung's progress."""
        if mode not in ("allreduce", "async"):
            raise ValueError("sync mode must be 'allreduce' or 'async', "
                             "got %r" % (mode,))
        if self.sync == "allreduce" and mode == "async":
            raise ValueError("step was built with sync='allreduce' — it "
                             "has no async rung")
        if mode == self._applied_sync:
            return
        if mode == "async":
            self._ensure_built()  # attaches the service client
            # the service adopts THIS replica's CURRENT params as the
            # authoritative copy — its seed-time snapshot is stale by
            # however many collective steps ran (and the fused rung
            # donated those seed buffers anyway)
            self._svc_client.sync_params(
                {str(i): p._data._data for i, p in enumerate(self._gp)})
        elif self._svc_client is not None:
            pulled = self._svc_client.pull_params(timeout=self.pull_timeout)
            for i, p in enumerate(self._gp):
                if str(i) in pulled:
                    # copy: the fused rung will DONATE this buffer, and
                    # the service must keep its own copy alive
                    p._data._data = jnp.array(pulled[str(i)])
        self._applied_sync = mode
        self.sync_policy.effective = mode

    def observe_stragglers(self, straggler_ranks) -> str:
        """One straggler-detector frame into the policy ladder
        (``supervisor.straggler_verdicts`` rank list, possibly empty);
        applies any rung switch the policy decides and returns the
        effective mode.  The supervised loop calls this every step
        boundary under ``sync="auto"``."""
        mode = self.sync_policy.observe(straggler_ranks)
        if mode != self._applied_sync:
            self.set_sync_mode(mode)
        return self._applied_sync

    def _async_call(self, x, y):
        """One async step: local fwd+bwd, compressed push, bounded-
        staleness pull, install the pulled params.  Counters advance
        exactly as the fused rung's (the checkpoint boundary hook and
        the supervisor read the same step count either way)."""
        self._ensure_built()
        xv = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yv = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        p_vals = [p._data._data for p in self._gp]
        aux_vals = [p._data._data for p in self._aux]
        if self._grad_jit is None:
            from ..kvstore.gradient_compression import _donate_ok

            self._grad_jit = jax.jit(
                self._make_grad_step(),
                donate_argnums=(1, 4) if self._donate and _donate_ok()
                else ())
        loss, grads, new_aux, self._key_dev = self._grad_jit(
            p_vals, aux_vals, xv, yv, self._key_dev)
        for p, v in zip(self._aux, new_aux):
            p._data._data = v
        client = self._svc_client
        client.push_step({str(i): g for i, g in enumerate(grads)})
        pulled = client.pull_params(timeout=self.pull_timeout)
        for i, p in enumerate(self._gp):
            p._data._data = jnp.asarray(pulled[str(i)])
        self._step_count += 1
        self._step_dev = self._step_dev + 1
        self._maybe_checkpoint()
        return NDArray(loss)

    def __call__(self, x, y):
        from .. import profiler

        if not profiler.is_running():
            return self._step_once(x, y)
        # one "mx.train_step" span a call on the host plane of the trace
        # mx.profiler records, beside the device ops it dispatched
        with jax.profiler.StepTraceAnnotation("mx.train_step",
                                              step_num=self._step_count):
            return self._step_once(x, y)

    def _step_once(self, x, y):
        if self._applied_sync == "async":
            return self._async_call(x, y)
        self._ensure_built()

        xv = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yv = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        p_vals = [p._data._data for p in self._gp]
        aux_vals = [p._data._data for p in self._aux]
        if self.mesh is not None:
            if not self._placed:
                p_vals, aux_vals = self._place_state(p_vals, aux_vals)
            xv, yv = self._place_batch(xv, yv)
        self._maybe_apply_passes((p_vals, aux_vals, self._opt_state, xv,
                                  yv, self._key_dev, self._step_dev,
                                  self._scaler_dev))
        self._maybe_lint((p_vals, aux_vals, self._opt_state, xv, yv,
                          self._key_dev, self._step_dev, self._scaler_dev))
        # the AOT executable is shape-pinned; any other batch shape/dtype
        # falls back to the jit wrapper, which retraces transparently
        fn = self._jit
        if self._compiled is not None and self._compiled_key == (
                (xv.shape, str(xv.dtype)), (yv.shape, str(yv.dtype))):
            fn = self._compiled
        (loss, new_p, new_aux, new_s, self._key_dev, self._step_dev,
         self._scaler_dev, ok) = fn(
            p_vals, aux_vals, self._opt_state, xv, yv, self._key_dev,
            self._step_dev, self._scaler_dev)
        # host mirror of the device counter, advanced only on success so the
        # two can't drift when a step raises (bad shapes, donation errors);
        # with nonfinite containment the DEVICE counter is authoritative
        # (a skipped step does not advance it)
        self._step_count += 1
        for p, v in zip(self._gp, new_p):
            p._data._data = v
        for p, v in zip(self._aux, new_aux):
            p._data._data = v
        self._opt_state = new_s
        # the boundary checkpoint runs BEFORE a possible raise below: a
        # pending preemption save must not be dropped because the final
        # step happened to overflow
        self._maybe_checkpoint()
        if self.nonfinite == "raise" and not bool(ok):
            # state is already installed — and provably unchanged, the
            # guard selected the old buffers — so training CAN continue
            # after catching this
            raise FloatingPointError(
                "non-finite gradients after %d applied updates (call %d "
                "of this step); params/optimizer state were left "
                "unchanged (nonfinite='raise')"
                % (int(self._step_dev), self._step_count))
        return NDArray(loss)

    # ------------------------------------------------------------------
    @property
    def loss_scale(self):
        """The CURRENT loss scale (reads the carried device state)."""
        if self._scaler_dev is None:
            return self._scale_cfg.init_scale if self._dynamic_scale \
                else float(self._scale_cfg or 1.0)
        return float(self._scaler_dev[0])

    @property
    def skipped_steps(self):
        """How many steps the non-finite guard has skipped so far."""
        return 0 if self._scaler_dev is None else int(self._scaler_dev[2])

    @property
    def step_count(self):
        """Applied-update count (device counter: skipped steps excluded)."""
        return self._step_count if self._step_dev is None \
            else int(self._step_dev)

    # ------------------------------------------------------------------
    # durable state (parallel/checkpoint.py)
    def _checkpoint_state(self):
        """The full training state as one pytree: params, aux state,
        optimizer state (dp-sharded leaves stay sharded — the manager
        saves per-rank shards without gathering), PRNG key, device step
        counter and loss-scale state."""
        self._ensure_built()
        state = {"params": [p._data._data for p in self._gp],
                 "aux": [p._data._data for p in self._aux],
                 "opt_state": self._opt_state,
                 "rng_key": self._key_dev,
                 "step": self._step_dev,
                 "loss_scale": self._scaler_dev}
        if self._svc_client is not None:
            # async rung durable state: compressor residuals (+ sparse
            # step counters), the bounded-staleness clock and — when
            # this step owns the service — the authoritative server
            # params/updater state (docs/RESILIENCE.md §8 resume flow)
            state["param_service"] = self._svc_client.state_dict()
        return state

    def _checkpoint_shardings(self):
        """Placement tree congruent with :meth:`_checkpoint_state` —
        what restore uses to put every restored leaf back on its exact
        device layout (None leaves mean default placement)."""
        if self.mesh is None or self._shardings is None:
            return None
        p_sh, aux_sh, state_sh, _, repl = self._shardings
        return {"params": list(p_sh), "aux": list(aux_sh),
                "opt_state": state_sh, "rng_key": repl, "step": repl,
                "loss_scale": (repl, repl, repl)}

    def _as_manager(self, directory_or_manager, keep_last=3):
        from .checkpoint import CheckpointManager

        if isinstance(directory_or_manager, CheckpointManager):
            return directory_or_manager
        return CheckpointManager(directory_or_manager, keep_last=keep_last)

    @staticmethod
    def _host_int(x) -> int:
        """Host value of a replicated device scalar — via the first
        addressable shard, which works for multihost global arrays
        (``device_get`` would demand full addressability)."""
        if hasattr(x, "addressable_data"):
            return int(np.asarray(x.addressable_data(0)))
        return int(jax.device_get(x))

    def _topology(self):
        """JSON description of this step's training topology — stamped
        into every checkpoint's meta so an elastic restore can name
        saved-vs-current in its refusals."""
        mesh = None if self.mesh is None else \
            {a: int(s) for a, s in self.mesh.shape.items()}
        return {"mesh": mesh, "batch_axis": self.batch_axis,
                "zero": self.zero,
                "pipeline_stages": self.pipeline_stages,
                "processes": jax.process_count()}

    def _elastic_policy(self):
        """Pytree congruent with :meth:`_checkpoint_state` marking what
        an elastic (changed-dp-width) restore may re-shape: ``None``
        leaves demand the exact saved shape; an ``int`` is the LOGICAL
        leading dim of a ZeRO-1 optimizer-state leaf whose stored dim
        is padded to a multiple of the dp width — the manager re-slices
        and re-pads those (``CheckpointManager.restore(elastic=)``).
        Everything else — params, aux, RNG key, step counter,
        loss-scale state — is topology-independent by construction.

        The marks are computed for every ZeRO-ELIGIBLE param (≥1-d, not
        tp/ep-sharded) regardless of this step's own ``zero`` mode: a
        ZeRO-mode change is itself elastic (the state re-pads either
        way, ``checkpoint._topology_mismatch``), so a ``zero=0`` run
        must still be able to un-pad a ``zero=1`` checkpoint's
        optimizer state."""
        if self.zero and self._zero_pad0 is not None:
            covered = [pad is not None for pad in self._zero_pad0]
        else:
            covered = []
            for p in self._gp:
                spec = tuple(self.param_shardings.get(p.name, P()))
                sharded = any(e is not None and e != () for e in spec)
                covered.append(not sharded and len(p.shape) >= 1)
        marks = [int(p.shape[0]) if c else None
                 for p, c in zip(self._gp, covered)]
        policy = {"params": [None] * len(self._gp),
                  "aux": [None] * len(self._aux),
                  "opt_state": self.opt.state_shardings(marks),
                  "rng_key": None, "step": None,
                  "loss_scale": (None, None, None)}
        if self._svc_client is not None:
            # exact-shape leaves: residuals/clock/server params never
            # re-pad (the async rung is mesh-free by construction)
            policy["param_service"] = jax.tree_util.tree_map(
                lambda _: None, self._svc_client.state_dict())
        return policy

    def save_checkpoint(self, directory_or_manager, keep_last=3,
                        data_iter=None):
        """Atomically checkpoint the full training state (see
        ``docs/RESILIENCE.md``).  Returns the committed directory.

        ``data_iter`` — an iterator implementing the iterator-state
        protocol (``state_dict()``; ``io/io.py``): its mid-epoch
        position rides the manifest, committed atomically with the
        arrays, so ``restore_checkpoint(..., data_iter=)`` resumes the
        data stream at the exact next batch instead of silently
        replaying the epoch from batch 0.  Defaults to the iterator
        bound by ``attach_checkpoint(data_iter=...)``.

        On a process-spanning (multihost) mesh every process must call
        this cooperatively with the same shared directory: each stages
        only its addressable shards plus a done-marker, and process 0
        verifies all markers before atomically publishing the single
        manifest (``parallel/checkpoint.py``'s commit protocol)."""
        self._ensure_built()
        mgr = self._as_manager(directory_or_manager, keep_last)
        state = self._checkpoint_state()
        if data_iter is None:
            data_iter = self._ckpt_data_iter
        meta = {"topology": self._topology()}
        if data_iter is not None:
            meta["data_iter"] = data_iter.state_dict()
        return mgr.save(self._host_int(self._step_dev), state, meta=meta)

    def restore_checkpoint(self, directory_or_manager, step=None,
                           data_iter=None):
        """Restore params/optimizer state/RNG/step/loss-scale from the
        newest intact checkpoint (or ``step=``), placing every leaf back
        on its training sharding.  Returns the restored step number.
        Training resumes bit-identically to the uninterrupted run.

        ``data_iter`` — restore the data stream too: the iterator is
        ``load_state_dict``-ed to the checkpointed mid-epoch position
        (exact next batch, same shuffle order).  Raises
        :class:`~.checkpoint.CheckpointError` when the checkpoint was
        saved without iterator state — resuming would replay data.
        Defaults to the iterator bound by
        ``attach_checkpoint(data_iter=...)`` (symmetric with
        ``save_checkpoint``); an implicitly-bound iterator facing a
        checkpoint without iterator state warns instead of raising, so
        attaching first and restoring second keeps working against
        pre-protocol checkpoints.  The reverse mismatch — the
        checkpoint carries iterator state but no iterator was passed
        or attached — warns too: the restored run would silently
        replay its epoch from batch 0.

        **Elastic restore**: a checkpoint saved on a different dp width
        (e.g. dp=8 → this step's dp=4) restores bit-exactly — the
        dp-padded ZeRO-1 optimizer-state leaves are re-sliced/re-padded
        to this width, per-process iterator states are re-split across
        the new process count, and everything else (params, RNG key,
        step counter, loss-scale state) is topology-independent.  What
        CANNOT be re-sharded (a pipeline width change, a diverged
        sharded data stream, a different batching) raises
        :class:`~.checkpoint.CheckpointTopologyError` naming the saved
        and current topologies."""
        from .checkpoint import CheckpointTopologyError

        self._ensure_built()
        mgr = self._as_manager(directory_or_manager)
        like = self._checkpoint_state()
        step_no, state, meta = mgr.restore(
            like, step=step, shardings=self._checkpoint_shardings(),
            return_meta=True, elastic=self._elastic_policy(),
            topology=self._topology())
        saved_topo = (meta or {}).get("topology")
        explicit_iter = data_iter is not None
        if data_iter is None:
            data_iter = self._ckpt_data_iter
        if data_iter is not None:
            iter_state = self._resolve_iter_state(meta, saved_topo)
            if iter_state is None:
                msg = ("checkpoint step %d carries no data-iterator state "
                       "(saved without data_iter=) — restoring this "
                       "iterator would silently replay the epoch from "
                       "batch 0; re-save with save_checkpoint(..., "
                       "data_iter=it) or restore without data_iter"
                       % step_no)
                if explicit_iter:
                    from .checkpoint import CheckpointError

                    raise CheckpointError(msg)
                import warnings

                warnings.warn(msg + " (iterator left untouched)")
            else:
                try:
                    data_iter.load_state_dict(iter_state)
                except (ValueError, KeyError) as e:
                    # batching/shuffle/dataset drift: the iterator names
                    # the exact field; wrap it with the topologies so an
                    # elastic restart knows WHICH run disagrees
                    raise CheckpointTopologyError(
                        "checkpoint step %d: the data iterator refused "
                        "the checkpointed stream state: %s (saved "
                        "topology: %s; current topology: %s)"
                        % (step_no, e, saved_topo, self._topology())) \
                        from e
        elif (meta or {}).get("data_iter") is not None:
            import warnings

            warnings.warn(
                "checkpoint step %d carries data-iterator state but no "
                "data_iter was passed or attached — the data stream "
                "will replay its epoch from batch 0; pass "
                "restore_checkpoint(..., data_iter=it) (or "
                "attach_checkpoint(data_iter=it)) to resume mid-epoch"
                % step_no)
        for p, v in zip(self._gp, state["params"]):
            p._data._data = v
        for p, v in zip(self._aux, state["aux"]):
            p._data._data = v
        self._opt_state = state["opt_state"]
        self._key_dev = state["rng_key"]
        self._step_dev = state["step"]
        self._scaler_dev = tuple(state["loss_scale"])
        if self._svc_client is not None and "param_service" in state:
            self._svc_client.load_state_dict(state["param_service"])
        self._step_count = int(step_no)
        # the restored key IS the training stream: suppress the fresh
        # draw _ensure_built would otherwise do on a reseed epoch bump
        self._key_epoch = rng.epoch()
        if self.mesh is not None:
            # every leaf was device_put onto its training sharding by
            # the manager; skip the one-time placement pass
            self._placed = True
        return step_no

    def _resolve_iter_state(self, meta, saved_topo):
        """This process's share of the checkpointed data-stream state.
        A multi-process save carries one state per saved process under
        ``data_iter_parts``; they are re-split across the CURRENT
        process count (``distributed.resplit_iter_state`` — verbatim at
        the same width, re-stamped when every part agrees, refused with
        the topologies named when the shards diverged)."""
        parts = (meta or {}).get("data_iter_parts")
        if not parts:
            return (meta or {}).get("data_iter")
        from . import distributed as _dist
        from .checkpoint import CheckpointTopologyError

        try:
            return _dist.resplit_iter_state(
                parts, jax.process_index(), jax.process_count())
        except ValueError as e:
            raise CheckpointTopologyError(
                "%s (saved topology: %s; current topology: %s)"
                % (e, saved_topo, self._topology())) from e

    def attach_checkpoint(self, directory_or_manager, every=None,
                          keep_last=3, data_iter=None):
        """Bind a checkpoint manager to the step loop: saves at the next
        step boundary whenever a preemption/checkpoint request is
        pending (``checkpoint.install_preemption_hook`` / SIGTERM), and
        every ``every`` applied steps if given.  Returns the manager.

        ``data_iter`` — the training data iterator; every boundary save
        then includes its mid-epoch state (see ``save_checkpoint``), so
        a preemption-triggered checkpoint resumes the data stream at
        the exact next batch.  Without it, a loop that consumes a
        stateful iterator resumes by replaying data (graftlint GL008
        flags that pattern)."""
        from . import checkpoint as _ckpt

        if every is not None and int(every) < 1:
            raise ValueError("every must be >= 1 or None")
        if data_iter is not None:
            # fail NOW, while the mistake is cheap: an iterator without
            # the state protocol would otherwise surface as
            # NotImplementedError from state_dict() at the SIGTERM
            # boundary save — losing the preemption checkpoint entirely
            from ..io.io import DataIter as _DataIter

            sd = getattr(type(data_iter), "state_dict", None)
            if sd is None or sd is _DataIter.state_dict:
                raise ValueError(
                    "data_iter=%r does not implement the iterator-state "
                    "protocol (state_dict/load_state_dict) — wrap it in "
                    "io.ResilientIter or use a protocol-aware iterator "
                    "(NDArrayIter, ImageRecordIter, ...) so boundary "
                    "saves can carry the data position"
                    % type(data_iter).__name__)
        self._ckpt_manager = self._as_manager(directory_or_manager,
                                              keep_last)
        self._ckpt_every = int(every) if every else None
        self._ckpt_data_iter = data_iter
        self._ckpt_prev_count = self._step_count
        # requests predating the attach are not ours to honor
        self._ckpt_seen_request = _ckpt.request_seq()
        return self._ckpt_manager

    def _maybe_checkpoint(self):
        """Step-boundary hook: honor a pending preemption request (and
        the periodic schedule) against the attached manager.  The
        schedule runs off the HOST step mirror — never a per-step
        device sync; the device counter is read only when a save
        actually happens (inside save_checkpoint, which blocks anyway).
        """
        if self._ckpt_manager is None:
            return
        from . import checkpoint as _ckpt

        # per-step request bookkeeping: one request_checkpoint() (the
        # SIGTERM hook) must reach EVERY attached step loop, so each
        # remembers the last sequence IT honored — no global clear
        seq = _ckpt.request_seq()
        requested = seq > self._ckpt_seen_request
        due = requested
        if self._ckpt_every:
            # boundary CROSSING, not exact divisibility: run_steps
            # advances the counter by k per call, so `% every == 0`
            # would miss nearly every boundary for k > 1
            prev, cur = self._ckpt_prev_count, self._step_count
            self._ckpt_prev_count = cur
            due = due or prev // self._ckpt_every != cur // self._ckpt_every
        if due:
            try:
                self.save_checkpoint(self._ckpt_manager)
            except BaseException as e:
                import warnings

                if requested:
                    # a PREEMPTION-requested save failed (disk full,
                    # lost peer): log, restore the pre-hook signal
                    # disposition, and re-raise.  Leaving the hook
                    # installed would swallow every further SIGTERM
                    # into another doomed save request — after this, a
                    # repeated signal terminates the process normally
                    # and the last COMMITTED checkpoint is what resume
                    # sees.  A purely PERIODIC save failing (no signal
                    # involved) keeps the hook: the next boundary may
                    # well succeed, and graceful preemption must not be
                    # silently disabled by one transient blip.
                    warnings.warn(
                        "preemption checkpoint save failed (%s: %s); "
                        "restoring the previous signal disposition so a "
                        "repeated preemption signal terminates instead "
                        "of re-requesting a save that cannot succeed"
                        % (type(e).__name__, e))
                    _ckpt.uninstall_preemption_hook()
                else:
                    warnings.warn(
                        "periodic checkpoint save failed (%s: %s); the "
                        "last committed checkpoint is unchanged and the "
                        "schedule will retry at the next boundary"
                        % (type(e).__name__, e))
                raise
            self._ckpt_seen_request = seq


def make_train_step(net, loss_fn, optimizer="sgd", mesh=None, batch_axis="dp",
                    param_shardings=None, compute_dtype=None, donate=True,
                    pipeline_stages=None, num_micro=1, pipeline_axis="pp",
                    pipeline_remat=False, zero=0, lint=None, lint_suppress=(),
                    nonfinite=None, loss_scale=None, cost=None,
                    hbm_budget=None, cost_device="tpu-v5e", passes=None,
                    numerics=None, input_range=None,
                    skip_streak_budget=None, sync="allreduce",
                    staleness_bound=None, compression=None,
                    **opt_kwargs) -> TrainStep:
    """Build the fused train step (fwd+bwd+optimizer in one XLA program).

    ``pipeline_stages=K`` + ``num_micro=M`` runs the net as a K-stage SPMD
    pipeline over the mesh's ``pipeline_axis``: the (iterable, stacked)
    net's children are split into K congruent stages, the batch into M
    microbatches, and forward/backward run the software-pipelined 1F1B/
    GPipe tick schedule with per-rank microbatch gradient accumulation —
    still one jitted, donated program.  ``pipeline_remat=True`` recomputes
    stage activations in the backward ticks instead of stashing them.
    Composes with dp: a ``{'dp': d, 'pp': K}`` mesh shards microbatches
    over dp while stages flow over pp.

    ``zero=1`` turns on ZeRO-1 weight-update sharding over the mesh's
    ``batch_axis`` (arXiv:2004.13336): each replica consumes only its
    1/N gradient shard (the all-reduce + per-rank-slice pattern XLA's
    reduce-scatter-creation pass — the paper's contribution — compiles
    into a reduce-scatter on TPU), optimizer state lives dp-sharded
    (1/N per device, pad-and-slice for leading dims that don't divide),
    each replica updates only its weight shard, and the updated params
    all-gather back.  Composes with ``pipeline_stages`` on a dp×pp mesh
    (the accumulated microbatch grads reduce once per step).  Pass
    ``multi_precision=True`` (an optimizer kwarg) to keep f32 master
    weights in the — now 1/N-cost — optimizer state for bf16 params,
    and ``rescale_grad=`` to scale gradients as the reference update
    ops do.

    ``lint`` (default: env ``MXTPU_LINT``, else ``"warn"``) runs
    graftlint Level 1 over the traced step before its first compile —
    ``"error"`` raises :class:`~..analysis.LintError` on error-severity
    findings, ``"warn"`` emits a warning, ``"off"`` disables.
    ``lint_suppress`` drops the given ``GLxxx`` codes, or ``GL2*``-style
    prefix globs (docs/ANALYSIS.md).

    ``cost`` (default: env ``MXTPU_COST``, else ``"off"``) runs the
    graftcost trace-time cost model over the same pre-compile trace
    (``analysis/cost_model.py``): predicted FLOPs / fusion-aware HBM
    bytes / peak live-buffer memory / per-axis comm volume, surfaced as
    ``step.cost_report`` (a JSON-serializable
    :class:`~..analysis.cost_model.CostReport`).  ``"check"``
    additionally enforces the GL2xx diagnostics: GL201 — predicted peak
    memory over ``hbm_budget`` (bytes) — raises *at trace time, before
    any compile*; GL202/GL203/GL204 (multi-pass re-reads, comm-dominated
    roofline, remat/donation config without a memory win) warn.
    ``cost_device`` picks the roofline denominators from the device-spec
    registry (``tpu-v5e`` default; ``cpu-proxy`` for relative numbers
    off-chip).

    ``passes`` (default: env ``MXTPU_PASSES``, else none) runs the
    graftpass rewrite pipeline over the traced step before its first
    compile (``analysis/passes.py``, docs/PASSES.md): an ordered list
    of registry names (``"amp_bf16"``, ``"space_to_depth"``,
    ``"cse_dead_aux"``, ...) or :class:`~..analysis.GraftPass`
    instances.  Every pass declares an exactness contract the framework
    verifies by construction — abstract eval, re-lint (a pass may not
    introduce jaxpr-level graftlint findings: GL302), graftcost
    before/after
    receipts (``step.pass_receipts``; a pointless bit-exact rewrite is
    skipped: GL303) and a seeded concrete probe (GL301) — refusing,
    with :class:`~..analysis.LintError` and zero compiles spent, any
    rewrite that breaks its declaration.  Weight-quantizing passes
    no-op on a train step (its params are donated and updated in
    place); they belong on ``ServeEngine(passes=...)``.

    ``numerics`` (default: env ``MXTPU_NUMERICS``, else ``"off"``) runs
    the graftrange value-range & precision abstract interpreter over
    the same pre-compile trace (``analysis/value_range.py``,
    docs/ANALYSIS.md GL4xx): per-variable intervals, NaN-possibility
    and effective precision, checked as GL401 (possible overflow-to-inf
    — exp of unbounded logits without max-subtraction), GL402
    (invalid-domain op — log/rsqrt/div reachable at ≤0, the
    E[x²]−E[x]² cancellation), GL403 (bf16 under/overflow on a demoted
    edge — the per-op ``amp_bf16`` installation gate), GL404 (silent
    f64/weak-type promotion — the hand-fixed adam/attention-scale bug
    class) and GL405 (loss-scale advisory naming the suggested scale).
    ``"error"`` raises :class:`~..analysis.LintError` *before any
    compile*; findings surface as ``step.range_report``
    (:class:`~..analysis.value_range.RangeReport`), and
    ``step.analyze_numerics(x, y)`` runs the walk on demand with zero
    compiles.  ``input_range`` declares the batch's real value range —
    a ``(lo, hi)`` tuple for ``x`` or ``{"x": (lo, hi), "y": ...}`` —
    sharpening the analysis (unannotated floats are assumed
    unknown-but-finite; integer/uint8 inputs seed from their dtype).

    ``nonfinite`` contains bad steps INSIDE the program: ``"skip"``
    leaves params, aux state, optimizer state and the step counter
    bit-identical when any gradient is non-finite (one fused all-finite
    reduction + select guard — no per-param host syncs, donation-safe,
    composes with pipelining and ``zero=1``); ``"raise"`` additionally
    raises :class:`FloatingPointError` on the host (state still
    protected); ``"off"`` (default without a dynamic scaler) keeps the
    unguarded program.  ``loss_scale`` is ``None``, a static positive
    scale, ``"dynamic"``, or a :class:`DynamicLossScale` policy — the
    dynamic scale + counters ride the step's carried device state
    (halve on overflow, double every ``scale_window`` clean steps,
    matching ``contrib/amp/loss_scaler.py``) and are surfaced as
    ``step.loss_scale`` / ``step.skipped_steps``.
    ``sync`` picks the gradient-exchange rung
    (``parallel/param_service.py``, docs/RESILIENCE.md §8):
    ``"allreduce"`` (default) is the fused collective step;
    ``"async"`` runs bounded-staleness asynchronous push/pull against
    a parameter service — the optimizer moves server-side, each rank
    pushes (optionally compressed) gradients and pulls fresh params,
    and a rank may run at most ``staleness_bound`` steps (default 4)
    ahead of the slowest live peer before its pull blocks;
    ``"auto"`` starts on the collective rung and lets the supervisor's
    straggler detector degrade to async and recover back
    (``step.observe_stragglers`` / :class:`~.param_service.SyncPolicy`
    hysteresis).  Async requires ``mesh=None`` (one replica per rank
    process) and composes with ``compression`` — ``"topk"``,
    ``"randomk"``, ``"int8"``, ``"2bit"`` or a compressor instance
    (``kvstore/gradient_compression.py``): pushes shrink on the wire
    while error-feedback residuals keep convergence, ride the step's
    checkpoint (``param_service`` subtree) and are priced at trace
    time by graftcost (``report.meta["push_volume"]``, zero compiles).
    ``skip_streak_budget`` DECLARES a bound on consecutive skipped
    steps: the supervised loop (``parallel/supervisor.py``) enforces it
    as a divergence verdict, and declaring it (or a dynamic scale)
    silences graftlint GL012 — ``nonfinite="skip"`` under a static
    scale with no streak bound is a run that can stall forever while
    looking alive.  See ``docs/RESILIENCE.md`` for the policy matrix,
    and ``step.save_checkpoint`` / ``step.restore_checkpoint`` /
    ``step.attach_checkpoint`` for durable, shard-aware
    checkpoint/resume (``parallel/checkpoint.py``).
    """
    opt = FunctionalOptimizer(optimizer, **opt_kwargs)
    return TrainStep(net, loss_fn, opt, compute_dtype=compute_dtype, mesh=mesh,
                     batch_axis=batch_axis, param_shardings=param_shardings,
                     donate=donate, pipeline_stages=pipeline_stages,
                     num_micro=num_micro, pipeline_axis=pipeline_axis,
                     pipeline_remat=pipeline_remat, zero=zero, lint=lint,
                     lint_suppress=lint_suppress, nonfinite=nonfinite,
                     loss_scale=loss_scale, cost=cost, hbm_budget=hbm_budget,
                     cost_device=cost_device, passes=passes,
                     numerics=numerics, input_range=input_range,
                     skip_streak_budget=skip_streak_budget, sync=sync,
                     staleness_bound=staleness_bound,
                     compression=compression)
