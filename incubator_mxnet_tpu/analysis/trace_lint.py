"""graftlint Level 1: trace-time (jaxpr) analysis of sharded programs.

The move from engine-mediated mutation to pure traced programs
(tracing.py) converted a class of runtime crashes into *silent*
compile-time miscompiles: a non-bijective ppermute ring drops a shard
instead of deadlocking, a PartitionSpec whose rank disagrees with its
operand resharded wrongly by GSPMD yields finite-but-wrong numerics
(the jax 0.4.x stacked-operand hazard documented at
``parallel/train_step.py`` ``_make_pipeline_step``), a donated buffer
aliased twice reads freed memory, and an aux loss registered inside a
``jax.checkpoint`` region simply vanishes from the objective.  This
module walks the jaxpr of a function (or one you traced yourself) and
reports those hazards as stable ``GL00x`` diagnostics *before* the
first XLA compile.

Entry points:

- :func:`lint_traceable` — trace ``fn(*args)`` with ``jax.make_jaxpr``
  and run every check (GL001–GL004; GL005 with ``recompile_probe=True``).
- :func:`lint_jaxpr` — run GL001–GL003 over an existing ClosedJaxpr.
- :func:`check_permutation` / :func:`validate_permutation` — the GL001
  core, shared with the eager check in ``parallel/collectives.py``.
- :func:`check_partition_spec` — the GL002 rank/axis core, shared with
  eager call-site validation (``parallel/moe.py``).
- :func:`recompile_probe` — the GL005 cache-key-stability probe.
"""
from __future__ import annotations

import os
from collections import Counter
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import core as jex_core

from .diagnostics import CODES, Diagnostic, LintError, LintReport, Severity

__all__ = ["capture_effect_diagnostics", "check_inference_param_donation",
           "check_legacy_checkpoint_path",
           "check_permutation", "validate_permutation",
           "check_partition_spec", "check_swap_compatibility",
           "check_unbounded_skip", "check_ungated_swap",
           "check_zero_state_shardings",
           "donated_leaf_indices", "lint_jaxpr", "lint_traceable",
           "recompile_probe"]


# ---------------------------------------------------------------------------
# GL001 — collective permutation hygiene
# ---------------------------------------------------------------------------

def check_permutation(perm, axis_size: Optional[int], axis_name: Any,
                      where: str = "") -> List[Diagnostic]:
    """Check a ``ppermute`` (source, dest) pair list over an axis.

    ERROR: duplicated sources, duplicated destinations, or ranks outside
    ``[0, axis_size)`` — these deadlock or race on real hardware.
    INFO: a well-formed but partial (non-bijective) permutation — ranks
    not listed send nothing / receive zeros.  That is exactly the
    pipeline fill/drain pattern, so it is informational; a *ring* must
    include the wraparound edge or a shard is silently dropped.
    """
    diags: List[Diagnostic] = []
    pairs = list(perm)
    srcs = [p[0] for p in pairs]
    dsts = [p[1] for p in pairs]
    ax = repr(axis_name) if not isinstance(axis_name, str) else axis_name

    def _dups(seq):
        return sorted(k for k, c in Counter(seq).items() if c > 1)

    dup_s, dup_d = _dups(srcs), _dups(dsts)
    if dup_s:
        diags.append(Diagnostic(
            "GL001", Severity.ERROR,
            "ppermute over axis %s: duplicated source ranks %s — a rank "
            "cannot send its shard to two destinations in one "
            "CollectivePermute" % (ax, dup_s), where=where))
    if dup_d:
        diags.append(Diagnostic(
            "GL001", Severity.ERROR,
            "ppermute over axis %s: duplicated destination ranks %s — "
            "two sources writing one destination is a data race (XLA "
            "rejects it at compile or corrupts the shard)" % (ax, dup_d),
            where=where))
    if axis_size is not None:
        oob = sorted({r for r in srcs + dsts
                      if not (isinstance(r, (int, np.integer))
                              and 0 <= int(r) < axis_size)})
        if oob:
            diags.append(Diagnostic(
                "GL001", Severity.ERROR,
                "ppermute over axis %s (size %d): ranks %s out of range "
                "[0, %d)" % (ax, axis_size, oob, axis_size), where=where))
        if not (dup_s or dup_d or oob):
            missing_src = sorted(set(range(axis_size)) - set(srcs))
            missing_dst = sorted(set(range(axis_size)) - set(dsts))
            if missing_src or missing_dst:
                diags.append(Diagnostic(
                    "GL001", Severity.INFO,
                    "ppermute over axis %s (size %d) is not bijective: "
                    "ranks %s never send, ranks %s receive zeros"
                    % (ax, axis_size, missing_src, missing_dst),
                    where=where,
                    hint="fine for pipeline fill/drain; a ring must "
                         "include the wraparound edge (i, (i+1) %% n) or "
                         "the last shard is silently dropped"))
    return diags


def validate_permutation(perm, axis_size: int, axis_name: Any,
                         where: str = ""):
    """Eager GL001: raise ``ValueError`` on malformed permutations
    (duplicates / out-of-range), naming the axis and the offending and
    missing ranks.  Partial permutations pass (pipeline fill/drain)."""
    diags = check_permutation(perm, axis_size, axis_name, where=where)
    errs = [d for d in diags if d.severity >= Severity.ERROR]
    if errs:
        detail = "; ".join(d.message for d in errs)
        info = [d.message for d in diags if d.severity < Severity.ERROR]
        if info:
            detail += " (also: %s)" % "; ".join(info)
        raise ValueError("invalid collective permutation [GL001]: "
                         + detail)


# ---------------------------------------------------------------------------
# GL002 — partition-spec / mesh consistency
# ---------------------------------------------------------------------------

def check_partition_spec(spec, ndim: int, mesh, where: str = "",
                         operand: str = "operand") -> List[Diagnostic]:
    """Check one PartitionSpec-like (tuple of axis-name entries) against
    an operand rank and a mesh: every named axis must exist in the mesh
    and the spec must not have more entries than the operand has dims."""
    diags: List[Diagnostic] = []
    entries = tuple(spec)
    axis_names = set(getattr(mesh, "axis_names", ()) or ())
    if len(entries) > ndim:
        diags.append(Diagnostic(
            "GL002", Severity.ERROR,
            "partition spec %r has %d entries but %s is %d-dimensional "
            "— GSPMD will mis-shard or reject it"
            % (entries, len(entries), operand, ndim), where=where))
    for dim, entry in enumerate(entries):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        for name in names:
            if not isinstance(name, str):
                diags.append(Diagnostic(
                    "GL002", Severity.ERROR,
                    "partition spec %r names non-string axis %r at dim "
                    "%d — axis names are strings (did you pass a device "
                    "rank?)" % (entries, name, dim), where=where))
            elif axis_names and name not in axis_names:
                diags.append(Diagnostic(
                    "GL002", Severity.ERROR,
                    "partition spec %r shards dim %d over axis %r which "
                    "does not exist in mesh axes %s"
                    % (entries, dim, name, sorted(axis_names)),
                    where=where))
    return diags


def _names_dict_to_spec(names: Dict[int, Tuple[str, ...]],
                        ndim: int) -> tuple:
    spec = [None] * max([ndim] + [d + 1 for d in names])
    for d, axes in names.items():
        spec[d] = tuple(axes) if len(axes) != 1 else axes[0]
    return tuple(spec)


#: ops that only rearrange a buffer — a sharding-hazard source is chased
#: through these back to its real producer
_LAYOUT_PRIMS = {"reshape", "transpose", "convert_element_type", "squeeze",
                 "expand_dims", "rev", "copy"}


def _chase_var(var, producers):
    """Follow ``var`` back through layout-only ops; returns the var at
    the first non-layout producer (or the top-level input/constant)."""
    seen = 0
    while isinstance(var, jex_core.Var) and var in producers and seen < 64:
        eqn = producers[var]
        if eqn.primitive.name in _LAYOUT_PRIMS and eqn.invars:
            var = eqn.invars[0]
            seen += 1
            continue
        break
    return var


def _chase_producer(var, producers):
    """Follow ``var`` back through layout-only ops to the primitive that
    materialized it; returns the primitive name or None (top-level
    input / constant)."""
    var = _chase_var(var, producers)
    if isinstance(var, jex_core.Var) and var in producers:
        return producers[var].primitive.name
    return None


def _spec_names(spec) -> dict:
    """A shard_map eqn's PartitionSpec as ``{dim: (axis, ...)}`` over its
    sharded dims (empty = replicated)."""
    return {d: tuple(e) if isinstance(e, (tuple, list)) else (e,)
            for d, e in enumerate(spec) if e is not None}


def _check_shard_map_eqn(eqn, diags: List[Diagnostic],
                         producers: dict, where: str):
    mesh = eqn.params["mesh"]
    sizes = dict(mesh.shape)
    multi_axis = len(sizes) > 1
    in_names = [_spec_names(s) for s in eqn.params["in_specs"]]
    out_names = [_spec_names(s) for s in eqn.params["out_specs"]]
    for i, (var, names) in enumerate(zip(eqn.invars, in_names)):
        aval = var.aval
        ndim = getattr(aval, "ndim", 0)
        w = "%s: shard_map operand %d (%s)" % (where, i, aval.str_short())
        for d in sorted(names):
            if d >= ndim:
                diags.append(Diagnostic(
                    "GL002", Severity.ERROR,
                    "in_spec shards dim %d of a %d-dimensional operand "
                    "— spec rank exceeds operand rank" % (d, ndim),
                    where=w))
        diags.extend(check_partition_spec(
            _names_dict_to_spec(names, ndim), max(ndim, 1), mesh,
            where=w, operand="operand %d" % i))
        # The jax 0.4.x GSPMD stacked-operand miscompile
        # (parallel/train_step.py _make_pipeline_step): an array
        # STACKED inside the jitted program (jnp.stack/concatenate of
        # per-stage values) fed to shard_map with a sharded in_spec on
        # a multi-axis mesh reshards WRONG — finite but incorrect
        # numerics.  Values that are merely *rearranged* from inputs,
        # or produced by another shard_map with the same names
        # (forward→backward residuals), shard faithfully and are not
        # flagged.
        if names and multi_axis \
                and _chase_producer(var, producers) == "concatenate":
            axes = sorted({a for t in names.values() for a in t})
            diags.append(Diagnostic(
                "GL002", Severity.ERROR,
                "operand %d is stacked/concatenated inside the jitted "
                "program and fed to shard_map sharded over %s on the "
                "multi-axis mesh %s — jax 0.4.x GSPMD miscompiles this "
                "resharding silently (finite but wrong numerics)"
                % (i, axes, dict(sizes)),
                where=w,
                hint="pass the operand replicated (P()) and slice "
                     "per-rank with lax.axis_index inside the body, or "
                     "stack it outside jit and pass it as a top-level "
                     "argument (see parallel/train_step.py "
                     "_make_pipeline_step)"))
    for i, (var, names) in enumerate(zip(eqn.outvars, out_names)):
        ndim = getattr(var.aval, "ndim", 0)
        w = "%s: shard_map output %d" % (where, i)
        for d in sorted(names):
            if d >= ndim:
                diags.append(Diagnostic(
                    "GL002", Severity.ERROR,
                    "out_spec shards dim %d of a %d-dimensional output"
                    % (d, ndim), where=w))
        diags.extend(check_partition_spec(
            _names_dict_to_spec(names, ndim), max(ndim, 1), mesh,
            where=w, operand="output %d" % i))


# ---------------------------------------------------------------------------
# GL003 — donation aliasing
# ---------------------------------------------------------------------------

def _aval_key(aval):
    return (tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype",
                                                           "?")))


def _check_donation(jaxpr, donated_mask: Sequence[bool],
                    diags: List[Diagnostic], where: str):
    """GL003 over one jaxpr: a donated invar returned as more than one
    output aliases one mutated buffer into several results (ERROR); a
    donated invar with no shape/dtype-compatible output wastes the
    donation and invalidates the caller's array for nothing — any later
    read is a read-after-donate (WARNING)."""
    outvars = list(jaxpr.outvars)
    out_avals = Counter(_aval_key(v.aval) for v in outvars
                        if not isinstance(v, jex_core.Literal))
    for i, (var, donated) in enumerate(zip(jaxpr.invars, donated_mask)):
        if not donated:
            continue
        n_alias = sum(1 for ov in outvars if ov is var)
        if n_alias > 1:
            diags.append(Diagnostic(
                "GL003", Severity.ERROR,
                "donated input %d (%s) is returned as %d distinct "
                "outputs — XLA aliases the donated buffer to one of "
                "them; the others share the same (mutated) memory"
                % (i, var.aval.str_short(), n_alias),
                where=where,
                hint="return it once, or drop it from donate_argnums"))
        key = _aval_key(var.aval)
        if out_avals.get(key, 0) > 0:
            out_avals[key] -= 1
        else:
            diags.append(Diagnostic(
                "GL003", Severity.WARNING,
                "donated input %d (%s) has no output with a matching "
                "shape/dtype: the donation is wasted, and the caller's "
                "array is invalidated anyway — any later use is a "
                "read-after-donate error"
                % (i, var.aval.str_short()), where=where,
                hint="drop it from donate_argnums or return its "
                     "updated value"))


# ---------------------------------------------------------------------------
# GL006 — defeated ZeRO sharding
# ---------------------------------------------------------------------------

def check_zero_state_shardings(state_shardings, axis_name,
                               where: str = "") -> List[Diagnostic]:
    """GL006 core: every optimizer-state leaf of a ``zero=1`` step must
    be sharded over the dp axis.

    ``state_shardings`` is a pytree of sharding objects (``NamedSharding``
    or bare ``PartitionSpec``) covering the ZeRO-eligible parameters; a
    leaf whose spec never names ``axis_name`` keeps a full copy of the
    accumulator on every dp replica — exactly the N× memory the feature
    exists to remove.
    """
    diags: List[Diagnostic] = []
    leaves = jax.tree_util.tree_leaves(
        state_shardings,
        is_leaf=lambda x: hasattr(x, "spec") or hasattr(x, "_partitions"))
    for i, sh in enumerate(leaves):
        spec = getattr(sh, "spec", sh)
        axes = set()
        for e in tuple(spec or ()):
            if e is None:
                continue
            axes.update(e if isinstance(e, tuple) else (e,))
        if axis_name not in axes:
            how = "replicated" if not axes \
                else "sharded only over %s" % sorted(axes)
            diags.append(Diagnostic(
                "GL006", Severity.ERROR,
                "optimizer-state leaf %d is %s over the %r axis although "
                "the step was built with zero=1 — every dp replica holds "
                "the full accumulator, the N x memory the sharded update "
                "was meant to remove" % (i, how, axis_name),
                where=where,
                hint="shard the state leaf over %r (pad-and-slice a "
                     "leading dim that does not divide) or exclude the "
                     "parameter from the zero plan" % (axis_name,)))
    return diags


# ---------------------------------------------------------------------------
# GL007 — legacy checkpoint path reachable beside sharded state
# ---------------------------------------------------------------------------

def check_legacy_checkpoint_path(origin: str,
                                 where: str = "") -> List[Diagnostic]:
    """GL007 core: a ``zero=1`` fused step was built from a Trainer
    (``origin`` — its class name) whose legacy host-side
    ``save_states``/``load_states`` surface is still reachable.

    That path serializes the *updater's* host state: it can neither see
    the fused step's state at all nor represent a dp-SHARDED leaf —
    calling it "works" and silently writes a checkpoint that misses or
    truncates the optimizer state.  The Trainer raises at call time;
    this diagnostic surfaces the hazard at lint time, before a long run
    banks on a checkpoint it cannot restore from.
    """
    return [Diagnostic(
        "GL007", Severity.WARNING,
        "legacy %s.save_states/load_states cannot round-trip the "
        "dp-sharded optimizer state of this zero=1 fused step (they "
        "would silently save one rank's shard)" % origin,
        where=where,
        hint="checkpoint through the fused step instead: "
             "step.save_checkpoint(dir) / step.restore_checkpoint(dir) "
             "(parallel.checkpoint, docs/RESILIENCE.md)")]


def check_unbounded_skip(nonfinite: str, dynamic_scale: bool,
                         skip_streak_budget,
                         where: str = "") -> List[Diagnostic]:
    """GL012 core: ``nonfinite="skip"`` under a STATIC loss scale with
    no skip-streak bound anywhere.

    The skip guard protects state bit-exactly — but with a static
    scale nothing ever *adapts* out of the overflow: a batch of
    corrupt records, a bad learning-rate spike, or a too-high scale
    makes EVERY subsequent step overflow, and each one is silently
    skipped.  The loop keeps spinning, the step counter stands still,
    and the run looks alive while training nothing — an unbounded
    silent skip-streak is a stalled run that a dashboard reads as
    healthy.  A dynamic scale bounds the streak by construction (it
    halves out of the overflow); a declared ``skip_streak_budget``
    bounds it by policy (the supervisor's divergence detector turns
    the streak into a verdict, ``parallel/supervisor.py``).  With
    neither, this warns before a long run banks on it.
    """
    if nonfinite != "skip" or dynamic_scale or \
            skip_streak_budget is not None:
        return []
    return [Diagnostic(
        "GL012", Severity.WARNING,
        "nonfinite='skip' with a static loss scale and no skip-streak "
        "bound: every overflowed step is skipped silently and the "
        "scale never adapts — a poisoned run skips forever while "
        "looking alive (stalled, not failed, and nothing will ever "
        "say so)",
        where=where,
        hint="use loss_scale='dynamic' (the scale halves out of a "
             "streak by construction), or declare "
             "make_train_step(skip_streak_budget=N) and drive the loop "
             "through parallel/supervisor.py — its divergence detector "
             "turns a streak past the budget into a rollback/respawn "
             "verdict (docs/RESILIENCE.md §7)")]


def check_unsaved_compressor_state(compression, sync: str,
                                   where: str = "") -> List[Diagnostic]:
    """GL013 core: an error-feedback compressor bound to a step whose
    checkpoint save set can never include its residual state.

    Error-feedback compression is only unbiased *over time*: whatever a
    step's sparsification/quantization drops is banked in the residual
    and re-injected into the next gradient.  On the async rungs
    (``sync='async'|'auto'``) the compressor rides the step's
    ``param_service`` checkpoint subtree, so kill-and-resume keeps the
    bank.  On ``sync='allreduce'`` the step's checkpoint state has no
    compressor slot at all — a resumed run restarts the residual at
    zero, silently re-dropping everything banked since the last push,
    and loss parity with the uncompressed run quietly degrades.  The
    GL008 analogy, for compressor state instead of iterator state.
    """
    if compression is None or sync != "allreduce":
        return []
    kind = getattr(compression, "kind", type(compression).__name__)
    return [Diagnostic(
        "GL013", Severity.WARNING,
        "error-feedback compression (%r) on a sync='allreduce' step: "
        "the residual state is not in the checkpoint save set, so a "
        "resumed run silently drops the accumulated residual and the "
        "compression stops being unbiased over time" % (kind,),
        where=where,
        hint="build the step with sync='async' or sync='auto' — its "
             "param_service checkpoint subtree carries the compressor's "
             "state_dict() — or persist "
             "compressor.state_dict()/load_state_dict() alongside your "
             "own checkpoints (docs/RESILIENCE.md §8)")]


def check_inference_param_donation(donated_leaves, param_leaves,
                                   where: str = "") -> List[Diagnostic]:
    """GL010 core: an *inference* program whose donated flat invars
    intersect its model-parameter invars.

    Donation is the right call for per-request state (a decode cache, a
    scratch input buffer): those buffers are dead after the call.  The
    parameters are the opposite — they are the server's long-lived,
    device-resident state, reused by every request.  Donating them
    invalidates the host handles after the FIRST call; the second
    request reads freed (or recycled) buffers — silently wrong numerics
    on some backends, a crash on others.  The training analog is GL003
    (donation aliasing); this is its serving-side complement, caught at
    trace time like GL003, before the program ever compiles.

    ``donated_leaves`` / ``param_leaves`` are flat invar indices of the
    traced program (``donated_leaf_indices`` maps jit-style positional
    argnums to them).
    """
    overlap = sorted(set(donated_leaves) & set(param_leaves))
    if not overlap:
        return []
    show = overlap[:8]
    more = "" if len(overlap) <= 8 else " (+%d more)" % (len(overlap) - 8)
    return [Diagnostic(
        "GL010", Severity.ERROR,
        "%d model-parameter leaves (flat invars %s%s) are in the donated "
        "argnums of an inference program — a served model's weights must "
        "survive the call, and XLA will reuse their buffers for outputs: "
        "every request after the first computes on freed memory"
        % (len(overlap), show, more),
        where=where,
        hint="donate only per-request state (the input buffer, the decode "
             "cache); keep params device-resident and un-donated "
             "(serve/engine.py holds them for the life of the engine)")]


def check_swap_compatibility(served, candidate, missing=(), extra=(),
                             where: str = "") -> List[Diagnostic]:
    """GL011 core: a hot weight swap whose candidate param set drifts
    from the served signature.

    ``served`` / ``candidate`` are aligned sequences of ``(name, shape,
    dtype)`` descriptors (``ServeEngine.param_signature`` shape);
    ``missing`` / ``extra`` name tree-level drift (params absent from /
    foreign to the served tree).  The zero-recompile contract of a hot
    swap is *same avals ⇒ same AOT programs*: any shape or dtype drift
    re-keys every bucket program and turns the swap into a compile
    storm under live traffic — the GL005 hazard at its worst, so the
    swap path rejects it eagerly at swap time, before anything is
    staged (``serve/engine.py::update_params``).  One aggregated
    diagnostic names the first few drifts.
    """
    served = list(served)
    candidate = list(candidate)
    drifts = []
    if len(candidate) != len(served):
        # never zip-truncate a tree drift into a clean verdict: a
        # standalone caller may not pre-pad the way the engine does
        drifts.append("param count %d -> %d" % (len(served),
                                                len(candidate)))
    for (name, s_shape, s_dtype), (_n, c_shape, c_dtype) in zip(served,
                                                                candidate):
        if c_shape is None:
            continue  # tree-level drift, reported via missing/extra
        if tuple(c_shape) != tuple(s_shape):
            drifts.append("%s: shape %s -> %s"
                          % (name, tuple(s_shape), tuple(c_shape)))
        if c_dtype != s_dtype:
            drifts.append("%s: dtype %s -> %s" % (name, s_dtype, c_dtype))
    for n in missing:
        drifts.append("%s: missing from candidate" % n)
    for n in extra:
        drifts.append("%s: not in the served tree" % n)
    if not drifts:
        return []
    show = "; ".join(drifts[:6])
    more = "" if len(drifts) <= 6 else " (+%d more)" % (len(drifts) - 6)
    return [Diagnostic(
        "GL011", Severity.ERROR,
        "swap candidate drifts from the served param signature in %d "
        "place(s): %s%s — same shapes/dtypes are the zero-recompile "
        "contract; this swap would re-key and recompile every bucket "
        "program under live traffic" % (len(drifts), show, more),
        where=where,
        hint="export the candidate from the same architecture and "
             "precision as the served version (engine.param_signature "
             "is the pinned contract); for an architecture change, "
             "stand up a new engine and cut traffic over instead")]


def check_ungated_swap(canary, canary_tol, context=None,
                       where: str = "") -> List[Diagnostic]:
    """GL014 core: an *unattended* hot swap with no canary gate.

    ``context`` is the swap caller's self-identification — the
    promotion daemon and every other automated path stamp one
    (``update_params(..., context="promotion")``); interactive/manual
    swaps pass none and are not this check's business.  With a context
    but neither ``canary`` rows nor a ``canary_tol``, the only gate
    left between a candidate and the fleet is the default zeros
    canary's finiteness check — a finite-but-wrong candidate (bad LR
    spike, mislabeled run, stale export) promotes cleanly and serves
    garbage until a human notices.  An unattended path must gate on
    *drift*, not just finiteness: held-out canary rows plus a
    tolerance make a bad candidate roll back automatically, which is
    the whole point of having a daemon.
    """
    if context is None or context == "":
        return []
    if canary is not None or canary_tol is not None:
        return []
    return [Diagnostic(
        "GL014", Severity.WARNING,
        "update_params from an unattended context (%r) with neither "
        "canary rows nor canary_tol: the only remaining gate is the "
        "default zeros canary's finiteness check, so a finite-but-"
        "wrong candidate promotes straight into live traffic"
        % (context,),
        where=where,
        hint="pass canary= (held-out rows the incumbent is known-good "
             "on) and canary_tol= so output drift triggers the "
             "automatic rollback (docs/RESILIENCE.md §9); a deliberate "
             "ungated swap can suppress with lint_suppress=('GL014',)")]


def check_process_local_ckpt_dir(directory: str,
                                 process_count: int) -> List[Diagnostic]:
    """GL009 core: a multi-process (``jax.distributed``) run pointed its
    ``CheckpointManager`` at a process-LOCAL directory (``/tmp``,
    ``$TMPDIR``, a relative path).

    The coordinated commit protocol assumes every process stages into
    the SAME directory: on per-host tmp storage each process writes a
    private, incomplete stage, process 0's marker wait times out (or
    worse, a single-host test "passes"), and the job has no restorable
    checkpoint at all.  Emitted at manager construction — before a long
    run banks on it.
    """
    import tempfile

    if int(process_count) <= 1:
        return []
    path = os.path.abspath(str(directory))
    locals_ = {os.path.abspath(tempfile.gettempdir())}
    for env in ("TMPDIR", "TMP", "TEMP"):
        v = os.environ.get(env)
        if v:
            locals_.add(os.path.abspath(v))
    hit = next((t for t in sorted(locals_)
                if path == t or path.startswith(t + os.sep)), None)
    if hit is None and os.path.isabs(str(directory)):
        return []
    what = "process-local temp dir %s" % hit if hit is not None else \
        "relative path (resolves per-process working dir)"
    return [Diagnostic(
        "GL009", Severity.WARNING,
        "CheckpointManager directory %r is a %s while jax.distributed "
        "spans %d processes — each host would stage a private, "
        "incomplete checkpoint and the multi-process commit can never "
        "complete" % (str(directory), what, int(process_count)),
        where="CheckpointManager(directory=%r)" % str(directory),
        hint="point every process at the same shared filesystem "
             "(NFS/GCS-fuse/lustre) path; docs/RESILIENCE.md "
             "'Multi-host & elastic'")]


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------

def _sub_jaxprs(params):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            if isinstance(u, jex_core.ClosedJaxpr):
                yield u.jaxpr
            elif isinstance(u, jex_core.Jaxpr):
                yield u


def _walk(jaxpr, axis_sizes: Dict[str, int], diags: List[Diagnostic],
          path: str = "jaxpr", replicated_invars=frozenset()):
    """Recursive jaxpr walk.  Carries a producer map (var -> defining
    eqn) within each jaxpr for the GL002 stacked-operand check;
    ``replicated_invars`` are shard_map-body invars whose in_spec is
    fully replicated (empty names), for the GL006 redundant-all-gather
    check."""
    producers: Dict[Any, Any] = {}
    for n, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        where = "%s[%d] %s" % (path, n, prim)
        if prim in ("ppermute", "pshuffle"):
            axes = eqn.params.get("axis_name")
            axes = axes if isinstance(axes, (tuple, list)) else (axes,)
            if all(a in axis_sizes for a in axes):
                size = int(np.prod([axis_sizes[a] for a in axes]))
                label = axes[0] if len(axes) == 1 else tuple(axes)
                diags.extend(check_permutation(
                    eqn.params.get("perm", ()), size, label, where=where))
        elif prim == "all_gather" and replicated_invars:
            src = _chase_var(eqn.invars[0], producers)
            if src in replicated_invars:
                diags.append(Diagnostic(
                    "GL006", Severity.WARNING,
                    "all_gather over axis %r of an operand that enters "
                    "this shard_map replicated (in_spec P()) — the "
                    "gather multiplies an already-full buffer by the "
                    "axis size for no information"
                    % (eqn.params.get("axis_name"),), where=where,
                    hint="drop the all_gather, or shard the operand's "
                         "in_spec over the axis so the gather "
                         "re-materializes real shards"))
        elif prim == "shard_map":
            _check_shard_map_eqn(eqn, diags, producers, where)
            mesh = eqn.params["mesh"]
            inner_env = dict(axis_sizes)
            inner_env.update({k: int(v) for k, v in dict(mesh.shape).items()})
            body = eqn.params["jaxpr"]
            repl = frozenset(
                v for v, spec in zip(body.invars, eqn.params["in_specs"])
                if not _spec_names(spec))
            _walk(body, inner_env, diags, path=where,
                  replicated_invars=repl)
        elif prim == "pjit":
            closed = eqn.params["jaxpr"]
            donated = eqn.params.get("donated_invars")
            if donated and any(donated):
                _check_donation(closed.jaxpr, donated, diags, where)
            _walk(closed.jaxpr, axis_sizes, diags, path=where)
        else:
            # scan/while/cond/checkpoint/custom_* bodies: run the axis
            # and permutation checks inside (carries enter fresh, so
            # the stacked-operand chase conservatively stops at the
            # boundary — no false GL002 positives on loop state)
            for sub in _sub_jaxprs(eqn.params):
                _walk(sub, axis_sizes, diags, path=where)
        for v in eqn.outvars:
            if isinstance(v, jex_core.Var):
                producers[v] = eqn


def lint_jaxpr(closed_jaxpr, *, axis_sizes: Optional[Dict[str, int]] = None,
               donated_leaves: Sequence[int] = (),
               suppress: Tuple[str, ...] = ()) -> LintReport:
    """Run GL001–GL003 over an already-traced ``ClosedJaxpr``.

    ``axis_sizes`` seeds named-axis sizes for permutation checks outside
    any ``shard_map`` (inside one, sizes come from its mesh).
    ``donated_leaves`` are flat invar indices donated at the top level.
    """
    jaxpr = closed_jaxpr.jaxpr if isinstance(
        closed_jaxpr, jex_core.ClosedJaxpr) else closed_jaxpr
    diags: List[Diagnostic] = []
    if donated_leaves:
        mask = [i in set(donated_leaves) for i in range(len(jaxpr.invars))]
        _check_donation(jaxpr, mask, diags, "jaxpr")
    _walk(jaxpr, dict(axis_sizes or {}), diags)
    return LintReport(diags, suppress=suppress)


# ---------------------------------------------------------------------------
# GL004 — effects dropped by inner trace regions
# ---------------------------------------------------------------------------

def _dynamic_trace():
    """The currently-active jax trace object — delegated to the single
    implementation in ``tracing.py`` so registration-time and pop-time
    origins can never disagree about what 'current trace' means."""
    from .. import tracing

    return tracing._dynamic_trace()


def _gl004_hook(diags: List[Diagnostic]):
    """pop_trace hook: when a TraceContext is popped, any aux loss /
    aux write whose registration trace is not the trace active *now*
    was registered inside an inner region (jax.checkpoint, scan body,
    shard_map body) that has already been finalized — the enclosing
    consumer will silently drop it (or leak a dead tracer)."""

    def hook(ctx):
        cur = _dynamic_trace()
        if cur is None:
            return
        origins = getattr(ctx, "aux_loss_origins", ())
        for i, v in enumerate(ctx.aux_losses):
            org = origins[i] if i < len(origins) else None
            if org is not None and org is not cur:
                diags.append(Diagnostic(
                    "GL004", Severity.ERROR,
                    "aux loss #%d (shape %s) was registered inside an "
                    "inner trace region (jax.checkpoint/remat, scan or "
                    "shard_map body) that has already been finalized — "
                    "the enclosing step will silently drop it from the "
                    "objective" % (i, getattr(v, "shape", "?")),
                    where="TraceContext.aux_losses[%d]" % i,
                    hint="lift it out as an output of the inner region "
                         "and re-register it outside (see gluon/block.py "
                         "_forward_remat), or register it outside the "
                         "checkpointed code"))
        worigins = getattr(ctx, "aux_write_origins", {})
        for oid, (holder, _v) in list(ctx.aux_writes.items()):
            org = worigins.get(oid)
            if org is not None and org is not cur:
                name = getattr(holder, "name", repr(holder))
                diags.append(Diagnostic(
                    "GL004", Severity.ERROR,
                    "aux-state write to %r was registered inside a "
                    "finalized inner trace region — committing it will "
                    "silently store a dead tracer" % name,
                    where="TraceContext.aux_writes[%r]" % name,
                    hint="route the write through the region's outputs "
                         "(gluon/block.py _forward_remat does this for "
                         "jax.checkpoint)"))

    return hook


@contextmanager
def capture_effect_diagnostics():
    """Collect GL004 diagnostics for every TraceContext popped while the
    context is active.  Wrap this around *the trace you are already
    paying for* (e.g. ``jax.jit(...).trace(*args)``) and the GL004
    check costs nothing extra — the fused train step lints this way so
    its lint trace is the same trace jit caches for the first call."""
    from .. import tracing

    diags: List[Diagnostic] = []
    hook = _gl004_hook(diags)
    tracing._pop_hooks().append(hook)
    try:
        yield diags
    finally:
        tracing._pop_hooks().remove(hook)


# ---------------------------------------------------------------------------
# GL005 — recompile hazard probe
# ---------------------------------------------------------------------------

def _consts_differ(c1, c2) -> bool:
    if len(c1) != len(c2):
        return True
    for a, b in zip(c1, c2):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            return True
        if a.size <= (1 << 20) and not np.array_equal(a, b):
            return True
    return False


def recompile_probe(fn, args: tuple, kwargs: Optional[dict] = None
                    ) -> List[Diagnostic]:
    """GL005: probe ``fn``'s compile-cache-key stability.

    (a) Host Python scalars / weak-typed arrays among the example
        arguments: their avals are weak-typed, so the same call site
        alternating ``2.0`` / ``np.float32(2)`` / ``jnp.float32(2)``
        builds a distinct executable per variant.
    (b) Re-trace: trace ``fn`` twice with identical avals and compare
        programs and embedded constants.  A difference means the trace
        captures ambient state (np.random, time, id()/hash iteration
        order) — the cached program is irreproducible and every retrace
        (shape change, cache eviction) recompiles to *different* code.
    """
    kwargs = kwargs or {}
    diags: List[Diagnostic] = []
    flat, _ = jax.tree_util.tree_flatten((args, kwargs))
    for i, leaf in enumerate(flat):
        if isinstance(leaf, (bool, int, float, complex)):
            diags.append(Diagnostic(
                "GL005", Severity.WARNING,
                "argument leaf %d is a host Python scalar (%s): its "
                "aval is weak-typed, so alternating scalar kinds at "
                "this position retriggers compilation per variant"
                % (i, type(leaf).__name__),
                where="args[leaf %d]" % i,
                hint="pass jnp.asarray(v, dtype) once, or carry the "
                     "value on-device (cf. the donated step counter in "
                     "parallel/train_step.py)"))
        else:
            aval = getattr(leaf, "aval", None)
            if aval is not None and getattr(aval, "weak_type", False):
                diags.append(Diagnostic(
                    "GL005", Severity.WARNING,
                    "argument leaf %d is a weak-typed array — promote "
                    "it with an explicit dtype to pin one cache entry"
                    % i, where="args[leaf %d]" % i))
    try:
        j1 = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
        j2 = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    except Exception:
        return diags
    if str(j1) != str(j2) or _consts_differ(j1.consts, j2.consts):
        diags.append(Diagnostic(
            "GL005", Severity.WARNING,
            "tracing twice with identical avals produced different "
            "programs — the function captures trace-time state "
            "(np.random / time / hash order); its compile cache entry "
            "is not reproducible and retraces recompile to different "
            "code",
            hint="thread randomness through an explicit key "
                 "(tracing.TraceContext.next_key) and timestamps "
                 "through arguments"))
    return diags


# ---------------------------------------------------------------------------
# front door
# ---------------------------------------------------------------------------

def donated_leaf_indices(args, donate_argnums) -> List[int]:
    """Map jit-style positional ``donate_argnums`` to flat invar indices
    of the traced program (each pytree argument spans its leaf count)."""
    donate = set(donate_argnums or ())
    idx, off = [], 0
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in donate:
            idx.extend(range(off, off + n))
        off += n
    return idx


def lint_traceable(fn, args: tuple = (), kwargs: Optional[dict] = None, *,
                   donate_argnums: Sequence[int] = (),
                   axis_sizes: Optional[Dict[str, int]] = None,
                   suppress: Tuple[str, ...] = (),
                   recompile_probe: bool = False) -> LintReport:
    """Trace ``fn(*args, **kwargs)`` abstractly and lint the program.

    Runs GL001 (permutations), GL002 (partition specs + the stacked-
    operand hazard), GL003 (donation, per ``donate_argnums`` — positional
    argnums as you would pass to ``jax.jit``), GL004 (aux effects
    dropped by inner trace regions, via a ``tracing.pop_trace`` hook
    active only for the duration of this trace), and — when
    ``recompile_probe=True`` — GL005.  Tracing is abstract: no compile,
    no device transfer, no FLOPs.

    ``suppress``: diagnostic codes to drop from the report (they remain
    inspectable under ``report.suppressed``).
    """
    kwargs = kwargs or {}
    with capture_effect_diagnostics() as diags:
        closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    report = LintReport(suppress=suppress)
    report.extend(diags)
    donated = donated_leaf_indices(args, donate_argnums)
    sub = lint_jaxpr(closed, axis_sizes=axis_sizes,
                     donated_leaves=donated)
    report.extend(sub.diagnostics)
    if recompile_probe:
        report.extend(globals()["recompile_probe"](fn, args, kwargs))
    return report
