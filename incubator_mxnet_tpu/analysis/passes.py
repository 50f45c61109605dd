"""graftpass: a verified trace-time jaxpr→jaxpr rewrite engine.

graftlint (``trace_lint.py``) and graftcost (``cost_model.py``) *read*
the traced program on the pre-compile ``jit.trace()`` hook; this module
*rewrites* it — the nnvm/Relay pass infrastructure (Relay,
arXiv:1810.00952; SURVEY.md §L5a on MXNet's quantization/AMP graph
rewrites) done the JAX way.  A :class:`GraftPass` is a jaxpr→jaxpr
transform that **declares an exactness contract**, and the
:class:`PassManager` **verifies the declaration by construction** before
any rewrite is installed:

1. **abstract eval** — the rewritten program's output avals must match
   the input program's exactly (shape and dtype; a pass may change the
   interior, never the interface);
2. **re-lint** — the rewritten jaxpr is run back through graftlint; a
   pass may not introduce a jaxpr-level graftlint finding — the
   GL001–GL003 walks plus the in-walk GL006 class; builder-level
   checks cannot be altered by a jaxpr rewrite — the input program
   did not have (GL302);
3. **cost receipts** — graftcost runs before and after, stamping every
   rewrite with a predicted FLOPs / HBM-bytes / peak-memory receipt; a
   ``bit_exact`` rewrite whose predicted HBM bytes *increase* is
   pointless and is skipped (GL303);
4. **concrete probe** — both programs are evaluated (eagerly, no XLA
   compile) on a seeded probe batch and compared per the contract
   (GL301 on violation; the rewrite is refused, the original program is
   kept, and zero compiles were spent).

Contracts:

- ``bit_exact`` — the rewrite computes the *same mathematical terms*.
  Verified bitwise on an **exact-arithmetic probe**: inputs drawn from
  small positive dyadics ({2⁻⁶ … 2⁻³}, see ``_DYADIC``) make every
  float product/sum exactly representable, so float addition is
  associative on the probe — a wrong rewrite (a dropped, duplicated or
  shifted term) shows up bitwise, while pure reassociation (which XLA
  does not pin down anyway) cannot.  Positive and small are both
  load-bearing: negatives would NaN variance-like params, large
  magnitudes would saturate tanh/softmax and round a perturbation away.
- ``tolerance(atol)`` — max |new − ref| ≤ atol · max |ref| per output,
  on a seeded random probe (the AMP / low-precision contract).
- ``argmax_preserving(atol)`` — ``tolerance`` plus argmax over the last
  axis identical for every ranked output (the quantized-classifier
  contract).

Shipped passes (the registry; ``tools/graftpass.py --list``):

- ``quantize_int8`` / ``quantize_int4`` — weight-only symmetric
  quantization of long-lived parameter inputs (float, ndim ≥ 2): each
  eligible invar is replaced by an (intN codes, f32 amax) pair with a
  dequantize prologue, exactly the ``ops/quantization.py`` convention.
  Invar-changing: the result carries a value transform callers apply to
  their stored parameters (``ServeEngine``'s int8 tier is this pass).
- ``amp_bf16`` — AMP-style selective dtype rewriting: matmul/conv
  compute in bf16 (f32 accumulation via ``preferred_element_type``),
  reductions/softmax/norms untouched in f32 (``tolerance``).
- ``space_to_depth`` — the conv1 rewrite (PERF.md lever b): a k×k
  stride-2 conv over few input channels becomes a ⌈k/2⌉×⌈k/2⌉ stride-1
  conv over 4× the channels via a space-to-depth rearrangement of input
  and kernel — same terms, better MXU lane utilization (``bit_exact``).
- ``cse_dead_aux`` — common-subexpression elimination (the duplicated
  BN-stat computation GL202 detects) + dead-code elimination of
  equations no output depends on (``bit_exact``).

graftsched (per-site schedules): every shipped rewrite pass except
``cse_dead_aux`` is *site-parameterized* — it enumerates its applicable
sites (:meth:`GraftPass.enumerate_sites`, stable ``"<primitive>:<k>"``
addresses into the traced jaxpr) and honors a per-site decision vector
instead of being all-or-nothing.  A :class:`PassSchedule` maps pass →
site → decision with a canonical serialization and a stable hash that
keys the compile cache; the legacy pass-list path is exactly the
all-sites schedule (bitwise-equivalent sugar).  Receipts carry one row
per site with the pass's cost delta attributed across its installed
sites (``cost_model.eqn_site_weight`` proportional split — the rows sum
to the pass's whole before/after delta by construction).  A configured
pass that matched zero sites is flagged GL304 (warning): a silent no-op
composition must not read as "optimized".

Entry points: :class:`PassManager`, :func:`resolve_passes`,
:func:`resolve_schedule`, :class:`PassSchedule`, :func:`register_pass`,
:data:`PASS_REGISTRY`; wired in as ``make_train_step(passes=...)`` /
``ServeEngine(passes=...)`` / ``MXTPU_PASSES`` (config.py) /
``tools/graftpass.py``; GL301–GL304 in docs/ANALYSIS.md; the guide is
docs/PASSES.md.
"""
from __future__ import annotations

import hashlib
import json

from dataclasses import dataclass, field, replace as _dc_replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import core as jcore
from jax.extend import core as jex_core

from .diagnostics import Diagnostic, LintError, LintReport, Severity

__all__ = ["AmpBf16Pass", "Contract", "CseDeadAuxPass", "GraftPass",
           "PASS_REGISTRY", "PassContext", "PassManager", "PassReceipt",
           "PassResult", "PassSchedule", "PassSite", "PipelineResult",
           "QuantizeWeightsPass", "SpaceToDepthPass", "get_pass",
           "register_pass", "resolve_passes", "resolve_schedule"]


# ---------------------------------------------------------------------------
# contracts
# ---------------------------------------------------------------------------

def _is_float_dtype(dt) -> bool:
    """np.issubdtype alone misses the ml_dtypes floats (bfloat16,
    float8): classifying them as non-float would demand bitwise
    equality under a tolerance contract and spuriously refuse valid
    rewrites (and silently skip their argmax checks)."""
    dt = np.dtype(dt)
    return np.issubdtype(dt, np.floating) or jnp.issubdtype(dt,
                                                            jnp.floating)


@dataclass(frozen=True)
class Contract:
    """A pass's exactness declaration — what the framework verifies.

    ``kind``: ``"bit_exact"`` | ``"tolerance"`` | ``"argmax"``.
    ``atol`` is relative to the per-output scale (max |reference|);
    unused for ``bit_exact``.
    """
    kind: str
    atol: float = 0.0

    @staticmethod
    def bit_exact() -> "Contract":
        return Contract("bit_exact")

    @staticmethod
    def tolerance(atol: float) -> "Contract":
        return Contract("tolerance", float(atol))

    @staticmethod
    def argmax_preserving(atol: float) -> "Contract":
        return Contract("argmax", float(atol))

    def describe(self) -> str:
        if self.kind == "bit_exact":
            return "bit_exact"
        if self.kind == "tolerance":
            return "tolerance(atol=%g)" % self.atol
        return "argmax_preserving(atol=%g)" % self.atol

    # -- verification --------------------------------------------------
    def check(self, ref: Sequence[Any], new: Sequence[Any]
              ) -> Tuple[bool, Dict[str, Any]]:
        """Compare probe outputs per this contract.  Returns
        ``(ok, detail)``; detail is the receipt's ``probe`` record."""
        refs = [np.asarray(r) for r in ref]
        news = [np.asarray(n) for n in new]
        if len(refs) != len(news):
            return False, {"error": "output count %d -> %d"
                           % (len(refs), len(news))}
        detail: Dict[str, Any] = {"outputs": len(refs)}
        if self.kind == "bit_exact":
            bad = [i for i, (r, n) in enumerate(zip(refs, news))
                   if r.dtype != n.dtype or not np.array_equal(r, n)]
            detail["bitwise"] = not bad
            if bad:
                i = bad[0]
                detail["first_mismatch"] = {
                    "output": i,
                    "max_abs_err": float(np.max(np.abs(
                        refs[i].astype(np.float64)
                        - news[i].astype(np.float64)), initial=0.0))}
            return not bad, detail
        # PER OUTPUT, as declared: pooling error and scale across
        # outputs would let a corrupted small-magnitude output hide
        # behind a large one's tolerance budget
        ok = True
        worst_rel, max_err, scale = 0.0, 0.0, 0.0
        for i, (r, n) in enumerate(zip(refs, news)):
            if not _is_float_dtype(r.dtype):
                if not np.array_equal(r, n):
                    return False, {"error": "non-float output %d changed"
                                   % i}
                continue
            err_i = float(np.max(np.abs(
                r.astype(np.float64) - n.astype(np.float64)),
                initial=0.0))
            scale_i = float(np.max(np.abs(r), initial=0.0))
            tol_i = self.atol * (scale_i + 1e-12)
            if err_i > tol_i:
                ok = False
                detail.setdefault("violations", []).append(
                    {"output": i, "max_abs_err": err_i,
                     "scale": scale_i, "atol": tol_i})
            worst_rel = max(worst_rel, err_i / (scale_i + 1e-12))
            max_err = max(max_err, err_i)
            scale = max(scale, scale_i)
        detail.update(max_abs_err=max_err, scale=scale,
                      worst_rel_err=worst_rel, atol_rel=self.atol)
        if self.kind == "argmax":
            # a ranking is only OWED preservation where the reference
            # decided it beyond the tolerance margin: a top-2 gap
            # inside 2·atol·scale_i is noise ANY in-tolerance rewrite
            # may flip (a feature-map output full of near-ties must
            # not veto a rewrite the tolerance clause accepts)
            argmax_ok, checked = True, 0
            for r, n in zip(refs, news):
                if not _is_float_dtype(r.dtype) \
                        or r.ndim < 1 or r.shape[-1] < 2:
                    continue
                tol_i = self.atol * (float(np.max(np.abs(r),
                                                  initial=0.0)) + 1e-12)
                r2 = r.reshape(-1, r.shape[-1]).astype(np.float64)
                n2 = n.reshape(-1, n.shape[-1]).astype(np.float64)
                top2 = np.sort(r2, axis=-1)[:, -2:]
                decided = (top2[:, 1] - top2[:, 0]) > 2.0 * tol_i
                checked += int(decided.sum())
                argmax_ok = argmax_ok and bool(np.array_equal(
                    np.argmax(r2[decided], axis=-1),
                    np.argmax(n2[decided], axis=-1)))
            detail["argmax_identical"] = argmax_ok
            detail["argmax_rows_checked"] = checked
            ok = ok and argmax_ok
        return ok, detail


# ---------------------------------------------------------------------------
# pass plumbing
# ---------------------------------------------------------------------------

@dataclass
class PassContext:
    """Caller-side facts a pass pipeline needs.

    ``param_invars`` — flat invar indices that are long-lived model
    parameters (quantization targets); empty means no invar is a
    quantizable weight (the train step: params are donated and updated,
    quantizing them would be nonsense).  ``allow_invar_change`` — False
    refuses invar-changing results outright (builders whose donation/
    sharding specs are pinned to the invar layout).  ``donated_leaves``
    feeds the re-lint's GL003 walk.  ``probe_overrides`` supplies real
    values for specific invars on tolerance/argmax probes (e.g. the
    engine's actual weights — a far sharper parity signal than random
    ones); ``bit_exact`` probes always synthesize exact-arithmetic
    values instead.  ``probe``: ``"auto"`` (on) | ``"off"``.
    """
    param_invars: frozenset = frozenset()
    allow_invar_change: bool = True
    donated_leaves: Tuple[int, ...] = ()
    axis_sizes: Optional[Dict[str, int]] = None
    probe: str = "auto"
    probe_seed: int = 0
    probe_overrides: Dict[int, Any] = field(default_factory=dict)
    #: graftrange hookup (analysis/value_range.py): "off" skips the
    #: range gate in precision-aware passes; "warn" excludes unsafe ops
    #: (GL403 warning); "error" refuses the whole pass on an unsafe
    #: edge.  ``input_ranges`` maps flat invar indices to
    #: (lo, hi[, positive]) seeds — builder annotations / observed
    #: warmup ranges.
    numerics: str = "off"
    input_ranges: Optional[Dict[int, Any]] = None
    where: str = "graftpass"
    #: graftsched decision vector for ONE pass: None = every site
    #: (the legacy all-or-nothing path, now the all-sites sugar); a
    #: frozenset of site ids = only those sites rewrite.  The manager
    #: sets this per pass from its :class:`PassSchedule` — callers
    #: building a context by hand normally leave it None.
    sites: Optional[frozenset] = None


# ---------------------------------------------------------------------------
# sites & schedules (graftsched)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PassSite:
    """One applicable rewrite location of a site-parameterized pass.

    ``id`` is the stable site address: ``"<primitive>:<k>"`` for
    equation sites, where ``k`` counts the equations of that primitive
    in top-level walk order of the traced jaxpr — EVERY equation of the
    primitive advances the counter, matching or not, so the address
    survives both retrace (the walk order IS the jaxpr) and matcher
    changes — and ``"invar:<i>"`` for parameter-invar sites (quantize).
    ``flops``/``hbm_bytes`` are the *local, unfused* weights of the
    original site (``cost_model.eqn_site_weight``): the proportional
    basis for per-site delta attribution, never absolute predictions —
    the pass-level before/after cost totals stay the authority.
    """
    id: str
    kind: str = "eqn"      # "eqn" | "invar"
    detail: str = ""
    flops: float = 0.0
    hbm_bytes: float = 0.0


def _site_on(ctx: "PassContext", site_id: str) -> bool:
    """Decision-vector check a pass rule applies per candidate site."""
    sites = getattr(ctx, "sites", None)
    return sites is None or site_id in sites


class _SiteWalk:
    """Per-primitive ordinal counter shared by ``enumerate_sites`` and
    the retrace rules, so both derive identical site addresses from the
    same deterministic eqn walk."""

    def __init__(self):
        self._n: Dict[str, int] = {}

    def sid(self, prim_name: str) -> str:
        i = self._n.get(prim_name, 0)
        self._n[prim_name] = i + 1
        return "%s:%d" % (prim_name, i)


def _eqn_weight(eqn) -> Tuple[float, float]:
    from .cost_model import eqn_site_weight

    return eqn_site_weight(eqn)


class PassSchedule:
    """pass → site → decision: which sites of which passes rewrite.

    ``entries`` is an ordered tuple of ``(pass_name, decision)`` —
    pipeline order is semantic.  A decision is ``True`` (every site),
    ``False`` (pass disabled) or a ``{site_id: bool}`` map where only
    the ids mapped to True rewrite; unnamed sites stay off, and ids
    absent from a given program are ignored (a schedule authored on one
    batch signature degrades gracefully on another — GL304 flags the
    resulting silent no-op).

    ``canonical()`` / ``to_json()`` are the stable serialization:
    pipeline order preserved, site maps key-sorted, compact separators.
    ``hash()`` is its sha256 prefix (16 hex chars) — equal schedules
    hash equal across processes, distinct schedules never collide in
    the :class:`~..parallel.aot.CompileCache` (the hash rides
    ``cache_extra``).
    """

    def __init__(self, entries: Sequence[Tuple[str, Any]]):
        norm: List[Tuple[str, Any]] = []
        for name, dec in entries:
            if isinstance(dec, dict):
                dec = {str(k): bool(v) for k, v in dec.items()}
            else:
                dec = bool(dec)
            norm.append((str(name), dec))
        self.entries: Tuple[Tuple[str, Any], ...] = tuple(norm)

    # -- constructors --------------------------------------------------
    @staticmethod
    def from_passes(passes) -> "PassSchedule":
        """The all-sites schedule of a pass list — what the legacy
        ``passes=`` on/off path means under graftsched."""
        return PassSchedule([(p.name, True)
                             for p in resolve_passes(passes)])

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "PassSchedule":
        """Inverse of :meth:`canonical` (``{"passes": [{"name": ...,
        "sites": {...}} | {"name": ..., "enabled": bool}, ...]}``).
        A ``sites`` *list* of ids is accepted as hand-authoring sugar
        for ``{id: true}``; any other non-map ``sites`` value raises —
        silently reading it as all-sites would alias a different
        schedule hash in the compile cache."""
        if not isinstance(d, dict) or not isinstance(d.get("passes"),
                                                     (list, tuple)):
            raise ValueError("schedule dict needs a 'passes' list, got %r"
                             % (d,))
        entries: List[Tuple[str, Any]] = []
        for e in d["passes"]:
            sites = e.get("sites")
            if isinstance(sites, dict):
                entries.append((e["name"], sites))
            elif isinstance(sites, (list, tuple, set, frozenset)):
                entries.append((e["name"], {str(s): True for s in sites}))
            elif sites is not None:
                raise ValueError(
                    "schedule entry for %r: 'sites' must be a "
                    "{site_id: bool} map or a list of site ids, got %r"
                    % (e.get("name"), sites))
            else:
                entries.append((e["name"], e.get("enabled", True)))
        return PassSchedule(entries)

    # -- queries -------------------------------------------------------
    def pass_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.entries)

    def decision_for(self, name: str):
        for n, dec in self.entries:
            if n == name:
                return dec
        return None

    def enabled(self, name: str) -> bool:
        """False only when the schedule explicitly turns the whole pass
        (or every one of its named sites) off."""
        dec = self.decision_for(name)
        if dec is None:
            return True  # pass outside the schedule: all-sites default
        if isinstance(dec, dict):
            return any(dec.values())
        return bool(dec)

    def sites_for(self, name: str) -> Optional[frozenset]:
        """The decision vector for one pass: None = every site."""
        dec = self.decision_for(name)
        if dec is None or dec is True:
            return None
        if isinstance(dec, dict):
            return frozenset(k for k, v in dec.items() if v)
        return frozenset()

    # -- serialization -------------------------------------------------
    def canonical(self) -> Dict[str, Any]:
        rows: List[Dict[str, Any]] = []
        for n, dec in self.entries:
            if isinstance(dec, dict):
                rows.append({"name": n,
                             "sites": {k: bool(dec[k])
                                       for k in sorted(dec)}})
            else:
                rows.append({"name": n, "enabled": bool(dec)})
        return {"version": 1, "passes": rows}

    def to_json(self) -> str:
        return json.dumps(self.canonical(), sort_keys=True,
                          separators=(",", ":"))

    def hash(self) -> str:
        return hashlib.sha256(self.to_json().encode()).hexdigest()[:16]

    def __eq__(self, other):
        return isinstance(other, PassSchedule) \
            and self.entries == other.entries

    def __hash__(self):
        return hash(self.to_json())

    def __repr__(self):
        return "PassSchedule(%s, hash=%s)" % (
            ", ".join("%s=%s" % (n, "all" if dec is True else
                                 ("off" if dec is False else
                                  sorted(k for k, v in dec.items() if v)))
                      for n, dec in self.entries), self.hash())


@dataclass
class PassResult:
    """One pass's raw rewrite, before verification.

    ``invar_splits`` maps an original flat invar index to the number of
    invars that replace it (absent = unchanged); ``transform_one`` maps
    one original invar's concrete value to its replacement value list
    (identity when None).  Invar-preserving passes leave both empty.
    """
    closed_jaxpr: Any
    hits: int = 0
    invar_splits: Dict[int, int] = field(default_factory=dict)
    transform_one: Optional[Callable[[int, Any], List[Any]]] = None
    notes: str = ""
    #: advisory diagnostics the pass itself emitted (e.g. amp_bf16's
    #: GL403 per-op exclusions) — copied onto the receipt by the manager
    diagnostics: List[Diagnostic] = field(default_factory=list)
    #: precision-safety verdict of a range-gated pass (the GL403 gate):
    #: {"checked": n, "excluded": n, "safe": bool, ...}
    precision: Optional[Dict[str, Any]] = None
    #: graftsched: site id -> exclusion reason for sites the pass itself
    #: refused to rewrite (amp_bf16's per-op GL403 range gate) — the
    #: manager marks those sites excluded on the per-site receipt rows
    excluded_sites: Dict[str, str] = field(default_factory=dict)


@dataclass
class PassReceipt:
    """The stamped before/after record of one pass application."""
    name: str
    contract: str
    changed: bool = False
    installed: bool = False
    hits: int = 0
    flops_before: float = 0.0
    flops_after: float = 0.0
    hbm_bytes_before: float = 0.0
    hbm_bytes_after: float = 0.0
    peak_bytes_before: float = 0.0
    peak_bytes_after: float = 0.0
    #: resident bytes of the param invars (ctx.param_invars) — the
    #: quantize tiers' 4x story lives here, not in traffic totals
    param_bytes_before: float = 0.0
    param_bytes_after: float = 0.0
    probe: Optional[Dict[str, Any]] = None
    #: graftrange precision-safety verdict (amp_bf16's GL403 gate):
    #: {"checked", "excluded", "safe", "detail"} — None when the pass
    #: is not range-gated or numerics was off
    precision: Optional[Dict[str, Any]] = None
    notes: str = ""
    diagnostics: List[Diagnostic] = field(default_factory=list)
    #: graftsched per-site rows (site-parameterized passes only): one
    #: dict per enumerated site — ``{"site", "kind", "detail",
    #: "decision", "installed", "excluded", "flops_delta",
    #: "hbm_bytes_delta", "param_bytes_delta", "contract", "probe_ok"}``
    #: — with the pass's whole before/after delta attributed across its
    #: installed sites (the rows sum to the pass delta by construction)
    sites: Optional[List[Dict[str, Any]]] = None

    def to_dict(self) -> dict:
        return {"name": self.name, "contract": self.contract,
                "changed": self.changed, "installed": self.installed,
                "hits": self.hits,
                "flops_before": self.flops_before,
                "flops_after": self.flops_after,
                "hbm_bytes_before": self.hbm_bytes_before,
                "hbm_bytes_after": self.hbm_bytes_after,
                "peak_bytes_before": self.peak_bytes_before,
                "peak_bytes_after": self.peak_bytes_after,
                "param_bytes_before": self.param_bytes_before,
                "param_bytes_after": self.param_bytes_after,
                "probe": self.probe, "precision": self.precision,
                "notes": self.notes,
                "diagnostics": [d.to_dict() for d in self.diagnostics],
                "sites": self.sites}


@dataclass
class PipelineResult:
    """The whole pipeline's outcome: the (possibly rewritten) program,
    one receipt per pass, and the composed invar bookkeeping callers
    use to transform their stored argument values."""
    closed_jaxpr: Any
    receipts: List[PassReceipt] = field(default_factory=list)
    invar_splits: Dict[int, int] = field(default_factory=dict)
    _transforms: List[Tuple[Dict[int, int], Callable]] = \
        field(default_factory=list)
    diagnostics: List[Diagnostic] = field(default_factory=list)

    @property
    def changed(self) -> bool:
        return any(r.installed for r in self.receipts)

    def transform_invar(self, idx: int, value: Any) -> List[Any]:
        """Replacement value list for ORIGINAL flat invar ``idx``
        (length 1 when unchanged).  Only single-level splits compose
        today — one invar-changing pass per pipeline (enforced by the
        manager)."""
        for splits, fn in self._transforms:
            if idx in splits:
                return list(fn(idx, value))
        return [value]

    def transform_flat(self, flat_vals: Sequence[Any]) -> List[Any]:
        out: List[Any] = []
        for i, v in enumerate(flat_vals):
            out.extend(self.transform_invar(i, v))
        return out


class GraftPass:
    """Base class: a named jaxpr→jaxpr transform with a contract.

    Subclasses implement :meth:`run` returning a :class:`PassResult`
    (or None / ``hits == 0`` for "nothing to do here").  The manager —
    never the pass — decides installation: abstract eval, re-lint, cost
    receipt and the concrete probe all gate it.
    """

    name: str = "graftpass"
    contract: Contract = Contract.bit_exact()
    description: str = ""
    #: graftsched: True for passes that enumerate sites and honor the
    #: per-site decision vector (``ctx.sites``); whole-program passes
    #: (cse_dead_aux) leave it False and only take on/off decisions
    site_aware: bool = False

    def run(self, closed_jaxpr, ctx: PassContext) -> Optional[PassResult]:
        raise NotImplementedError

    def enumerate_sites(self, closed_jaxpr,
                        ctx: PassContext) -> List[PassSite]:
        """Applicable sites of this pass in ``closed_jaxpr`` (stable
        addresses, :class:`PassSite`).  Enumeration reports
        applicability and IGNORES ``ctx.sites`` — the decision vector
        only filters :meth:`run`.  Whole-program passes return []."""
        return []

    def __repr__(self):
        return "%s(name=%r, contract=%s)" % (
            type(self).__name__, self.name, self.contract.describe())


# ---------------------------------------------------------------------------
# the interpreter core (rewrite-by-retrace)
# ---------------------------------------------------------------------------

def _default_bind(eqn, invals):
    """Evaluate one equation the way ``jcore.eval_jaxpr`` would."""
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    outs = eqn.primitive.bind(*subfuns, *invals, **bind_params)
    return list(outs) if eqn.primitive.multiple_results else [outs]


def interpret(jaxpr, consts, args, rule=None, skip=None):
    """Walk one (open) jaxpr, evaluating each equation — through
    ``rule(eqn, invals)`` when it returns outputs, the primitive's own
    bind otherwise.  ``skip`` is a set of ``id(eqn)`` to drop entirely
    (DCE).  Works under tracing (the retrace route) and eagerly (the
    probe route)."""
    env: Dict[Any, Any] = {}

    def read(v):
        return v.val if isinstance(v, jex_core.Literal) else env[v]

    for v, c in zip(jaxpr.constvars, consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a
    for eqn in jaxpr.eqns:
        if skip is not None and id(eqn) in skip:
            continue
        invals = [read(v) for v in eqn.invars]
        outs = rule(eqn, invals) if rule is not None else None
        if outs is None:
            outs = _default_bind(eqn, invals)
        for v, o in zip(eqn.outvars, outs):
            if isinstance(v, jex_core.Var):
                env[v] = o
    return [read(v) for v in jaxpr.outvars]


def retrace(closed_jaxpr, rule=None, skip=None):
    """Re-trace ``closed_jaxpr`` through :func:`interpret`, producing a
    new ClosedJaxpr over the same invar avals."""
    jaxpr, consts = closed_jaxpr.jaxpr, closed_jaxpr.consts
    specs = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
             for v in jaxpr.invars]
    return jax.make_jaxpr(
        lambda *a: interpret(jaxpr, consts, list(a), rule, skip))(*specs)


def eval_closed(closed_jaxpr, flat_vals):
    """Eager (no XLA ahead-of-time compile) evaluation of a closed
    jaxpr on concrete values — the probe executor."""
    return jcore.eval_jaxpr(closed_jaxpr.jaxpr, closed_jaxpr.consts,
                            *flat_vals)


# -- probe synthesis --------------------------------------------------------

#: exact-arithmetic alphabet: products/sums of these stay exactly
#: representable in f32 for thousands of terms, so float addition is
#: associative on the probe and reassociation cannot mask a term bug.
#: Positive-only (a negative draw landing on a variance-like param —
#: BN running stats — would NaN the whole probe and make the bitwise
#: comparison vacuous) and SMALL (contraction sums land in the
#: sensitive range of tanh/sigmoid/softmax instead of their saturated
#: plateaus, where a wrong rewrite's perturbation would round away);
#: magnitude diversity distinguishes a shifted/dropped/duplicated term
_DYADIC = np.array([0.015625, 0.03125, 0.0625, 0.125])


def synth_probe(avals, seed: int = 0, dyadic: bool = False,
                overrides: Optional[Dict[int, Any]] = None) -> List[Any]:
    """One deterministic concrete value per aval.  ``dyadic`` draws
    floats from the exact-arithmetic alphabet (bit_exact probes);
    otherwise standard normals.  ``overrides`` (ignored when dyadic)
    substitutes caller-provided real values by flat index."""
    rng = np.random.RandomState(seed)
    vals: List[Any] = []
    for i, a in enumerate(avals):
        if not dyadic and overrides and i in overrides:
            vals.append(np.asarray(overrides[i]))
            continue
        dt = np.dtype(a.dtype)
        # _is_float_dtype, not bare np.issubdtype: zero-filling an
        # ml_dtypes float (bfloat16/float8) would make the GL301 probe
        # vacuous (x*1.001 of 0 compares bit-identical)
        if _is_float_dtype(dt):
            v = rng.choice(_DYADIC, size=a.shape) if dyadic \
                else rng.normal(0.0, 1.0, size=a.shape)
            vals.append(v.astype(dt))
        elif np.issubdtype(dt, np.unsignedinteger):
            # PRNG-key material and friends: fixed, well-formed bits
            vals.append((rng.randint(1, 1 << 30, size=a.shape)
                         if a.shape else np.asarray(rng.randint(1, 1 << 30))
                         ).astype(dt))
        elif np.issubdtype(dt, np.integer):
            vals.append(rng.randint(0, 4, size=a.shape).astype(dt)
                        if a.shape else dt.type(1))
        elif dt == np.bool_:
            vals.append((rng.rand(*a.shape) > 0.5) if a.shape
                        else np.bool_(True))
        else:
            vals.append(np.zeros(a.shape, dt))
    return vals


# ---------------------------------------------------------------------------
# shipped pass: weight-only quantization (int8 / int4)
# ---------------------------------------------------------------------------

class QuantizeWeightsPass(GraftPass):
    """Weight-only symmetric intN quantization of parameter invars.

    Every flat invar in ``ctx.param_invars`` that is floating and
    ndim ≥ 2 (matrices/filters carry the bytes; biases and BN vectors
    stay float — their error would be per-channel, their size is noise)
    is replaced by an ``(intN codes, f32 amax)`` pair, dequantized to
    the original dtype in a prologue the rest of the program consumes
    unchanged — the ``ops/quantization.py`` convention (scale =
    qmax/amax, zero-point free), so a tensor round-tripped through this
    pass and one through the reference-parity ops land on identical
    codes.  ``bits=4`` stores int4-range codes in an int8 container
    (XLA's int4 compute support is backend-dependent; the convention —
    qmax 7 — is the real int4 one, so a packing step is a storage
    change, not a numerics change).
    """

    site_aware = True

    def __init__(self, bits: int = 8):
        if bits not in (8, 4):
            raise ValueError("bits must be 8 or 4, got %r" % (bits,))
        self.bits = bits
        self.qmax = 127 if bits == 8 else 7
        self.name = "quantize_int%d" % bits
        # int8 weight error is ~0.4 % of scale per matmul on small nets;
        # int4 is ~16x coarser and cannot promise ranking stability
        self.contract = Contract.argmax_preserving(0.05) if bits == 8 \
            else Contract.tolerance(0.25)
        self.description = ("weight-only symmetric int%d: eligible param "
                            "invars become (int%d, amax) pairs with a "
                            "dequantize prologue" % (bits, bits))

    def _eligible(self, jaxpr, ctx: PassContext) -> List[int]:
        out = []
        for i in sorted(ctx.param_invars):
            if i >= len(jaxpr.invars):
                continue
            a = jaxpr.invars[i].aval
            if jnp.issubdtype(a.dtype, jnp.floating) \
                    and getattr(a, "ndim", 0) >= 2:
                out.append(i)
        return out

    def quantize(self, w):
        # the ONE guarded implementation (ops/quantization.py): amax==0
        # and NaN'd channels yield zero codes + amax 0, never NaN codes
        from ..ops.quantization import symmetric_quantize

        q, amax = symmetric_quantize(jnp.asarray(w), qmax=self.qmax)
        return [q, amax]

    def enumerate_sites(self, closed_jaxpr,
                        ctx: PassContext) -> List[PassSite]:
        jaxpr = closed_jaxpr.jaxpr
        out: List[PassSite] = []
        for i in self._eligible(jaxpr, ctx):
            a = jaxpr.invars[i].aval
            nbytes = float(np.prod(a.shape, dtype=np.int64)
                           * np.dtype(a.dtype).itemsize)
            out.append(PassSite(
                "invar:%d" % i, kind="invar",
                detail="param %s[%s]" % (np.dtype(a.dtype).name,
                                         ",".join(map(str, a.shape))),
                hbm_bytes=nbytes))
        return out

    def run(self, closed_jaxpr, ctx: PassContext) -> Optional[PassResult]:
        jaxpr = closed_jaxpr.jaxpr
        eligible = [i for i in self._eligible(jaxpr, ctx)
                    if _site_on(ctx, "invar:%d" % i)]
        if not eligible:
            return None
        esel = set(eligible)
        qmax = float(self.qmax)
        orig_avals = [v.aval for v in jaxpr.invars]

        def rewritten(*new_flat):
            it = iter(new_flat)
            orig_vals = []
            for i, a in enumerate(orig_avals):
                if i in esel:
                    q, amax = next(it), next(it)
                    orig_vals.append(
                        (q.astype(jnp.float32) * (amax / qmax))
                        .astype(a.dtype))
                else:
                    orig_vals.append(next(it))
            return jcore.eval_jaxpr(jaxpr, closed_jaxpr.consts, *orig_vals)

        specs = []
        for i, a in enumerate(orig_avals):
            if i in esel:
                specs.append(jax.ShapeDtypeStruct(a.shape, jnp.int8))
                specs.append(jax.ShapeDtypeStruct((), jnp.float32))
            else:
                specs.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
        new_closed = jax.make_jaxpr(rewritten)(*specs)

        def transform_one(idx, value):
            return self.quantize(value) if idx in esel else [value]

        return PassResult(
            new_closed, hits=len(eligible),
            invar_splits={i: 2 for i in eligible},
            transform_one=transform_one,
            notes="%d param invar(s) quantized to int%d"
                  % (len(eligible), self.bits))


# ---------------------------------------------------------------------------
# shipped pass: AMP-style selective dtype rewriting
# ---------------------------------------------------------------------------

class AmpBf16Pass(GraftPass):
    """Matmul/conv in bf16, everything else untouched.

    Rewrites every f32 ``dot_general`` / ``conv_general_dilated``: the
    operands are cast to bf16 and the op accumulates in f32
    (``preferred_element_type``), so the interface dtype — and every
    reduction, softmax and norm downstream, which this pass never
    touches — stays f32.  The MXNet AMP graph rewrite (SURVEY.md §L5a)
    as a trace-time pass.
    """

    name = "amp_bf16"
    site_aware = True
    description = ("selective dtype rewrite: f32 matmul/conv operands in "
                   "bf16 with f32 accumulation; reductions/softmax/norms "
                   "stay f32; per-op GL403 range gate under numerics=")

    _PRIMS = ("dot_general", "conv_general_dilated")

    def __init__(self, atol: float = 0.05):
        self.contract = Contract.tolerance(atol)

    @classmethod
    def _candidate(cls, eqn) -> bool:
        if eqn.primitive.name not in cls._PRIMS:
            return False
        if eqn.outvars[0].aval.dtype != jnp.float32:
            return False
        a, b = eqn.invars[0].aval, eqn.invars[1].aval
        return a.dtype == jnp.float32 and b.dtype == jnp.float32

    def enumerate_sites(self, closed_jaxpr,
                        ctx: PassContext) -> List[PassSite]:
        walk, out = _SiteWalk(), []
        for eqn in closed_jaxpr.jaxpr.eqns:
            prim = eqn.primitive.name
            if prim not in self._PRIMS:
                continue
            sid = walk.sid(prim)
            if not self._candidate(eqn):
                continue
            fl, by = _eqn_weight(eqn)
            out.append(PassSite(
                sid, detail="%s -> %s"
                % (prim, eqn.outvars[0].aval.str_short()),
                flops=fl, hbm_bytes=by))
        return out

    def run(self, closed_jaxpr, ctx: PassContext) -> Optional[PassResult]:
        hits = [0]
        # graftrange installation gate (GL403, docs/ANALYSIS.md): with
        # ctx.numerics on, the value-range walk runs over the INPUT
        # program once and every demotion candidate's operand ranges
        # are checked against bfloat16 — an edge whose proven range
        # does not fit bf16 is EXCLUDED from demotion (the pass is no
        # longer all-or-nothing) or, under numerics="error", refuses
        # the whole pass before any compile.  Unknown ranges fit: bf16
        # shares f32's exponent range, so only a proven excursion is a
        # hazard.
        gate = getattr(ctx, "numerics", "off") != "off"
        ranges: Optional[Dict[Any, Any]] = None
        excluded: List[Tuple[str, str]] = []
        if gate:
            from .value_range import analyze_ranges

            ranges = analyze_ranges(
                closed_jaxpr, input_ranges=ctx.input_ranges,
                axis_sizes=ctx.axis_sizes, collect=False).var_ranges

        def _bf16_unsafe(eqn):
            if ranges is None:
                return None
            from .value_range import bf16_fit, VRange as _VR

            for iv in eqn.invars[:2]:
                vr = ranges.get(iv) if isinstance(iv, jex_core.Var) else None
                if vr is None and not isinstance(iv, jex_core.Var):
                    import numpy as _np

                    val = _np.asarray(iv.val)
                    m = float(_np.max(_np.abs(val))) if val.size else 0.0
                    vr = _VR(-m, m)
                if vr is None:
                    continue
                ok, reason = bf16_fit(vr)
                if not ok:
                    return reason
            return None

        walk = _SiteWalk()

        def rule(eqn, invals):
            if eqn.primitive.name not in self._PRIMS:
                return None
            sid = walk.sid(eqn.primitive.name)
            out_aval = eqn.outvars[0].aval
            if out_aval.dtype != jnp.float32:
                return None
            a, b = invals[0], invals[1]
            if a.dtype != jnp.float32 or b.dtype != jnp.float32:
                return None
            # the schedule's decision vector filters BEFORE the range
            # gate: a site the schedule turned off is neither demoted
            # nor counted among the GL403-checked candidates
            if not _site_on(ctx, sid):
                return None
            reason = _bf16_unsafe(eqn)
            if reason is not None:
                excluded.append((sid, reason))
                return None
            params = dict(eqn.params)
            params["preferred_element_type"] = jnp.dtype(jnp.float32)
            out = eqn.primitive.bind(a.astype(jnp.bfloat16),
                                     b.astype(jnp.bfloat16), **params)
            hits[0] += 1
            return [out]

        new_closed = retrace(closed_jaxpr, rule)
        diags: List[Diagnostic] = []
        precision = None
        if gate:
            precision = {"checked": hits[0] + len(excluded),
                         "excluded": len(excluded),
                         "safe": not excluded,
                         "detail": [r for _, r in excluded[:4]]}
            if excluded:
                if ctx.numerics == "error":
                    raise LintError(LintReport([Diagnostic(
                        "GL403", Severity.ERROR,
                        "amp_bf16: %d of %d demotion candidate(s) have "
                        "operand ranges that do not fit bfloat16 "
                        "(first: %s) — the pass is refused under "
                        "numerics='error', the original program is "
                        "kept, zero compiles spent"
                        % (len(excluded), hits[0] + len(excluded),
                           excluded[0][1]),
                        where=ctx.where,
                        hint="fix the edge's scale (or annotate the "
                             "real input range), or run "
                             "numerics='warn' to demote only the safe "
                             "ops")]))
                diags.append(Diagnostic(
                    "GL403", Severity.WARNING,
                    "amp_bf16: excluded %d of %d matmul/conv "
                    "candidate(s) from bf16 demotion — %s"
                    % (len(excluded), hits[0] + len(excluded),
                       "; ".join(r for _, r in excluded[:2])),
                    where=ctx.where,
                    hint="the remaining ops still demote; rescale the "
                         "flagged edge (or tighten input_range=) to "
                         "recover it"))
        if not hits[0]:
            if not diags:
                return None
            # nothing demotable was SAFE: surface the verdict on a
            # no-op receipt instead of silently dropping it
            return PassResult(closed_jaxpr, hits=0, diagnostics=diags,
                              precision=precision,
                              excluded_sites=dict(excluded),
                              notes="all %d candidate(s) excluded by "
                                    "the GL403 range gate"
                                    % len(excluded))
        return PassResult(new_closed, hits=hits[0],
                          diagnostics=diags, precision=precision,
                          excluded_sites=dict(excluded),
                          notes="%d matmul/conv op(s) moved to bf16 "
                                "compute%s"
                                % (hits[0],
                                   "" if not excluded
                                   else ", %d excluded by the GL403 "
                                        "range gate" % len(excluded)))


# ---------------------------------------------------------------------------
# shipped pass: conv1 space-to-depth
# ---------------------------------------------------------------------------

class SpaceToDepthPass(GraftPass):
    """The conv1 rewrite (docs/PERF.md lever b, ROADMAP item 1).

    A k×k stride-2 convolution over few input channels (ResNet's 7×7/s2
    over RGB) wastes the MXU: 3 channels pad to the 8-lane sublane
    width, so >60 % of the loaded operand is zeros.  Rearranging 2×2
    spatial blocks into channels (space-to-depth) and regrouping the
    (zero-padded to k+1) kernel the same way yields a ⌈(k+1)/2⌉-sized
    stride-1 VALID conv over 4× the channels — for conv1 exactly the
    112×112×12 program PERF.md names — computing the *same terms*
    (``bit_exact``; the concrete probe runs on the exact-arithmetic
    alphabet where reassociation is invisible and any shifted/dropped
    term is not).  Applies to NCHW/OIHW 2-D convs with stride (2, 2),
    no dilation, groups 1 and ≤ ``max_in_channels`` input channels,
    without touching model code.
    """

    name = "space_to_depth"
    contract = Contract.bit_exact()
    description = ("k x k stride-2 conv over few channels -> space-to-"
                   "depth + stride-1 conv over 4x channels (conv1 MXU "
                   "utilization, PERF.md lever b)")

    site_aware = True

    def __init__(self, max_in_channels: int = 7):
        # below the 8-sublane width is where the win lives
        self.max_in_channels = int(max_in_channels)

    def enumerate_sites(self, closed_jaxpr, ctx) -> List[PassSite]:
        sites, walk = [], _SiteWalk()
        for eqn in closed_jaxpr.jaxpr.eqns:
            if eqn.primitive.name != "conv_general_dilated":
                continue
            sid = walk.sid("conv_general_dilated")
            if not self._match(eqn):
                continue
            lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
            fl, by = _eqn_weight(eqn)
            sites.append(PassSite(
                sid, detail="%dx%d/s2 conv %s * %s"
                % (rhs.shape[2], rhs.shape[3], lhs.str_short(),
                   rhs.str_short()),
                flops=fl, hbm_bytes=by))
        return sites

    def _match(self, eqn) -> bool:
        if eqn.primitive.name != "conv_general_dilated":
            return False
        p = eqn.params
        dn = p["dimension_numbers"]
        if tuple(dn.lhs_spec) != (0, 1, 2, 3) \
                or tuple(dn.rhs_spec) != (0, 1, 2, 3) \
                or tuple(dn.out_spec) != (0, 1, 2, 3):
            return False  # only canonical NCHW/OIHW 2-D convs
        if tuple(p["window_strides"]) != (2, 2):
            return False
        if tuple(p.get("lhs_dilation") or (1, 1)) != (1, 1) \
                or tuple(p.get("rhs_dilation") or (1, 1)) != (1, 1):
            return False
        if int(p.get("feature_group_count", 1)) != 1 \
                or int(p.get("batch_group_count", 1)) != 1:
            return False
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        kh, kw = rhs.shape[2], rhs.shape[3]
        if kh != kw or kh % 2 == 0:
            return False  # odd k pads to k+1; even k would need k+2
        if rhs.shape[1] > self.max_in_channels:
            return False
        (pt, pb), (pl, pr) = [tuple(q) for q in p["padding"]]
        h, w = lhs.shape[2], lhs.shape[3]
        # the 2x2 block grid must tile the padded extent
        return (h + pt + pb) % 2 == 0 and (w + pl + pr) % 2 == 0

    def run(self, closed_jaxpr, ctx: PassContext) -> Optional[PassResult]:
        hits = [0]
        walk = _SiteWalk()

        def rule(eqn, invals):
            if eqn.primitive.name != "conv_general_dilated":
                return None
            sid = walk.sid("conv_general_dilated")
            if not self._match(eqn) or not _site_on(ctx, sid):
                return None
            x, w = invals
            p = eqn.params
            (pt, pb), (pl, pr) = [tuple(q) for q in p["padding"]]
            o, c, k, _ = w.shape
            # lax.pad, the bare primitive: jnp.pad traces as a nested jit,
            # which the cost walk prices as a pass of its own (GL303 then
            # refuses the rewrite) where XLA folds the pad into the conv
            xp = jax.lax.pad(x, np.zeros((), x.dtype),
                             ((0, 0, 0), (0, 0, 0), (pt, pb, 0), (pl, pr, 0)))
            n, _, h, wd = xp.shape
            z = xp.reshape(n, c, h // 2, 2, wd // 2, 2) \
                  .transpose(0, 1, 3, 5, 2, 4) \
                  .reshape(n, c * 4, h // 2, wd // 2)
            wp = jax.lax.pad(w, np.zeros((), w.dtype),
                             ((0, 0, 0), (0, 0, 0), (0, 1, 0), (0, 1, 0)))
            kk = (k + 1) // 2
            w2 = wp.reshape(o, c, kk, 2, kk, 2) \
                   .transpose(0, 1, 3, 5, 2, 4) \
                   .reshape(o, c * 4, kk, kk)
            params = dict(p)
            params["window_strides"] = (1, 1)
            params["padding"] = ((0, 0), (0, 0))
            out = eqn.primitive.bind(z, w2, **params)
            hits[0] += 1
            return [out]

        new_closed = retrace(closed_jaxpr, rule)
        if not hits[0]:
            return None
        return PassResult(new_closed, hits=hits[0],
                          notes="%d stride-2 conv(s) rewritten to "
                                "space-to-depth stride-1 form" % hits[0])


# ---------------------------------------------------------------------------
# shipped pass: CSE + dead-code elimination
# ---------------------------------------------------------------------------

class CseDeadAuxPass(GraftPass):
    """Common-subexpression + dead-code elimination at the jaxpr level.

    The traced program computes BN batch stats twice (normalize path +
    running-stats update) and autodiff re-emits identical chains — the
    multi-pass traffic GL202 detects; this pass merges them so the
    *program* says what XLA would discover, making every downstream
    analysis (and backend) see one computation.  Equations whose
    outputs no program output depends on — dead aux values, unused RNG
    splits — are dropped outright (those, XLA would also fold, but the
    trace-time cost receipts and lint reports otherwise keep charging
    them).  Control-flow, RNG and effectful equations are never merged
    (two RNG draws are two draws).
    """

    name = "cse_dead_aux"
    contract = Contract.bit_exact()
    description = ("merge duplicate pure computations (the BN-stat "
                   "GL202 pattern) and drop equations no output needs")

    _NO_CSE = ("random_bits", "random_wrap", "random_unwrap",
               "random_seed", "random_fold_in", "threefry2x32",
               "rng_bit_generator")

    def _live_eqns(self, jaxpr) -> Tuple[set, int]:
        """ids of eqns some output (or effect) depends on."""
        needed = {v for v in jaxpr.outvars if isinstance(v, jex_core.Var)}
        live, dead = set(), 0
        for eqn in reversed(jaxpr.eqns):
            if any(isinstance(v, jex_core.Var) and v in needed
                   for v in eqn.outvars) or eqn.effects:
                live.add(id(eqn))
                needed.update(v for v in eqn.invars
                              if isinstance(v, jex_core.Var))
            else:
                dead += 1
        return live, dead

    def run(self, closed_jaxpr, ctx: PassContext) -> Optional[PassResult]:
        jaxpr = closed_jaxpr.jaxpr
        live, n_dead = self._live_eqns(jaxpr)
        dup = [0]
        seen: Dict[tuple, list] = {}

        def key_of(eqn, invals):
            try:
                return (eqn.primitive.name, str(eqn.params),
                        tuple(id(v) for v in invals))
            except Exception:  # unprintable params: skip CSE for it
                return None

        def rule(eqn, invals):
            prim = eqn.primitive.name
            if prim in self._NO_CSE or eqn.effects \
                    or any(isinstance(sub, (jex_core.Jaxpr, jex_core.ClosedJaxpr))
                           for v in eqn.params.values()
                           for sub in (v if isinstance(v, (tuple, list))
                                       else (v,))):
                return None  # control flow / RNG / effects: never merge
            k = key_of(eqn, invals)
            if k is None:
                return None
            prior = seen.get(k)
            if prior is not None:
                dup[0] += 1
                return prior
            outs = _default_bind(eqn, invals)
            seen[k] = outs
            return outs

        skip = {id(e) for e in jaxpr.eqns if id(e) not in live}
        if not skip and not jaxpr.eqns:
            return None
        new_closed = retrace(closed_jaxpr, rule, skip=skip)
        hits = n_dead + dup[0]
        if not hits:
            return None
        return PassResult(new_closed, hits=hits,
                          notes="%d duplicate eqn(s) merged, %d dead "
                                "eqn(s) dropped" % (dup[0], n_dead))


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

PASS_REGISTRY: Dict[str, Callable[[], GraftPass]] = {
    "quantize_int8": lambda: QuantizeWeightsPass(bits=8),
    "quantize_int4": lambda: QuantizeWeightsPass(bits=4),
    "amp_bf16": AmpBf16Pass,
    "space_to_depth": SpaceToDepthPass,
    "cse_dead_aux": CseDeadAuxPass,
}


def register_pass(name: str, factory) -> None:
    """Add a pass to the registry (``factory``: zero-arg callable or a
    GraftPass instance).  Registered passes become ``passes=`` names,
    autotune knobs and CLI targets."""
    if not callable(factory):
        inst = factory
        factory = lambda: inst  # noqa: E731
    PASS_REGISTRY[str(name)] = factory


def get_pass(name: str) -> GraftPass:
    factory = PASS_REGISTRY.get(str(name))
    if factory is None:
        raise ValueError("unknown graftpass %r (registry: %s)"
                         % (name, sorted(PASS_REGISTRY)))
    p = factory()
    return p


def resolve_passes(value=None) -> Tuple[GraftPass, ...]:
    """The shared ``passes=`` resolution: explicit value > the
    ``MXTPU_PASSES`` env (config.py, comma-separated names) > ().
    Accepts a comma string, an iterable of names and/or GraftPass
    instances, or None."""
    if value is None:
        from .. import config as _cfg

        value = str(_cfg.get("MXTPU_PASSES", "") or "")
    if isinstance(value, str):
        value = [s.strip() for s in value.split(",") if s.strip()]
    elif isinstance(value, GraftPass):
        value = [value]
    out: List[GraftPass] = []
    for v in value:
        out.append(get_pass(v) if isinstance(v, str) else v)
    for p in out:
        if not isinstance(p, GraftPass):
            raise ValueError("passes entries must be registry names or "
                             "GraftPass instances, got %r" % (p,))
    return tuple(out)


def resolve_schedule(value=None):
    """The shared ``passes=`` resolution, schedule-aware: returns
    ``(passes_tuple, schedule_or_None)``.  A :class:`PassSchedule` (or
    its canonical dict form, recognized by the ``"passes"`` key) pins
    both the pass order and the per-site decision vectors; anything
    else goes through :func:`resolve_passes` with schedule ``None`` —
    the legacy whole-pass path, equivalent to every site on."""
    if isinstance(value, PassSchedule):
        sched = value
    elif isinstance(value, dict) and "passes" in value:
        sched = PassSchedule.from_dict(value)
    else:
        return resolve_passes(value), None
    return tuple(get_pass(n) for n in sched.pass_names()), sched


# ---------------------------------------------------------------------------
# the manager
# ---------------------------------------------------------------------------

class PassManager:
    """Runs an ordered pass pipeline over one traced program, verifying
    every rewrite before installing it (module docstring has the four
    gates).  GL301/GL302 refusals raise :class:`~.diagnostics.LintError`
    eagerly — like the GL011 swap gate, a pass that breaks its own
    declaration cannot be silently skipped when the caller explicitly
    asked for it; GL303 (pointless rewrite) warns and keeps the
    original.  ``raise_on_error=False`` collects instead (the CLI's
    report-everything mode)."""

    def __init__(self, passes, *, schedule=None, device: str = "tpu-v5e",
                 n_devices: int = 1, raise_on_error: bool = True):
        if schedule is not None and not isinstance(schedule, PassSchedule):
            schedule = PassSchedule.from_dict(schedule)
        if passes is None and schedule is not None:
            self.passes = tuple(get_pass(n)
                                for n in schedule.pass_names())
        else:
            self.passes = resolve_passes(passes)
        self.schedule = schedule
        self.device = device
        self.n_devices = max(int(n_devices), 1)
        self.raise_on_error = bool(raise_on_error)

    # -- helpers -------------------------------------------------------
    def _cost(self, closed, ctx: PassContext):
        from .cost_model import analyze_jaxpr

        return analyze_jaxpr(closed, axis_sizes=ctx.axis_sizes,
                             donated_leaves=ctx.donated_leaves,
                             device=self.device, n_devices=self.n_devices)

    @staticmethod
    def _lint_counts(closed, ctx: PassContext) -> Dict[str, int]:
        from collections import Counter

        from .trace_lint import lint_jaxpr

        rep = lint_jaxpr(closed, axis_sizes=ctx.axis_sizes,
                         donated_leaves=ctx.donated_leaves)
        return dict(Counter(d.code for d in rep.diagnostics
                            if d.severity >= Severity.WARNING))

    @staticmethod
    def _remap_indices(indices, splits: Dict[int, int],
                       n_invars: int) -> Tuple[int, ...]:
        """Flat invar indices after an invar-splitting rewrite (a split
        index expands to all of its replacement slots)."""
        if not splits:
            return tuple(indices)
        start, off = {}, 0
        for i in range(n_invars):
            start[i] = off
            off += splits.get(i, 1)
        out: List[int] = []
        for i in indices:
            if i in start:
                out.extend(range(start[i], start[i] + splits.get(i, 1)))
        return tuple(out)

    @staticmethod
    def _remap_ranges(ranges, splits: Dict[int, int],
                      n_invars: int) -> Optional[Dict[int, Any]]:
        """``input_ranges`` keys after an invar-splitting rewrite: a
        split invar's seed is dropped (its replacement (codes, amax)
        pair has a different value semantics), the rest shift."""
        if not ranges:
            return ranges
        if not splits:
            return dict(ranges)
        start, off = {}, 0
        for i in range(n_invars):
            start[i] = off
            off += splits.get(i, 1)
        return {start[i]: r for i, r in ranges.items()
                if i in start and i not in splits}

    @staticmethod
    def _param_bytes(closed, param_invars) -> float:
        total = 0.0
        for i in param_invars:
            if i < len(closed.jaxpr.invars):
                a = closed.jaxpr.invars[i].aval
                try:
                    total += float(np.prod(a.shape, dtype=np.int64)
                                   * np.dtype(a.dtype).itemsize)
                except TypeError:
                    pass
        return total

    def _probe(self, p: GraftPass, cur, res: PassResult,
               ctx: PassContext) -> Tuple[bool, Dict[str, Any]]:
        avals = [v.aval for v in cur.jaxpr.invars]
        dyadic = p.contract.kind == "bit_exact"
        vals = synth_probe(avals, seed=ctx.probe_seed, dyadic=dyadic,
                           overrides=ctx.probe_overrides)
        ref = eval_closed(cur, vals)
        new_vals = vals
        if res.transform_one is not None:
            new_vals = []
            for i, v in enumerate(vals):
                new_vals.extend(res.transform_one(i, v)
                                if i in res.invar_splits else [v])
        got = eval_closed(res.closed_jaxpr, new_vals)
        return p.contract.check(jax.device_get(ref), jax.device_get(got))

    def _refuse(self, receipt: PassReceipt, diag: Diagnostic,
                diags: List[Diagnostic]):
        receipt.diagnostics.append(diag)
        diags.append(diag)
        if diag.severity >= Severity.ERROR and self.raise_on_error:
            raise LintError(LintReport([diag]))
        import warnings

        warnings.warn("graftpass: %s" % diag.format(), stacklevel=4)

    @staticmethod
    def _site_rows(sites, site_vec, excluded, receipt,
                   installed: bool):
        """Per-site receipt rows (``PassReceipt.sites``).  The whole-
        pass gate-3 delta is distributed over the sites the rewrite
        actually touched, proportionally to each site's local unfused
        weight (``cost_model.eqn_site_weight``) — so the rows sum to
        the receipt's before/after delta exactly, by construction."""
        if not sites:
            return None
        excluded = excluded or {}
        on = [s for s in sites
              if (site_vec is None or s.id in site_vec)
              and s.id not in excluded]

        def shares(weights):
            tot = float(sum(weights))
            if tot > 0:
                return [w / tot for w in weights]
            n = max(len(weights), 1)
            return [1.0 / n] * len(weights)

        f_share = shares([s.flops for s in on])
        b_share = shares([s.hbm_bytes for s in on])
        pos = {s.id: j for j, s in enumerate(on)}
        d_fl = receipt.flops_after - receipt.flops_before
        d_by = receipt.hbm_bytes_after - receipt.hbm_bytes_before
        d_pb = receipt.param_bytes_after - receipt.param_bytes_before
        rows = []
        for s in sites:
            j = pos.get(s.id)
            inst = bool(installed and j is not None)
            rows.append({
                "site": s.id, "kind": s.kind, "detail": s.detail,
                "decision": bool(site_vec is None or s.id in site_vec),
                "excluded": excluded.get(s.id),
                "installed": inst,
                "flops_delta": d_fl * f_share[j] if inst else 0.0,
                "hbm_bytes_delta": d_by * b_share[j] if inst else 0.0,
                "param_bytes_delta": d_pb * b_share[j] if inst else 0.0,
                "contract": receipt.contract,
                # True: the installed rewrite passed the gate-4 probe;
                # None: probe skipped (probe="off") or site untouched
                "probe_ok": (True if inst and receipt.probe is not None
                             else None),
            })
        return rows

    # -- the pipeline --------------------------------------------------
    def run(self, closed_jaxpr, ctx: Optional[PassContext] = None
            ) -> PipelineResult:
        ctx = ctx or PassContext()
        cur = closed_jaxpr
        result = PipelineResult(closed_jaxpr=cur)
        invar_changed = False
        # the re-lint baseline is only needed once a pass actually
        # rewrites something — a pipeline of no-ops (quantize on a
        # train step, space_to_depth with no target) must not pay a
        # lint walk per run (the engine runs one pipeline per bucket)
        pre_lint: Optional[Dict[str, int]] = None
        pre_cost = self._cost(cur, ctx)
        cur_ctx = ctx
        sched = self.schedule
        for p in self.passes:
            receipt = PassReceipt(name=p.name,
                                  contract=p.contract.describe(),
                                  flops_before=pre_cost.total_flops,
                                  hbm_bytes_before=pre_cost.hbm_bytes,
                                  peak_bytes_before=pre_cost.peak_bytes,
                                  param_bytes_before=self._param_bytes(
                                      cur, cur_ctx.param_invars))
            result.receipts.append(receipt)
            receipt.flops_after = receipt.flops_before
            receipt.hbm_bytes_after = receipt.hbm_bytes_before
            receipt.peak_bytes_after = receipt.peak_bytes_before
            receipt.param_bytes_after = receipt.param_bytes_before
            site_vec = sched.sites_for(p.name) if sched else None
            if sched is not None and not sched.enabled(p.name):
                # every site off is a deliberate decision, not a silent
                # no-op — record it and move on (no GL304)
                receipt.notes = "disabled by schedule"
                continue
            sites = (p.enumerate_sites(cur, cur_ctx)
                     if p.site_aware else [])
            ctx_p = (_dc_replace(cur_ctx, sites=site_vec)
                     if site_vec is not None else cur_ctx)
            res = p.run(cur, ctx_p)
            if res is not None:
                # pass-emitted advisories (amp_bf16's GL403 exclusions)
                # and the precision verdict ride the receipt either way
                receipt.diagnostics.extend(res.diagnostics)
                result.diagnostics.extend(res.diagnostics)
                receipt.precision = res.precision
            receipt.sites = self._site_rows(
                sites, site_vec,
                res.excluded_sites if res is not None else {},
                receipt, installed=False)
            if res is None or res.hits == 0:
                receipt.notes = res.notes if res else "no rewrite target"
                # GL304: the caller named this pass and it changed
                # NOTHING — unless the pass itself explained why (the
                # GL403 range gate), the composition silently reads as
                # "optimized" while being a no-op
                explained = res is not None and bool(res.diagnostics
                                                     or res.excluded_sites)
                if not explained:
                    n_on = len([s for s in sites if site_vec is None
                                or s.id in site_vec])
                    self._refuse(receipt, Diagnostic(
                        "GL304", Severity.WARNING,
                        "pass %r matched zero sites — %s; the "
                        "composition is a silent no-op here"
                        % (p.name,
                           "the schedule enabled %d of %d reported "
                           "site(s)" % (n_on, len(sites)) if sites
                           else "no applicable site in the program"),
                        where=ctx.where,
                        hint="drop the pass from passes=/MXTPU_PASSES "
                             "or fix the schedule's site ids"),
                        result.diagnostics)
                continue
            receipt.changed = True
            receipt.hits = res.hits
            receipt.notes = res.notes
            # refusal paths keep the original program, so "after" stays
            # "before" (set above) until the cost gate measures the
            # real rewrite
            # invar policy: one splitting pass per pipeline, and only
            # where the caller can re-map its stored values
            if res.invar_splits:
                if not ctx.allow_invar_change:
                    raise ValueError(
                        "pass %r changes the program's invar layout but "
                        "this builder pinned it (donation/sharding specs "
                        "key off the argument structure)" % p.name)
                if invar_changed:
                    raise ValueError(
                        "pipeline has two invar-changing passes; compose "
                        "them into one or run two pipelines")
            # gate 1: abstract eval — the interface is inviolable
            old_out = [v.aval for v in cur.jaxpr.outvars]
            new_out = [v.aval for v in res.closed_jaxpr.jaxpr.outvars]
            mismatch = len(old_out) != len(new_out) or any(
                tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype
                for a, b in zip(old_out, new_out))
            if mismatch:
                self._refuse(receipt, Diagnostic(
                    "GL301", Severity.ERROR,
                    "pass %r changed the program's output signature "
                    "(%s -> %s) — a rewrite may change the interior, "
                    "never the interface; refused, original program "
                    "kept, zero compiles spent"
                    % (p.name,
                       [a.str_short() for a in old_out[:4]],
                       [b.str_short() for b in new_out[:4]]),
                    where=ctx.where), result.diagnostics)
                continue
            n_in = len(cur.jaxpr.invars)
            new_ctx = PassContext(
                param_invars=frozenset(self._remap_indices(
                    cur_ctx.param_invars, res.invar_splits, n_in)),
                allow_invar_change=ctx.allow_invar_change,
                donated_leaves=self._remap_indices(
                    cur_ctx.donated_leaves, res.invar_splits, n_in),
                axis_sizes=ctx.axis_sizes, probe=ctx.probe,
                probe_seed=ctx.probe_seed,
                probe_overrides={} if res.invar_splits
                else cur_ctx.probe_overrides,
                numerics=cur_ctx.numerics,
                input_ranges=self._remap_ranges(
                    cur_ctx.input_ranges, res.invar_splits, n_in),
                where=ctx.where)
            # gate 2: re-lint — a pass may not introduce findings
            if pre_lint is None:
                pre_lint = self._lint_counts(cur, cur_ctx)
            post_lint = self._lint_counts(res.closed_jaxpr, new_ctx)
            introduced = sorted(
                code for code, n in post_lint.items()
                if n > pre_lint.get(code, 0))
            if introduced:
                self._refuse(receipt, Diagnostic(
                    "GL302", Severity.ERROR,
                    "pass %r introduced graftlint finding(s) %s the "
                    "input program did not have — a pass may fix "
                    "programs, never break them; refused, original "
                    "program kept" % (p.name, introduced),
                    where=ctx.where), result.diagnostics)
                continue
            # gate 3: graftcost before/after — the receipt's stamp
            post_cost = self._cost(res.closed_jaxpr, new_ctx)
            receipt.flops_after = post_cost.total_flops
            receipt.hbm_bytes_after = post_cost.hbm_bytes
            receipt.peak_bytes_after = post_cost.peak_bytes
            receipt.param_bytes_after = self._param_bytes(
                res.closed_jaxpr, new_ctx.param_invars)
            # gate 4: the concrete probe — GL301 outranks GL303, so a
            # wrong rewrite is named a contract violation even when it
            # also happens to cost more
            if ctx.probe != "off":
                ok, detail = self._probe(p, cur, res, cur_ctx)
                receipt.probe = detail
                if not ok:
                    self._refuse(receipt, Diagnostic(
                        "GL301", Severity.ERROR,
                        "pass %r violates its declared %s contract on "
                        "the seeded concrete probe (%s) — refused, "
                        "original program kept, zero compiles spent"
                        % (p.name, p.contract.describe(),
                           {k: v for k, v in detail.items()
                            if k != "outputs"}),
                        where=ctx.where), result.diagnostics)
                    continue
            if p.contract.kind == "bit_exact" \
                    and post_cost.hbm_bytes > pre_cost.hbm_bytes * 1.001:
                self._refuse(receipt, Diagnostic(
                    "GL303", Severity.WARNING,
                    "pass %r predicts MORE HBM traffic (%.2f -> %.2f MB) "
                    "with no exactness gain to show for it — the rewrite "
                    "is pointless here and is skipped"
                    % (p.name, pre_cost.hbm_bytes / 1e6,
                       post_cost.hbm_bytes / 1e6),
                    where=ctx.where,
                    hint="a bit-exact rewrite must pay for itself in the "
                         "cost receipt; tune the pass's applicability "
                         "filter"), result.diagnostics)
                continue
            # install
            receipt.installed = True
            receipt.sites = self._site_rows(
                sites, site_vec, res.excluded_sites, receipt,
                installed=True)
            cur = res.closed_jaxpr
            pre_lint = post_lint
            pre_cost = post_cost
            cur_ctx = new_ctx
            if res.invar_splits:
                invar_changed = True
                result.invar_splits = dict(res.invar_splits)
                result._transforms.append((dict(res.invar_splits),
                                           res.transform_one))
        result.closed_jaxpr = cur
        return result
