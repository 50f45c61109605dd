"""graftrange: trace-time value-range & precision abstract interpreter.

graftlint checks program *structure* (GL0xx), graftcost prices its
*bytes* (GL2xx), graftpass rewrites it under verified contracts
(GL3xx) — but all three are numerically blind: ``amp_bf16`` demotes
every matmul regardless of operand magnitudes, the dynamic loss scaler
is runtime trial-and-error, and the repo has hand-fixed at least three
silent f64/instability bugs (the adam ``beta**int`` bias-correction
promotion, the ``np.float64`` attention scale) that a dtype/range
analysis would have caught at trace time.  This module is that
analysis: an abstract interpreter over the jaxpr that propagates, per
variable, a value interval, a NaN-possibility flag and the effective
precision, on the same zero-compile ``jit.trace()`` hook the other
analyzers share.  Following Relay's argument that a typed, analyzable
IR is what makes framework-level program analysis tractable
(arXiv:1810.00952), the jaxpr's avals carry the dtypes and the
interpreter adds the missing value semantics.

The abstract domain (:class:`VRange`) per variable:

- ``lo`` / ``hi`` — interval bounds.  ``None`` means *unknown but
  finite*: arithmetic over unknown magnitudes stays unknown (absorbing)
  instead of compounding to spurious infinities through deep matmul
  chains — only the exp family maps "unknown" to a proven overflow
  hazard, because ``exp`` overflows f32 at x ≈ 88.7, an utterly
  plausible logit.  A bound of ``±inf`` means the value can *really*
  be infinite (proven overflow).  Known bounds come from literals and
  consts (concrete values), caller annotations
  (``make_train_step(input_range=)``, the engine's warmup-observed
  sample), dtype facts (uint8 inputs, token-id iinfo ranges, bool) and
  the refinements below — and known bounds legitimately compound
  (an annotated ``[0, 1e20]`` squared proves overflow).
- ``positive`` — strictly greater than zero (``exp`` outputs, softmax
  denominators); refines a ``lo`` of 0/None for domain checks.
- ``nan`` — NaN possible on some input.
- ``dtype`` — the aval dtype (the effective-precision half: a float64
  var in a ≤f32 program is a silent promotion, GL404).

Relational refinements (what plain interval arithmetic cannot see):

- ``x - max(x)`` — a subtraction whose subtrahend chases (through
  ``stop_gradient`` / ``broadcast_in_dim`` / reshape / the
  ``max(-inf, .)`` jnp.max-initial idiom) to a ``reduce_max`` **of the
  same minuend** is bounded above by 0: ``jax.nn.softmax``'s
  max-subtraction lints clean while a manual ``exp(logits)`` without
  it trips GL401.
- ``x * x`` / ``square`` / ``abs`` / ``maximum(., c>=0)`` are
  non-negative: the in-repo BatchNorm's ``maximum(E[x²]-E[x]², 0)``
  clamp lints clean while the *unclamped* cancellation difference —
  whose interval admits small negatives — trips GL402 under a
  downstream ``rsqrt``/``log``.
- ``exp`` is treated as strictly positive (documented approximation:
  an attention row that is *entirely* mask ``-inf`` is the one NaN
  source this misses), so masked-softmax denominators divide clean.

The GL4xx family this computes (docs/ANALYSIS.md):

- **GL401** possible overflow-to-inf (exp of unbounded logits; proven
  out-of-dtype-range arithmetic).
- **GL402** possible invalid-domain op (log/sqrt/rsqrt reachable at a
  negative or zero value — the E[x²]−E[x]² pattern; division by a
  possibly-zero denominator — the unguarded ``amax`` scale).
- **GL403** bf16 under/overflow on a demoted edge (a convert to bf16,
  or an ``amp_bf16`` demotion candidate, whose proven range does not
  fit bfloat16) — the ``amp_bf16`` installation gate
  (:func:`bf16_fit`, ``analysis/passes.py``).
- **GL404** silent f64/weak-type promotion: an f64 value materializing
  from literals/consts in a program whose declared inputs are ≤f32 —
  the recurring hand-fixed bug class, machine-caught.
- **GL405** loss-scale advisory (:func:`loss_scale_diags`): the static
  bound on the smallest representable grad magnitude under the
  configured ``loss_scale`` and compute dtype, naming the suggested
  scale; an oversized static f16 scale that provably overflows every
  scaled grad is an error.

Entry points: :func:`analyze_ranges` over a ClosedJaxpr (inlining
pjit/remat/custom_* per call site like graftcost, widening scan/while
carries to a fixpoint), wired in as ``make_train_step(numerics=,
input_range=)`` / ``ServeEngine(numerics=)`` / ``MXTPU_NUMERICS``
(``step.range_report`` / ``engine.range_report``), the ``amp_bf16``
per-op gate, and the ``--ranges`` table printers in
``tools/graftpass.py`` / ``tools/graftlint.py``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from jax.extend import core as jex_core

from .diagnostics import Diagnostic, Severity

__all__ = ["VRange", "RangeReport", "analyze_ranges", "bf16_fit",
           "loss_scale_diags", "observed_range", "parse_range_arg",
           "BF16_MAX", "BF16_TINY_SUBNORMAL"]


def parse_range_arg(s) -> Tuple[float, float]:
    """Parse a CLI-style ``'lo,hi'`` range string — the ONE grammar
    behind every ``--input-range`` flag (tools/graftpass.py,
    tools/autotune.py).  Raises ``ValueError`` with a usable message
    for the CLIs to surface as a usage error."""
    lo, sep, hi = str(s).partition(",")
    try:
        if not sep:
            raise ValueError
        return (float(lo), float(hi))
    except ValueError:
        raise ValueError("expected 'lo,hi' (e.g. 0,1), got %r" % (s,))


def observed_range(value) -> Optional["VRange"]:
    """Observed extrema of one CONCRETE array as a :class:`VRange`
    seed — the ONE seeding discipline shared by the serving engine
    (frozen weights + warmup sample) and the ``--ranges`` CLIs.  A
    tensor containing non-finite values seeds ``nan=True`` with
    unknown bounds (the analysis stays sound); opaque/empty values
    seed nothing (None)."""
    try:
        arr = np.asarray(value)
    except Exception:  # noqa: BLE001 — device arrays: go through host
        import jax as _jax

        arr = np.asarray(_jax.device_get(value))
    if arr.dtype.kind not in ("f", "i", "u", "b") or arr.size == 0:
        return None
    a64 = arr.astype(np.float64, copy=False)
    if not np.isfinite(a64).all():
        return VRange(None, None, False, True)
    lo, hi = float(a64.min()), float(a64.max())
    return VRange(lo, hi, positive=lo > 0)


#: largest finite bfloat16 (same 8-bit exponent as f32, 7-bit mantissa)
BF16_MAX = 3.3895313892515355e38
#: smallest positive bfloat16 subnormal — f32 magnitudes below it flush
#: to zero when demoted
BF16_TINY_SUBNORMAL = 9.183549615799121e-41
#: exp-family ops whose overflow threshold is computed per output
#: dtype (f32 exp overflows at x ~ 88.7, f16 at ~ 11.09, f64 at ~ 709)
_EXP_FAMILY = ("exp", "exp2", "expm1", "cosh", "sinh")


def _exp_overflow_x(prim: str, dtype) -> float:
    """Input threshold past which ``prim`` overflows ``dtype``."""
    fm = _finite_max(dtype)
    if fm is None:
        fm = float(np.finfo(np.float32).max)
    ln_fm = math.log(fm)
    if prim == "exp2":
        return ln_fm / math.log(2.0)
    if prim in ("cosh", "sinh"):
        return ln_fm + math.log(2.0)  # cosh(x) ~ e^x / 2
    return ln_fm                      # exp / expm1


# ---------------------------------------------------------------------------
# the abstract value
# ---------------------------------------------------------------------------

@dataclass
class VRange:
    """Abstract value of one variable.  ``lo``/``hi`` of ``None`` mean
    *unknown but finite* on that side; ``±inf`` means provably can be
    infinite.  ``positive`` refines ``lo`` (strictly > 0); ``nan``
    means NaN is possible."""
    lo: Optional[float] = None
    hi: Optional[float] = None
    positive: bool = False
    nan: bool = False
    dtype: Any = None

    # -- predicates ----------------------------------------------------
    def max_abs(self) -> Optional[float]:
        """Largest possible magnitude, or None when unknown."""
        if self.lo is None or self.hi is None:
            return None
        return max(abs(self.lo), abs(self.hi))

    def may_be_negative(self) -> bool:
        return not self.positive and (self.lo is None or self.lo < 0)

    def may_be_zero(self) -> bool:
        if self.positive:
            return False  # strictly positive by refinement
        lo = self.lo
        hi = self.hi
        if lo is not None and lo > 0:
            return False
        if hi is not None and hi < 0:
            return False
        # unknown-unknown divisors are NOT flagged (a generic x/y would
        # drown the report); a *known* bound touching zero is
        return lo is not None or hi is not None

    def may_be_inf(self) -> bool:
        return (self.lo == -math.inf) or (self.hi == math.inf)

    def describe(self) -> str:
        def b(v, s):
            return s if v is None else "%.3g" % v

        s = "[%s, %s]" % (b(self.lo, "-?"), b(self.hi, "+?"))
        flags = []
        if self.positive:
            flags.append(">0")
        if self.nan:
            flags.append("nan?")
        return s + ("" if not flags else " " + ",".join(flags))


def _known(x: VRange) -> bool:
    return x.lo is not None and x.hi is not None


def _rng(lo, hi, positive=False, nan=False, dtype=None) -> VRange:
    return VRange(lo, hi, positive, nan, dtype)


def _unknown(dtype=None, nan=False, positive=False) -> VRange:
    return VRange(None, None, positive, nan, dtype)


def _join(a: VRange, b: VRange) -> VRange:
    lo = None if (a.lo is None or b.lo is None) else min(a.lo, b.lo)
    hi = None if (a.hi is None or b.hi is None) else max(a.hi, b.hi)
    return VRange(lo, hi, a.positive and b.positive, a.nan or b.nan,
                  a.dtype or b.dtype)


def _from_concrete(val, dtype=None) -> VRange:
    """VRange of a literal/const with a concrete value."""
    try:
        arr = np.asarray(val)
        if arr.dtype == np.bool_:
            return _rng(0.0, 1.0, dtype=arr.dtype)
        if arr.size == 0:
            return _rng(0.0, 0.0, dtype=arr.dtype)
        if arr.size > (1 << 22):       # don't scan huge consts
            return _unknown(dtype=arr.dtype)
        nan = bool(np.isnan(arr).any()) if arr.dtype.kind == "f" else False
        with np.errstate(invalid="ignore"):
            lo = float(np.nanmin(arr)) if not np.isnan(arr).all() \
                else math.nan
            hi = float(np.nanmax(arr)) if not np.isnan(arr).all() \
                else math.nan
        if math.isnan(lo) or math.isnan(hi):
            return _unknown(dtype=arr.dtype, nan=True)
        return _rng(lo, hi, positive=lo > 0, nan=nan, dtype=arr.dtype)
    except Exception:  # noqa: BLE001 — opaque consts stay unknown
        return _unknown(dtype=dtype)


def _default_for_aval(aval) -> VRange:
    """Conservative seed for an unannotated program input."""
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return _unknown()
    try:
        dt = np.dtype(dt)
    except TypeError:
        return _unknown()  # extended dtypes (PRNG keys) stay opaque
    if dt == np.bool_:
        return _rng(0.0, 1.0, dtype=dt)
    if dt.kind in ("i", "u"):
        info = np.iinfo(dt)
        return _rng(float(info.min), float(info.max),
                    positive=info.min > 0, dtype=dt)
    # floats: unknown magnitude, assumed finite and non-NaN at entry
    return _unknown(dtype=dt)


def _finite_max(dtype) -> Optional[float]:
    """Largest finite value of a float dtype, or None for non-floats.
    ml_dtypes floats (bfloat16, float8) have numpy kind 'V' and
    ``np.finfo`` rejects them ("not inexact") — they go through
    ``ml_dtypes.finfo``; a bare kind-check would silently disable the
    bf16 overflow clamp (the GL403 convert check)."""
    try:
        dt = np.dtype(dtype)
    except TypeError:
        return None
    if dt.kind == "f":
        return float(np.finfo(dt).max)
    try:
        import ml_dtypes

        return float(ml_dtypes.finfo(dt).max)
    except Exception:  # noqa: BLE001 — ints/bools/opaque dtypes
        return None


def bf16_fit(vr: VRange) -> Tuple[bool, str]:
    """Does a value with this range survive demotion to bfloat16?

    Unknown bounds fit (bf16 shares f32's exponent range — only a
    *proven* excursion past it is a hazard); a known magnitude above
    ``BF16_MAX`` overflows to inf, and a known nonzero magnitude
    entirely below the smallest bf16 subnormal flushes to zero.
    Returns ``(ok, reason)``."""
    m = vr.max_abs()
    if m is None:
        return True, ""
    if m > BF16_MAX:
        return False, ("operand range %s exceeds the bf16 finite max "
                       "%.3g — demotion overflows to inf"
                       % (vr.describe(), BF16_MAX))
    if 0.0 < m < BF16_TINY_SUBNORMAL:
        return False, ("operand magnitudes (at most %.3g) sit entirely "
                       "below the smallest bf16 subnormal %.3g — "
                       "demotion flushes the tensor to zero"
                       % (m, BF16_TINY_SUBNORMAL))
    return True, ""


# ---------------------------------------------------------------------------
# interval arithmetic helpers (None = unknown-finite)
# ---------------------------------------------------------------------------

def _n_add(a: Optional[float], b: Optional[float]) -> Optional[float]:
    if a is None or b is None:
        # unknown + anything-finite-or-unknown = unknown; an infinite
        # side dominates even an unknown one
        if a in (math.inf, -math.inf):
            return a
        if b in (math.inf, -math.inf):
            return b
        return None
    s = a + b
    return None if math.isnan(s) else s


def _n_mul_candidates(a: VRange, b: VRange) -> Tuple[Optional[float],
                                                     Optional[float]]:
    if not _known(a) or not _known(b):
        # magnitudes unknown: result unknown-finite (the absorbing rule
        # that keeps deep products from compounding to fake infinities);
        # a genuinely-infinite operand still yields unknown, carried by
        # the caller's may_be_inf handling
        return None, None
    cands = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            with np.errstate(invalid="ignore", over="ignore"):
                v = x * y
            cands.append(0.0 if math.isnan(v) else v)
    return min(cands), max(cands)


def _clamp_overflow(vr: VRange, dtype) -> Tuple[VRange, bool]:
    """Known bounds past the output dtype's finite max become ±inf.
    Returns (possibly-widened range, overflowed?)."""
    fm = _finite_max(dtype)
    if fm is None:
        return vr, False
    over = False
    lo, hi = vr.lo, vr.hi
    if hi is not None and hi > fm:
        hi, over = math.inf, True
    if lo is not None and lo < -fm:
        lo, over = -math.inf, True
    if over:
        return VRange(lo, hi, vr.positive, vr.nan, dtype), True
    return vr, False


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

@dataclass
class RangeReport:
    """One program's range analysis: the per-var table raw material,
    hazard sites and the aggregated GL4xx diagnostics."""
    rows: List[Dict[str, Any]] = field(default_factory=list)
    sites: Dict[str, List[Dict[str, Any]]] = field(default_factory=dict)
    diagnostics: List[Diagnostic] = field(default_factory=list)
    meta: Dict[str, Any] = field(default_factory=dict)
    #: top-level Var -> VRange (the amp gate's lookup map); not
    #: serialized
    var_ranges: Dict[Any, VRange] = field(default_factory=dict)

    def by_code(self, code: str) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.code == code]

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics
                if d.severity >= Severity.ERROR]

    def to_dict(self) -> dict:
        return {"version": 1,
                "rows": list(self.rows),
                "sites": {k: list(v) for k, v in sorted(self.sites.items())},
                "diagnostics": [d.to_dict() for d in self.diagnostics],
                "meta": dict(self.meta)}

    def format(self, max_rows: int = 48,
               include_diagnostics: bool = True) -> str:
        """The per-var range table (tools/graftpass.py --ranges).
        ``include_diagnostics=False`` prints rows only — for callers
        that already rendered the diagnostics through their own
        (filtered) report."""
        lines = ["%-28s %-12s %-14s %-22s %s"
                 % ("var", "kind", "dtype/shape", "range", "flags")]
        for r in self.rows[:max_rows]:
            flags = []
            if r.get("positive"):
                flags.append(">0")
            if r.get("nan"):
                flags.append("nan?")
            if r.get("inf"):
                flags.append("inf?")
            lines.append("%-28s %-12s %-14s %-22s %s"
                         % (str(r.get("name", "?"))[:28], r.get("kind", ""),
                            "%s%s" % (r.get("dtype", "?"),
                                      list(r.get("shape", ()))),
                            r.get("range", "?"), ",".join(flags)))
        if len(self.rows) > max_rows:
            lines.append("... (%d more rows)" % (len(self.rows) - max_rows))
        if include_diagnostics:
            for d in self.diagnostics:
                lines.append(d.format())
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------

#: call-like primitives whose bodies are walked inline (per call site,
#: like graftcost: a pjit boundary has no numeric meaning)
_INLINE = {"pjit", "closed_call", "core_call", "xla_call", "named_call",
           "remat", "remat2", "checkpoint", "custom_jvp_call",
           "custom_vjp_call", "custom_jvp_call_jaxpr",
           "custom_vjp_call_jaxpr", "custom_lin"}

#: ops through which the max-subtraction / provenance chase sees
_TRANSPARENT = {"stop_gradient", "broadcast_in_dim", "reshape", "squeeze",
                "expand_dims", "copy", "convert_element_type",
                "transpose"}

_PASS_THROUGH = {"reshape", "transpose", "broadcast_in_dim", "squeeze",
                 "expand_dims", "rev", "slice", "dynamic_slice",
                 "stop_gradient", "copy", "real", "reduce_precision",
                 "gather", "take", "take_along_axis", "pad",
                 "dynamic_update_slice", "concatenate", "tie_in",
                 "optimization_barrier"}

#: bounded elementwise maps: prim -> (lo, hi, positive)
_BOUNDED = {"tanh": (-1.0, 1.0, False), "sin": (-1.0, 1.0, False),
            "cos": (-1.0, 1.0, False), "erf": (-1.0, 1.0, False),
            "logistic": (0.0, 1.0, True), "erfc": (0.0, 2.0, True)}


class _Site:
    """One hazard site (pre-aggregation)."""
    __slots__ = ("code", "prim", "where", "detail", "severity")

    def __init__(self, code, prim, where, detail,
                 severity=Severity.ERROR):
        self.code, self.prim, self.where = code, prim, where
        self.detail, self.severity = detail, severity


class _Interp:
    def __init__(self, axis_sizes: Optional[Dict[str, int]] = None):
        #: named-axis sizes (caller-seeded; shard_map meshes extend it
        #: for their bodies) — the psum-family transfer's multiplier
        self.axis_sizes: Dict[str, int] = dict(axis_sizes or {})
        self.sites: List[_Site] = []
        #: does any DECLARED program input (top-level invar) carry f64?
        #: — only then is the program legitimately-f64 and GL404 quiet
        self.f64_inputs = False
        #: ids of f64 constvars: closure-captured f64 arrays are GL404
        #: *origins* (like f64 literals), never a license for f64
        self.f64_consts: set = set()

    # -- provenance chase ---------------------------------------------
    @staticmethod
    def _chase(var, producers, depth=12):
        """Follow ``var`` back through value-transparent ops (and
        ``max``/``min`` against an infinite literal — the jnp.max
        ``initial=`` idiom)."""
        while isinstance(var, jex_core.Var) and depth > 0:
            eqn = producers.get(id(var))
            if eqn is None:
                return var, None
            prim = eqn.primitive.name
            if prim in _TRANSPARENT and eqn.invars:
                var = eqn.invars[0]
            elif prim in ("max", "min") and len(eqn.invars) == 2:
                lits = [v for v in eqn.invars
                        if isinstance(v, jex_core.Literal)]
                others = [v for v in eqn.invars
                          if not isinstance(v, jex_core.Literal)]
                if len(lits) == 1 and len(others) == 1 \
                        and np.isinf(np.asarray(lits[0].val)).all():
                    var = others[0]
                else:
                    return var, eqn
            else:
                return var, eqn
            depth -= 1
        return var, None

    def _is_max_of(self, sub_rhs, minuend, producers):
        """True when ``sub_rhs`` chases to ``reduce_max(minuend)`` (or
        ``reduce_max`` of something ``minuend`` itself chases to) —
        the softmax max-subtraction pattern."""
        root, eqn = self._chase(sub_rhs, producers)
        if eqn is None or eqn.primitive.name not in ("reduce_max", "max"):
            return False
        if eqn.primitive.name == "max":
            # max(-inf, reduce_max(x)) already unwrapped by _chase;
            # a residual two-var max is not the pattern
            return False
        operand = eqn.invars[0]
        m_root, _ = self._chase(minuend, producers)
        o_root, _ = self._chase(operand, producers)
        return o_root is m_root or operand is minuend

    # -- one equation --------------------------------------------------
    def eval_eqn(self, eqn, ins: List[VRange], producers,
                 where: str) -> List[VRange]:
        prim = eqn.primitive.name
        out_avals = [getattr(v, "aval", None) for v in eqn.outvars]
        odt = getattr(out_avals[0], "dtype", None) if out_avals else None

        def done(vr: VRange, flag_overflow=True) -> List[VRange]:
            vr.dtype = odt
            if flag_overflow and vr.may_be_inf():
                was_inf = any(x.may_be_inf() for x in ins)
                if not was_inf:
                    self.sites.append(_Site(
                        "GL401", prim, where,
                        "%s of %s can overflow to inf"
                        % (prim, ins[0].describe() if ins else "?")))
            return [vr] + [_unknown(getattr(a, "dtype", None))
                           for a in out_avals[1:]]

        nan = any(x.nan for x in ins)
        if prim in _PASS_THROUGH:
            base = ins[0] if ins else _unknown()
            out = VRange(base.lo, base.hi, base.positive, nan, odt)
            if prim in ("pad", "dynamic_update_slice", "concatenate"):
                # pad's padding VALUE is operand 1 — joining all
                # operands covers it (no blanket [0,0] join: a pad of
                # positives with a positive fill must stay positive)
                out = _join(out, _join_all(ins)) if len(ins) > 1 else out
            return [out] + [_unknown(getattr(a, "dtype", None))
                            for a in out_avals[1:]]

        if prim in ("add", "add_any", "sub", "sub_any"):
            a, b = ins[0], ins[1]
            if prim.startswith("sub"):
                if self._is_max_of(eqn.invars[1], eqn.invars[0], producers):
                    # x - max(x) <= 0 (and well-defined: max >= x
                    # elementwise, so the inf-inf NaN of a fully-masked
                    # row is the documented miss)
                    return done(VRange(None, 0.0, False, False, odt),
                                flag_overflow=False)
                lo = _n_add(a.lo, None if b.hi is None else -b.hi)
                hi = _n_add(a.hi, None if b.lo is None else -b.lo)
                pos = False
            else:
                lo = _n_add(a.lo, b.lo)
                hi = _n_add(a.hi, b.hi)
                pos = (a.positive and not b.may_be_negative()) or \
                      (b.positive and not a.may_be_negative())
            # inf + (-inf) / inf - inf: NaN possible
            if (a.may_be_inf() or b.may_be_inf()):
                nan = True
            vr, _ = _clamp_overflow(VRange(lo, hi, pos, nan, odt), odt)
            return done(vr)

        if prim == "mul":
            a, b = ins[0], ins[1]
            if len(eqn.invars) == 2 and eqn.invars[0] is eqn.invars[1] \
                    and isinstance(eqn.invars[0], jex_core.Var):
                m = a.max_abs()
                vr, _ = _clamp_overflow(
                    VRange(0.0, None if m is None else m * m, False,
                           nan, odt), odt)
                return done(vr)
            lo, hi = _n_mul_candidates(a, b)
            # sign awareness survives unknown magnitudes: a product of
            # non-negatives is non-negative (beta2*var + (1-beta2)*g**2
            # must keep its lo=0 for the adam sqrt to lint clean)
            a_nn = a.positive or (a.lo is not None and a.lo >= 0)
            b_nn = b.positive or (b.lo is not None and b.lo >= 0)
            if lo is None and a_nn and b_nn:
                lo = 0.0 if (a.lo is None or b.lo is None) \
                    else a.lo * b.lo
            pos = a.positive and b.positive
            if (a.may_be_inf() and b.may_be_zero()) or \
                    (b.may_be_inf() and a.may_be_zero()):
                nan = True
            vr, _ = _clamp_overflow(VRange(lo, hi, pos, nan, odt), odt)
            return done(vr)

        if prim in ("div", "rem"):
            a, b = ins[0], ins[1]
            if prim == "div" and b.may_be_zero():
                self.sites.append(_Site(
                    "GL402", prim, where,
                    "division by a possibly-zero denominator %s"
                    % b.describe()))
                nan = True
            if b.lo is not None and b.lo > 0:
                # strictly-positive divisor with a known floor: bounds
                # survive per-side even when the other side is unknown
                # (mean = sum/n must keep the sum's lo=0)
                if a.lo is None:
                    lo = None
                elif a.lo >= 0:
                    lo = 0.0 if b.hi is None else a.lo / b.hi
                else:
                    lo = a.lo / b.lo
                if a.hi is None:
                    hi = None
                elif a.hi >= 0:
                    hi = a.hi / b.lo
                else:
                    hi = 0.0 if b.hi is None else a.hi / b.hi
                vr = VRange(lo, hi, a.positive and b.positive, nan, odt)
            else:
                a_nn = a.positive or (a.lo is not None and a.lo >= 0)
                vr = VRange(0.0 if (a_nn and b.positive) else None,
                            None, a.positive and b.positive, nan, odt)
            return done(vr)

        if prim == "neg":
            a = ins[0]
            return done(VRange(None if a.hi is None else -a.hi,
                               None if a.lo is None else -a.lo,
                               False, nan, odt))

        if prim in ("abs", "sign"):
            a = ins[0]
            if prim == "sign":
                return done(VRange(-1.0, 1.0, a.positive, nan, odt))
            m = a.max_abs()
            lo = 0.0
            if a.positive and a.lo is not None:
                lo = abs(a.lo)
            return done(VRange(lo, m, a.positive, nan, odt))

        if prim in ("max", "min", "clamp"):
            if prim == "clamp":
                lo_b, x, hi_b = ins[0], ins[1], ins[2]
                lo = x.lo if lo_b.lo is None else (
                    lo_b.lo if x.lo is None else max(x.lo, lo_b.lo))
                hi = x.hi if hi_b.hi is None else (
                    hi_b.hi if x.hi is None else min(x.hi, hi_b.hi))
                return done(VRange(lo, hi, x.positive or
                                   (lo_b.positive), nan, odt))
            a, b = ins[0], ins[1]
            if prim == "max":
                lo = a.lo if b.lo is None else (
                    b.lo if a.lo is None else max(a.lo, b.lo))
                # a known non-negative arm clamps from below even when
                # the other arm is unknown (the BN maximum(.., 0) guard)
                if lo is None:
                    for arm in (a, b):
                        if arm.lo is not None and arm.lo >= 0:
                            lo = arm.lo
                hi = None if (a.hi is None or b.hi is None) \
                    else max(a.hi, b.hi)
                pos = a.positive or b.positive or \
                    (lo is not None and lo > 0)
            else:
                hi = a.hi if b.hi is None else (
                    b.hi if a.hi is None else min(a.hi, b.hi))
                lo = None if (a.lo is None or b.lo is None) \
                    else min(a.lo, b.lo)
                pos = a.positive and b.positive
            return done(VRange(lo, hi, pos, nan, odt))

        if prim in _EXP_FAMILY:
            a = ins[0]
            thr = _exp_overflow_x(prim, odt)
            hi_in = a.hi if prim != "cosh" else a.max_abs()
            overflow = hi_in is None or hi_in > thr
            if prim == "sinh" and not overflow:
                overflow = a.lo is None or a.lo < -thr
            if overflow:
                self.sites.append(_Site(
                    "GL401", prim, where,
                    "%s of operand range %s overflows %s past x ~ %.3g "
                    "(inf in the program)"
                    % (prim, a.describe(),
                       str(odt) if odt is not None else "f32", thr)))
            # the specific site above is the one GL401 record for this
            # eqn; flag_overflow=False keeps done() from adding a
            # second, generic copy of it
            lo_out: Optional[float]
            if prim in ("exp", "exp2"):
                base = math.e if prim == "exp" else 2.0
                lo_out = 0.0 if a.lo is None else \
                    _safe_pow(base, a.lo)
                hi_out = math.inf if overflow else _safe_pow(base, hi_in)
                return done(VRange(lo_out, hi_out, True, nan, odt),
                            flag_overflow=False)
            if prim == "expm1":
                lo_out = -1.0 if a.lo is None else math.expm1(min(a.lo,
                                                                  700.0))
                hi_out = math.inf if overflow else math.expm1(hi_in)
                return done(VRange(lo_out, hi_out, False, nan, odt),
                            flag_overflow=False)
            return done(VRange(None, math.inf if overflow else None,
                               prim == "cosh", nan, odt),
                        flag_overflow=False)

        if prim in ("log", "log1p", "log2"):
            a = ins[0]
            shift = 1.0 if prim == "log1p" else 0.0
            bad = (a.lo is None and not a.positive) or \
                  (a.lo is not None and a.lo + shift <= 0
                   and not (a.positive and shift == 0))
            if bad:
                self.sites.append(_Site(
                    "GL402", prim, where,
                    "%s of operand range %s reachable at <= %g (NaN / "
                    "-inf in the program)" % (prim, a.describe(), -shift)))
                nan = True
            return done(VRange(None, None, False, nan, odt),
                        flag_overflow=False)

        if prim in ("sqrt", "rsqrt", "cbrt"):
            a = ins[0]
            if prim != "cbrt":
                neg = a.may_be_negative()
                zero_hazard = prim == "rsqrt" and a.may_be_zero() \
                    and not a.positive
                if neg or zero_hazard:
                    self.sites.append(_Site(
                        "GL402", prim, where,
                        "%s of operand range %s reachable at %s"
                        % (prim, a.describe(),
                           "< 0 (NaN)" if neg else "0 (inf)")))
                    nan = nan or neg
            if prim == "sqrt":
                lo = math.sqrt(a.lo) if (a.lo is not None and a.lo > 0) \
                    else 0.0
                hi = None if a.hi is None or a.hi < 0 \
                    else math.sqrt(max(a.hi, 0.0))
                return done(VRange(lo, hi, a.positive, nan, odt))
            return done(VRange(None, None, prim == "rsqrt" and a.positive,
                               nan, odt), flag_overflow=False)

        if prim == "integer_pow":
            a = ins[0]
            y = int(eqn.params.get("y", 1))
            if y < 0 and a.may_be_zero():
                self.sites.append(_Site(
                    "GL402", prim, where,
                    "x**%d with base range %s reachable at 0"
                    % (y, a.describe())))
                nan = True
            if y >= 0 and y % 2 == 0:
                m = a.max_abs()
                vr = VRange(0.0, None if m is None else _safe_pow(m, y),
                            a.positive, nan, odt)
            elif y >= 0:
                lo = None if a.lo is None else _safe_pow_signed(a.lo, y)
                hi = None if a.hi is None else _safe_pow_signed(a.hi, y)
                vr = VRange(lo, hi, a.positive, nan, odt)
            else:
                vr = VRange(None, None, a.positive, nan, odt)
            vr, _ = _clamp_overflow(vr, odt)
            return done(vr)

        if prim == "pow":
            a, b = ins[0], ins[1]
            if a.may_be_negative():
                # fractional powers of negatives NaN; stay quiet unless
                # the exponent is known non-integer? conservative: nan
                nan = True
            pos = a.positive
            if _known(a) and _known(b) and a.lo >= 0:
                cands = [_safe_pow(x, y) for x in (a.lo, a.hi)
                         for y in (b.lo, b.hi)]
                vr = VRange(min(cands), max(cands), pos, nan, odt)
            else:
                vr = VRange(0.0 if a.positive or (a.lo is not None
                                                  and a.lo >= 0)
                            else None, None, pos, nan, odt)
            vr, over = _clamp_overflow(vr, odt)
            return done(vr)

        if prim in ("reduce_sum", "cumsum"):
            a = ins[0]
            n = _red_count(eqn, prim)
            lo = None if a.lo is None else a.lo * n
            hi = None if a.hi is None else a.hi * n
            vr, _ = _clamp_overflow(
                VRange(lo, hi, a.positive, nan, odt), odt)
            return done(vr)

        if prim in ("reduce_max", "reduce_min", "cummax", "cummin",
                    "sort"):
            a = ins[0]
            return done(VRange(a.lo, a.hi, a.positive, nan, odt))

        if prim in ("reduce_prod", "cumprod"):
            return done(_unknown(odt, nan=nan))

        if prim in ("reduce_and", "reduce_or", "reduce_xor", "argmax",
                    "argmin", "top_k", "eq", "ne", "lt", "le", "gt",
                    "ge", "and", "or", "xor", "not", "is_finite",
                    "population_count", "clz", "iota", "axis_index"):
            if prim == "iota":
                n = max(int(np.prod(getattr(out_avals[0], "shape", (1,))
                                    or (1,))), 1)
                return done(VRange(0.0, float(n - 1), False, False, odt))
            if prim in ("argmax", "argmin", "top_k"):
                return done(_rng(0.0, None, dtype=odt))
            if prim == "axis_index":
                ax = eqn.params.get("axis_name")
                size = self.axis_sizes.get(ax)
                return done(VRange(0.0, None if size is None
                                   else float(size) - 1, False, False,
                                   odt), flag_overflow=False)
            if prim in ("population_count", "clz"):
                bits = np.dtype(odt).itemsize * 8 if odt is not None \
                    else 64
                return done(VRange(0.0, float(bits), False, False, odt),
                            flag_overflow=False)
            if prim in ("and", "or", "xor", "not", "reduce_and",
                        "reduce_or", "reduce_xor") \
                    and not (odt is not None
                             and np.dtype(odt) == np.bool_):
                # integer bitwise ops: a [0,1] "proven" bound would be
                # a lie — fall back to the dtype range
                return done(_default_for_aval(out_avals[0]),
                            flag_overflow=False)
            # boolean logic / comparisons / is_finite
            return done(VRange(0.0, 1.0, False, False, odt),
                        flag_overflow=False)

        if prim in ("dot_general", "conv_general_dilated"):
            a, b = ins[0], ins[1]
            k = _contraction_len(eqn)
            am, bm = a.max_abs(), b.max_abs()
            if am is None or bm is None:
                vr = VRange(None, None, False, nan, odt)
            else:
                m = am * bm * k
                vr = VRange(-m, m, False, nan, odt)
                vr, _ = _clamp_overflow(vr, odt)
            return done(vr)

        if prim == "select_n":
            cases = ins[1:]
            if not cases:
                return done(_unknown(odt, nan=nan))
            out = cases[0]
            for c in cases[1:]:
                out = _join(out, c)
            # the predicate's nan does not poison a select of clean arms
            out = VRange(out.lo, out.hi, out.positive,
                         any(c.nan for c in cases), odt)
            return done(out, flag_overflow=False)

        if prim == "convert_element_type":
            a = ins[0]
            src = getattr(getattr(eqn.invars[0], "aval", None), "dtype",
                          None)
            vr, over = _clamp_overflow(
                VRange(a.lo, a.hi, a.positive, nan, odt), odt)
            if over and _dtype_name(odt) == "bfloat16":
                self.sites.append(_Site(
                    "GL403", prim, where,
                    "convert %s -> bfloat16 of a value with proven "
                    "range %s — past the bf16 finite max %.3g, the "
                    "demoted edge is inf" % (src, a.describe(),
                                             BF16_MAX)))
            m = a.max_abs()
            if m is not None and 0.0 < m < BF16_TINY_SUBNORMAL \
                    and _dtype_name(odt) == "bfloat16":
                self.sites.append(_Site(
                    "GL403", prim, where,
                    "convert %s -> bfloat16 of magnitudes at most %.3g "
                    "— entirely below the smallest bf16 subnormal, the "
                    "demoted edge flushes to zero" % (src, m)))
            return done(vr, flag_overflow=over)

        if prim in ("erf_inv", "atanh"):
            # ±inf only at the exact boundary of the domain (measure
            # zero through jax.random's open intervals): unknown-finite
            return done(_unknown(odt, nan=nan), flag_overflow=False)

        if prim in _BOUNDED:
            lo, hi, pos = _BOUNDED[prim]
            return done(VRange(lo, hi, pos, nan, odt))

        if prim in ("reduce_window_max", "reduce_window_min"):
            a = ins[0]
            return done(VRange(a.lo, a.hi, a.positive, nan, odt))
        if prim == "reduce_window_sum":
            return done(_unknown(odt, nan=nan))

        if prim in ("psum", "psum2", "pmax", "pmin", "all_gather",
                    "reduce_scatter", "psum_scatter", "ppermute",
                    "pshuffle", "all_to_all", "pbroadcast"):
            a = ins[0] if ins else _unknown()
            if prim in ("psum", "psum2", "reduce_scatter",
                        "psum_scatter"):
                # a sum of n per-device terms: bounds scale by the
                # axis size when it is known (a [0,1] value psummed
                # over an 8-way axis is [0,8]); unknown axes absorb
                axes = eqn.params.get("axes",
                                      eqn.params.get("axis_name"))
                axes = axes if isinstance(axes, (tuple, list)) \
                    else (axes,)
                n = 1.0
                for ax in axes:
                    size = self.axis_sizes.get(ax)
                    if size is None:
                        n = None
                        break
                    n *= float(size)
                if n is None:
                    return done(_unknown(odt, nan=nan,
                                         positive=a.positive))
                lo = None if a.lo is None else a.lo * n
                hi = None if a.hi is None else a.hi * n
                vr, _ = _clamp_overflow(
                    VRange(lo, hi, a.positive, nan, odt), odt)
                return done(vr)
            return done(VRange(a.lo, a.hi, a.positive, nan, odt))

        if prim in ("random_bits", "threefry2x32", "rng_bit_generator",
                    "random_wrap", "random_unwrap", "random_split",
                    "random_seed", "random_fold_in"):
            return [_default_for_aval(a) for a in out_avals]

        if prim in ("scatter", "scatter_add", "scatter-add",
                    "select_and_scatter_add", "select_and_gather_add"):
            out = _join_all(ins) if ins else _unknown()
            out = _join(out, _rng(0.0, 0.0))  # scatter init zeros
            out.dtype = odt
            out.nan = nan
            return [out] + [_unknown(getattr(a, "dtype", None))
                            for a in out_avals[1:]]

        if prim == "square":
            a = ins[0]
            m = a.max_abs()
            vr = VRange(0.0, None if m is None else m * m, a.positive,
                        nan, odt)
            vr, _ = _clamp_overflow(vr, odt)
            return done(vr)

        # anything else: unknown-finite, nan-propagating
        return [_unknown(getattr(a, "dtype", None), nan=nan)
                for a in out_avals] or [_unknown(nan=nan)]

    # -- one jaxpr ------------------------------------------------------
    def walk(self, jaxpr, env: Dict[Any, VRange], consts: Sequence[Any],
             where: str = "jaxpr", depth: int = 0,
             collect: bool = True) -> List[VRange]:
        """Forward pass over one (open) jaxpr.  ``env`` must already
        bind ``jaxpr.invars``; constvars are bound from ``consts``
        (concrete values when available)."""
        producers: Dict[int, Any] = {}

        for cv, cval in zip(jaxpr.constvars, consts):
            env[cv] = _from_concrete(cval,
                                     getattr(cv.aval, "dtype", None))
        for cv in jaxpr.constvars:
            if _dtype_is_f64(getattr(cv.aval, "dtype", None)):
                # an f64 CONST is a promotion origin, not a license:
                # its first consumer is the GL404 site
                self.f64_consts.add(id(cv))
        for cv in jaxpr.constvars[len(consts):]:
            env[cv] = _default_for_aval(cv.aval)

        def read(v) -> VRange:
            if isinstance(v, jex_core.Literal):
                return _from_concrete(v.val,
                                      getattr(v.aval, "dtype", None))
            return env.get(v) or _default_for_aval(v.aval)

        sites_enabled = collect
        for n, eqn in enumerate(jaxpr.eqns):
            prim = eqn.primitive.name
            w = "%s[%d] %s" % (where, n, prim)
            ins = [read(v) for v in eqn.invars]
            # GL404: an f64 output materializing with no non-literal
            # f64 operand — the value was promoted by a literal/const
            if sites_enabled:
                self._check_f64(eqn, w)
            if prim in _INLINE and depth < 24:
                outs = self._call(eqn, ins, w, depth, collect)
            elif prim == "scan":
                outs = self._scan(eqn, ins, w, depth, collect)
            elif prim == "while":
                outs = self._while(eqn, ins, w, depth, collect)
            elif prim == "cond":
                outs = self._cond(eqn, ins, w, depth, collect)
            elif prim == "shard_map":
                outs = self._shard_map(eqn, ins, w, depth, collect)
            else:
                n_sites = len(self.sites)
                outs = self.eval_eqn(eqn, ins, producers, w)
                if not sites_enabled:
                    del self.sites[n_sites:]
            for v, o in zip(eqn.outvars, outs):
                if isinstance(v, jex_core.Var):
                    env[v] = o
                    producers[id(v)] = eqn
        return [read(v) for v in jaxpr.outvars]

    def _check_f64(self, eqn, where):
        outs_f64 = [v for v in eqn.outvars
                    if _dtype_is_f64(getattr(getattr(v, "aval", None),
                                             "dtype", None))]
        if not outs_f64 or self.f64_inputs:
            return
        has_var_f64 = any(
            isinstance(v, jex_core.Var) and id(v) not in self.f64_consts
            and _dtype_is_f64(getattr(v.aval, "dtype", None))
            for v in eqn.invars)
        if has_var_f64:
            # fed by an already-f64 value (itself flagged at its own
            # origin): one site per promotion chain, not per consumer
            return
        lit_f64 = [v for v in eqn.invars
                   if isinstance(v, jex_core.Literal)
                   and _dtype_is_f64(getattr(v.aval, "dtype", None))]
        const_f64 = any(isinstance(v, jex_core.Var)
                        and id(v) in self.f64_consts
                        for v in eqn.invars)
        if lit_f64:
            via = ("an f64 literal operand (%s)"
                   % np.asarray(lit_f64[0].val).ravel()[:1])
        elif const_f64:
            via = "a closure-captured f64 const operand"
        else:
            via = "weak-type promotion of its operands"
        self.sites.append(_Site(
            "GL404", eqn.primitive.name, where,
            "%s produces float64 via %s although no program input is "
            "f64 — a silent promotion under the package-wide x64 flag "
            "(the beta**int / np.float64-scale bug class)"
            % (eqn.primitive.name, via)))

    # -- control flow ---------------------------------------------------
    def _bodies(self, params):
        for v in params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                if isinstance(u, jex_core.ClosedJaxpr):
                    yield u
                elif isinstance(u, jex_core.Jaxpr):
                    yield jex_core.ClosedJaxpr(u, ())

    def _call(self, eqn, ins, where, depth, collect):
        for body in self._bodies(eqn.params):
            j = body.jaxpr
            if len(j.invars) != len(ins):
                continue
            env = dict(zip(j.invars, ins))
            outs = self.walk(j, env, body.consts, where, depth + 1,
                             collect)
            if len(outs) == len(eqn.outvars):
                return outs
        return [_unknown(getattr(getattr(v, "aval", None), "dtype", None))
                for v in eqn.outvars]

    def _scan(self, eqn, ins, where, depth, collect):
        p = eqn.params
        body = p["jaxpr"]
        j = body.jaxpr
        n_consts = int(p.get("num_consts", 0))
        n_carry = int(p.get("num_carry", 0))
        consts_in = ins[:n_consts]
        carry = list(ins[n_consts:n_consts + n_carry])
        xs = ins[n_consts + n_carry:]
        # xs enter the body one slice at a time: same range.  Settle
        # the carry SILENTLY first (join per iteration; anything still
        # growing after 3 passes widens to unknown-finite), then run
        # ONE diagnostic walk with the settled carry — hazards driven
        # by a growing carry (exp of a doubling value) are seen at the
        # widened bounds, and the ys ranges come from that same sound
        # walk, never from an unconverged intermediate iterate.
        for it in range(3):
            env = dict(zip(j.invars, consts_in + carry + xs))
            outs = self.walk(j, env, body.consts, where, depth + 1,
                             collect=False)
            new_carry = [_join(c, o) for c, o in zip(carry, outs[:n_carry])]
            if all(_same_range(c, nc)
                   for c, nc in zip(carry, new_carry)):
                carry = new_carry
                break
            if it == 2:
                carry = [
                    VRange(None, None, c.positive and nc.positive,
                           c.nan or nc.nan, nc.dtype)
                    if not _same_range(c, nc) else nc
                    for c, nc in zip(carry, new_carry)]
            else:
                carry = new_carry
        env = dict(zip(j.invars, consts_in + carry + xs))
        outs = self.walk(j, env, body.consts, where, depth + 1, collect)
        carry = [_join(c, o) for c, o in zip(carry, outs[:n_carry])]
        return carry + outs[n_carry:]

    def _while(self, eqn, ins, where, depth, collect):
        p = eqn.params
        body = p.get("body_jaxpr")
        n_c = int(p.get("body_nconsts", 0))
        cn = int(p.get("cond_nconsts", 0))
        carry = [VRange(None, None, False, c.nan, c.dtype)
                 for c in ins[cn + n_c:]]
        if body is not None:
            j = body.jaxpr
            env = dict(zip(j.invars, ins[cn:cn + n_c] + carry))
            outs = self.walk(j, env, body.consts, where, depth + 1,
                             collect)
            return [_join(c, o) for c, o in zip(carry, outs)]
        return carry

    def _cond(self, eqn, ins, where, depth, collect):
        branches = eqn.params.get("branches", ())
        opnds = ins[1:]
        joined: Optional[List[VRange]] = None
        for br in branches:
            closed = br if isinstance(br, jex_core.ClosedJaxpr) \
                else jex_core.ClosedJaxpr(br, ())
            j = closed.jaxpr
            if len(j.invars) != len(opnds):
                continue
            env = dict(zip(j.invars, opnds))
            outs = self.walk(j, env, closed.consts, where, depth + 1,
                             collect)
            joined = outs if joined is None else \
                [_join(a, b) for a, b in zip(joined, outs)]
        return joined or [_unknown(getattr(getattr(v, "aval", None),
                                           "dtype", None))
                          for v in eqn.outvars]

    def _shard_map(self, eqn, ins, where, depth, collect):
        body = eqn.params.get("jaxpr")
        if body is None:
            return [_unknown() for _ in eqn.outvars]
        closed = body if isinstance(body, jex_core.ClosedJaxpr) \
            else jex_core.ClosedJaxpr(body, ())
        j = closed.jaxpr
        if len(j.invars) != len(ins):
            return [_unknown() for _ in eqn.outvars]
        env = dict(zip(j.invars, ins))
        mesh = eqn.params.get("mesh")
        saved = self.axis_sizes
        if mesh is not None:
            self.axis_sizes = dict(saved)
            self.axis_sizes.update({str(k): int(v)
                                    for k, v in dict(mesh.shape).items()})
        try:
            return self.walk(j, env, closed.consts, where, depth + 1,
                             collect)
        finally:
            self.axis_sizes = saved


def _join_all(ins: Sequence[VRange]) -> VRange:
    out = ins[0]
    for x in ins[1:]:
        out = _join(out, x)
    return out


def _same_range(a: VRange, b: VRange) -> bool:
    return a.lo == b.lo and a.hi == b.hi and a.positive == b.positive \
        and a.nan == b.nan


def _safe_pow(base: float, y: float) -> float:
    try:
        with np.errstate(over="ignore"):
            v = math.pow(base, y)
    except OverflowError:
        return math.inf
    except (ValueError, ZeroDivisionError):
        return math.inf  # 0**-n / domain corner: treat as unbounded
    return v


def _safe_pow_signed(x: float, y: int) -> float:
    s = -1.0 if (x < 0 and y % 2 == 1) else 1.0
    return s * _safe_pow(abs(x), y)


def _dtype_name(dt) -> str:
    try:
        return np.dtype(dt).name
    except TypeError:
        return str(dt)


def _dtype_is_f64(dt) -> bool:
    try:
        return np.dtype(dt) == np.float64
    except TypeError:
        return False


def _red_count(eqn, prim) -> float:
    if prim == "cumsum":
        axis = eqn.params.get("axis", 0)
        shape = getattr(eqn.invars[0].aval, "shape", ())
        return float(shape[axis]) if shape else 1.0
    axes = eqn.params.get("axes", ())
    shape = getattr(eqn.invars[0].aval, "shape", ())
    n = 1.0
    for a in axes:
        if a < len(shape) and isinstance(shape[a], (int, np.integer)):
            n *= float(shape[a])
    return max(n, 1.0)


def _contraction_len(eqn) -> float:
    prim = eqn.primitive.name
    if prim == "dot_general":
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        shape = getattr(eqn.invars[0].aval, "shape", ())
        k = 1.0
        for d in lhs_c:
            if d < len(shape):
                k *= float(shape[d])
        return max(k, 1.0)
    dn = eqn.params["dimension_numbers"]
    rhs = getattr(eqn.invars[1].aval, "shape", ())
    k = float(rhs[dn.rhs_spec[1]]) if rhs else 1.0
    for d in dn.rhs_spec[2:]:
        k *= float(rhs[d])
    return max(k, 1.0)


# ---------------------------------------------------------------------------
# diagnostics assembly
# ---------------------------------------------------------------------------

def _aggregate(sites: List[_Site]) -> List[Diagnostic]:
    """One diagnostic per code, naming the count and the first sites —
    a deep net can hit one hazard hundreds of times and the report must
    stay readable (the GL202 aggregation discipline)."""
    hints = {
        "GL401": "subtract the row-wise max before exp (jax.nn.softmax/"
                 "log_softmax already do), clamp the operand, or declare "
                 "the real input range via make_train_step(input_range=) "
                 "so the analysis can prove the bound",
        "GL402": "clamp the operand non-negative before the root "
                 "(jnp.maximum(v, 0.0) + eps — the in-repo BatchNorm "
                 "form) or guard the denominator away from zero "
                 "(jnp.maximum(amax, tiny), ops/quantization.py)",
        "GL403": "exclude the op from bf16 demotion (the amp_bf16 pass "
                 "does this automatically under numerics='warn'), or "
                 "rescale/clamp the edge into bf16 range",
        "GL404": "compute the scalar in f32 (np.float32(...) / "
                 "jnp.float32) — the adam bias-correction and decoder "
                 "attention-scale fixes — or drop the x64 flag "
                 "dependence; weak Python floats promote through "
                 "integer operands",
        "GL405": "set loss_scale to the suggested value (or 'dynamic'); "
                 "bf16/f32 share f32's exponent range, so scaling only "
                 "pays for f16 gradients",
    }
    by_code: Dict[str, List[_Site]] = {}
    for s in sites:
        by_code.setdefault(s.code, []).append(s)
    out: List[Diagnostic] = []
    for code in sorted(by_code):
        group = by_code[code]
        sev = max(s.severity for s in group)
        shown = "; ".join("%s (%s)" % (s.detail, s.where)
                          for s in group[:3])
        more = "" if len(group) <= 3 else " (+%d more sites)" \
            % (len(group) - 3)
        out.append(Diagnostic(
            code, sev,
            "%d site(s): %s%s" % (len(group), shown, more),
            where="graftrange value-range walk",
            hint=hints.get(code, "")))
    return out


def loss_scale_diags(compute_dtype, loss_scale, dynamic: bool,
                     where: str = "") -> List[Diagnostic]:
    """GL405: static loss-scale advisory from the configured scale and
    compute dtype — the numerics of ``contrib/amp/loss_scaler.py`` as
    a trace-time bound instead of runtime trial and error.

    ``loss_scale`` is the static scale (float) or None; ``dynamic``
    marks a DynamicLossScale config (self-tuning: no advisory).  The
    smallest unscaled-grad magnitude representable after scaling is
    ``tiny(dtype)/S``; the overflow ceiling is ``max(dtype)/S``."""
    diags: List[Diagnostic] = []
    dt = np.dtype(compute_dtype) if compute_dtype is not None \
        else np.dtype(np.float32)
    is_f16 = dt == np.float16
    if dynamic:
        return diags
    s = float(loss_scale) if loss_scale else None
    if is_f16:
        f16 = np.finfo(np.float16)
        if s is None:
            diags.append(Diagnostic(
                "GL405", Severity.WARNING,
                "compute dtype float16 with no loss scale: gradient "
                "magnitudes below %.3g flush to zero in the backward "
                "pass — suggested loss_scale: 2**14 (or 'dynamic')"
                % float(f16.tiny), where=where,
                hint="make_train_step(loss_scale=2**14) or "
                     "loss_scale='dynamic'"))
        elif float(f16.max) / s < 1.0:
            diags.append(Diagnostic(
                "GL405", Severity.ERROR,
                "static loss_scale %.3g with compute dtype float16: "
                "the scaled-grad overflow ceiling f16max/S = %.3g sits "
                "below 1.0, so any gradient of ordinary magnitude "
                "overflows and EVERY step is skipped — suggested "
                "loss_scale: 2**14" % (s, float(f16.max) / s),
                where=where,
                hint="make_train_step(loss_scale=2**14) or "
                     "loss_scale='dynamic'"))
        return diags
    if s is not None and s != 1.0:
        diags.append(Diagnostic(
            "GL405", Severity.WARNING,
            "static loss_scale %.3g with compute dtype %s: bf16/f32 "
            "share float32's exponent range, so scaling buys no "
            "representable-gradient headroom here (the smallest "
            "representable grad magnitude is already ~1e-38) — "
            "suggested scale: 1 (drop loss_scale), or reserve scaling "
            "for float16" % (s, dt.name), where=where,
            hint="drop loss_scale, or keep 'dynamic' only as an "
                 "overflow tripwire"))
    return diags


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def analyze_ranges(closed_jaxpr, *,
                   input_ranges: Optional[Dict[int, Any]] = None,
                   invar_labels: Optional[Dict[int, str]] = None,
                   axis_sizes: Optional[Dict[str, int]] = None,
                   collect: bool = True,
                   meta: Optional[Dict[str, Any]] = None) -> RangeReport:
    """Abstractly interpret value ranges over one traced program (no
    compile, no execution — the walk runs on the ``jit.trace()`` jaxpr
    the first call reuses).

    ``input_ranges`` maps flat invar indices to ``(lo, hi)`` /
    ``(lo, hi, positive)`` tuples or :class:`VRange` seeds — declared
    annotations (``make_train_step(input_range=)``), observed warmup
    samples, optimizer-state facts.  Unannotated floats default to
    *unknown finite*; integers/bools to their dtype ranges.
    ``invar_labels`` names invars in the report table.  ``axis_sizes``
    seeds named-axis sizes for collectives outside any ``shard_map``
    (inside one, sizes come from its mesh) — the psum-family bound
    multiplier.  ``collect=False`` skips hazard-site collection (the
    amp gate's cheap mode: only ``var_ranges`` is needed).
    """
    jaxpr = closed_jaxpr.jaxpr if isinstance(closed_jaxpr,
                                             jex_core.ClosedJaxpr) \
        else closed_jaxpr
    consts = getattr(closed_jaxpr, "consts", ())
    interp = _Interp(axis_sizes=axis_sizes)
    env: Dict[Any, VRange] = {}
    input_ranges = input_ranges or {}
    labels = invar_labels or {}
    for i, v in enumerate(jaxpr.invars):
        seed = input_ranges.get(i)
        if seed is None:
            vr = _default_for_aval(v.aval)
        elif isinstance(seed, VRange):
            vr = VRange(seed.lo, seed.hi, seed.positive, seed.nan,
                        getattr(v.aval, "dtype", None))
        else:
            t = tuple(seed)
            lo = None if t[0] is None else float(t[0])
            hi = None if (len(t) < 2 or t[1] is None) else float(t[1])
            pos = bool(t[2]) if len(t) > 2 else (lo is not None and lo > 0)
            vr = VRange(lo, hi, pos, False,
                        getattr(v.aval, "dtype", None))
        env[v] = vr
        if _dtype_is_f64(getattr(v.aval, "dtype", None)):
            interp.f64_inputs = True
    outs = interp.walk(jaxpr, env, consts, collect=collect)

    report = RangeReport(meta=dict(meta or {}))
    report.var_ranges = {v: env[v] for v in env
                         if isinstance(v, jex_core.Var)}
    if collect:
        for i, v in enumerate(jaxpr.invars):
            vr = env[v]
            report.rows.append({
                "name": labels.get(i, "in[%d]" % i), "kind": "input",
                "dtype": str(getattr(v.aval, "dtype", "?")),
                "shape": tuple(getattr(v.aval, "shape", ())),
                "range": vr.describe(), "lo": vr.lo, "hi": vr.hi,
                "positive": vr.positive, "nan": vr.nan,
                "inf": vr.may_be_inf()})
        for i, (v, vr) in enumerate(zip(jaxpr.outvars, outs)):
            report.rows.append({
                "name": "out[%d]" % i, "kind": "output",
                "dtype": str(getattr(getattr(v, "aval", None), "dtype",
                                     "?")),
                "shape": tuple(getattr(getattr(v, "aval", None), "shape",
                                       ())),
                "range": vr.describe(), "lo": vr.lo, "hi": vr.hi,
                "positive": vr.positive, "nan": vr.nan,
                "inf": vr.may_be_inf()})
        for s in interp.sites:
            report.sites.setdefault(s.code, []).append(
                {"prim": s.prim, "where": s.where, "detail": s.detail})
        report.diagnostics = _aggregate(interp.sites)
    return report
