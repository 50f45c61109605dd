"""graftcost: trace-time HBM/FLOPs/comm cost model for traced programs.

The roofline argument in ``docs/PERF.md`` — ResNet-50's fused step moves
~280 MB/img, so the 3,000 img/s north star is byte-bound, not
FLOP-bound — lived only as prose.  This module computes it, per program,
at ``jit.trace()`` time: a jaxpr walker (the same traversal family as
``trace_lint.py``) that predicts, per equation and rolled up per
category, FLOPs, HBM bytes read/written under a **fusion-aware** model,
**peak live-buffer memory** honoring donation/remat/state shardings, and
per-mesh-axis **communication volume** — then checks the predictions as
``GL2xx`` diagnostics through the same :class:`~.diagnostics.Diagnostic`
machinery graftlint owns.  No compile, no execution: the analysis walks
the abstract trace the first call reuses anyway.

The fusion model (matches the measured XLA behavior in PERF.md — 5
passes/layer fwd, ~6 bwd for conv+BN):

- conv / dot_general (MXU ops) are standalone passes: they read their
  (materialized) inputs from HBM and write their output.
- elementwise / layout ops fuse: a chain of them is ONE pass.  An
  elementwise value consumed by several fusion groups is *recomputed*
  into each (XLA duplicates cheap producers rather than materializing),
  so each consuming group re-reads the chain's materialized leaves —
  exactly the "read X for stats, read X again for normalize" BN cost.
- reductions fuse their elementwise producers (convert_reduce_fusion)
  but still re-read each materialized leaf: a reduction over a conv
  output is one extra full pass over it.
- scatter/gather, collectives, concatenation, RNG and control-flow
  boundaries materialize their outputs.

Peak memory is a linear liveness scan over materialized buffers:
non-donated top-level inputs are held for the whole program, donated
inputs die at their last read (and greedily alias a shape/dtype-matching
output, as XLA's donation does — the aliased output costs nothing);
``lax.scan`` charges its stacked per-iteration outputs ``length`` times
(the pipeline's activation stash); ``remat`` regions are walked as
traced, so their recompute FLOPs/bytes — and the stash they avoid — fall
out of the program itself.  Per-invar ``shard_factors`` divide the
resident bytes of sharded state (ZeRO-1 ``P('dp')`` optimizer leaves
cost 1/N per device — the exact figures ``tests/test_zero_sharding.py``
measures).

Entry points:

- :func:`analyze_jaxpr` — cost a ClosedJaxpr you already traced.
- :func:`analyze_traceable` — ``jax.make_jaxpr`` + analyze.
- :func:`check_cost` — GL201/GL202/GL203 over a :class:`CostReport`.
- ``make_train_step(cost="report"|"check", hbm_budget=...)`` /
  ``MXTPU_COST`` — the fused-step hook (``parallel/train_step.py``).
- ``tools/graftcost.py`` — the CLI (model + mesh + knobs, no step run).
"""
from __future__ import annotations

import json
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import core as jex_core

from .diagnostics import Diagnostic, Severity

__all__ = ["DeviceSpec", "DEVICE_SPECS", "CategoryCost", "CommCost",
           "CostReport", "analyze_jaxpr", "analyze_traceable",
           "check_cost", "shard_factor"]


# ---------------------------------------------------------------------------
# device-spec registry (roofline denominators)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DeviceSpec:
    """Peak rates for the roofline estimate.  ``flops_per_s`` is the
    dense-matmul peak at the step's compute dtype (bf16 on TPU);
    ``ici_bytes_per_s`` is per-chip interconnect bandwidth."""
    name: str
    flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: int
    ici_bytes_per_s: float


#: TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GiB, 1600 Gb/s ICI
#: (docs/PERF.md header).  cpu-proxy: a deliberately round, modest spec
#: for RELATIVE comparisons when no chip is reachable (ROADMAP item 4's
#: degraded mode) — absolute times from it are meaningless.
DEVICE_SPECS: Dict[str, DeviceSpec] = {
    "tpu-v5e": DeviceSpec("tpu-v5e", 197e12, 819e9, 16 * 2**30, 200e9),
    "cpu-proxy": DeviceSpec("cpu-proxy", 1e12, 50e9, 64 * 2**30, 5e9),
}


# ---------------------------------------------------------------------------
# primitive classification
# ---------------------------------------------------------------------------

_MXU = {"conv_general_dilated", "dot_general"}

_ELEMENTWISE = {
    "add", "add_any", "sub", "mul", "div", "rem", "pow", "integer_pow",
    "neg", "abs", "sign", "max", "min", "exp", "exp2", "expm1", "log",
    "log1p", "log2", "sqrt", "rsqrt", "cbrt", "square", "reciprocal",
    "tanh", "sin", "cos", "tan", "asin", "acos", "atan", "atan2", "sinh",
    "cosh", "asinh", "acosh", "atanh", "logistic", "erf", "erfc",
    "erf_inv", "floor", "ceil", "round", "clamp", "nextafter",
    "select_n", "eq", "ne", "lt", "le", "gt", "ge", "and", "or", "xor",
    "not", "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "convert_element_type", "bitcast_convert_type", "reduce_precision",
    "stop_gradient", "is_finite", "population_count", "clz", "real",
    "imag", "complex", "conj", "copy", "iota", "sub_any",
}

#: pure data movement — fuse, zero FLOPs; ``slice``/``pad`` read/write
#: only their own extent but we charge the materialized leaf in full
#: (rare on the hot paths; documented approximation)
_LAYOUT = {"reshape", "transpose", "broadcast_in_dim", "squeeze",
           "expand_dims", "rev", "slice", "pad", "dynamic_slice",
           "dynamic_update_slice"}

_REDUCTION = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
              "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
              "reduce_window_sum", "reduce_window_max", "reduce_window_min",
              "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
              "sort", "top_k"}

_SCATTER_GATHER = {"gather", "scatter", "scatter-add", "scatter-mul",
                   "scatter-min", "scatter-max", "scatter_add",
                   "select_and_scatter_add", "select_and_gather_add",
                   "take", "take_along_axis"}

#: collective -> wire-cost factor as a function of axis size n: the
#: ring-algorithm per-device bytes multiplier over the payload
_COLLECTIVE_WIRE = {
    "psum": lambda n: 2.0 * (n - 1) / n,          # ring all-reduce
    "psum2": lambda n: 2.0 * (n - 1) / n,         # jax 0.4.x name
    "pmax": lambda n: 2.0 * (n - 1) / n,
    "pmin": lambda n: 2.0 * (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,           # over the OUTPUT bytes
    "psum_scatter": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,                     # one hop
    "pshuffle": lambda n: 1.0,
    "all_to_all": lambda n: (n - 1) / n,
}

#: output materializes but the op itself is one fused pass over inputs
_CONCATLIKE = {"concatenate"}

_RANDOM = {"random_bits", "random_wrap", "random_unwrap", "random_split",
           "random_seed", "random_fold_in", "threefry2x32", "rng_bit_generator"}

#: hand-written kernels (Pallas custom calls).  A custom call is a real
#: pass barrier — XLA cannot fuse compute into or out of it — but by
#: construction it reads each operand and writes each output exactly
#: ONCE (the flash attention kernels, parallel/flash_attention.py).
#: The old model filed these under "other"→elementwise, where the
#: sibling co-fusion rule sometimes merged their reads with unrelated
#: elementwise groups and the view transposes around them were
#: sometimes charged as full passes — both wrong in opposite directions.
_CUSTOM = {"pallas_call", "tpu_custom_call", "custom_call"}

#: classes: "mxu" "elem" "layout" "reduce" "sg" "coll" "concat" "random"
#: "custom" "control" "other"
def _classify(prim_name: str) -> str:
    if prim_name in _MXU:
        return "mxu"
    if prim_name in _CUSTOM:
        return "custom"
    if prim_name in _ELEMENTWISE:
        return "elem"
    if prim_name in _LAYOUT:
        return "layout"
    if prim_name in _REDUCTION:
        return "reduce"
    if prim_name in _SCATTER_GATHER:
        return "sg"
    if prim_name in _COLLECTIVE_WIRE or prim_name in ("pbroadcast",
                                                      "axis_index"):
        return "coll"
    if prim_name in _CONCATLIKE:
        return "concat"
    if prim_name in _RANDOM:
        return "random"
    if prim_name in ("pjit", "closed_call", "core_call", "xla_call",
                     "custom_jvp_call", "custom_vjp_call",
                     "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
                     "remat", "remat2", "checkpoint", "scan", "while",
                     "cond", "shard_map", "named_call", "custom_lin"):
        return "control"
    return "other"


#: group-root class -> CostReport category
_CATEGORY = {"mxu": "conv", "elem": "elementwise", "layout": "elementwise",
             "concat": "elementwise", "random": "elementwise",
             "reduce": "reduction", "sg": "scatter_gather",
             "coll": "collective", "custom": "custom",
             "other": "elementwise"}

#: classes whose eqns force their elementwise operand chains to
#: materialize (they read real buffers, not fused producers).  custom
#: kernels belong here: XLA cannot fuse elementwise compute across a
#: custom-call boundary — but NOT in _FORCES_LAYOUT below: pure layout
#: views feeding a Pallas kernel fold into the kernel's DMA, exactly
#: like layout-into-MXU fusion.
_FORCES_OPERANDS = ("mxu", "sg", "coll", "control", "custom")

#: pure data movement feeding an MXU op is folded into its input by
#: XLA layout assignment (a transposed weight or a space-to-depth
#: rearrangement never round-trips HBM on its own) — so LAYOUT-only
#: chains materialize for fewer consumer classes than elementwise ones
_FORCES_LAYOUT = ("sg", "coll", "control")

#: classes that force an ELEMENTWISE producer to materialize even when
#: reached through a folding layout chain.  MXU is deliberately absent:
#: TPU convs input-fuse cheap elementwise producers (convert/scale)
#: through their operand views — the measured-calibrated behavior —
#: while a custom call is opaque to fusion and must be handed a real
#: buffer no matter how many views sit in between.
_FORCES_THROUGH_LAYOUT = ("sg", "coll", "control", "custom")


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    except TypeError:
        return 0


def _aval_elems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    try:
        return int(np.prod(shape, dtype=np.int64))
    except TypeError:
        return 0


#: MXU sublane tile width: a conv whose per-group input-channel count
#: sits below it loads (and multiplies) channel-padded operands — the
#: conv1 C=3 inefficiency the ``space_to_depth`` graftpass removes
_MXU_LANES = 8




def _conv_lane_amp(eqn) -> float:
    """Channel-padding amplification of one conv: ``lanes/cin`` when the
    per-group input-channel count is under the sublane width, else 1.
    Applied to the conv's FLOPs and its LHS read bytes — the hardware
    loads the padded tile whether or not the channels exist."""
    if eqn.primitive.name != "conv_general_dilated":
        return 1.0
    dn = eqn.params["dimension_numbers"]
    rhs = eqn.invars[1].aval
    cin = rhs.shape[dn.rhs_spec[1]]
    if not isinstance(cin, (int, np.integer)) or not 0 < cin < _MXU_LANES:
        return 1.0
    return _MXU_LANES / float(cin)


def _eqn_flops(eqn) -> float:
    """FLOPs of one equation (fused or not; 1 FLOP per output element
    for elementwise ops, 2·M·N·K-style for MXU ops, one per input
    element for reductions — the standard analytic conventions)."""
    prim = eqn.primitive.name
    cls = _classify(prim)
    if cls == "mxu":
        out = eqn.outvars[0].aval
        if prim == "conv_general_dilated":
            dn = eqn.params["dimension_numbers"]
            rhs = eqn.invars[1].aval
            rhs_spec = dn.rhs_spec
            cin_per_group = rhs.shape[rhs_spec[1]]
            k_spatial = 1
            for d in rhs_spec[2:]:
                k_spatial *= rhs.shape[d]
            return 2.0 * _aval_elems(out) * cin_per_group * k_spatial \
                * _conv_lane_amp(eqn)
        # dot_general
        (lhs_c, _rhs_c), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        k = 1
        for d in lhs_c:
            k *= lhs.shape[d]
        return 2.0 * _aval_elems(out) * k
    if cls == "elem":
        return float(max((_aval_elems(v.aval) for v in eqn.outvars),
                         default=0))
    if cls == "reduce":
        return float(max((_aval_elems(v.aval) for v in eqn.invars
                          if not isinstance(v, jex_core.Literal)), default=0))
    if cls == "sg":
        return float(max((_aval_elems(v.aval) for v in eqn.outvars),
                         default=0))
    if cls == "custom":
        # elementwise-grade arithmetic per element touched: a coarse
        # per-element figure keeps the compute roofline honest without
        # decoding the kernel body
        return float(sum(_aval_elems(v.aval) for v in eqn.outvars)
                     + sum(_aval_elems(v.aval) for v in eqn.invars
                           if not isinstance(v, jex_core.Literal)))
    return 0.0


def eqn_site_weight(eqn) -> Tuple[float, float]:
    """``(flops, hbm_bytes)`` of one equation viewed in isolation — the
    local, unfused weight graftsched uses to attribute a whole-pass
    cost delta across its sites (analysis/passes.py::PassManager.
    _site_rows).  Bytes are operand reads plus output writes with no
    fusion credit: attribution needs relative magnitudes between sites
    of one pass, not the fused program traffic ``analyze_jaxpr``
    models."""
    reads = sum(_aval_bytes(v.aval) for v in eqn.invars
                if not isinstance(v, jex_core.Literal))
    writes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
    return _eqn_flops(eqn), float(reads + writes)


# ---------------------------------------------------------------------------
# accumulators
# ---------------------------------------------------------------------------

@dataclass
class CategoryCost:
    """Rolled-up cost of one op category (PERF.md-table row)."""
    flops: float = 0.0
    hbm_read_bytes: float = 0.0
    hbm_write_bytes: float = 0.0
    passes: int = 0  # fusion groups (≈ full HBM passes)

    @property
    def hbm_bytes(self) -> float:
        return self.hbm_read_bytes + self.hbm_write_bytes

    def to_dict(self) -> dict:
        return {"flops": self.flops, "hbm_read_bytes": self.hbm_read_bytes,
                "hbm_write_bytes": self.hbm_write_bytes,
                "passes": self.passes}


@dataclass
class CommCost:
    """Per-mesh-axis collective volume.  ``payload_bytes`` is the data
    moved through collectives; ``wire_bytes`` applies the ring hop-count
    factor (allreduce 2(n−1)/n, allgather/reduce-scatter (n−1)/n,
    ppermute 1 hop) — the per-device ICI roofline numerator."""
    payload_bytes: float = 0.0
    wire_bytes: float = 0.0
    ops: int = 0

    def to_dict(self) -> dict:
        return {"payload_bytes": self.payload_bytes,
                "wire_bytes": self.wire_bytes, "ops": self.ops}


class _Acc:
    """Per-jaxpr cost accumulator, mergeable upward with a multiplier."""

    def __init__(self):
        self.cat: Dict[str, CategoryCost] = defaultdict(CategoryCost)
        self.comm: Dict[str, CommCost] = defaultdict(CommCost)
        self.peak: float = 0.0
        # initial live bytes (the jaxpr's invars + consts) — a sub-
        # jaxpr's operands are views of buffers ALREADY live in its
        # caller, so control eqns add only (peak - base) on top
        self.base: float = 0.0
        # (bytes, groups, shape, dtype) of multi-pass re-read leaves —
        # a top-32 census for the GL202 message; the TOTAL repeat
        # traffic is carried separately so truncation never clips it
        self.rereads: List[Tuple[float, int, tuple, str]] = []
        self.reread_extra_bytes: float = 0.0

    def merge(self, child: "_Acc", mult: float):
        for k, c in child.cat.items():
            mine = self.cat[k]
            mine.flops += c.flops * mult
            mine.hbm_read_bytes += c.hbm_read_bytes * mult
            mine.hbm_write_bytes += c.hbm_write_bytes * mult
            mine.passes += int(c.passes * max(mult, 1))
        for ax, c in child.comm.items():
            mine = self.comm[ax]
            mine.payload_bytes += c.payload_bytes * mult
            mine.wire_bytes += c.wire_bytes * mult
            mine.ops += int(c.ops * max(mult, 1))
        self.rereads.extend(child.rereads)
        self.rereads.sort(key=lambda r: -r[0])
        del self.rereads[32:]
        self.reread_extra_bytes += child.reread_extra_bytes * mult


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

@dataclass
class CostReport:
    """Structured prediction for ONE traced program (JSON-serializable;
    field reference in docs/ANALYSIS.md).  Totals are whole-program
    (all devices); ``peak_bytes`` and ``*_per_device`` honor the given
    shard factors, so dp-sharded (ZeRO-1) state costs 1/N."""
    device: str = "tpu-v5e"
    n_devices: int = 1
    categories: Dict[str, CategoryCost] = field(default_factory=dict)
    comm: Dict[str, CommCost] = field(default_factory=dict)
    peak_bytes: float = 0.0            # per device
    param_bytes: float = 0.0           # per device (replicated unless sharded)
    opt_state_bytes: float = 0.0       # global
    opt_state_bytes_per_device: float = 0.0
    #: GL202 raw material, structurally: one (bytes, n_reads, shape,
    #: dtype) row per large intermediate read by 2+ fusable groups —
    #: the model's accounting of the avoidable multi-pass traffic
    #: (custom-kernel reads never count).
    #: The census keeps the worst 32 rows; ``multipass_extra_bytes``
    #: is the UNtruncated total of the repeats (bytes x (reads - 1)).
    rereads: List[Tuple[float, int, tuple, str]] = field(
        default_factory=list)
    multipass_extra_bytes: float = 0.0
    diagnostics: List[Diagnostic] = field(default_factory=list)
    hbm_budget: Optional[float] = None
    # informational knobs echoed by the step hook / CLI
    meta: Dict[str, Any] = field(default_factory=dict)

    # -- totals --------------------------------------------------------
    @property
    def total_flops(self) -> float:
        return sum(c.flops for c in self.categories.values())

    @property
    def hbm_read_bytes(self) -> float:
        return sum(c.hbm_read_bytes for c in self.categories.values())

    @property
    def hbm_write_bytes(self) -> float:
        return sum(c.hbm_write_bytes for c in self.categories.values())

    @property
    def hbm_bytes(self) -> float:
        return self.hbm_read_bytes + self.hbm_write_bytes

    # -- roofline ------------------------------------------------------
    def spec(self) -> DeviceSpec:
        return DEVICE_SPECS[self.device]

    def roofline(self) -> Dict[str, float]:
        """Per-phase lower-bound seconds and the step-time estimate
        (max of the three rooflines — perfect overlap assumed)."""
        sp = self.spec()
        n = max(self.n_devices, 1)
        compute_s = self.total_flops / (sp.flops_per_s * n)
        hbm_s = self.hbm_bytes / (sp.hbm_bytes_per_s * n)
        comm_s = max((c.wire_bytes / sp.ici_bytes_per_s
                      for c in self.comm.values()), default=0.0)
        return {"compute_s": compute_s, "hbm_s": hbm_s, "comm_s": comm_s,
                "step_s": max(compute_s, hbm_s, comm_s)}

    # -- serialization -------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "version": 1,
            "device": self.device,
            "n_devices": self.n_devices,
            "categories": {k: v.to_dict()
                           for k, v in sorted(self.categories.items())},
            "totals": {"flops": self.total_flops,
                       "hbm_read_bytes": self.hbm_read_bytes,
                       "hbm_write_bytes": self.hbm_write_bytes,
                       "hbm_bytes": self.hbm_bytes},
            "peak_bytes": self.peak_bytes,
            "multipass_extra_bytes": self.multipass_extra_bytes,
            "rereads": [{"bytes": b, "reads": n, "shape": list(s),
                         "dtype": d} for b, n, s, d in self.rereads],
            "param_bytes": self.param_bytes,
            "opt_state_bytes": self.opt_state_bytes,
            "opt_state_bytes_per_device": self.opt_state_bytes_per_device,
            "comm": {k: v.to_dict() for k, v in sorted(self.comm.items())},
            "roofline": self.roofline(),
            "hbm_budget": self.hbm_budget,
            "diagnostics": [d.to_dict() for d in self.diagnostics],
            "meta": self.meta,
        }

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=False)

    def format(self) -> str:
        """PERF.md-style category table + roofline summary."""
        rf = self.roofline()
        lines = ["graftcost (%s x%d): %.1f GFLOP, %.3f GB HBM, peak "
                 "%.1f MB/device"
                 % (self.device, self.n_devices, self.total_flops / 1e9,
                    self.hbm_bytes / 1e9, self.peak_bytes / 1e6),
                 "%-16s %12s %12s %12s %8s"
                 % ("category", "GFLOP", "read GB", "write GB", "passes")]
        for k, c in sorted(self.categories.items(),
                           key=lambda kv: -kv[1].hbm_bytes):
            lines.append("%-16s %12.2f %12.3f %12.3f %8d"
                         % (k, c.flops / 1e9, c.hbm_read_bytes / 1e9,
                            c.hbm_write_bytes / 1e9, c.passes))
        for ax, c in sorted(self.comm.items()):
            lines.append("comm[%s]: %.3f GB payload, %.3f GB wire, %d ops"
                         % (ax, c.payload_bytes / 1e9, c.wire_bytes / 1e9,
                            c.ops))
        lines.append("roofline: compute %.2f ms, hbm %.2f ms, comm %.2f ms "
                     "-> step >= %.2f ms"
                     % (1e3 * rf["compute_s"], 1e3 * rf["hbm_s"],
                        1e3 * rf["comm_s"], 1e3 * rf["step_s"]))
        if self.hbm_budget:
            lines.append("hbm budget: %.1f MB (peak %s)"
                         % (self.hbm_budget / 1e6,
                            "OVER" if self.peak_bytes > self.hbm_budget
                            else "ok"))
        for d in self.diagnostics:
            lines.append(d.format())
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the walker
# ---------------------------------------------------------------------------

def _sub_closed(params):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            if isinstance(u, jex_core.ClosedJaxpr):
                yield u.jaxpr
            elif isinstance(u, jex_core.Jaxpr):
                yield u


class _PVar:
    """Fresh per-call-site identity for an inlined body's var (jax
    reuses one body jaxpr object across call sites, so body vars alone
    cannot carry identity)."""
    __slots__ = ("aval",)

    def __init__(self, aval):
        self.aval = aval


def _is_var(v) -> bool:
    return isinstance(v, (jex_core.Var, _PVar))


class _VEqn:
    """One flattened equation: the original eqn plus its invars/outvars
    resolved to global identities (call-site cloned)."""
    __slots__ = ("eqn", "invars", "outvars")

    def __init__(self, eqn, invars, outvars):
        self.eqn = eqn
        self.invars = invars
        self.outvars = outvars

    @property
    def primitive(self):
        return self.eqn.primitive

    @property
    def params(self):
        return self.eqn.params


def _res(alias: Dict[Any, Any], v):
    """Resolve a var through CSE alias chains."""
    seen = 0
    while _is_var(v) and v in alias and seen < 128:
        v = alias[v]
        seen += 1
    return v


#: call-like primitives whose bodies XLA inlines into one module — a
#: pjit/remat/custom_* boundary is NOT a fusion barrier and must not
#: force its operands to materialize
_INLINE_PRIMS = {"pjit", "closed_call", "core_call", "xla_call",
                 "custom_jvp_call", "custom_vjp_call",
                 "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
                 "remat", "remat2", "checkpoint", "named_call"}


class _Walker:
    def __init__(self, large_bytes: int):
        self.large_bytes = large_bytes

    # -- inlining ------------------------------------------------------
    @staticmethod
    def _inline_body(eqn):
        if eqn.primitive.name not in _INLINE_PRIMS:
            return None
        for k in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            b = eqn.params.get(k)
            if isinstance(b, jex_core.ClosedJaxpr):
                return b.jaxpr
            if isinstance(b, jex_core.Jaxpr):
                return b
        return None

    def _flatten(self, jaxpr, env, flat, consts=None, depth=0):
        """Inline call-like sub-jaxprs into one flat :class:`_VEqn`
        list.  ``env`` maps this scope's local vars to global
        identities; every call site gets fresh clones, so a body jaxpr
        reused by several sites (jax caches them) costs each site its
        own passes.  ``consts`` collects the fresh identities minted
        for inlined bodies' constvars (real buffers the liveness scan
        must credit)."""

        def look(v):
            if not isinstance(v, jex_core.Var):
                return v  # Literal
            return env.get(v, v)

        for eqn in jaxpr.eqns:
            body = self._inline_body(eqn)
            if body is not None and len(body.invars) == len(eqn.invars) \
                    and len(body.outvars) == len(eqn.outvars) \
                    and depth < 32:
                benv = {}
                for bi, ov in zip(body.invars, eqn.invars):
                    benv[bi] = look(ov)
                for cv in body.constvars:
                    benv[cv] = _PVar(cv.aval)
                    if consts is not None:
                        consts.append(benv[cv])
                self._flatten(body, benv, flat, consts, depth + 1)
                for eo, bo in zip(eqn.outvars, body.outvars):
                    if isinstance(eo, jex_core.Var):
                        env[eo] = benv.get(bo, bo) \
                            if isinstance(bo, jex_core.Var) else bo
                continue
            inv = [look(v) for v in eqn.invars]
            outv = []
            for o in eqn.outvars:
                if not isinstance(o, jex_core.Var):
                    outv.append(o)
                    continue
                g = o if depth == 0 else _PVar(o.aval)
                env[o] = g
                outv.append(g)
            flat.append(_VEqn(eqn, inv, outv))

    # -- CSE -----------------------------------------------------------
    def _cse(self, flat, alias):
        """XLA eliminates common subexpressions before fusion — the
        traced program computes BN batch stats twice (once for
        normalize, once for the running-stats update) and autodiff
        re-emits identical x̂ chains, all of which compile to ONE
        computation.  Extends ``alias`` (dup var -> canonical var) and
        returns the (virtual) eqns to skip entirely."""
        dup_eqns = set()
        seen: Dict[tuple, Any] = {}
        for veqn in flat:
            if _classify(veqn.primitive.name) in ("control", "random"):
                continue
            try:
                key = (veqn.primitive.name, str(veqn.params),
                       tuple(id(_res(alias, v))
                             if _is_var(_res(alias, v))
                             else ("lit", str(_res(alias, v)))
                             for v in veqn.invars))
            except Exception:  # unhashable/unprintable params: skip CSE
                continue
            prior = seen.get(key)
            if prior is None:
                seen[key] = veqn
            else:
                dup_eqns.add(id(veqn))
                for o, po in zip(veqn.outvars, prior.outvars):
                    if _is_var(o):
                        alias[o] = _res(alias, po)
        return dup_eqns

    # -- var maps ------------------------------------------------------
    def _build_maps(self, flat, out_vars, alias, dup_eqns):
        producers, consumers = {}, defaultdict(list)
        for veqn in flat:
            if id(veqn) in dup_eqns:
                continue
            for v in veqn.invars:
                rv = _res(alias, v)
                if _is_var(rv):
                    consumers[rv].append(veqn)
            for o in veqn.outvars:
                if _is_var(o):
                    producers[o] = veqn
        outset = {id(_res(alias, v)) for v in out_vars if _is_var(v)}
        return producers, consumers, outset

    def _eff_consumers(self, v, producers, consumers, outset, memo):
        """Consumers of ``v`` reached through chains of NON-materializing
        pure LAYOUT ops: a reshape/transpose between a producer and its
        real reader is a relabeling, not a compute stage — the
        materialization force of the reader acts through it (an
        elementwise op feeding a Pallas kernel via the kernel's bitcast
        view still cannot fuse into the kernel).  A layout hop that
        itself materializes (a view a scatter reads, a program output)
        absorbs the force instead: the producer fuses into that write.
        """
        out, stack, seen = [], [v], set()
        while stack:
            u = stack.pop()
            for c in consumers.get(u, ()):
                if id(c) in seen:
                    continue
                seen.add(id(c))
                if _classify(c.primitive.name) == "layout":
                    for o in c.outvars:
                        if _is_var(o) and not self._materialized(
                                o, producers, consumers, outset, memo):
                            stack.append(o)
                else:
                    out.append(c)
        return out

    def _materialized(self, v, producers, consumers, outset, memo):
        if not _is_var(v):
            return False
        r = memo.get(id(v))
        if r is not None:
            return r
        if v not in producers:          # jaxpr invar or constvar
            memo[id(v)] = True
            return True
        cls = _classify(producers[v].primitive.name)
        if cls not in ("elem", "layout"):
            r = True
        elif id(v) in outset:
            r = True
        elif cls == "layout":
            # a pure view materializes only for DIRECT readers that
            # need a real reshuffled buffer (scatter/collective/
            # control); MXU and custom kernels fold views into their
            # input DMA
            r = any(_classify(c.primitive.name) in _FORCES_LAYOUT
                    for c in consumers.get(v, ()))
        else:
            # elementwise: forced by any DIRECT non-fusing reader, or
            # by a fusion-opaque reader (custom kernel/scatter/
            # collective/control) reached through a non-materializing
            # layout chain (the view folds, the compute does not; MXU
            # readers input-fuse through views — see
            # _FORCES_THROUGH_LAYOUT)
            r = any(_classify(c.primitive.name) in _FORCES_OPERANDS
                    for c in consumers.get(v, ())) \
                or any(_classify(c.primitive.name)
                       in _FORCES_THROUGH_LAYOUT
                       for c in self._eff_consumers(v, producers,
                                                    consumers, outset,
                                                    memo))
        memo[id(v)] = r
        return r

    def _fused_leaves(self, veqn, producers, consumers, outset, memo,
                      alias):
        """Materialized vars the fused group rooted at ``veqn`` reads."""
        leaves, seen = [], set()
        stack = [rv for rv in (_res(alias, v) for v in veqn.invars)
                 if _is_var(rv)]
        while stack:
            v = stack.pop()
            if id(v) in seen:
                continue
            seen.add(id(v))
            if self._materialized(v, producers, consumers, outset, memo):
                leaves.append(v)
            else:
                stack.extend(
                    ru for ru in (_res(alias, u)
                                  for u in producers[v].invars)
                    if _is_var(ru))
        return leaves

    # -- one jaxpr -----------------------------------------------------
    def analyze(self, jaxpr, axis_sizes: Dict[str, int],
                donated: frozenset = frozenset(),
                invar_factors: Optional[Dict[Any, float]] = None) -> _Acc:
        """Walk one (open) jaxpr.  ``donated``: invars freed at last
        use; ``invar_factors``: var -> shard divisor for resident
        bytes (dp-sharded state etc.)."""
        acc = _Acc()
        env: Dict[Any, Any] = {}
        flat: List[_VEqn] = []
        inlined_consts: List[Any] = []
        self._flatten(jaxpr, env, flat, inlined_consts)
        alias: Dict[Any, Any] = {}
        dup_eqns = self._cse(flat, alias)

        def res(v):
            if isinstance(v, jex_core.Var):
                v = env.get(v, v)
            return _res(alias, v)

        out_ids = [res(v) for v in jaxpr.outvars]
        producers, consumers, outset = self._build_maps(flat, out_ids,
                                                        alias, dup_eqns)
        memo: Dict[int, bool] = {}
        invar_factors = invar_factors or {}

        def eff_bytes(v):
            return _aval_bytes(v.aval) / max(invar_factors.get(v, 1.0), 1.0)

        # liveness pre-pass over materialized vars
        last_use: Dict[Any, int] = {}
        n_eqns = len(flat)
        for i, veqn in enumerate(flat):
            if id(veqn) in dup_eqns:
                continue
            for v in veqn.invars:
                rv = _res(alias, v)
                if _is_var(rv):
                    last_use[rv] = i
        for rv in out_ids:
            if _is_var(rv):
                last_use[rv] = n_eqns
        invars = [v for v in jaxpr.invars]
        for v in invars:
            if v not in donated:
                last_use[v] = n_eqns      # caller still owns the buffer
        # constants (top-level constvars + identities minted for inlined
        # bodies' consts) are real buffers: credited at program start and
        # held for the executable's lifetime — without the credit, the
        # frees pass would debit bytes that were never added
        const_vars = list(getattr(jaxpr, "constvars", ())) + inlined_consts
        for cv in const_vars:
            last_use[cv] = n_eqns
        # greedy donation aliasing (the GL003 matcher): a donated invar
        # whose shape/dtype matches an outvar reuses its buffer — the
        # output costs nothing extra
        aliased_out = set()
        free_donated = []
        for v in invars:
            if v in donated:
                free_donated.append((tuple(getattr(v.aval, "shape", ())),
                                     str(getattr(v.aval, "dtype", "?"))))
        for ov in out_ids:
            if not _is_var(ov):
                continue
            key = (tuple(getattr(ov.aval, "shape", ())),
                   str(getattr(ov.aval, "dtype", "?")))
            if key in free_donated:
                free_donated.remove(key)
                aliased_out.add(id(ov))

        live = sum(eff_bytes(v) for v in invars) \
            + sum(eff_bytes(v) for v in const_vars)
        acc.peak = live
        acc.base = live
        # frees[i]: vars whose last use is eqn i
        frees = defaultdict(list)
        for v, i in last_use.items():
            if i < n_eqns:
                frees[i].append(v)

        reread_count: Dict[Any, int] = defaultdict(int)
        # sibling co-fusion (XLA multi-output fusion): ALL reduction
        # groups reading a tensor within one program REGION compile to
        # ONE pass over it (BN's sum(x)/sum(x·x); the bwd's
        # sum(dY)/sum(dY·x̂) + the broadcast-transpose reductions — the
        # measured convert_reduce_fusion behavior, docs/PERF.md), and
        # likewise for sibling elementwise groups.  Model: per leaf,
        # one read per fusable CATEGORY until a non-fusing consumer
        # (conv/custom kernel/scatter/collective — a real pass barrier
        # in time, e.g. the dW conv between a layer's bwd and the next
        # layer's bwd) reads it, which opens a new region.
        seen_cats: Dict[Any, set] = {}

        for i, eqn in enumerate(flat):
            if id(eqn) in dup_eqns:
                continue  # CSE'd away: computed (and charged) once
            prim = eqn.primitive.name
            cls = _classify(prim)
            inner_peak = 0.0
            if cls == "control":
                inner_peak = self._control(eqn, acc, axis_sizes)
            else:
                # flops per eqn, by its own class
                fl = _eqn_flops(eqn)
                if fl:
                    acc.cat[_CATEGORY[cls]].flops += fl
                # traffic per fusion-group root
                root = cls not in ("elem", "layout") or any(
                    self._materialized(o, producers, consumers, outset,
                                       memo)
                    for o in eqn.outvars if _is_var(o))
                if root:
                    category = _CATEGORY[cls]
                    cofusable = category in ("reduction", "elementwise")
                    c = acc.cat[category]
                    c.passes += 1
                    for leaf in self._fused_leaves(eqn, producers,
                                                   consumers, outset,
                                                   memo, alias):
                        if cofusable:
                            seen = seen_cats.setdefault(leaf, set())
                            if category in seen:
                                continue  # co-fused sibling read it
                            seen.add(category)
                            # the GL202 census counts only FUSABLE
                            # repeat reads: a conv or custom kernel
                            # re-reading an operand is necessary
                            # compute traffic, while a second
                            # reduction/elementwise pass over a big
                            # intermediate is exactly the avoidable
                            # multi-pass BN pattern (a custom
                            # kernel's own read is never counted)
                            reread_count[leaf] += 1
                        else:
                            seen_cats[leaf] = set()  # pass barrier
                        c.hbm_read_bytes += _aval_bytes(leaf.aval)
                    if prim == "conv_general_dilated":
                        # sublane channel padding: the LHS loads at the
                        # tile width even when cin is smaller
                        amp = _conv_lane_amp(eqn)
                        if amp > 1.0 and _is_var(eqn.invars[0]):
                            c.hbm_read_bytes += (amp - 1.0) * _aval_bytes(
                                eqn.invars[0].aval)
                    for o in eqn.outvars:
                        if _is_var(o) and \
                                self._materialized(o, producers, consumers,
                                                   outset, memo):
                            c.hbm_write_bytes += _aval_bytes(o.aval)
                            # fresh buffer: its first read is a new pass
                            seen_cats.pop(o, None)
                if cls == "coll":
                    self._collective(eqn, acc, axis_sizes)
            # liveness: outputs materialize now
            for o in eqn.outvars:
                if _is_var(o) and id(o) not in aliased_out \
                        and self._materialized(o, producers, consumers,
                                               outset, memo):
                    live += eff_bytes(o)
            acc.peak = max(acc.peak, live + inner_peak)
            for v in frees.get(i, ()):
                if self._materialized(v, producers, consumers, outset,
                                      memo):
                    live -= eff_bytes(v)
        # GL202 raw material: leaves read by 2+ groups.  The extra-byte
        # TOTAL is accumulated before the census truncates to its
        # top-32 rows — `multipass_extra_bytes` must never under-count
        # exactly when the multi-pass traffic is largest.
        for v, n in reread_count.items():
            b = _aval_bytes(v.aval)
            if n >= 2 and b >= self.large_bytes:
                acc.rereads.append((float(b), n,
                                    tuple(getattr(v.aval, "shape", ())),
                                    str(getattr(v.aval, "dtype", "?"))))
                acc.reread_extra_bytes += float(b) * (n - 1)
        acc.rereads.sort(key=lambda r: -r[0])
        del acc.rereads[32:]
        return acc

    # -- control-flow equations ---------------------------------------
    def _control(self, eqn, acc: _Acc, axis_sizes) -> float:
        prim = eqn.primitive.name
        params = eqn.params
        if prim == "scan":
            body = params["jaxpr"].jaxpr
            length = int(params.get("length", 1))
            child = self.analyze(body, axis_sizes)
            acc.merge(child, length)
            # the stacked per-iteration ys (the activation stash) ARE
            # the scan eqn's outvars — the caller's liveness scan
            # credits them when the eqn's outputs materialize — and the
            # body's invars are views of outer-live buffers (carry init,
            # xs), so only the body-internal EXCESS rides on top here
            return max(child.peak - child.base, 0.0)
        if prim == "while":
            peak = 0.0
            for sub in _sub_closed(params):
                child = self.analyze(sub, axis_sizes)
                acc.merge(child, 1.0)   # trip count unknowable: 1
                peak = max(peak, child.peak - child.base)
            return peak
        if prim == "cond":
            branches = params.get("branches", ())
            best: Optional[_Acc] = None
            for br in branches:
                sub = br.jaxpr if isinstance(br, jex_core.ClosedJaxpr) else br
                child = self.analyze(sub, axis_sizes)
                if best is None or child_total(child) > child_total(best):
                    best = child
            if best is not None:
                acc.merge(best, 1.0)
                return max(best.peak - best.base, 0.0)
            return 0.0
        if prim == "shard_map":
            mesh = params["mesh"]
            sizes = dict(axis_sizes)
            sizes.update({k: int(v) for k, v in dict(mesh.shape).items()})
            n = int(np.prod(list(dict(mesh.shape).values()))) or 1
            body = params["jaxpr"]
            child = self.analyze(body, sizes)
            # the body runs once per device: global work = n x body —
            # but comm is reported PER DEVICE, so undo the n after merge
            acc.merge(child, float(n))
            for ax in child.comm:
                mine = acc.comm[ax]
                mine.payload_bytes -= child.comm[ax].payload_bytes * (n - 1)
                mine.wire_bytes -= child.comm[ax].wire_bytes * (n - 1)
                mine.ops -= int(child.comm[ax].ops * (n - 1))
            return max(child.peak - child.base, 0.0)
        # pjit / remat / custom_* / named_call: inline
        peak = 0.0
        for sub in _sub_closed(params):
            donated = frozenset()
            dmask = params.get("donated_invars")
            if dmask:
                donated = frozenset(v for v, d in zip(sub.invars, dmask)
                                    if d)
            child = self.analyze(sub, axis_sizes, donated=donated)
            acc.merge(child, 1.0)
            peak = max(peak, child.peak - child.base)
        return peak

    def _collective(self, eqn, acc: _Acc, axis_sizes):
        prim = eqn.primitive.name
        wire_fn = _COLLECTIVE_WIRE.get(prim)
        if wire_fn is None:
            return
        # ppermute/all_gather/all_to_all bind the axis under "axis_name";
        # the psum family (psum/pmax/pmin/psum_scatter) binds "axes" on
        # jax 0.4.x — missing it would zero out the allreduce wire model
        axes = eqn.params.get("axis_name", eqn.params.get("axes"))
        if axes is None:
            return
        axes = axes if isinstance(axes, (tuple, list)) else (axes,)
        n = 1
        for a in axes:
            n *= int(axis_sizes.get(a, 1))
        if n <= 1:
            return
        label = axes[0] if len(axes) == 1 else "x".join(str(a)
                                                        for a in axes)
        if prim == "all_gather":
            payload = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        else:
            payload = sum(_aval_bytes(v.aval) for v in eqn.invars
                          if _is_var(v))
        c = acc.comm[str(label)]
        c.payload_bytes += payload
        c.wire_bytes += payload * wire_fn(n)
        c.ops += 1


def child_total(acc: _Acc) -> float:
    return sum(c.hbm_read_bytes + c.hbm_write_bytes
               for c in acc.cat.values())


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def shard_factor(sharding, mesh=None) -> float:
    """Shard divisor of one placement: the product of the mesh-axis
    sizes its PartitionSpec names (1.0 for replicated / None)."""
    if sharding is None:
        return 1.0
    spec = getattr(sharding, "spec", sharding)
    mesh = getattr(sharding, "mesh", mesh)
    if mesh is None:
        return 1.0
    sizes = dict(mesh.shape)
    f = 1.0
    for e in tuple(spec or ()):
        if e is None:
            continue
        for name in (e if isinstance(e, tuple) else (e,)):
            f *= float(sizes.get(name, 1))
    return f


def analyze_jaxpr(closed_jaxpr, *,
                  axis_sizes: Optional[Dict[str, int]] = None,
                  donated_leaves: Sequence[int] = (),
                  invar_shard_factors: Optional[Sequence[float]] = None,
                  device: str = "tpu-v5e", n_devices: int = 1,
                  hbm_budget: Optional[float] = None,
                  large_intermediate_bytes: int = 16 << 20,
                  meta: Optional[Dict[str, Any]] = None) -> CostReport:
    """Cost one traced program (no compile, no execution).

    ``donated_leaves``: flat invar indices donated at the top level
    (freed at last use + aliased into matching outputs for the peak
    model).  ``invar_shard_factors``: per-flat-invar resident-byte
    divisor (a ``P('dp')``-sharded ZeRO state leaf on a dp=8 mesh has
    factor 8).  ``axis_sizes`` seeds named-axis sizes for collectives
    outside any shard_map.  GL201 (over ``hbm_budget``), GL202
    (multi-pass re-reads ≥ ``large_intermediate_bytes``) and GL203
    (comm-dominated) land in ``report.diagnostics``.
    """
    jaxpr = closed_jaxpr.jaxpr if isinstance(closed_jaxpr,
                                             jex_core.ClosedJaxpr) \
        else closed_jaxpr
    donated = frozenset(jaxpr.invars[i] for i in donated_leaves
                        if i < len(jaxpr.invars))
    factors = {}
    if invar_shard_factors:
        for v, f in zip(jaxpr.invars, invar_shard_factors):
            if f and f > 1:
                factors[v] = float(f)
    walker = _Walker(large_intermediate_bytes)
    acc = walker.analyze(jaxpr, dict(axis_sizes or {}), donated=donated,
                         invar_factors=factors)
    report = CostReport(device=device, n_devices=max(int(n_devices), 1),
                        categories=dict(acc.cat), comm=dict(acc.comm),
                        peak_bytes=acc.peak, rereads=list(acc.rereads),
                        multipass_extra_bytes=acc.reread_extra_bytes,
                        hbm_budget=hbm_budget, meta=dict(meta or {}))
    report.diagnostics = check_cost(report, rereads=acc.rereads)
    return report


def analyze_traceable(fn, args: tuple = (), kwargs: Optional[dict] = None,
                      *, donate_argnums: Sequence[int] = (),
                      **analyze_kwargs) -> CostReport:
    """Trace ``fn(*args, **kwargs)`` abstractly and cost the program."""
    from .trace_lint import donated_leaf_indices

    kwargs = kwargs or {}
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    donated = donated_leaf_indices(args, donate_argnums)
    return analyze_jaxpr(closed, donated_leaves=donated, **analyze_kwargs)


def check_cost(report: CostReport,
               rereads: Sequence[Tuple[float, int, tuple, str]] = (),
               hbm_budget: Optional[float] = None) -> List[Diagnostic]:
    """The GL20x rules over a finished report.  GL201 is the eager
    infeasibility gate (ERROR — ``cost="check"`` raises before any
    compile); GL202/GL203 are advisory (fusion opportunity /
    comm-dominated roofline)."""
    diags: List[Diagnostic] = []
    budget = hbm_budget if hbm_budget is not None else report.hbm_budget
    if budget and report.peak_bytes > budget:
        diags.append(Diagnostic(
            "GL201", Severity.ERROR,
            "predicted peak live-buffer memory %.1f MB exceeds the HBM "
            "budget %.1f MB (by %.1fx) — this config cannot fit; "
            "rejected at trace time, before any compile"
            % (report.peak_bytes / 1e6, budget / 1e6,
               report.peak_bytes / budget),
            where="graftcost peak-memory model",
            hint="shrink the batch / enable pipeline_remat / shard "
                 "state with zero=1, or raise hbm_budget"))
    if rereads:
        # the report carries the UNtruncated total; fall back to the
        # census rows only when called with a bare rereads list
        total_extra = report.multipass_extra_bytes \
            or sum(b * (n - 1) for b, n, _, _ in rereads)
        worst = rereads[0]
        diags.append(Diagnostic(
            "GL202", Severity.WARNING,
            "%d large intermediate(s) are re-read by 2+ fusion groups "
            "(~%.2f GB of repeat HBM traffic); worst: %s %s read %d "
            "times — the multi-pass BN stats/normalize pattern"
            % (len(rereads), total_extra / 1e9, worst[2], worst[3],
               worst[1]),
            where="graftcost fusion model",
            hint="when the repeats are DUPLICATE computations (BN stats "
                 "traced twice), the cse_dead_aux graftpass merges them "
                 "at trace time — passes=('cse_dead_aux',) / "
                 "MXTPU_PASSES (docs/PASSES.md); the rest is what XLA's "
                 "own fusion leaves (no shipped kernel removes it)"))
    rf = report.roofline()
    if rf["comm_s"] > max(rf["compute_s"], rf["hbm_s"]) and rf["comm_s"] > 0:
        diags.append(Diagnostic(
            "GL203", Severity.WARNING,
            "comm-dominated step: collective wire time %.2f ms exceeds "
            "the compute (%.2f ms) and HBM (%.2f ms) rooflines on %s"
            % (1e3 * rf["comm_s"], 1e3 * rf["compute_s"],
               1e3 * rf["hbm_s"], report.device),
            where="graftcost roofline",
            hint="increase per-device batch (amortize the collectives) "
                 "or reduce the sharded axis size"))
    return diags


def push_volume_report(entries, compressor=None) -> Dict[str, Any]:
    """Trace-time pricing of one async push (``parallel/param_service``
    wire volume), from tensor shapes alone — zero compiles spent.

    ``entries`` — ``(name, shape, dtype)`` triples, one per pushed
    gradient (the step's trainable params).  ``compressor`` — an
    error-feedback compressor from ``kvstore/gradient_compression``
    (``payload_nbytes(shape, dtype)`` protocol) or ``None`` for dense
    f32 pushes.  Returns a JSON-serializable dict: per-tensor and total
    compressed/dense bytes and the overall reduction ratio — what
    ``TrainStep.analyze_cost`` attaches as ``report.meta["push_volume"]``
    on async/compressed steps.
    """
    rows = []
    total_c = total_d = 0
    for name, shape, dtype in entries:
        n = int(np.prod(shape, dtype=np.int64)) if len(shape) else 1
        dense = n * 4  # the uncompressed wire is f32 regardless of dtype
        comp = dense if compressor is None else \
            int(compressor.payload_nbytes(tuple(shape), dtype))
        rows.append({"name": str(name), "shape": tuple(int(s) for s in shape),
                     "dense_nbytes": int(dense),
                     "push_nbytes": int(comp)})
        total_c += comp
        total_d += dense
    return {"compressor": None if compressor is None
            else getattr(compressor, "kind", type(compressor).__name__),
            "tensors": rows,
            "push_nbytes": int(total_c),
            "dense_nbytes": int(total_d),
            "reduction": (float(total_d) / float(total_c))
            if total_c else 1.0}
