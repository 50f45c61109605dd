"""Operator registry.

Reference model: ``NNVM_REGISTER_OP`` + typed attributes (FCompute<cpu/gpu>,
FInferShape, FGradient, ... — see ``include/mxnet/op_attr_types.h:217-315``
and SURVEY.md Appendix A).  TPU-native model: every op registers ONE
implementation — a pure JAX function (``fn``) that XLA compiles for TPU *and*
CPU — and gradients come from ``jax.vjp`` at record time instead of a
registered FGradient pass.  Shape/dtype inference is ``jax.eval_shape`` over
the same fn, so there is no separate inference code to keep in sync.

The registry drives three frontends:
- ``mx.nd.*``    eager execution (+ autograd tape)       [Imperative::Invoke]
- ``mx.sym.*``   graph node creation                      [nnvm::Symbol]
- direct raw-array calls inside traced programs           [FCompute<tpu>]
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

__all__ = ["Op", "register", "get_op", "list_ops", "invoke", "invoke_raw", "OPS"]

OPS: Dict[str, "Op"] = {}


class Op:
    """A registered operator.

    Attributes
    ----------
    fn : callable(*arrays, **attrs) -> array or tuple of arrays
        Pure JAX implementation (the FCompute<tpu> equivalent).
    num_inputs : int or None (variadic)
    num_outputs : int
    differentiable : bool — False skips tape recording (e.g. argmax, shape ops
        with int outputs).
    needs_rng : bool — fn takes a ``key`` kwarg supplied from the stateful
        PRNG (eager) or trace key (compiled); mirrors ResourceRequest::kRandom.
    mutate_idx : tuple — indices of inputs the reference op mutates
        (FMutateInputs); kept as metadata for executor aliasing/donation.
    aux_update : callable(in_vals, out_vals, **attrs) -> {input_idx: new_val}
        or None — functional form of the reference's FMutateInputs side
        effects: given the op's traced inputs/outputs, returns replacement
        values for the mutated inputs (e.g. BatchNorm running stats).  The
        symbolic Executor and any whole-graph trace commit these through the
        generic aux-write channel; eager frontends commit them directly.
    """

    def __init__(self, name, fn, num_inputs=None, num_outputs=1,
                 differentiable=True, needs_rng=False, mutate_idx=(),
                 aliases=(), doc="", aux_update=None, no_trace=False):
        self.name = name
        self.fn = fn
        self.num_inputs = num_inputs
        self.num_outputs = num_outputs
        self.differentiable = differentiable
        self.needs_rng = needs_rng
        self.mutate_idx = tuple(mutate_idx)
        self.aliases = tuple(aliases)
        self.doc = doc or (fn.__doc__ or "")
        self.aux_update = aux_update
        # no_trace: fn must run on concrete arrays only (data-dependent
        # output shapes, host callbacks) — excluded from jit wrapping
        self.no_trace = no_trace

    def __repr__(self):
        return "Op(%s)" % self.name

    # -- inference ---------------------------------------------------------
    def infer(self, in_avals: Sequence[jax.ShapeDtypeStruct], **attrs):
        """Infer output shapes/dtypes via abstract evaluation."""
        out = jax.eval_shape(functools.partial(self.fn, **attrs), *in_avals)
        return out if isinstance(out, (tuple, list)) else (out,)


def register(name, fn=None, **kwargs):
    """Register an op (decorator or direct). ``aliases`` adds extra names."""
    def _do(f):
        op = Op(name, f, **kwargs)
        OPS[name] = op
        for a in op.aliases:
            OPS[a] = op
        return f

    if fn is not None:
        return _do(fn)
    return _do


def get_op(name: str) -> Op:
    try:
        return OPS[name]
    except KeyError:
        raise NotImplementedError(
            "operator %r is not registered in this framework (reference parity "
            "gap — see SURVEY.md §2.4)" % name
        ) from None


def list_ops() -> List[str]:
    return sorted(OPS.keys())


def _dmlc_type_name(default):
    """Map a python default to a dmlc::Parameter-style type string
    (dmlc/parameter.h field-type names as they appear in op docs)."""
    if isinstance(default, bool):
        return "boolean"
    if isinstance(default, int):
        return "int"
    if isinstance(default, float):
        return "float"
    if isinstance(default, str):
        return "string"
    if isinstance(default, (tuple, list)):
        return "Shape(tuple)"
    if default is None:
        return "string or None"
    return type(default).__name__


def op_info(name: str) -> Dict[str, Any]:
    """dmlc::Parameter-style reflection for a registered op.

    The reference exposes each op's parameter schema (declared via
    DMLC_DECLARE_PARAMETER, dmlc/parameter.h) through
    MXSymbolGetAtomicSymbolInfo (src/c_api/c_api_symbolic.cc) and code-gens
    python wrappers + docs from it.  Here the schema is derived from the
    FCompute signature itself: leading positional parameters are tensor
    inputs, keyword parameters (with defaults) are op attributes.

    Returns dict with: name, description, inputs [(name, type)], arguments
    [(name, type_str, default_repr or None)], num_outputs, aliases.
    """
    import inspect

    # the symbol layer owns the authoritative input-vs-attribute
    # classification (it drives graph composition); reuse it so reflection,
    # composition and docs can never disagree
    from ..symbol.symbol import _input_arg_names

    op = get_op(name)
    sig = inspect.signature(op.fn)
    in_names = _input_arg_names(op)
    inputs: List[Any] = []
    arguments: List[Any] = []
    for p in sig.parameters.values():
        if p.kind == p.VAR_POSITIONAL:
            inputs.append((p.name, "NDArray[]"))
            continue
        if p.kind == p.VAR_KEYWORD:
            continue
        if op.needs_rng and p.name == "key":
            continue  # internal PRNG resource (ResourceRequest::kRandom)
        if in_names is not None and p.name in in_names:
            inputs.append((p.name, "NDArray" if
                           p.default is inspect.Parameter.empty
                           else "NDArray, optional"))
        elif p.default is inspect.Parameter.empty:
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
                inputs.append((p.name, "NDArray"))  # variadic-op leading arg
            else:
                arguments.append((p.name, "required", None))
        else:
            arguments.append((p.name, "%s, optional" %
                              _dmlc_type_name(p.default), repr(p.default)))
    return {
        "name": op.name,
        "description": (op.doc or "").strip(),
        "inputs": inputs,
        "arguments": arguments,
        "num_outputs": op.num_outputs,
        "aliases": list(op.aliases),
    }


def op_doc(name: str) -> str:
    """Render op_info as a reference-style docstring (the text
    MXSymbolGetAtomicSymbolInfo feeds into generated wrappers)."""
    info = op_info(name)
    lines = [info["name"], ""]
    if info["description"]:
        lines += [info["description"], ""]
    if info["inputs"]:
        lines.append("Inputs:")
        for n, t in info["inputs"]:
            lines.append("    %s : %s" % (n, t))
        lines.append("")
    if info["arguments"]:
        lines.append("Parameters:")
        for n, t, d in info["arguments"]:
            lines.append("    %s : %s%s" % (n, t,
                                            "" if d is None
                                            else ", default=%s" % d))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Invocation
# ---------------------------------------------------------------------------


# AMP cast policy (contrib/amp): when active, inputs of ops in `lo` are
# cast to the low-precision target and inputs of ops in `hi` to float32
# before dispatch — the runtime analog of the reference's ReducePrecision
# graph pass (src/nnvm/low_precision_pass.cc).
AMP_POLICY: Dict[str, Any] = {"active": False, "target": None,
                              "lo": frozenset(), "hi": frozenset(),
                              "cond": {}}


def _amp_cast_inputs(op: Op, arrays, attrs=None):
    if not AMP_POLICY["active"]:
        return arrays
    name = op.name
    cond = AMP_POLICY["cond"].get(name)
    if cond is not None and attrs is not None \
            and str(attrs.get(cond[0])) in cond[1]:
        tgt = jnp.float32      # conditional fp32 (e.g. softrelu Activation)
    elif name in AMP_POLICY["lo"]:
        tgt = AMP_POLICY["target"]
    elif name in AMP_POLICY["hi"]:
        tgt = jnp.float32
    else:
        return arrays
    return [a.astype(tgt)
            if a is not None and hasattr(a, "dtype")
            and jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != tgt
            else a for a in arrays]


def invoke_raw(op: Op, arrays: Sequence[Any], **attrs):
    """Run op.fn on raw jax arrays (trace-safe path)."""
    if op.needs_rng and "key" not in attrs:
        from .. import rng

        attrs["key"] = rng.next_key()
    with jax.named_scope("op." + op.name):
        return op.fn(*_amp_cast_inputs(op, list(arrays), attrs), **attrs)


def invoke(name: str, inputs: Sequence[Any], out=None, **attrs):
    """Imperative invoke on NDArrays, with autograd recording.

    Mirrors Imperative::Invoke (``src/imperative/imperative.cc:89``): infer +
    execute + (if recording) tape.  Returns NDArray or list of NDArrays.
    """
    from .. import profiler

    if profiler.is_running():
        with profiler.Operator(name):
            return _invoke_impl(name, inputs, out, **attrs)
    return _invoke_impl(name, inputs, out, **attrs)


# eager-dispatch jit cache: one compiled executable per (op, static attrs)
# — the Imperative::Invoke fast path.  Without it each eager op executes
# primitive-by-primitive (one tiny dispatch per jnp call); with it the whole
# op body is a single cached XLA computation, which is what makes
# non-hybridized Gluon usable (the reference's imperative path is its fast
# path for the same reason: one fused engine push per op).
_EAGER_JIT: Dict[Any, Any] = {}


def _attr_key(v):
    if isinstance(v, (list,)):
        return tuple(_attr_key(x) for x in v)
    hash(v)
    return v


def _eager_fn(op: Op, attrs):
    """Jitted op body with attrs baked static, or None when not cacheable
    (unhashable attrs like subgraph Symbols, rng key operands, or ops
    flagged no_trace e.g. data-dependent-shape kernels)."""
    if op.no_trace or op.needs_rng:
        return None
    from .. import autograd, tracing

    if tracing.current_trace() is not None:
        # inside a whole-graph trace (CachedOp/Executor) the op body is
        # being traced into the outer program — a nested jit is pure
        # overhead AND would poison the cache with the trace's train mode
        return None
    try:
        # ambient train mode is baked into the traced program (BatchNorm /
        # Dropout read it at trace time), so it must be part of the key
        key = (op.name, autograd.is_training(), tuple(sorted(
            (k, _attr_key(v)) for k, v in attrs.items())))
        hash(key)
    except TypeError:
        return None
    fn = _EAGER_JIT.get(key)
    if fn is None:
        fn = jax.jit(functools.partial(op.fn, **attrs))
        _EAGER_JIT[key] = fn
    return fn


def _invoke_impl(name: str, inputs: Sequence[Any], out=None, **attrs):
    from .. import autograd
    from ..ndarray import NDArray

    op = OPS[name] if name in OPS else get_op(name)
    datas = [
        None if i is None else (i._data if isinstance(i, NDArray) else jnp.asarray(i))
        for i in inputs
    ]
    datas = _amp_cast_inputs(op, datas, attrs)

    if op.needs_rng:
        from .. import rng

        attrs.setdefault("key", rng.next_key())

    recording = (
        autograd.is_recording()
        and op.differentiable
        and any(autograd.requires_grad(i) for i in inputs if isinstance(i, NDArray))
    )
    jfn = _eager_fn(op, attrs)
    if jfn is None:
        # the body is traced into an outer program (or cannot be cached):
        # name it there, so the compiled program's op names say which op
        # built each instruction (docs/PROFILING.md)
        def jfn(*a):
            with jax.named_scope("op." + op.name):
                return op.fn(*a, **attrs)

    if recording:
        # differentiate only wrt non-None tensor inputs
        live = [j for j, d in enumerate(datas) if d is not None]

        def fn(*xs, _datas=tuple(datas), _live=tuple(live)):
            full = list(_datas)
            for j, x in zip(_live, xs):
                full[j] = x
            return jfn(*full)

        out_datas, vjp_fn = jax.vjp(fn, *[datas[j] for j in live])
        live_inputs = [inputs[j] for j in live]
    else:
        out_datas = jfn(*datas)

    multi = isinstance(out_datas, (tuple, list))
    outs_list = list(out_datas) if multi else [out_datas]
    nd_outs = [NDArray(o) for o in outs_list]

    if recording:
        node = autograd.TapeNode(vjp_fn, live_inputs, nd_outs, name=name)
        autograd.attach_node(nd_outs, node)

    if out is not None:
        # write into provided output buffer(s) — reference kWriteTo semantics.
        # Fewer buffers than outputs is allowed (trailing state outputs are
        # dropped, matching reference ops whose extra states are mutated
        # internally); MORE is an error — the surplus handles would silently
        # keep stale data.
        outs = out if isinstance(out, (tuple, list)) else [out]
        if len(outs) > len(nd_outs):
            raise ValueError(
                "op %r produced %d output(s) but %d output buffer(s) were "
                "provided" % (name, len(nd_outs), len(outs)))
        for dst, src in zip(outs, nd_outs):
            dst._data = src._data
            dst._ag_node = getattr(src, "_ag_node", None)
            dst._ag_out_idx = getattr(src, "_ag_out_idx", 0)
        return out
    if multi:
        return nd_outs
    return nd_outs[0]
