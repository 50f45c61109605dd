"""Gluon Block / HybridBlock / CachedOp.

Parity: ``python/mxnet/gluon/block.py`` (Block.__call__ :688, HybridBlock
trace→CachedOp :932-969, hybridize :1042, save/load_parameters :416/:472).

TPU-native CachedOp: instead of taping a small nnvm graph and replaying it
through the engine (``src/imperative/cached_op.cc``), ``hybridize()`` traces
the block's *whole* forward into one pure function and ``jax.jit``s it — the
XLA program is the "static_alloc + static_shape" fast path by construction.
Under ``autograd.record`` the jitted program is differentiated with one
``jax.vjp`` call, so the tape holds a single node per hybrid block call
(backward = one more XLA program, as in cached_op.cc:1254).

Statefulness (BN running stats, dropout PRNG) is functionalized through
:mod:`..tracing`: aux writes surface as extra jit outputs committed after the
call; PRNG keys enter as explicit operands.
"""
from __future__ import annotations

import contextlib
import re
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from .. import autograd, profiler, rng, tracing
from ..ndarray import NDArray
from ..ndarray import ndarray as _nd_mod
from .parameter import DeferredInitializationError, Parameter, ParameterDict

_REMAT_STATE = threading.local()
_REMAT_STATE.active = False

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp",
           "pure_forward"]


def pure_forward(block, params, param_vals, inputs, training=False,
                 key=None):
    """Run ``block``'s forward as a pure function of explicit buffers:
    bind values in place of the Parameters inside a fresh TraceContext,
    run ``_forward_impl``, unwrap the outputs.  The serving engine
    (``serve/engine.py``) builds its inference programs on this;
    :class:`CachedOp` and the fused train step keep their own inlined
    copies of the ritual because they consume the trace context
    mid-flight (aux-write outputs, aux losses, the scaled-loss hook) —
    if the binding protocol ever changes, change all three.

    ``params`` are the Parameter objects (gradient AND aux), and
    ``param_vals`` the congruent raw arrays bound in their place inside
    a fresh :class:`~..tracing.TraceContext`; ``inputs`` is one raw
    array or a tuple of them.  Returns ``(out_vals, tc)``: the raw
    output value(s) in the block's own output structure (NDArray leaves
    unwrapped), and the trace context — callers that run with
    ``training=True`` read ``tc.aux_writes`` / ``tc.aux_losses`` from
    it; inference callers (``training=False``: BatchNorm uses running
    stats, dropout is identity) can ignore it.
    """
    tc = tracing.TraceContext(key, training=training)
    for p, v in zip(params, param_vals):
        tc.bindings[id(p)] = v
    tracing.push_trace(tc)
    try:
        with autograd.pause():
            args = inputs if isinstance(inputs, (list, tuple)) \
                else (inputs,)
            outs = block._forward_impl(*[NDArray(v) for v in args])
    finally:
        tracing.pop_trace()
    out_vals = jax.tree.map(
        lambda o: o._data if isinstance(o, NDArray) else o, outs,
        is_leaf=lambda x: isinstance(x, NDArray))
    return out_vals, tc


class _BlockScope:
    """Name scoping for automatic prefixes (block.py _BlockScope parity)."""

    _state = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def current():
        return getattr(_BlockScope._state, "value", None)

    @staticmethod
    def create(prefix, params, hint):
        current = _BlockScope.current()
        if current is None:
            if prefix is None:
                if not hasattr(_BlockScope._state, "counter"):
                    _BlockScope._state.counter = {}
                count = _BlockScope._state.counter.get(hint, 0)
                prefix = "%s%d_" % (hint, count)
                _BlockScope._state.counter[hint] = count + 1
            return prefix, ParameterDict(prefix, params)
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        parent_prefix = current._block.prefix
        parent_params = current._block._params
        full_prefix = parent_prefix + prefix
        return full_prefix, ParameterDict(full_prefix,
                                          params if params is not None
                                          else parent_params._shared)

    def __enter__(self):
        self._old_scope = _BlockScope.current()
        _BlockScope._state.value = self
        return self

    def __exit__(self, *exc):
        _BlockScope._state.value = self._old_scope


class Block:
    """Base building block (gluon.Block parity)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        hint = self._alias()
        self._prefix, self._params = _BlockScope.create(prefix, params, hint)
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: Dict[str, Parameter] = {}

    def _alias(self):
        return self.__class__.__name__.lower()

    # ------------------------------------------------------------------
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self):
        return self._scope

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):  # parity stub
        raise NotImplementedError("forward hooks: planned")

    def collect_params(self, select=None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update({k: v for k, v in self._params.items()})
            for name, p in self._reg_params.items():
                ret._params.setdefault(p.name, p)
        else:
            pattern = re.compile(select)
            ret.update({k: v for k, v in self._params.items() if pattern.match(k)})
            for name, p in self._reg_params.items():
                if pattern.match(p.name):
                    ret._params.setdefault(p.name, p)
        for child in self._children.values():
            ret.update(child.collect_params(select)._params)
        return ret

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)
        for child in self._children.values():
            child.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    # ------------------------------------------------------------------
    def _collect_params_with_prefix(self, prefix=""):
        """Structural param names ("0.weight") — format-stable across
        differently-prefixed but identically-structured blocks, matching the
        reference's save_parameters format (block.py:416)."""
        if prefix:
            prefix += "."
        ret = {prefix + name: p for name, p in self._reg_params.items()}
        for cname, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + cname))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        arg = {name: p.data() for name, p in params.items()}
        from ..ndarray import save as nd_save

        nd_save(filename, arg)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from ..ndarray import load as nd_load

        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        if loaded and params and not any(k in params for k in loaded):
            # fall back: file saved with full (prefixed) parameter names
            by_name = {p.name: p for p in params.values()}
            params = {k: by_name.get(k) for k in loaded if by_name.get(k)}
        for name, p in params.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise RuntimeError(
                    "Parameter %s is missing in file %s" % (name, filename))
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise RuntimeError(
                    "Parameters in file not in Block: %s" % sorted(extra))

    # alias parity with older API
    save_params = save_parameters
    load_params = load_parameters

    # ------------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        return self.forward(*args, **kwargs)

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        lines = ["%s summary:" % self.name]
        for name, p in self.collect_params().items():
            lines.append("  %-40s %s" % (name, p.shape))
        s = "\n".join(lines)
        print(s)
        return s

    def __repr__(self):
        children = "".join("\n  (%s): %s" % (k, repr(v).replace("\n", "\n  "))
                           for k, v in self._children.items())
        return "%s(%s)" % (type(self).__name__, children)


class CachedOp:
    """Whole-graph jit executor for a hybridized block (cached_op.cc analog)."""

    def __init__(self, block: "HybridBlock"):
        self._block = block
        self._jits: Dict[Any, Any] = {}
        self._aux_holders: List[Parameter] = []
        self._out_treedef = None
        self._gp: List[Parameter] = []
        self._aux: List[Parameter] = []

    def _collect(self):
        params = list(self._block.collect_params().values())
        self._gp = [p for p in params if p.grad_req != "null"]
        self._aux = [p for p in params if p.grad_req == "null"]

    def _build(self, training: bool, statics):
        gp_list, aux_list = self._gp, self._aux
        block = self._block
        cached = self

        def pure(gp_vals, aux_vals, in_vals, key):
            tc = tracing.TraceContext(key, training)
            for p, v in zip(gp_list, gp_vals):
                tc.bindings[id(p)] = v
            for p, v in zip(aux_list, aux_vals):
                tc.bindings[id(p)] = v
            tracing.push_trace(tc)
            try:
                with autograd.pause():
                    args = cached._unflatten_inputs(in_vals, statics)
                    outs = block._forward_impl(*args)
            finally:
                tracing.pop_trace()
            flat, treedef = jax.tree.flatten(
                outs, is_leaf=lambda x: isinstance(x, NDArray))
            cached._out_treedef = treedef
            out_vals = [o._data if isinstance(o, NDArray) else o for o in flat]
            holders, writes = tc.collect_aux()
            cached._aux_holders = holders
            return out_vals, writes

        return jax.jit(pure)

    @staticmethod
    def _split_inputs(args):
        """Partition call args (arbitrary pytrees of NDArrays + literals)
        into traced leaves + a hashable static skeleton."""
        leaves, treedef = jax.tree.flatten(
            list(args), is_leaf=lambda x: isinstance(x, NDArray))
        in_vals, statics = [], []
        for leaf in leaves:
            if isinstance(leaf, NDArray):
                statics.append(None)
                in_vals.append(leaf._data)
            else:
                statics.append(("lit", leaf))
        return in_vals, (treedef, tuple(statics))

    @staticmethod
    def _unflatten_inputs(in_vals, statics):
        treedef, leaf_statics = statics
        leaves, i = [], 0
        for s in leaf_statics:
            if s is None:
                leaves.append(NDArray(in_vals[i]))
                i += 1
            else:
                leaves.append(s[1])
        return jax.tree.unflatten(treedef, leaves)

    def __call__(self, *args):
        block = self._block
        # deferred init: fall back to one eager call (gluon does deferred init
        # on first call too), which also initializes shapes
        self._collect()
        if any(p._data is None for p in self._gp + self._aux):
            # deferred init: one eager pass initializes shapes (gluon does
            # deferred init on first call too); jit from the next call on
            out = block._forward_impl(*args)
            self._collect()
            return out

        in_vals, statics = self._split_inputs(args)
        training = autograd.is_training()
        jkey = (training, statics)
        if jkey not in self._jits:
            self._jits[jkey] = self._build(training, statics)
        jfn = self._jits[jkey]

        gp_vals = [p._data._data for p in self._gp]
        aux_vals = [p._data._data for p in self._aux]
        key = rng.next_key()

        recording = autograd.is_recording() and self._gp
        if recording:
            (out_vals, writes), vjp_fn = jax.vjp(
                lambda g, i: jfn(g, aux_vals, i, key), gp_vals, in_vals,
                has_aux=False)
        else:
            out_vals, writes = jfn(gp_vals, aux_vals, in_vals, key)

        out_nds = [NDArray(v) for v in out_vals]

        if recording:
            arg_leaves = [a for a in jax.tree.leaves(
                list(args), is_leaf=lambda x: isinstance(x, NDArray))
                if isinstance(a, NDArray)]
            nd_inputs = [p._data for p in self._gp] + arg_leaves

            def tape_vjp(cot, _vjp=vjp_fn, _n=len(out_vals),
                         _nw=len(writes)):
                cots = list(cot) if isinstance(cot, tuple) else [cot]
                # cotangent for aux writes = zeros (not differentiated)
                wcots = [jnp.zeros_like(w) for w in writes]
                gp_g, in_g = _vjp((cots, wcots))
                return list(gp_g) + list(in_g)

            node = autograd.TapeNode(tape_vjp, nd_inputs, out_nds,
                                     name="CachedOp(%s)" % block.name)
            autograd.attach_node(out_nds, node)

        # commit aux-state writes (BN running stats etc.)
        for holder, val in zip(self._aux_holders, writes):
            if isinstance(holder, Parameter):
                holder._data._data = val
            else:
                holder._data = val

        outs = jax.tree.unflatten(self._out_treedef, out_nds)
        return outs


class HybridBlock(Block):
    """Block that can be traced into one XLA program (gluon.HybridBlock)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op: Optional[CachedOp] = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape,
                           **kwargs)
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def infer_shape(self, *args):
        """Hook for layers with deferred-shape parameters."""
        raise DeferredInitializationError(
            "%s has uninitialized parameters and no shape inference; "
            "initialize() with explicit shapes" % self.name)

    def _gather_params(self):
        out = {}
        for name, p in self._reg_params.items():
            out[name] = p.data()
        return out

    def forward(self, x, *args):
        from ..symbol.symbol import Symbol

        if isinstance(x, Symbol):
            # symbolic trace (export/quantize path): params become vars and
            # nested blocks recurse through this same branch
            from .. import symbol as sym_mod

            params = {name: p.var()
                      for name, p in self._reg_params.items()}
            return self.hybrid_forward(sym_mod, x, *args, **params)
        if (self._active and tracing.current_trace() is None
                and isinstance(x, NDArray)):
            if self._cached_op is None:
                self._cached_op = CachedOp(self)
            return self._cached_op(x, *args)
        return self._forward_impl(x, *args)

    def _forward_impl(self, x, *args):
        """Forward body (never routes through CachedOp).  Inside a
        whole-graph trace it runs under ``jax.named_scope("<Class>.<name>")``
        so the compiled program's op names say which block built each
        instruction (docs/PROFILING.md); an eager call is not wrapped."""
        if tracing.current_trace() is None:
            return self._forward_body(x, *args)
        with jax.named_scope("%s.%s" % (type(self).__name__, self.name)):
            return self._forward_body(x, *args)

    def _forward_body(self, x, *args):
        from .. import ndarray as F  # noqa: N812

        try:
            params = self._gather_params()
        except DeferredInitializationError:
            self.infer_shape(x, *args)
            for p in self._reg_params.values():
                if p._data is None:
                    if p._deferred_init is not None:
                        p._finish_deferred_init(p.shape)
                    else:
                        raise
            params = self._gather_params()
        if tracing.current_trace() is not None \
                and not getattr(_REMAT_STATE, "active", False) \
                and isinstance(x, NDArray) and self._remat_wanted():
            return self._forward_remat(F, params, x, *args)
        return self.hybrid_forward(F, x, *args, **params)

    def _remat_wanted(self):
        if self._flags.get("remat") is not None:
            return bool(self._flags.get("remat"))
        from .. import config as _cfg

        v = str(_cfg.get("MXNET_BACKWARD_DO_MIRROR", "") or "").strip()
        if not v:
            return False
        try:
            return int(v) != 0  # dmlc::GetEnv parses a nonzero int
        except ValueError:
            return v.lower() in ("true", "yes", "on")

    def _forward_remat(self, F, params, x, *args):  # noqa: N803
        """Gradient rematerialization: wrap this block's forward in
        ``jax.checkpoint`` so its interior activations are recomputed in
        the backward pass instead of saved (the reference's memory-mirror
        pass, ``src/nnvm/gradient.cc`` MXNET_BACKWARD_DO_MIRROR).  Opt in
        per block via ``hybridize(remat=True)`` (cascades; the outermost
        opted-in block on each call path becomes the remat region) or
        globally via MXNET_BACKWARD_DO_MIRROR=1.  Aux-state writes (BN
        running stats) are routed through the checkpoint as outputs so
        they stay valid in the outer trace.  What an op names
        ``tracing.REMAT_KEEP`` is kept, not recomputed."""
        tc = tracing.current_trace()
        pnames = sorted(params)
        pvals = [params[n]._data for n in pnames]
        all_in = (x,) + args
        arr_idx = [i for i, a in enumerate(all_in) if isinstance(a, NDArray)]
        arr_vals = [all_in[i]._data for i in arr_idx]
        shape_meta = {"treedef": None, "aux": []}

        def inner(arr_vals, pvals):
            full = list(all_in)
            for i, v in zip(arr_idx, arr_vals):
                full[i] = NDArray(v)
            nd_params = {n: NDArray(v) for n, v in zip(pnames, pvals)}
            before = dict(tc.aux_writes)
            n_aux_loss = len(tc.aux_losses)
            _REMAT_STATE.active = True
            try:
                out = self.hybrid_forward(F, *full, **nd_params)
            finally:
                _REMAT_STATE.active = False
            # arbitrary pytree outputs (RNN cells return (out, [states]))
            flat, treedef = jax.tree.flatten(
                out, is_leaf=lambda o: isinstance(o, NDArray))
            shape_meta["treedef"] = treedef
            outs = [o._data if isinstance(o, NDArray) else o for o in flat]
            # aux values written inside carry inner tracers: lift them out
            # as checkpoint outputs and restore the outer dict/order
            writes = []
            shape_meta["aux"] = []
            for k in list(tc.aux_writes):
                h, v = tc.aux_writes[k]
                if k not in before:
                    shape_meta["aux"].append(h)
                    writes.append(v)
                    del tc.aux_writes[k]
                    if k in tc.aux_order:
                        tc.aux_order.remove(k)
                elif before[k][1] is not v:
                    shape_meta["aux"].append(h)
                    writes.append(v)
                    tc.aux_writes[k] = before[k]
            # aux losses (MoE load balancing) registered inside the
            # checkpoint also carry inner tracers: lift them out as
            # outputs and re-register in the outer trace
            losses = tc.aux_losses[n_aux_loss:]
            del tc.aux_losses[n_aux_loss:]
            # keep the GL004 origin bookkeeping aligned (tracing.py);
            # the lifted losses re-register below with the outer origin
            del tc.aux_loss_origins[n_aux_loss:]
            return outs, writes, losses

        outs, writes, losses = jax.checkpoint(
            inner, policy=jax.checkpoint_policies.save_only_these_names(
                tracing.REMAT_KEEP))(arr_vals, pvals)
        for h, v in zip(shape_meta["aux"], writes):
            tc.write_aux(h, v)
        for al in losses:
            tc.add_aux_loss(al)
        return jax.tree.unflatten(shape_meta["treedef"],
                                  [NDArray(o) for o in outs])

    def hybrid_forward(self, F, x, *args, **kwargs):  # noqa: N803
        raise NotImplementedError

    def shape_init(self, *input_shapes, dtype="float32"):
        """Finish deferred parameter init by tracing the forward abstractly.

        Runs one forward under ``jax.eval_shape`` — no FLOPs and no per-op
        compilation — which triggers each layer's deferred-shape resolution
        exactly like the reference's first-real-batch deferred init
        (``python/mxnet/gluon/block.py:688``) but in milliseconds instead of
        a full eager device pass.  Initializers still run eagerly on the
        resolved concrete shapes.  Inference mode: no aux state (BN running
        stats) is touched.
        """
        from .parameter import _bulk_materialize, shape_only_init

        specs = [jax.ShapeDtypeStruct(tuple(s), jnp.dtype(dtype))
                 for s in input_shapes]

        def probe(*vals):
            with autograd.pause():
                out = self._forward_impl(*[NDArray(v) for v in vals])
            flat, _ = jax.tree.flatten(
                out, is_leaf=lambda o: isinstance(o, NDArray))
            return [o._data if isinstance(o, NDArray) else o for o in flat]

        with profiler.Setup("mx.block.shape_init"):
            with shape_only_init():
                jax.eval_shape(probe, *specs)
            # shapes are now resolved; run all real initializers in one
            # program
            _bulk_materialize(list(self.collect_params().values()))
        return self

    def export(self, path, epoch=0):
        """Export to symbol-json + params files (block.py:1080 parity)."""
        from .. import symbol as sym_mod

        params = self.collect_params()
        inputs = [sym_mod.var("data")]
        out = self._trace_symbol(inputs)
        out.save("%s-symbol.json" % path)
        aux_names = set(out.list_auxiliary_states())
        arg = {}
        for name, p in params.items():
            tag = "aux:" if name in aux_names else "arg:"
            arg[tag + name] = p.data()
        from ..ndarray import save as nd_save

        nd_save("%s-%04d.params" % (path, epoch), arg)
        return "%s-symbol.json" % path, "%s-%04d.params" % (path, epoch)

    def _trace_symbol(self, inputs):
        # forward() routes Symbol inputs through the symbolic branch, so
        # nested children trace correctly too
        return self(*inputs)


class SymbolBlock(HybridBlock):
    """Wrap a Symbol + params as a Block (gluon SymbolBlock :1334)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from .. import symbol as sym_mod

        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(outputs)
        self._out_sym = outputs
        self._in_syms = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        in_names = {s.name for s in self._in_syms}
        for arg in outputs.list_arguments():
            if arg not in in_names:
                self.params.get(arg, allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod

        out = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        block = SymbolBlock(out, inputs)
        if param_file:
            from ..ndarray import load as nd_load

            loaded = nd_load(param_file)
            for k, v in loaded.items():
                name = k.split(":", 1)[-1]
                if name in block.params:
                    block.params[name].set_data(v)
        return block

    def forward(self, *args):
        bindings = {s.name: a for s, a in zip(self._in_syms, args)}
        for name, p in self.params.items():
            bindings[name] = p.data()
        return self._out_sym.eval_with(bindings)
