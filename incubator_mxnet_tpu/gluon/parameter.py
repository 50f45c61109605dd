"""Gluon Parameter / ParameterDict.

Parity: ``python/mxnet/gluon/parameter.py`` — deferred shape init, grad_req,
lr_mult/wd_mult, save/load.  TPU-native: ``data()`` returns the live buffer
eagerly, or the trace-bound tracer inside a CachedOp/Executor trace (the
functional analog of the reference handing engine Vars to CachedOp).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from .. import autograd, profiler, tracing
from ..base import np_dtype
from ..context import Context, cpu, current_context
from ..ndarray import NDArray
from ..ndarray import ndarray as _nd_mod

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError"]


class DeferredInitializationError(Exception):
    """Parameter accessed before shape is known (parameter.py parity)."""


import contextlib
import threading

_SHAPE_ONLY = threading.local()


def _shape_only_mode() -> bool:
    return getattr(_SHAPE_ONLY, "on", False)


@contextlib.contextmanager
def shape_only_init():
    """Within this scope, deferred init only RESOLVES shapes: ``data()``
    returns an abstract zeros placeholder and the real initializer is NOT
    run.  Used by ``HybridBlock.shape_init`` to finish deferred shapes under
    ``jax.eval_shape`` without leaking tracers into parameter storage or the
    global PRNG (initializers run eagerly afterwards)."""
    prev = getattr(_SHAPE_ONLY, "on", False)
    _SHAPE_ONLY.on = True
    span = profiler.Setup("mx.params.shape_only")
    span.start()
    try:
        yield
    finally:
        span.stop()
        _SHAPE_ONLY.on = prev


class Parameter:
    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._stype = stype
        self._data: Optional[NDArray] = None
        self._grad: Optional[NDArray] = None
        self._deferred_init = None  # (init, ctx, default_init)
        self._sym_var = None

    # ------------------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        self._grad_req = req
        if self._data is not None:
            self._init_grad()

    def _shape_known(self):
        return self.shape is not None and all(s > 0 for s in self.shape)

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        if self._data is not None and not force_reinit:
            return
        if default_init is None:
            from .. import initializer

            default_init = initializer.Uniform()
        if not self._shape_known():
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init)
                return
            raise ValueError(
                "Cannot initialize Parameter %s because it has invalid shape %s"
                % (self.name, self.shape))
        self._finish_init(init, ctx, default_init)

    def _finish_init(self, init, ctx, default_init):
        from .. import initializer

        if isinstance(ctx, Context):
            ctx = [ctx]
        ctx = ctx or [current_context()]
        init = init or self.init or default_init
        if isinstance(init, str):
            init = initializer.registry_create(init)
        data = _nd_mod.zeros(self.shape, ctx=ctx[0], dtype=np_dtype(self.dtype))
        desc = initializer.InitDesc(self.name, attrs={})
        init(desc, data)
        self._data = data
        self._deferred_init = None
        self._init_grad()

    def _finish_deferred_init(self, shape):
        self.shape = tuple(shape)
        if self._deferred_init is None:
            raise DeferredInitializationError(self.name)
        if _shape_only_mode():
            return  # shape resolved; real init deferred to after the trace
        init, ctx, default_init = self._deferred_init
        self._finish_init(init, ctx, default_init)

    def _init_grad(self):
        if self._grad_req == "null":
            self._grad = None
            # the data array holds the buffer autograd attached to it
            autograd.mark_variables([self._data], [None], "null")
            return
        self._grad = _nd_mod.zeros(self.shape, dtype=np_dtype(self.dtype))
        autograd.mark_variables([self._data], [self._grad], [self._grad_req])

    # ------------------------------------------------------------------
    def data(self, ctx=None) -> NDArray:
        tc = tracing.current_trace()
        if tc is not None and id(self) in tc.bindings:
            return NDArray(tc.bindings[id(self)])
        if self._data is None:
            if self._deferred_init is not None:
                if _shape_only_mode() and self._shape_known():
                    # abstract placeholder — only valid inside eval_shape
                    return NDArray(jnp.zeros(self.shape, np_dtype(self.dtype)))
                raise DeferredInitializationError(
                    "Parameter %s has not been initialized yet (deferred)"
                    % self.name)
            raise RuntimeError(
                "Parameter %s has not been initialized. Call .initialize() "
                "first." % self.name)
        return self._data

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None) -> NDArray:
        if self._grad is None:
            raise RuntimeError(
                "Parameter %s does not have gradient (grad_req='null')" % self.name)
        return self._grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        return [self._data.context if self._data is not None else cpu()]

    def set_data(self, data):
        if isinstance(data, NDArray):
            data = data._data
        if self._data is None:
            self._data = NDArray(jnp.asarray(data))
            self.shape = self._data.shape
            self._init_grad()
        else:
            self._data._data = jnp.asarray(data, self._data.dtype)

    def zero_grad(self):
        if self._grad is not None:
            self._grad._data = jnp.zeros_like(self._grad._data)

    def reset_ctx(self, ctx):
        pass  # single logical device space under XLA

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            self._data._data = self._data._data.astype(np_dtype(dtype))
            self._init_grad()

    def var(self):
        if self._sym_var is None:
            from .. import symbol

            self._sym_var = symbol.var(self.name, shape=self.shape,
                                       dtype=self.dtype)
        return self._sym_var

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (self.name, self.shape, self.dtype)


def _bulk_materialize(params) -> None:
    """Materialize many pending parameters in ONE jitted program.

    Per-param eager init costs one small XLA compile per (op, shape) pair —
    ~60s for ResNet-50's ~160 parameters.  Tracing every initializer (and
    grad-buffer zeros) into a single program pays one compile total, and the
    persistent compilation cache carries it across processes.  Falls back to
    the per-param eager path if an initializer is not traceable (e.g. one
    that computes with raw numpy).
    """
    import jax

    from .. import initializer as _initmod

    pending = [p for p in params
               if p._data is None and p._deferred_init is not None
               and p._shape_known()]
    if not pending:
        return
    nbytes = sum(int(np.prod(p.shape)) * np.dtype(np_dtype(p.dtype)).itemsize
                 for p in pending)
    with profiler.Setup("mx.params.materialize", parameters=len(pending),
                        bytes=nbytes):
        recipes = []
        for p in pending:
            init, ctx, default_init = p._deferred_init
            init = init or p.init or default_init or _initmod.Uniform()
            if isinstance(init, str):
                init = _initmod.registry_create(init)
            recipes.append((p, init))

        def make():
            outs = []
            for p, init in recipes:
                data = _nd_mod.zeros(p.shape, dtype=np_dtype(p.dtype))
                init(_initmod.InitDesc(p.name, attrs={}), data)
                g = (jnp.zeros(p.shape, np_dtype(p.dtype))
                     if p._grad_req != "null" else None)
                outs.append((data._data, g))
            return outs

        try:
            outs = jax.jit(make)()
        except Exception:
            for p in pending:
                p._finish_deferred_init(p.shape)
            return
        for (p, _init), (v, g) in zip(recipes, outs):
            p._data = NDArray(v)
            p._deferred_init = None
            if p._grad_req != "null":
                p._grad = NDArray(g)
                autograd.mark_variables([p._data], [p._grad], [p._grad_req])
            else:
                p._grad = None


class Constant(Parameter):
    """Parameter fixed at a constant value (gluon.Constant parity)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = _nd_mod.array(value)
        self.value = value
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype)

        class _CInit:
            def __call__(self, desc, arr):
                arr._data = value._data

        self.init = _CInit()


class ParameterDict:
    """Ordered name → Parameter mapping with prefix (gluon ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params: Dict[str, Parameter] = {}
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def get(self, name, **kwargs) -> Parameter:
        """Create-or-retrieve a parameter named ``prefix + name``."""
        full = self._prefix + name
        if full in self._params:
            param = self._params[full]
            for k, v in kwargs.items():
                if k == "shape" and v is not None and param.shape is not None:
                    v = tuple(v)
                    inferred = tuple(
                        b if a in (0, -1, None) else a for a, b in zip(param.shape, v)
                    ) if len(v) == len(param.shape) else v
                    param.shape = inferred
            return param
        if self._shared is not None and full in self._shared:
            param = self._shared[full]
            self._params[full] = param
            return param
        param = Parameter(full, **kwargs)
        self._params[full] = param
        return param

    def get_constant(self, name, value=None) -> Constant:
        full = self._prefix + name
        if full in self._params:
            return self._params[full]
        c = Constant(full, value)
        self._params[full] = c
        return c

    def update(self, other):
        for k, v in other.items():
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        # batch all known-shape inits into one compiled program; params with
        # unknown shapes defer exactly as before
        bulk = []
        for p in self.values():
            if p._data is not None:
                if not force_reinit:
                    continue
                p._data = None
                p._grad = None
            if p._shape_known():
                p._deferred_init = (None, ctx, init)
                bulk.append(p)
            else:
                p.initialize(init=None, ctx=ctx, default_init=init,
                             force_reinit=force_reinit)
        _bulk_materialize(bulk)

    def zero_grad(self):
        for p in self.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        pass

    def setattr(self, name, value):
        for p in self.values():
            setattr(p, name, value)

    def save(self, filename, strip_prefix=""):
        from ..ndarray import save as nd_save

        arg = {}
        for name, p in self.items():
            n = name[len(strip_prefix):] if name.startswith(strip_prefix) else name
            arg[n] = p.data()
        nd_save(filename, arg)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from ..ndarray import load as nd_load

        loaded = nd_load(filename)
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise RuntimeError("Parameter %s missing in file %s" % (name, filename))
        if not ignore_extra:
            extra = set(loaded) - set(self._params)
            if extra:
                raise RuntimeError("Extra parameters in file: %s" % sorted(extra))

    def __repr__(self):
        return "ParameterDict(%s)" % ", ".join(self.keys())
