"""Runtime-compiled user kernels (``mx.rtc`` parity).

Reference: ``CudaModule`` (``python/mxnet/rtc.py:42`` + NVRTC compile in
``src/common/rtc.cc:49``) — user supplies CUDA C source at runtime, gets
launchable kernels.

TPU-native: the kernel language is **Pallas**.  ``PallasModule`` takes
Python source that defines Pallas kernel functions (``pl``, ``pltpu``,
``jax``, ``jnp`` are pre-imported into the compilation namespace, the
moral analog of nvrtc's builtin headers), compiles it at runtime, and
``get_kernel`` wraps a function for launching: grid/block specs map to the
reference's grid/block launch geometry, and the same code runs interpreted
on CPU backends (like the reference's debugging path) and Mosaic-compiled
on TPU.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _backend
from .ndarray import NDArray

__all__ = ["PallasModule", "CudaModule"]


class _Kernel:
    """Launchable kernel (rtc.py Kernel.launch analog)."""

    def __init__(self, fn, name):
        self._fn = fn
        self.name = name

    def launch(self, args: Sequence[Any], out_shape, grid=None,
               in_specs=None, out_specs=None, scratch_shapes=(),
               interpret: Optional[bool] = None):
        """Run the kernel via ``pl.pallas_call``.

        args: NDArrays/jax arrays; out_shape: jax.ShapeDtypeStruct (or a
        (shape, dtype) tuple, or list thereof); grid/in_specs/out_specs:
        pallas launch geometry (the reference's grid_dims/block_dims).
        """
        if interpret is None:
            interpret = _backend.pallas_interpret()

        def norm_shape(s):
            if isinstance(s, jax.ShapeDtypeStruct):
                return s
            shape, dtype = s
            return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))

        multi = isinstance(out_shape, (list, tuple)) \
            and not (len(out_shape) == 2 and isinstance(out_shape[0],
                                                        (list, tuple))
                     and isinstance(out_shape[1], (str, type(jnp.float32))))
        shapes = [norm_shape(s) for s in out_shape] if multi \
            else norm_shape(out_shape)
        kwargs = {}
        if grid is not None:
            kwargs["grid"] = grid
        if in_specs is not None:
            kwargs["in_specs"] = in_specs
        if out_specs is not None:
            kwargs["out_specs"] = out_specs
        if scratch_shapes:
            kwargs["scratch_shapes"] = list(scratch_shapes)
        call = pl.pallas_call(self._fn, out_shape=shapes,
                              interpret=interpret, **kwargs)
        vals = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                for a in args]
        out = call(*vals)
        if isinstance(out, (list, tuple)):
            return [NDArray(o) for o in out]
        return NDArray(out)

    __call__ = launch


class PallasModule:
    """Compile Pallas source at runtime (CudaModule analog).

    Example::

        src = '''
        def scale_kernel(x_ref, o_ref, *, factor=2.0):
            o_ref[...] = x_ref[...] * factor
        '''
        mod = mx.rtc.PallasModule(src, exports=["scale_kernel"])
        k = mod.get_kernel("scale_kernel")
        y = k.launch([x], out_shape=(x.shape, x.dtype))
    """

    def __init__(self, source: str, options=(), exports=()):
        self.source = source
        self.exports = tuple(exports)
        ns = {"jax": jax, "jnp": jnp, "pl": pl, "pltpu": pltpu}
        exec(compile(source, "<rtc.PallasModule>", "exec"), ns)  # noqa: S102
        self._ns = ns
        for name in self.exports:
            if name not in ns:
                raise ValueError("export %r not defined in source" % name)

    def get_kernel(self, name: str, signature: str = "") -> _Kernel:
        """``signature`` accepted for reference API parity (types come from
        the launch arguments under JAX tracing, so it is unused)."""
        if name not in self._ns or not callable(self._ns[name]):
            raise ValueError("kernel %r not found" % name)
        return _Kernel(self._ns[name], name)


# The reference name: user code does mx.rtc.CudaModule(...); keep the name
# as an alias so ported scripts fail with a clear message only if they pass
# actual CUDA C (exec raises SyntaxError) rather than an AttributeError.
CudaModule = PallasModule
