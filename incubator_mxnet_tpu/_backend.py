"""What the package asks of the JAX backend it finds, in one place.

Two questions, each with one answer for every caller:

* :func:`pallas_interpret` — do the Pallas kernels run through the
  interpreter (XLA-CPU, the tests) or through Mosaic (a TPU)?
* :func:`use_compile_cache` — where does JAX keep its persistent
  compilation cache?

and one wrapper every Pallas kernel's caller takes,
:func:`lowered_once`.

Importing this module initializes no backend.
"""
from __future__ import annotations

import functools
import os

import jax

__all__ = ["pallas_interpret", "lowered_once", "use_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pallas_interpret() -> bool:
    """True exactly when the default backend is ``cpu``; False on
    ``tpu``.  Any other backend is an error: the kernels were written
    for Mosaic, and a silent interpreter run elsewhere would pass for
    the real thing."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        "Pallas kernels of this package run compiled on 'tpu' and "
        "interpreted on 'cpu'; the default jax backend is %r" % backend)


def lowered_once(mover):
    """``mover`` under ``jax.jit`` (the interpreter's answer is part of its
    key).  A ``pallas_call`` is traced and lowered anew wherever it is
    called, a tenth of a second each time, and a step runs every kernel of
    a layer forward, recomputed and backward, in every layer, with the
    same shapes; a jitted function is traced once a process and lowered
    once a program, and called (the decoder cell's ``setup_s``: +6 s
    without, PR 33)."""
    def keyed(interpret, *args):
        return mover(*args)

    keyed.__name__ = mover.__name__
    keyed = jax.jit(keyed, static_argnums=0)

    @functools.wraps(mover)
    def call(*args):
        return keyed(pallas_interpret(), *args)

    return call


def use_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory:
    what ``JAX_COMPILATION_CACHE_DIR`` says — JAX has read it from the
    environment already, and nothing sets it in code — else
    ``<checkout>/.jax_cache``.  The path is part of the cache key, so it
    never carries a pid, a time or a temporary name."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
