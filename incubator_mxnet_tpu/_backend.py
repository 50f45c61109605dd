"""What the package asks of the JAX backend it finds, in one place.

Two questions, each with one answer for every caller:

* :func:`pallas_interpret` — do the Pallas kernels run through the
  interpreter (XLA-CPU, the tests) or through Mosaic (a TPU)?
* :func:`use_compile_cache` — where does JAX keep its persistent
  compilation cache?

Importing this module initializes no backend.
"""
from __future__ import annotations

import os

import jax

__all__ = ["pallas_interpret", "use_compile_cache"]

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
