"""incubator-mxnet-tpu: a TPU-native deep learning framework with the
capabilities of Apache MXNet.

Built from scratch on JAX/XLA/Pallas: eager NDArray + autograd tape, symbolic
Symbol/Executor lowering whole graphs to single XLA programs, Gluon-style
blocks with hybridize→jit, mesh-parallel KVStore, and a TPU-first parallelism
layer (data/tensor/sequence/pipeline parallel over ``jax.sharding.Mesh``).

Usage mirrors the reference frontend::

    import incubator_mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()
"""
from __future__ import annotations

import sys as _sys
import time as _time

_IMPORT_T0 = _time.monotonic()  # the span ``mx.import`` starts here
_JAX_WAS_IMPORTED = "jax" in _sys.modules

__version__ = "0.1.0"

import jax as _jax

_IMPORT_JAX_T1 = _time.monotonic()

# float64/int64 are first-class dtypes in the reference (mshadow base.h);
# enable x64 so Cast/astype honor them. All framework defaults remain
# explicit float32, and python scalars stay weakly typed, so this does not
# change default numerics.
_jax.config.update("jax_enable_x64", True)

# Importing the package initializes no jax backend: the process that
# holds the chip is whichever first asks jax for its devices.

from . import base
from .base import MXNetError
from .context import Context, cpu, cpu_pinned, current_context, gpu, num_devices, num_gpus, tpu
from . import engine
from . import rng as _rng_core  # noqa: F401
from . import ops
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd
from . import random
from . import initializer
from . import initializer as init
from . import optimizer
from .optimizer import Optimizer
from . import lr_scheduler
from . import gluon
from . import metric
from . import callback
from . import util
from .util import is_np_array, set_np, reset_np
from .attribute import AttrScope
from .name import NameManager
from . import recordio
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import executor
from . import io
from . import module
from . import module as mod
from . import model
from . import test_utils
from . import numpy as np  # noqa: A004 - mx.np NumPy-compatible namespace
from . import numpy_extension as npx
from . import parallel
from . import kvstore
from . import kvstore as kv
from .kvstore import KVStore
from . import rnn
from . import contrib
from . import operator
from . import image
from . import profiler
from . import monitor
from .monitor import Monitor
from . import visualization
from . import visualization as viz
from . import runtime
from . import rtc
from . import subgraph
from . import config
from . import library
from . import resource
from . import tensorboard
from . import torch_bridge

# the set-up timeline (profiler.Setup): jax's own events from here on, and
# this import as its first span
profiler._listen_to_jax()
profiler.setup_span(
    "mx.import.jax", _IMPORT_T0, _IMPORT_JAX_T1,
    parent=profiler.setup_span("mx.import", _IMPORT_T0, _time.monotonic(),
                               jax_was_imported=_JAX_WAS_IMPORTED))

# MXNET_PROFILER_AUTOSTART / MXNET_PROFILER_MODE (env_var.md): begin
# profiling at import so short scripts get a trace without code changes
if config.get("MXNET_PROFILER_AUTOSTART"):
    profiler.set_config(aggregate_stats=True)
    profiler.set_state("run")
