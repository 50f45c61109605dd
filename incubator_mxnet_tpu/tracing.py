"""Trace context: the functionalization bridge between eager NDArray semantics
and whole-graph jit.

MXNet semantics are stateful (in-place NDArray writes, BatchNorm aux-state
mutation, a global stateful PRNG).  XLA programs are pure.  When a CachedOp /
Executor traces a whole graph into one jitted function, stateful actions are
redirected here:

- ``next_key()``  — PRNG: eager mode advances the global philox state;
  inside a trace it derives a fresh key from the trace's key operand via
  ``fold_in`` on a Python-level counter (deterministic per trace).
- ``write_aux(param, value)`` — aux-state writes (e.g. BN running stats)
  are collected and returned as extra outputs of the jitted program, then
  committed to the real buffers by the caller.

This replaces the reference's engine-mediated mutation model
(``src/engine/threaded_engine.h`` versioned Vars) with a functional one.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

__all__ = ["TraceContext", "current_trace", "push_trace", "pop_trace",
           "REMAT_KEEP"]

_STATE = threading.local()

#: the ``jax.ad_checkpoint.checkpoint_name`` of what a block's remat region
#: (``hybridize(remat=True)``) keeps instead of recomputing.  Discrete
#: decisions such as a router's chosen experts: the recomputed forward is
#: another fusion with other roundings, and a decision made again near a tie
#: comes out differently, so the backward pass would differentiate a forward
#: that was never run.  And what is dear to make and cheap to hold: flash
#: attention's ``out`` and ``lse`` (docs/PROFILING.md has the list and the
#: rule for adding to it: milliseconds spared per GB held, read on the chip).
REMAT_KEEP = "remat_keep"

def _pop_hooks() -> List[Any]:
    """Per-thread observers called with the popped TraceContext on every
    pop_trace — the graftlint GL004 check (analysis/trace_lint.py)
    registers here for the duration of a lint trace to detect aux
    effects registered inside inner trace regions that have already
    been finalized.  Thread-local like the trace stack itself, so a
    lint window never observes another thread's pops."""
    if not hasattr(_STATE, "pop_hooks"):
        _STATE.pop_hooks = []
    return _STATE.pop_hooks


def _dynamic_trace():
    """The jax trace active right now.  Recorded per aux-effect
    registration so graftlint can tell 'registered in the trace that
    will consume it' from 'registered in an inner region that already
    finalized' (GL004)."""
    return jax.core.trace_ctx.trace


class TraceContext:
    def __init__(self, key: Optional[jax.Array], training: bool = True):
        self.key = key
        self.training = training
        self._counter = 0
        # aux writes keyed by object id, value = (holder, new_value)
        self.aux_writes: Dict[int, Any] = {}
        self.aux_order: List[int] = []
        # parameter bindings: id(Parameter) -> traced array standing in for
        # the parameter's buffer inside this trace
        self.bindings: Dict[int, Any] = {}
        # auxiliary scalar losses registered by blocks during the forward
        # (MoE load-balancing loss etc.); the fused train step adds their
        # sum to the task loss before differentiating
        self.aux_losses: List[Any] = []
        # jax trace active at each registration (parallel lists/dict;
        # consumed by graftlint GL004, maintained by _forward_remat when
        # it lifts effects out of a checkpoint region)
        self.aux_loss_origins: List[Any] = []
        self.aux_write_origins: Dict[int, Any] = {}

    def add_aux_loss(self, value, source=None):
        """Register a scalar auxiliary loss (e.g. an MoE load-balancing
        term) to be added to the training objective by the enclosing
        fused step.  ``source`` names the registering block for error
        messages."""
        shape = tuple(getattr(value, "shape", ()) or ())
        if shape != ():
            who = " registered by %s" % source if source else ""
            raise ValueError(
                "aux loss%s must be a scalar, got shape %s — a vector "
                "aux loss silently corrupts the training objective when "
                "the fused step sums it into the (scalar) task loss; "
                "reduce it first (e.g. .mean() or .sum())" % (who, shape))
        self.aux_losses.append(value)
        self.aux_loss_origins.append(_dynamic_trace())

    def next_key(self) -> jax.Array:
        if self.key is None:
            raise RuntimeError(
                "random op used inside a trace that was not given an rng key"
            )
        self._counter += 1
        return jax.random.fold_in(self.key, self._counter)

    def write_aux(self, holder, value):
        oid = id(holder)
        if oid not in self.aux_writes:
            self.aux_order.append(oid)
        self.aux_writes[oid] = (holder, value)
        self.aux_write_origins[oid] = _dynamic_trace()

    def collect_aux(self):
        """Return ([holders], [values]) in deterministic write order.
        Skips duplicated/stale order entries (a remat region may lift a
        write out and re-commit it, gluon/block.py _forward_remat)."""
        holders, values = [], []
        seen = set()
        for oid in self.aux_order:
            if oid in seen or oid not in self.aux_writes:
                continue
            seen.add(oid)
            h, v = self.aux_writes[oid]
            holders.append(h)
            values.append(v)
        return holders, values


def _stack() -> List[TraceContext]:
    if not hasattr(_STATE, "stack"):
        _STATE.stack = []
    return _STATE.stack


def current_trace() -> Optional[TraceContext]:
    s = _stack()
    return s[-1] if s else None


def push_trace(ctx: TraceContext) -> TraceContext:
    _stack().append(ctx)
    return ctx


def pop_trace() -> TraceContext:
    ctx = _stack().pop()
    for hook in list(_pop_hooks()):
        hook(ctx)
    return ctx
