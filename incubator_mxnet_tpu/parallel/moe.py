"""Expert parallelism: mixture-of-experts FFN with experts sharded over
the ``ep`` mesh axis.

Not present in the reference (its closest artifact is manual group2ctx model
parallelism); on TPU this is a natural capability of the sharding layer:
experts live on the leading (expert) dim, annotated with P('ep', ...), and
GSPMD turns the dispatch/combine einsums into all-to-alls over ICI.

Training: ``return_aux=True`` also returns the Switch-style load-balancing
loss ``E * sum_e f_e * p_e`` (f_e = fraction of routing decisions sent to
expert e, p_e = mean router probability), computed on the PRE-capacity
router decisions so overflowed tokens still push the router toward
balance.  ``capacity_factor`` drops routing decisions beyond
``ceil(capacity_factor * T * top_k / E)`` per expert (GShard k-major
priority: every rank-1 choice beats any rank-2 choice); dropped tokens
pass through with zero expert contribution, exactly like the reference
MoE systems' overflow path.

The second half of the file is the NO-DROP expert layer of a chip that holds
a share of the experts (``experts_held = (first, count)``), as four ops of
the registry: ``_contrib_moe_router`` routes every token over ALL the
experts (by the model's scoring rule: sigmoid scores, top-k with a
selection-only bias, normalise and scale; or top-k of the logits and a
softmax over the chosen; the selection optionally centred over blocks
of tokens) and counts the assignments per expert;
``_contrib_moe_dispatch`` sorts the assignments that fall on the experts
held here by expert and gathers their tokens' rows into that order;
``_contrib_moe_experts`` is ONE grouped product per matrix over those rows
(``lax.ragged_dot``: an expert's matrix meets only its own rows) with the
gate between them;
``_contrib_moe_combine`` sums each token's weighted results.  What absent experts would add is left out.
The row buffer holds every assignment there can be (tokens x top-k), so no
token is ever dropped, whatever the router does, and what is done with it
stops at ``n = sum(sizes)``, the rows that are in a group, a number only the
step itself knows: the grouped product's work follows the groups, and
everything else but one pass is a Pallas kernel of ``moe_rows.py`` whose
grid covers the whole buffer and whose steps past ``n`` do nothing: three
of the four passes that move rows (back to tokens, and both transposes),
and the expert op's elementwise passes between its products (the gate, in
the backward pass its transpose and the sum of the two cotangents of the
rows, which feed two products: JAX's own ``add_any`` cannot be given an
extent, so the op's backward is written out, ``_experts_bwd``).  The movers
take the rows and the tokens
at the model's own width; a row is padded to whole (8, 128) tiles of words
only where a DMA moves it (a slab: 2,560 bf16 values in 8 KiB), inside the
kernels, so nothing of the buffer's size is padded or cut around them.  A
kernel is one op in the device trace whatever its grid does, which a
``lax.cond`` between a small buffer and the full one is not (tried on the
chip, PR 30: 6 % of a step faster, and the ``conditional`` stands in the
trace as one op OVER its own ops, so that no sum of ops is the step's
time).  The one pass left, the rows into
expert order, stays XLA's gather over every row: the buffer it fills is
what a caller may take a statistic of (a float8 scale a tensor), so all of
it is written.  Past ``n`` the gate's and the product's results and every
cotangent of the op hold whatever the memory held before, NaN included;
nothing reads them
(``tests/test_moe_window_kernels.py`` sets them to NaN, and under the
interpreter ``tests/test_moe_held_rows.py`` finds them NaN).  Scalars are
moved between the assignments' order and the rows' by sorts that carry them:
XLA's gather of tokens x top-k scalars takes ten times a sort's time on a
TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import _backend
from ..tracing import REMAT_KEEP
from . import moe_rows

__all__ = ["moe_ffn", "moe_ffn_sharded", "load_balancing_loss",
           "moe_route", "moe_dispatch", "moe_experts", "moe_combine"]


def load_balancing_loss(probs, top_idx):
    """Switch/GShard auxiliary loss over router decisions.

    probs: (T, E) router softmax; top_idx: (T, K) selected experts.
    Returns ``E * sum_e f_e * p_e`` — minimized (→ 1.0) by a uniform
    router.  The f term is a hard count (no gradient); the p term pulls
    router probabilities toward balance.
    """
    num_experts = probs.shape[-1]
    sel = jax.nn.one_hot(top_idx, num_experts, dtype=probs.dtype)  # (T,K,E)
    f = jnp.mean(jnp.sum(sel, axis=1), axis=0) / sel.shape[1]  # (E,)
    p = jnp.mean(probs, axis=0)  # (E,)
    return num_experts * jnp.sum(f * p)


def moe_ffn(x, gate_w, w1, b1, w2, b2, top_k=1, capacity_factor=None,
            return_aux=False):
    """Token-choice MoE FFN (dense math; shardable).

    x: (tokens, d); gate_w: (d, E); w1: (E, d, hidden); w2: (E, hidden, d).
    Top-k gating with softmax-renormalized weights over the selected
    experts.  With ``capacity_factor``, each expert accepts at most
    ``ceil(capacity_factor * T * top_k / E)`` routing decisions (k-major
    priority); the rest are dropped from the combine.  With
    ``return_aux``, also returns the load-balancing loss.
    """
    num_experts = gate_w.shape[-1]
    logits = x @ gate_w  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, top_k)  # (T, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # dispatch tensor: (T, K, E) one-hot -> (E, T) combine weights
    disp = jax.nn.one_hot(top_idx, num_experts, dtype=x.dtype)  # (T,K,E)
    if return_aux:
        # pre-capacity decisions: overflowed tokens still teach the router
        aux = load_balancing_loss(probs, top_idx)
    if capacity_factor is not None:
        tokens = x.shape[0]
        capacity = max(1, int(math.ceil(
            capacity_factor * tokens * top_k / num_experts)))
        # k-major priority (GShard): all rank-1 choices outrank rank-2.
        # positions are COUNTS — computed in int32, not the activation
        # dtype: a bf16 cumsum loses integer precision past 256 decisions
        # and keeps/drops the wrong routing decisions at the boundary
        sel = jnp.swapaxes(disp, 0, 1).reshape(top_k * tokens, num_experts)
        sel_i = (sel > 0).astype(jnp.int32)
        pos = jnp.cumsum(sel_i, axis=0) - sel_i  # earlier decisions/expert
        sel = sel * (pos < capacity).astype(sel.dtype)
        disp = jnp.swapaxes(sel.reshape(top_k, tokens, num_experts), 0, 1)
    combine = jnp.einsum("tk,tke->te", top_p.astype(x.dtype), disp)  # (T,E)
    # expert compute on all tokens, masked-combined (dense formulation —
    # efficient when E is sharded over ep: einsums become a2a + local ffn)
    h = jnp.einsum("td,edf->etf", x, w1) + b1[:, None, :]
    h = jax.nn.gelu(h)
    y = jnp.einsum("etf,efd->etd", h, w2) + b2[:, None, :]
    out = jnp.einsum("etd,te->td", y, combine)
    if return_aux:
        return out, aux
    return out


def moe_ffn_sharded(x, gate_w, w1, b1, w2, b2, mesh: Mesh, top_k=1,
                    axis_name="ep", capacity_factor=None, return_aux=False):
    """Run moe_ffn with experts sharded over ``axis_name`` via GSPMD."""
    from ..analysis import LintReport, check_partition_spec

    # eager GL002: a bad axis name or an expert tensor of unexpected
    # rank would otherwise surface as a GSPMD mis-shard, not an error
    diags = []
    for name, arr, spec in (("w1", w1, P(axis_name, None, None)),
                            ("w2", w2, P(axis_name, None, None)),
                            ("b1", b1, P(axis_name)),
                            ("b2", b2, P(axis_name))):
        diags += check_partition_spec(spec, arr.ndim, mesh,
                                      where="moe_ffn_sharded(%s)" % name,
                                      operand=name)
    if gate_w.shape[-1] % dict(mesh.shape).get(axis_name, 1):
        raise ValueError(
            "moe_ffn_sharded: %d experts do not divide over mesh axis "
            "%r of size %d" % (gate_w.shape[-1], axis_name,
                               dict(mesh.shape).get(axis_name, 1)))
    LintReport(diags).raise_if_errors()
    e_spec = NamedSharding(mesh, P(axis_name))
    repl = NamedSharding(mesh, P())
    fn = jax.jit(functools.partial(moe_ffn, top_k=top_k,
                                   capacity_factor=capacity_factor,
                                   return_aux=return_aux),
                 in_shardings=(repl, repl, NamedSharding(mesh, P(axis_name, None, None)),
                               e_spec,
                               NamedSharding(mesh, P(axis_name, None, None)),
                               e_spec),
                 out_shardings=(repl, repl) if return_aux else repl)
    return fn(x, gate_w, w1, b1, w2, b2)


# ---------------------------------------------------------------------------
# the no-drop expert layer of a chip that holds (first, count) of the experts
# ---------------------------------------------------------------------------

def _one_hot(idx, n):
    """(..., n) float32 one-hot of int indices; an index outside [0, n)
    gives a row of zeros.  Counting and picking through it keeps both
    directions dense sums (a scatter of T * K scalars is serial on a TPU)."""
    return (idx[..., None] == jnp.arange(n, dtype=idx.dtype)).astype(
        jnp.float32)


def moe_route(x, router_weight, select_bias, top_k=1, route_norm=True,
              route_scale=1.0, score="sigmoid", centred=0):
    """Token-choice routing over all the experts.

    x: (T, d); router_weight: (E, d); select_bias: (E,), added to the scores
    for the SELECTION only; with ``centred`` = n the selection also reads
    each expert's score LESS ITS MEAN over the block of n consecutive tokens
    the token lies in (T a multiple of n), a selection bias that follows the
    scores along the sequence and as they move, so that what neighbouring
    tokens share in an expert's score chooses nothing.  The weights never
    see either.  ``score`` is the model's scoring rule, in float32 either
    way.  ``"sigmoid"``: the scores are ``sigmoid(x Wr^T)``
    and the weights the chosen scores, normalised over the chosen if
    ``route_norm``.  ``"softmax"``: the scores are the logits ``x Wr^T``
    themselves and the weights a softmax over the CHOSEN logits (they add up
    to one, so ``route_norm`` changes nothing and is not applied).  Returns
    ``(weights (T, K) float32, chosen experts (T, K) int32, assignments per
    expert (E,) float32)``, the weights times ``route_scale``.
    """
    if score not in ("sigmoid", "softmax"):
        raise ValueError("moe_route: score=%r" % (score,))
    if centred and x.shape[0] % centred:
        raise ValueError("moe_route: %d tokens in blocks of centred=%d"
                         % (x.shape[0], centred))
    scores = jnp.dot(
        x.astype(jnp.float32), router_weight.astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(scores)
    select = scores
    if centred:
        blocks = scores.reshape((-1, centred, scores.shape[-1]))
        select = (blocks - jnp.mean(blocks, 1, keepdims=True)).reshape(
            scores.shape)
    _, sel = jax.lax.top_k(
        select + jax.lax.stop_gradient(select_bias.astype(jnp.float32)),
        top_k)
    # a remat region keeps the choice: made again from the recomputed
    # scores, a near-tie falls the other way and the backward pass would
    # differentiate a routing the forward never ran
    sel = checkpoint_name(sel.astype(jnp.int32), REMAT_KEEP)
    chosen = _one_hot(sel, scores.shape[-1])             # (T, K, E)
    w = jnp.einsum("tke,te->tk", chosen, scores)
    if score == "softmax":
        w = jax.nn.softmax(w, -1)
    elif route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * route_scale, sel, jnp.sum(chosen, (0, 1))


def _in_row_order(row, values):
    """(T, K) values by assignment, as (R,) by row: ``row`` is a permutation,
    and a sort by it that carries the values costs a tenth of XLA's gather
    of T * K scalars."""
    return jax.lax.sort((row.reshape(-1), values.reshape(-1)),
                        num_keys=1)[1]


@_backend.lowered_once
def _rows_to_tokens(g, row, n):
    # each token's gradient is the sum over ITS held assignments' rows (the
    # transpose of x[token] would be a scatter-add over the rows with
    # repeated targets); of g only the rows below n are read
    return moe_rows.tokens_from_rows(g, row, n)


@jax.custom_vjp
def _gather_rows(x, order, row, n):
    # XLA's gather, over every row of the buffer: what a kernel that stops
    # at n spares here (0.25 against 0.42 ms on the chip) it spares by
    # leaving the rows past n unwritten, and whoever takes a statistic of
    # the whole buffer (a float8 scale a tensor) would read NaN there
    return x[order // row.shape[1]]


def _gather_rows_fwd(x, order, row, n):
    return _gather_rows(x, order, row, n), (row, n)


def _gather_rows_bwd(res, g):
    row, n = res
    return _rows_to_tokens(g, row, n), None, None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def moe_dispatch(x, sel, experts_held=(0, 1)):
    """Rows for the grouped product.  The assignments (token, choice) that
    fall on experts [first, first + count) are sorted by expert and their
    tokens' rows gathered in that order; every other assignment sorts after
    them.  Returns ``(rows (R, d), rows per held expert (count,) int32, row
    of each assignment (T, K) int32, assignment of each row (R,) int32)``
    with R = T * K: the buffer holds every assignment there can be, so none
    is dropped, whatever the router does.  The first ``sum(sizes)`` rows
    are in a group; the rest belong to none, and what the product makes of
    them is never read."""
    first, count = experts_held
    t, k = sel.shape
    local = sel - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    row = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    sizes = jnp.sum(_one_hot(key, count), 0).astype(jnp.int32)
    return _gather_rows(x, order, row, jnp.sum(sizes)), sizes, row, order


def _gate(act):
    """``(act, forward(a, b, n), backward(a, b, g, n))``: an activation
    with ``h = act(a) * b`` and its transpose as passes over the rows below
    ``n``."""
    def gate(a, b):
        return act(a) * b

    @_backend.lowered_once
    def _gate_rows(a, b, n):
        h, = moe_rows.on_held_rows(gate, 1, n, a, b, name="moe_gate")
        return h

    @_backend.lowered_once
    def _gate_rows_bwd(a, b, g, n):
        # (g * b * act'(a), g * act(a)) in ONE pass, act' by JAX's own rule
        return moe_rows.on_held_rows(
            lambda a, b, g: jax.vjp(gate, a, b)[1](g), 2, n, a, b, g,
            name="moe_gate_bwd")

    return act, _gate_rows, _gate_rows_bwd


#: the gate of a gated feed-forward by the name a model gives its
#: activation: (the activation, the gate over the held rows, its transpose)
_GATES = {"silu": _gate(jax.nn.silu), "relu": _gate(jax.nn.relu)}


@_backend.lowered_once
def _sum_rows(x, y, n):
    total, = moe_rows.on_held_rows(jnp.add, 1, n, x, y, name="moe_row_sum")
    return total


def _grouped(sizes, dtype):
    # for operands narrower than float32 the precision is pinned: the TPU's
    # grouped-product kernel refuses them under a process-wide
    # jax_default_matmul_precision of "highest"
    return functools.partial(
        jax.lax.ragged_dot, group_sizes=sizes,
        precision=None if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _experts(rows, w1, w3, w2, sizes, act):
    return _experts_fwd(rows, w1, w3, w2, sizes, act)[0]


def _experts_fwd(rows, w1, w3, w2, sizes, act):
    dot = _grouped(sizes, rows.dtype)
    a, b = dot(rows, w1), dot(rows, w3)
    _, gate, _ = _GATES[act]
    h = gate(a, b, jnp.sum(sizes))
    return dot(h, w2), (rows, a, b, h, w1, w3, w2, sizes)


def _experts_bwd(act, res, dys):
    # the backward is ours because JAX's own sums the two cotangents of
    # ``rows`` (it feeds two products) by an ``add_any`` over the whole
    # buffer, which cannot be given an extent; every product below is the
    # transpose JAX makes of ``lax.ragged_dot``
    rows, a, b, h, w1, w3, w2, sizes = res
    n = jnp.sum(sizes)
    dot = _grouped(sizes, rows.dtype)
    dh, dw2 = jax.vjp(dot, h, w2)[1](dys)
    _, _, gate_bwd = _GATES[act]
    da, db = gate_bwd(a, b, dh, n)
    from_a, dw1 = jax.vjp(dot, rows, w1)[1](da)
    from_b, dw3 = jax.vjp(dot, rows, w3)[1](db)
    return _sum_rows(from_a, from_b, n), dw1, dw3, dw2, None


_experts.defvjp(_experts_fwd, _experts_bwd)


def moe_experts(rows, w1, w3, w2, sizes, act="silu"):
    """Gated feed-forward of each expert over its own rows, ``(act(x W1) *
    (x W3)) W2`` with ``act`` the gate's activation (``"silu"`` or
    ``"relu"``), as grouped products: rows (R, d) sorted by expert,
    ``sizes`` (G,) rows each; w1, w3: (G, d, f); w2: (G, f, d).  Between
    the products the gate, and in the backward pass its transpose and the
    sum of the two cotangents of ``rows``, are passes over the rows below
    ``n = sum(sizes)`` (``moe_rows.on_held_rows``; float32 inside, one
    rounding).  All of ``rows`` is read by nobody but the products, which
    follow the groups; what comes out for the rows past ``n``, here and in
    the cotangent of ``rows``, is not defined: past the block of ``n`` it
    is not written."""
    return _experts(rows, w1, w3, w2, sizes, act)


@_backend.lowered_once
def _weighted_rows_to_tokens(ys, weights, row, n):
    return moe_rows.tokens_from_rows(ys, row, n, weights)


@_backend.lowered_once
def _weighted_tokens_to_rows(ys, weights, row, order, n, g):
    # each row below n takes its token's gradient times its weight, and in
    # the same pass its product with its own row of ys, which is the
    # weight's gradient; the rows past n's block are not written
    dys, dots = moe_rows.rows_from_tokens(
        g, order // row.shape[1], n, _in_row_order(row, weights), ys)
    # back by assignment: order is row's inverse
    dw = _in_row_order(order, dots).reshape(row.shape)
    return dys, jnp.where(row < n, dw, 0.0)


@jax.custom_vjp
def _weighted_sum(ys, weights, row, order, n):
    return _weighted_rows_to_tokens(ys, weights, row, n)


def _weighted_sum_fwd(ys, weights, row, order, n):
    return (_weighted_sum(ys, weights, row, order, n),
            (ys, weights, row, order, n))


def _weighted_sum_bwd(res, g):
    return _weighted_tokens_to_rows(*res, g) + (None, None, None)


_weighted_sum.defvjp(_weighted_sum_fwd, _weighted_sum_bwd)


def moe_combine(ys, weights, sizes, row, order):
    """Each token's result: the sum over its HELD assignments (the rows
    below ``sum(sizes)``) of weight times that row of ``ys``; (T, d) in
    ``ys``'s dtype."""
    return _weighted_sum(ys, weights.astype(jnp.float32), row, order,
                         jnp.sum(sizes))


from ..ops.registry import register as _register_op  # noqa: E402

_register_op("_contrib_moe_router", moe_route, num_inputs=3, num_outputs=3)
_register_op("_contrib_moe_dispatch", moe_dispatch, num_inputs=2,
             num_outputs=4)
_register_op("_contrib_moe_experts", moe_experts, num_inputs=5)
_register_op("_contrib_moe_combine", moe_combine, num_inputs=5)
