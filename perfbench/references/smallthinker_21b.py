"""Plain reference of the ``smallthinker`` decoder (SmallThinker-21BA3B):
forward pass, mean next-token cross-entropy, gradients and an AdamW step, in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.  No kernel, no sort, no cache;
it imports nothing of ``incubator_mxnet_tpu``.  What the family shares with
``afmoe`` to the letter (the RMS norm, rotate-half, one head's attention
under a window, the head and its loss, AdamW, and the walk over the blocks
that hands the gradient out group by group) is taken from
``references/trinity_mini.py``, whose text says who routes and why.

The equations follow ``config.json`` of the source and, for what that file
does not say (that the router reads the un-normed block input, the two
norms a block, no gate or per-head norm in the attention), the family's
public modelling code as the configuration's ``assumed`` block records it::

    h = E[ids];   per block, x its input:
    r = x Wr^T;   sel = top6(r + b);   w = softmax(r[sel])
    q, k, v = N1(x) Wq, N1(x) Wk, N1(x) Wv;   on a window layer rotary on q, k
    a = x + softmax_mask(q k^T / sqrt(hd)) v Wo
    y = a + sum_{e in sel, held} w_e (relu(N2(a) Wg_e) * (N2(a) Wu_e)) Wd_e
    logits = N(h_L) Whead

``b`` is a selection-only bias the published model does not have (zero in
the zoo; see ``balance``); where the configuration builds the net with
``centred_selection`` = n the choice is ``top6(r - mean of r over the
token's block of n + b)``, at every step (``route``).  A full layer (``sliding_window_layout`` 0) has
neither window nor rotary embedding; the published ``rope_layout`` names the
same layers as ``sliding_window_layout``, and this reference holds a
configuration to that.

``params`` is a flat dict of float32 arrays, matrices ``(outputs, inputs)``:

    embed_weight (rows, d)   head_weight (rows, d)   norm_gamma (d,)
    layer<i>_norm{1,2}_gamma (d,)
    layer<i>_attn_q_weight (H*hd, d)   layer<i>_attn_{k,v}_weight (Hkv*hd, d)
    layer<i>_attn_o_weight (d, H*hd)
    layer<i>_moe_router_weight (E, d)  layer<i>_moe_bias (E,)
    layer<i>_moe_w{1,3} (held, d, f)   layer<i>_moe_w2 (held, f, d)
    (w1 the gate's matrix Wg, w3 the up projection Wu, w2 the down Wd)

``cfg`` is ``model_cfg(configuration)``.  Besides the reference itself the
module declares what is the family's and a runner needs: ``model_cfg``,
``counters``, ``GRAD_GROUPS`` and ``CONTROLS``.
"""
import functools
import re

import jax
import jax.numpy as jnp

from perfbench.references.glm47_flash import CONTROLS as _GLM_CONTROLS
from perfbench.references.trinity_mini import (  # noqa: F401 (apply, step)
    _embed, _head_loss, _highest, _one_head, _split, _walk, adamw, apply,
    balanced_bias, gradients as _gradients, rms_norm, rotate_half, step)

_F32 = jnp.float32


def model_cfg(config):
    """What this reference needs of the configuration: the family's keys
    under their ``config.json`` names, and what
    ``references/trinity_mini.py``'s walk over the blocks reads under the
    names it reads them by."""
    layers = config["num_layers"]
    slides = list(config["sliding_window_layout"][:layers])
    if list(config["rope_layout"][:layers]) != slides:
        raise ValueError("rotary embedding on other layers than the window's")
    if not config["moe_primary_router_apply_softmax"]:
        raise ValueError("a router without the softmax over the chosen "
                         "logits is not this reference's")
    cfg = {k: config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "sliding_window_size", "rope_theta", "rms_norm_eps")}
    cfg["num_experts"] = config["moe_num_primary_experts"]
    cfg["num_experts_per_tok"] = config["moe_num_active_primary_experts"]
    cfg["experts_held"] = tuple(config["experts_held"])
    cfg["layer_types"] = ["sliding_attention" if s else "full_attention"
                          for s in slides]
    cfg["mup_enabled"] = False      # no embedding multiplier
    cfg["centred_selection"] = int(
        config.get("factory_kwargs", {}).get("centred_selection", 0))
    return cfg


def attention(p, pre, x, cfg, sliding):
    """``sliding`` (a bool, possibly traced): rotary embedding on q and k
    and the window; else neither."""
    s, _ = x.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = (x @ p[pre + "q_weight"].T).reshape(s, h, hd)
    k = (x @ p[pre + "k_weight"].T).reshape(s, hkv, hd)
    v = (x @ p[pre + "v_weight"].T).reshape(s, hkv, hd)
    rope = jax.vmap(functools.partial(rotate_half, theta=cfg["rope_theta"]),
                    1, 1)
    q = jnp.where(sliding, rope(q), q)
    k = jnp.where(sliding, rope(k), k)
    window = jnp.where(sliding, cfg["sliding_window_size"], s)
    head = jax.checkpoint(_one_head)
    # one head at a time (lax.map: a head's scores are S x S floats), each
    # key/value head serving h // hkv consecutive query heads
    out = jax.lax.map(lambda qkv: head(*qkv, window), (
        q.transpose(1, 0, 2), jnp.repeat(k, h // hkv, 1).transpose(1, 0, 2),
        jnp.repeat(v, h // hkv, 1).transpose(1, 0, 2)))
    return out.transpose(1, 0, 2).reshape(s, h * hd) @ p[pre + "o_weight"].T


def _centred(scores, block):
    """``scores`` (T, E) less each expert's mean over the block of ``block``
    consecutive tokens that a token lies in."""
    blocks = scores.reshape(-1, block, scores.shape[-1])
    return (blocks - jnp.mean(blocks, 1, keepdims=True)).reshape(scores.shape)


def route(x, router_weight, bias, cfg, forced=None, eps=0.0):
    """``(weights (T, K), chosen experts (T, K), facts)`` over all the
    experts, from the router's input ``x``: the scores are the logits ``x
    Wr^T``, the choice the top k of ``scores + bias``, the weights a softmax
    over the chosen logits (after which ``norm_topk_prob`` changes nothing).
    Under ``cfg["centred_selection"]`` = n the choice reads each expert's
    score less its mean over the block of n consecutive tokens of ``x``, one
    sequence, that the token lies in.

    ``forced``, ``eps`` and ``facts`` as in
    ``references/trinity_mini.py::route``, but for the margin's unit: a
    token's forced set is followed where it needs no more than ``eps`` times
    THAT TOKEN's spread of scores (their standard deviation over the
    experts).  Logits have whatever scale the router's input has (a first
    block reads embedding rows of rms 0.01, a later one a residual stream of
    rms 1), and so has what rounding moves them by: an absolute margin
    would mean another thing in every block."""
    scores = x @ router_weight.T
    block = cfg.get("centred_selection")
    biased = (_centred(scores, block) if block else scores) + bias
    _, sel = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    facts = {"scores": scores, "refused": jnp.zeros(4), "moved": _F32(0)}
    if forced is not None:
        experts = jnp.arange(scores.shape[-1])
        inside = (forced[..., None] == experts).any(1)            # (T, E)
        own = (sel[..., None] == experts).any(1)
        need = jnp.max(jnp.where(inside, -jnp.inf, biased), -1) \
            - jnp.min(jnp.where(inside, biased, jnp.inf), -1)
        margin = eps * jnp.std(scores, -1)
        facts["refused"] = jnp.mean(
            need[:, None] > margin[:, None]
            * jnp.asarray([0.25, 0.5, 1.0, 2.0]), 0)
        facts["moved"] = jnp.mean((inside & ~own).sum(-1) / sel.shape[-1])
        sel = jnp.where((need <= margin)[:, None], forced, sel)
    return jax.nn.softmax(jnp.take_along_axis(scores, sel, -1), -1), sel, \
        facts


def expert_ffn(p, pre, routed_on, x, cfg, forced=None, eps=0.0):
    """``(what the held experts add to x's tokens under the routing of
    routed_on, route's facts)``: ReLU-gated experts, no shared expert."""
    first, count = cfg["experts_held"]
    w, sel, facts = route(routed_on, p[pre + "router_weight"],
                          p[pre + "bias"], cfg, forced, eps)

    @jax.checkpoint
    def add_expert(y, held):
        n, wg, wu, wd = held
        mine = jnp.sum(jnp.where(sel == first + n, w, 0.0), -1)
        return y + mine[:, None] * ((jax.nn.relu(x @ wg) * (x @ wu)) @ wd), \
            None

    # one held expert after another (lax.scan), each on every token
    return jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(count), p[pre + "w1"], p[pre + "w3"], p[pre + "w2"]))[0], \
        facts


@_highest
def layer(p, x, sliding, forced=None, eps=0.0, *, cfg):
    """One block over ``x`` (S, d); ``p`` holds the layer's parameters under
    their names without ``layer<i>_``.  Returns ``(x, route's facts)``."""
    eps_n = cfg["rms_norm_eps"]
    a = x + attention(p, "attn_", rms_norm(x, p["norm1_gamma"], eps_n), cfg,
                      sliding)
    f, facts = expert_ffn(p, "moe_", x, rms_norm(a, p["norm2_gamma"], eps_n),
                          cfg, forced, eps)
    return a + f, facts


def _layer_vjp(p, x, g, sliding, forced=None, eps=0.0, *, cfg):
    """``(gradient of the layer's parameters, gradient of its input)`` for
    the output's gradient ``g``; the forward is computed again here.  What
    is not trained (``moe_bias``) gets no gradient."""
    fixed = {"moe_bias": p["moe_bias"]}
    _, pull = jax.vjp(
        lambda t, x: layer(dict(t, **fixed), x, sliding, forced, eps,
                           cfg=cfg)[0],
        {k: v for k, v in p.items() if k not in fixed}, x)
    return pull(g)


class Blocks:
    """The jitted pieces for one ``cfg`` (and, for ``update``, one
    ``recipe``): see ``references/trinity_mini.py::Blocks``.  ONE layer
    program serves every block, whether it slides being an argument."""

    def __init__(self, cfg, recipe=None):
        self.fwd = jax.jit(functools.partial(layer, cfg=cfg))
        self.vjp = jax.jit(functools.partial(_layer_vjp, cfg=cfg))
        self.head = jax.jit(jax.value_and_grad(functools.partial(
            _head_loss, eps=cfg["rms_norm_eps"]), (0, 1)))
        self.update = recipe and jax.jit(
            functools.partial(adamw, recipe=recipe), donate_argnums=(0, 2, 3))


def gradients(p, ids, labels, cfg, forced=None, eps=0.0, blocks=None):
    """``references/trinity_mini.py::gradients`` over this family's blocks:
    the loss and its gradient, handed out group by group."""
    return _gradients(p, ids, labels, cfg, forced, eps,
                      blocks or Blocks(cfg))


def loss_and_grads(p, ids, labels, cfg, forced=None, eps=0.0, blocks=None):
    """``(loss, {name: gradient}, facts)``: ``gradients`` gathered."""
    sweep = gradients(p, ids, labels, cfg, forced, eps, blocks)
    value, facts = next(sweep)
    grads = {}
    for group in sweep:
        grads.update(group)
    return value, grads, facts


def loss(p, ids, labels, cfg):
    """The loss alone, as one straightforward composition (what
    ``jax.grad`` differentiates as a whole; ``gradients`` must agree)."""
    outer, layers = _split(p, cfg)
    total = 0.0
    for n in range(ids.shape[0]):
        x = _embed(outer, ids[n], cfg)
        for kind, pl in zip(cfg["layer_types"], layers):
            x, _ = layer(pl, x, kind == "sliding_attention", cfg=cfg)
        total = total + _head_loss(outer, x, labels[n], cfg["rms_norm_eps"])
    return total / ids.shape[0]


def balance(p, ids, cfg, iterations, rate, decay, blocks=None):
    """``{layer<i>_moe_bias: selection bias}`` that evens out each router's
    load on the batch ``ids`` at the parameters ``p``: one forward pass,
    each block run once for its scores, given its bias, and run again under
    it.  A router that reads the un-normed stream sees in every token what
    neighbouring tokens have in common: an expert's logits then have a mean
    that outweighs what tells the tokens apart.  ``balanced_bias``, which
    steps in units of sigmoid scores, is handed the logits less that mean
    (each block's, where ``route`` centres the selection; else the batch's),
    in units of their spread.  Where the selection is not centred at every
    step the bias takes the batch's mean away too, once."""
    blocks = blocks or Blocks(cfg)
    outer, layers = _split(p, cfg)
    b, s = ids.shape
    after = _walk(cfg, None, 0.0, b, s)
    xs = [_embed(outer, ids[n], cfg) for n in range(b)]
    out = {}
    for i, pl in enumerate(layers):
        scores = jnp.concatenate([
            blocks.fwd(pl, xs[n], *after(i, n))[1]["scores"]
            for n in range(b)])
        block = cfg.get("centred_selection")
        left = _centred(scores, block or scores.shape[0])
        spread = jnp.std(left)
        bias = spread * balanced_bias(
            left / spread, cfg["num_experts_per_tok"], iterations, rate,
            decay)
        if not block:
            bias = bias - jnp.mean(scores, 0)
        pl = dict(pl, moe_bias=bias)
        out["layer%d_moe_bias" % i] = bias
        xs = [blocks.fwd(pl, xs[n], *after(i, n))[0] for n in range(b)]
    return out


# ---------------------------------------------------------------------------
# what the kernels have to do: operations and bytes, from shapes and counts
# ---------------------------------------------------------------------------

def admitted_pairs(seq, window):
    """Query-key pairs one head's causal mask admits over a sequence: all
    ``j <= i``, with ``window`` only ``i - window < j <= i``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def counters(config, loads, batch):
    """The work and byte counts the per-layer metrics read, per step on this
    chip, under the names the accepted metric files read.  ``loads`` is
    ``[(expert layer, assignments per expert)]`` of the step's own last
    step.  Each counts the mathematics (what any implementation must do),
    never a kernel's own recomputation: a training step is the forward
    products and twice as many in the backward pass."""
    c = config
    seq, d, f = c["seq_len"], c["hidden_size"], c["moe_ffn_hidden_size"]
    heads, hd = c["num_attention_heads"], c["head_dim"]
    first, count = c["experts_held"]
    tokens = batch * seq
    # attention: q k^T and p v over the admitted pairs, forward; the same two
    # and dq, dk, dv, dp (four) backward: six products of pairs x head_dim.
    # The window layers' part is counted apart as well.
    slides = c["sliding_window_layout"][:c["num_layers"]]
    window_pairs = admitted_pairs(seq, c["sliding_window_size"])
    pairs = sum(window_pairs if s else admitted_pairs(seq, None)
                for s in slides)
    attn_fwd_macs = 2 * pairs * hd * heads * batch
    window_fwd_macs = 2 * sum(slides) * window_pairs * hd * heads * batch
    # experts: three matrices of d x f a held assignment, forward; twice
    # that backward.  The assignments are the step's own.
    held = sum(float(n[first:first + count].sum()) for _, n in loads)
    total = sum(float(n.sum()) for _, n in loads)
    expert_fwd_macs = held * 3 * d * f
    # bytes the grouped products must move: each held expert's three
    # matrices read in the forward pass, read again and their gradients
    # written in the backward pass (bf16), and each held row in and out of
    # each product
    weight_bytes = len(loads) * count * 3 * d * f * 2 * 3
    row_bytes = held * (d + f + f + f + f + d) * 2 * 3
    # dispatch and combine: each held row of d gathered into expert order
    # and gathered back, forward, and the two transposes backward; read +
    # written.  The REAL bytes of a row, not the slab it travels as.
    dispatch_bytes = held * d * 2 * 2 * 4
    worst = [float(n[first:first + count].max()
                   / max(n[first:first + count].mean(), 1e-30))
             for _, n in loads]
    flops_per_sample = 3 * 2 * (c["fwd_macs_per_sample"] * batch
                                + attn_fwd_macs + expert_fwd_macs) / batch
    return {
        "flops_per_sample": flops_per_sample,
        "flops_per_module_per_chip": flops_per_sample * batch,
        "attn_flops_per_module": 3 * 2 * attn_fwd_macs,
        "attn_window_flops_per_module": 3 * 2 * window_fwd_macs,
        "expert_flops_per_module": 3 * 2 * expert_fwd_macs,
        "expert_bytes_per_module": weight_bytes + row_bytes,
        "dispatch_bytes_per_module": dispatch_bytes,
        # a dispatch moves bytes and multiplies nothing: its compute side is
        # one operation a byte, so that the bytes bound it
        "dispatch_ops_per_module": dispatch_bytes,
        "assignments_held": held,
        "assignments_routed": total,
        "moe_load_max_over_mean": sum(worst) / max(len(worst), 1),
        "assignments_dropped":
        tokens * c["moe_num_active_primary_experts"] * len(loads) - total,
    }


# ---------------------------------------------------------------------------
# what a comparison with this reference groups and must be able to tell
# ---------------------------------------------------------------------------

#: ``(group, pattern over a leaf's name)``: a leaf of the gradient belongs
#: to the first group whose pattern is found in it
GRAD_GROUPS = (
    ("attention", re.compile(r"_attn_")),
    ("experts", re.compile(r"_moe_w\d")),
    ("router", re.compile(r"_router_")),
    ("other", re.compile(r"")),
)


def _with(**attrs):
    """A replacement that calls the op it replaces with ``attrs`` set."""
    return lambda was: lambda *a, **kw: was(*a, **dict(kw, **attrs))


#: the ways to break the step on purpose, each ``(the registered op that is
#: replaced while the step is built, what replaces it, given what it was)``.
#: ``window``: the window layers see every earlier key.  ``rope``: q and k
#: are left unrotated on every layer.  ``gate``: the experts' gate is SiLU.
#: ``score``: sigmoid scores, normalised over the chosen, in place of the
#: softmax over the chosen logits.  ``centre``: the selection reads the
#: logits as they are, where the configuration centres it over blocks
#: (it tells nothing, and must not be asked for, where the configuration
#: does not).  ``expert`` (the last held expert of
#: every layer adds nothing) and ``float8`` (the expert product's inputs
#: rounded to e4m3 under a scale a tensor) are
#: ``references/glm47_flash.py``'s.  The router's PLACEMENT is no op's
#: attribute: ``tests/test_smallthinker_controls.py`` breaks it in the block.
CONTROLS = {
    "window": ("_contrib_flash_attention", _with(window=None)),
    "rope": _GLM_CONTROLS["rope"],
    "gate": ("_contrib_moe_experts", _with(act="silu")),
    "score": ("_contrib_moe_router", _with(score="sigmoid")),
    "centre": ("_contrib_moe_router", _with(centred=0)),
    "expert": _GLM_CONTROLS["expert"],
    "float8": _GLM_CONTROLS["float8"],
}
