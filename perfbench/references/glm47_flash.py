"""Plain reference of the ``glm4_moe_lite`` decoder (GLM-4.7-Flash): forward
pass, the two loss terms of a model with one prediction module, gradients
and an AdamW step, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.  No kernel, no sort, no cache;
it imports nothing of ``incubator_mxnet_tpu``.  What the family shares with
``afmoe`` to the letter (the RMS norm, rotate-half, the gated feed-forward,
the expert layer with ``route(forced, eps)``, AdamW, the bias that balances
a router) is taken from ``references/trinity_mini.py``, whose text says who
routes and why.

The equations follow ``config.json`` of the source and, for what that file
does not say (the block's two norms, the latents' norms, the prediction
module's form), the DeepSeek-V3 family's public modelling code and report
(arXiv:2412.19437, sections 2.1-2.2), as the configuration's ``assumed``
block records it::

    h = E[ids];   a = h + Attn(N1(h));   h = a + FFN(N2(a))
    Attn(x):  cq = Nq(x Wdq);  q = cq Wuq, heads of [q_nope | q_rope]
              [ckv | kr] = x Wdkv;  Nkv(ckv) Wukv gives heads of [k_nope | v]
              rotary on every q_rope and on kr, ONE vector for all the heads
              o_h = softmax_causal([q_nope | q_rope][k_nope | kr]^T
                                   / sqrt(nope + rope)) v_h;  concat(o_h) Wo
    logits = N(h_L) Whead
    u_i = [Nh(h_L,i) | Ne(E[t_{i+1}])] Wp;  g = Block(u);  mtp = Nm(g) Whead
    loss = mean_i CE(logits_i, y_i) + lambda mean_{i<S-1} CE(mtp_i, y_{i+1})

``params`` is a flat dict of float32 arrays, matrices ``(outputs, inputs)``:

    embed_weight (rows, d)   head_weight (rows, d)   norm_gamma (d,)
    layer<i>_norm{1,2}_gamma (d,)
    layer<i>_attn_q_a_weight (rq, d)         ..._q_a_norm_gamma (rq,)
    layer<i>_attn_q_b_weight (H*(nope+rope), rq)
    layer<i>_attn_kv_a_weight (rkv+rope, d)  ..._kv_a_norm_gamma (rkv,)
    layer<i>_attn_kv_b_weight (H*(nope+v), rkv)    ..._o_weight (d, H*v)
    layer<i>_ffn_* / layer<i>_moe_*: as in references/trinity_mini.py
    the prediction module is layer<num_layers> (the published checkpoint
    numbers it so): a block's leaves, and ..._hnorm_gamma, ..._enorm_gamma
    (d,), ..._eh_proj_weight (d, 2d), ..._head_norm_gamma (d,)

``cfg`` is ``model_cfg(configuration)``.  Besides the reference itself the
module declares what is the family's and a runner needs: ``model_cfg``,
``counters``, ``GRAD_GROUPS`` and ``CONTROLS``.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp

from perfbench.references.trinity_mini import (  # noqa: F401 (apply)
    _highest, adamw, apply, balanced_bias, expert_ffn, gated_ffn, rms_norm,
    rotate_half)

_F32 = jnp.float32
#: the prediction module's leaves that are not its block's
_MTP_LEAVES = ("hnorm_gamma", "enorm_gamma", "eh_proj_weight",
               "head_norm_gamma")


def model_cfg(config):
    """What this reference needs of the configuration: the family's keys
    under their ``config.json`` names, and the expert layer's under the
    names ``references/trinity_mini.py`` reads."""
    cfg = {k: config[k] for k in (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "v_head_dim", "rope_theta", "rms_norm_eps", "num_experts_per_tok",
        "num_nextn_predict_layers", "num_layers")}
    cfg["num_experts"] = config["n_routed_experts"]
    cfg["route_norm"] = config["norm_topk_prob"]
    cfg["route_scale"] = config["routed_scaling_factor"]
    cfg["experts_held"] = tuple(config["experts_held"])
    cfg["mtp_weight"] = config["loss_kwargs"]["mtp_weight"]
    return cfg


def _one_head(q, k, v):
    """Causal softmax attention of one head: q, k ``(S, nope + rope)``, v
    ``(S, v)``."""
    s, width = q.shape
    keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(keep, (q @ k.T) / math.sqrt(width), -jnp.inf)
    return jax.nn.softmax(scores, -1) @ v


def attention(p, pre, x, cfg):
    s, _ = x.shape
    h, nope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                   cfg["v_head_dim"])
    eps, latent = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    rope = functools.partial(rotate_half, theta=cfg["rope_theta"])
    cq = rms_norm(x @ p[pre + "q_a_weight"].T, p[pre + "q_a_norm_gamma"],
                  eps)
    q = (cq @ p[pre + "q_b_weight"].T).reshape(s, h, -1)
    kv = x @ p[pre + "kv_a_weight"].T
    ckv, kr = kv[:, :latent], rope(kv[:, latent:])
    kv = (rms_norm(ckv, p[pre + "kv_a_norm_gamma"], eps)
          @ p[pre + "kv_b_weight"].T).reshape(s, h, nope + vd)
    q = jnp.concatenate([q[..., :nope], jax.vmap(rope, 1, 1)(q[..., nope:])],
                        -1)
    # the one rotary key a position, beside every head's own columns
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(kr[:, None], (s, h, kr.shape[-1]))],
                        -1)
    head = jax.checkpoint(_one_head)
    # one head at a time (lax.map)
    out = jax.lax.map(lambda qkv: head(*qkv), (
        q.transpose(1, 0, 2), k.transpose(1, 0, 2),
        kv[..., nope:].transpose(1, 0, 2)))
    return out.transpose(1, 0, 2).reshape(s, h * vd) @ p[pre + "o_weight"].T


@_highest
def layer(p, x, forced=None, eps=0.0, *, cfg):
    """One block over ``x`` (S, d); ``p`` holds the layer's parameters under
    their names without ``layer<i>_``.  A dense layer is one whose ``p`` has
    ``ffn_w1_weight``.  Returns ``(x, route's facts or None)``."""
    eps_n = cfg["rms_norm_eps"]
    a = x + attention(p, "attn_", rms_norm(x, p["norm1_gamma"], eps_n), cfg)
    y = rms_norm(a, p["norm2_gamma"], eps_n)
    if "ffn_w1_weight" in p:
        return a + gated_ffn(y, p["ffn_w1_weight"], p["ffn_w3_weight"],
                             p["ffn_w2_weight"]), None
    f, facts = expert_ffn(p, "moe_", y, cfg, forced, eps)
    return a + f, facts


def _layer_vjp(p, x, g, forced=None, eps=0.0, *, cfg):
    """``(gradient of the layer's parameters, gradient of its input)`` for
    the output's gradient ``g``; the forward is computed again here.  What
    is not trained (``moe_bias``) gets no gradient."""
    fixed = {k: v for k, v in p.items() if k == "moe_bias"}
    _, pull = jax.vjp(
        lambda t, x: layer(dict(t, **fixed), x, forced, eps, cfg=cfg)[0],
        {k: v for k, v in p.items() if k not in fixed}, x)
    return pull(g)


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]


@_highest
def _head_loss(p, x, labels, eps):
    """The next token's term: mean cross-entropy of ``N(x) Whead``."""
    return jnp.mean(_cross_entropy(
        rms_norm(x, p["norm_gamma"], eps) @ p["head_weight"].T, labels))


@_highest
def _mtp_front(p, h, e, eps):
    """``[Nh(h) | Ne(e)] Wp``: the prediction module's input to its block."""
    return jnp.concatenate([rms_norm(h, p["hnorm_gamma"], eps),
                            rms_norm(e, p["enorm_gamma"], eps)], -1) \
        @ p["eh_proj_weight"].T


@_highest
def _mtp_loss(p, g, labels, eps):
    """The next-but-one token's term, unweighted: position ``i`` of the
    module predicts ``labels[i + 1]``, and the last has no target."""
    logits = rms_norm(g, p["head_norm_gamma"], eps) @ p["head_weight"].T
    return jnp.mean(_cross_entropy(logits[:-1], labels[1:]))


class Blocks:
    """The jitted pieces for one ``cfg`` (and, for ``update``, one
    ``recipe``): see ``references/trinity_mini.py::Blocks``."""

    def __init__(self, cfg, recipe=None):
        eps = cfg["rms_norm_eps"]
        self.fwd = jax.jit(functools.partial(layer, cfg=cfg))
        self.vjp = jax.jit(functools.partial(_layer_vjp, cfg=cfg))
        self.head = jax.jit(jax.value_and_grad(functools.partial(
            _head_loss, eps=eps), (0, 1)))
        self.front = jax.jit(functools.partial(_mtp_front, eps=eps))
        self.front_vjp = jax.jit(lambda p, h, e, g: jax.vjp(
            functools.partial(_mtp_front, eps=eps), p, h, e)[1](g))
        self.mtp = jax.jit(jax.value_and_grad(functools.partial(
            _mtp_loss, eps=eps), (0, 1)))
        self.update = recipe and jax.jit(
            functools.partial(adamw, recipe=recipe), donate_argnums=(0, 2, 3))


def _split(p, cfg):
    """``(the parameters outside the layers, [each block's under their local
    names], the prediction module's own leaves or None)``, all float32; the
    module's block is the last of the blocks."""
    p = {k: v.astype(_F32) for k, v in p.items()}
    layers = []
    for i in range(cfg["num_layers"] + cfg["num_nextn_predict_layers"]):
        pre = "layer%d_" % i
        layers.append({k[len(pre):]: p.pop(k) for k in list(p)
                       if k.startswith(pre)})
    module = None
    if cfg["num_nextn_predict_layers"]:
        module = {k: layers[-1].pop(k) for k in _MTP_LEAVES}
    return p, layers, module


def _walk(cfg, forced, eps, seq):
    """What ``Blocks.fwd`` and ``.vjp`` take after the block's input, for
    block ``i`` and sequence ``n``: the forced choices and the margin.
    Without ``forced`` a table of zeros and a margin that no set meets, so
    that the program is the same one either way."""
    none = jnp.zeros((seq, cfg["num_experts_per_tok"]), jnp.int32)

    def arguments(i, n):
        if forced is None or i not in forced:
            return none, _F32(-jnp.inf)
        return forced[i][n * seq:(n + 1) * seq], _F32(eps)

    return arguments


def _next(ids):
    """``t_{i+1}`` for every position: the ids rolled left by one (the last
    position wraps, and no loss term reads it)."""
    return jnp.roll(ids, -1, -1)


def gradients(p, ids, labels, cfg, forced=None, eps=0.0, blocks=None):
    """The loss of the batch ``ids`` (B, S) and its gradient, handed out as
    it is made: a generator that yields ``(loss, facts)`` first and then
    ``{name: gradient}`` group by group: the two heads' norms and the head
    (whose gradient is the sum over its two uses), the prediction module's
    block, the module's input side, the blocks from the last to the first,
    the embedding (summed over its two uses).  ``forced`` and ``facts`` as
    in ``references/trinity_mini.py::gradients``, the module's block
    counted as block ``num_layers``."""
    blocks = blocks or Blocks(cfg)
    outer, layers, module = _split(p, cfg)
    b, s = ids.shape
    n_main, lam = cfg["num_layers"], cfg["mtp_weight"]
    after = _walk(cfg, forced, eps, s)
    embed = outer["embed_weight"]
    xs = [[embed[ids[n]]] for n in range(b)]
    refused, moved = {}, {}

    def run(i, n, x):
        x, facts = blocks.fwd(layers[i], x, *after(i, n))
        if facts is not None and forced and i in forced:
            refused[i] = refused.get(i, 0) + facts["refused"] / b
            moved[i] = moved.get(i, 0) + facts["moved"] / b
        return x

    for i in range(n_main):
        for n in range(b):
            xs[n].append(run(i, n, xs[n][-1]))
    head = {k: outer[k] for k in ("norm_gamma", "head_weight")}
    value, g_head, g_x = 0.0, None, []

    def add(total, g, scale):
        return jax.tree.map(lambda a, c: a + c * scale, total, g) \
            if total else jax.tree.map(lambda c: c * scale, g)

    ends = [x[-1] for x in xs]
    for n in range(b):
        loss_n, (g, g_last) = blocks.head(head, xs[n].pop(), labels[n])
        value = value + loss_n / b
        g_head = add(g_head, g, 1.0 / b)
        g_x.append(g_last / b)
    g_block, g_front, g_next, us = None, None, [], []
    if module:
        nexts = [embed[_next(ids[n])] for n in range(b)]
        front = {k: module[k] for k in _MTP_LEAVES[:3]}
        tail = {"head_norm_gamma": module["head_norm_gamma"],
                "head_weight": outer["head_weight"]}
        g_tail, g_out = None, []
        for n in range(b):
            us.append(blocks.front(front, ends[n], nexts[n]))
            loss_n, (g, g_last) = blocks.mtp(
                tail, run(n_main, n, us[n]), labels[n])
            value = value + lam * loss_n / b
            g_tail = add(g_tail, g, lam / b)
            g_out.append(g_last * (lam / b))
        g_head["head_weight"] = g_head["head_weight"] + g_tail["head_weight"]
        g_head["layer%d_head_norm_gamma" % n_main] = g_tail["head_norm_gamma"]
    yield value, {"refused": [refused[i] for i in sorted(refused)],
                  "moved": [moved[i] for i in sorted(moved)]}
    yield g_head
    if module:
        for n in range(b):
            g, g_u = blocks.vjp(layers[n_main], us[n], g_out[n],
                                *after(n_main, n))
            g_block = add(g_block, g, 1.0)
            g, g_h, g_e = blocks.front_vjp(front, ends[n], nexts[n], g_u)
            g_front = add(g_front, g, 1.0)
            g_x[n] = g_x[n] + g_h
            g_next.append(g_e)
        yield {"layer%d_%s" % (n_main, k): g for k, g in g_block.items()}
        yield {"layer%d_%s" % (n_main, k): g for k, g in g_front.items()}
    for i in reversed(range(n_main)):
        total = None
        for n in range(b):
            g, g_x[n] = blocks.vjp(layers[i], xs[n].pop(), g_x[n],
                                   *after(i, n))
            total = add(total, g, 1.0)
        yield {"layer%d_%s" % (i, k): g for k, g in total.items()}
    g_embed = jnp.zeros_like(embed)
    for n in range(b):
        g_embed = g_embed.at[ids[n]].add(g_x[n])
        if module:
            g_embed = g_embed.at[_next(ids[n])].add(g_next[n])
    yield {"embed_weight": g_embed}


def loss_and_grads(p, ids, labels, cfg, forced=None, eps=0.0, blocks=None):
    """``(loss, {name: gradient}, facts)``: ``gradients`` gathered."""
    sweep = gradients(p, ids, labels, cfg, forced, eps, blocks)
    value, facts = next(sweep)
    grads = {}
    for group in sweep:
        grads.update(group)
    return value, grads, facts


def loss_terms(p, ids, labels, cfg):
    """``(the next token's term, the next but one's, unweighted)``, as one
    straightforward composition (what ``jax.grad`` differentiates as a
    whole; ``gradients`` must agree)."""
    outer, layers, module = _split(p, cfg)
    eps, n_main = cfg["rms_norm_eps"], cfg["num_layers"]
    first = second = 0.0
    for n in range(ids.shape[0]):
        x = outer["embed_weight"][ids[n]]
        for pl in layers[:n_main]:
            x, _ = layer(pl, x, cfg=cfg)
        first = first + _head_loss(outer, x, labels[n], eps)
        if module:
            u = _mtp_front(module, x, outer["embed_weight"][_next(ids[n])],
                           eps)
            g, _ = layer(layers[n_main], u, cfg=cfg)
            second = second + _mtp_loss(dict(module, **outer), g, labels[n],
                                        eps)
    return first / ids.shape[0], second / ids.shape[0]


def loss(p, ids, labels, cfg):
    first, second = loss_terms(p, ids, labels, cfg)
    return first + cfg["mtp_weight"] * second


def step(p, m, v, t, ids, labels, cfg, blocks):
    """One training step (``t`` 1-based) in place of ``p``, ``m``, ``v``,
    routing for itself, each group updated as its gradient comes.  Returns
    the loss (taken before the update)."""
    sweep = gradients(p, ids, labels, cfg, blocks=blocks)
    value, _ = next(sweep)
    for group in sweep:
        apply(p, m, v, t, group, blocks)
    return value


def balance(p, ids, cfg, iterations, rate, decay, blocks=None):
    """``{layer<i>_moe_bias: selection bias}`` that evens out each router's
    load on the batch ``ids`` at the parameters ``p``, the prediction
    module's router included: one forward pass, each expert layer run once
    for its scores, given ``balanced_bias`` of them, and run again under
    it."""
    blocks = blocks or Blocks(cfg)
    outer, layers, module = _split(p, cfg)
    b, s = ids.shape
    n_main = cfg["num_layers"]
    after = _walk(cfg, None, 0.0, s)
    xs = [outer["embed_weight"][ids[n]] for n in range(b)]
    out = {}
    for i, pl in enumerate(layers):
        if i == n_main:
            xs = [blocks.front({k: module[k] for k in _MTP_LEAVES[:3]}, xs[n],
                               outer["embed_weight"][_next(ids[n])])
                  for n in range(b)]
        if "moe_bias" in pl:
            scores = jnp.concatenate([
                blocks.fwd(pl, xs[n], *after(i, n))[1]["scores"]
                for n in range(b)])
            pl = dict(pl, moe_bias=balanced_bias(
                scores, cfg["num_experts_per_tok"], iterations, rate, decay))
            out["layer%d_moe_bias" % i] = pl["moe_bias"]
        if i < n_main:
            xs = [blocks.fwd(pl, xs[n], *after(i, n))[0] for n in range(b)]
    return out


# ---------------------------------------------------------------------------
# what the kernels have to do: operations and bytes, from shapes and counts
# ---------------------------------------------------------------------------

def counters(config, loads, batch):
    """The work and byte counts the per-layer metrics read, per step on this
    chip, under the names the accepted metric files read.  ``loads`` is
    ``[(expert layer, assignments per expert)]`` of the step's own last
    step.  Each counts the mathematics (what any implementation must do),
    never a kernel's own recomputation: a training step is the forward
    products and twice as many in the backward pass."""
    c = config
    seq, d, f = c["seq_len"], c["hidden_size"], c["moe_intermediate_size"]
    heads, first, count = (c["num_attention_heads"],) + tuple(
        c["experts_held"])
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    blocks = c["num_layers"] + c["num_nextn_predict_layers"]
    tokens = batch * seq
    # attention: q k^T over nope + rope columns and p v over v_head_dim, for
    # the pairs a causal mask admits, forward; the same two and dq, dk, dv,
    # dp backward: six products
    pairs = seq * (seq + 1) // 2 * heads * batch * blocks
    attn_fwd_macs = pairs * (width + c["v_head_dim"])
    # the latent projections: down to the two latents (the rotary key
    # beside one), up to the heads, and out
    mla_fwd_macs = tokens * blocks * (
        d * c["q_lora_rank"] + c["q_lora_rank"] * heads * width
        + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
        + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
        + heads * c["v_head_dim"] * d)
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
    # written
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
        "mla_proj_flops_per_module": 3 * 2 * mla_fwd_macs,
        "expert_flops_per_module": 3 * 2 * expert_fwd_macs,
        "expert_bytes_per_module": weight_bytes + row_bytes,
        "dispatch_bytes_per_module": dispatch_bytes,
        # a dispatch moves bytes and multiplies nothing: its compute side is
        # one operation a byte, so that the bytes bound it
        "dispatch_ops_per_module": dispatch_bytes,
        "assignments_held": held,
        "assignments_routed": total,
        "moe_load_max_over_mean": sum(worst) / max(len(worst), 1),
        "assignments_dropped": tokens * c["num_experts_per_tok"] * len(loads)
        - total,
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
    ("mtp", re.compile(r"_(hnorm|enorm|eh_proj|head_norm)_")),
    ("other", re.compile(r"")),
)


@jax.custom_vjp
def _round8(a):
    # float8 as it is used: a scale a tensor, worked out in float32, so that
    # the largest magnitude is e4m3's largest (240 with 4 exponent bits), 3
    # bits of mantissa; by lax.reduce_precision, since a cast there and
    # back is something the compiler may drop
    wide = a.astype(_F32)
    scale = jnp.max(jnp.abs(wide)) / 240
    return (jax.lax.reduce_precision(wide / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale).astype(a.dtype)


# straight through: the cotangent passes unrounded
_round8.defvjp(lambda a: (_round8(a), None), lambda _, g: (g,))

#: the ways to break the step on purpose, each ``(the registered op that is
#: replaced while the step is built, what replaces it, given what it was)``.
#: ``rope``: the rotary columns of q and k are left unrotated.  ``expert``:
#: the last held expert of every expert layer adds nothing (its output
#: matrix is zero).  ``float8``: the expert product's inputs (rows and
#: matrices) rounded to e4m3 under a scale a tensor, cotangent straight
#: through.  ``mtp_shift``: the prediction module is fed ``E[t_i]`` where
#: ``E[t_{i+1}]`` belongs (the net's roll of the ids is the step's only one:
#: the loss moves its labels by a slice).
CONTROLS = {
    "rope": ("_contrib_rotary", lambda was: lambda data, **kw: data),
    "expert": ("_contrib_moe_experts", lambda was: lambda *a, **kw: was(
        *a[:3], a[3].at[-1].set(0), *a[4:], **kw)),
    "float8": ("_contrib_moe_experts", lambda was: lambda *a, **kw: was(
        *map(_round8, a[:4]), *a[4:], **kw)),
    "mtp_shift": ("_np_roll", lambda was: lambda x, **kw: x),
}
