"""Plain reference of the ``afmoe`` decoder (Trinity-Mini): forward pass, mean
next-token cross-entropy, gradients and an AdamW step, in straightforward
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
No kernel, no sort, no cache; it imports nothing of ``incubator_mxnet_tpu``.

The equations follow ``config.json`` of the source and, for what that file
does not say (the output gate, the sandwich norms, no rotary on the full
layers, the embedding multiplier), the family's public modelling code as
the configuration's ``assumed`` block records it.

``params`` is a flat dict of float32 arrays.  The ``*_weight`` matrices are
stored ``(outputs, inputs)`` as the system's ``Dense`` stores them, and
applied as ``x @ W.T``; the held experts' stacked matrices are applied as
``x @ W[n]``:

    embed_weight (rows, d)          head_weight (rows, d)       norm_gamma (d,)
    layer<i>_norm<1..4>_gamma (d,)
    layer<i>_attn_{q,g}_weight (H*hd, d)   layer<i>_attn_{k,v}_weight (Hkv*hd, d)
    layer<i>_attn_o_weight (d, H*hd)       layer<i>_attn_{qnorm,knorm}_gamma (hd,)
    dense layers:   layer<i>_ffn_w{1,3}_weight (f, d)   layer<i>_ffn_w2_weight (d, f)
    expert layers:  layer<i>_moe_router_weight (E, d)   layer<i>_moe_bias (E,)
                    layer<i>_moe_w{1,3} (held, d, f)    layer<i>_moe_w2 (held, f, d)
                    layer<i>_moe_shared_w{1,3}_weight (f, d), ..._w2_weight (d, f)

``cfg`` holds the ``config.json`` keys plus the share: ``layer_types`` (the
layers that are run), ``num_dense_layers``, ``experts_held`` = (first,
count).  The expert layer routes over all ``num_experts``, adds what the
held experts give and the shared expert, and leaves out what the absent
experts would add; a loop over the held experts (``lax.scan``), each on
every token with its combine weight (zero where the token did not choose
it).

**Who routes.**  This reference, by its own float32 scores.  Which experts a
token's 8 are turns on differences of rounding size wherever the 8th and
the 9th score lie close, and one token that goes elsewhere is a visible
part of an expert's gradient; a program that computes in a narrower
precision is therefore compared under ITS choice exactly where this
reference cannot tell the candidates apart: ``route`` takes the choices of
the program compared (``forced``) and a margin ``eps``, and follows a
token's forced set only if every expert in it scores within ``eps`` of
every expert outside it that scores higher (the set is then a top-k of
scores moved by at most ``eps / 2`` each); a token whose forced set needs
more is routed by this reference's own choice and counted as refused.
Where this reference's own 8th score beats its 9th by more than ``eps``,
only its own choice passes.  The caller holds the share of refused tokens
to (next to) nothing.

**Block by block.**  ``Blocks`` holds the jitted pieces: one layer forward,
one layer's vector-Jacobian product (which computes that forward again),
the head with its loss, an AdamW update.  Whether a layer slides and whose
choices it follows are arguments, so the expert layers share ONE program
and the dense layers another, whatever the depth.  ``gradients`` walks them
from Python and hands the gradient out group by group, so that a caller
short of memory can use each group and drop it (``step``).  Inside a layer
one attention head (``lax.map``) and one expert (``lax.scan``) are alive at
once.
"""
import functools
import math

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def rotate_half(x, theta):
    """Rotary embedding over the whole last axis of ``(S, hd)``, positions
    0..S-1, the two halves paired (``rotate_half``)."""
    s, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=_F32) / hd)
    ang = jnp.arange(s, dtype=_F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    x1, x2 = x[:, :hd // 2], x[:, hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _one_head(q, k, v, window):
    """Causal softmax attention of one query head, ``(S, hd)`` each, over
    the keys ``i - window < j <= i``."""
    s, hd = q.shape
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    keep = (j <= i) & (j > i - window)
    scores = jnp.where(keep, (q @ k.T) / math.sqrt(hd), -jnp.inf)
    return jax.nn.softmax(scores, -1) @ v


def attention(p, pre, x, cfg, sliding):
    """``sliding`` (a bool, possibly traced): rotary embedding on q and k
    and the window; else neither."""
    s, _ = x.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = (x @ p[pre + "q_weight"].T).reshape(s, h, hd)
    k = (x @ p[pre + "k_weight"].T).reshape(s, hkv, hd)
    v = (x @ p[pre + "v_weight"].T).reshape(s, hkv, hd)
    gate = x @ p[pre + "g_weight"].T
    q = rms_norm(q, p[pre + "qnorm_gamma"], eps)
    k = rms_norm(k, p[pre + "knorm_gamma"], eps)
    rope = jax.vmap(functools.partial(rotate_half, theta=cfg["rope_theta"]),
                    1, 1)
    q = jnp.where(sliding, rope(q), q)
    k = jnp.where(sliding, rope(k), k)
    window = jnp.where(sliding, cfg["sliding_window"], s)
    head = jax.checkpoint(_one_head)
    # one head at a time (lax.map), each key/value head serving h // hkv
    # consecutive query heads
    out = jax.lax.map(lambda qkv: head(*qkv, window), (
        q.transpose(1, 0, 2), jnp.repeat(k, h // hkv, 1).transpose(1, 0, 2),
        jnp.repeat(v, h // hkv, 1).transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2).reshape(s, h * hd)
    return (out * jax.nn.sigmoid(gate)) @ p[pre + "o_weight"].T


def gated_ffn(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1.T) * (x @ w3.T)) @ w2.T


def route(x, router_weight, bias, cfg, forced=None, eps=0.0):
    """``(weights (T, K), chosen experts (T, K), facts)`` over all the
    experts: sigmoid scores, the top k of ``scores + bias``, the chosen
    scores normalised (``route_norm``) and scaled (``route_scale``).

    ``forced`` (T, K) is another router's choice for each token.  A token's
    forced set is taken instead of this function's own where it is a top-k
    within ``eps`` (see the module's text); the weights are this function's
    own scores at the experts taken.  ``facts``: ``scores`` (T, E);
    ``refused``, the shares of the tokens whose forced set needed a margin
    of more than ``eps`` times 1/4, 1/2, 1 and 2 (the third is the share
    that was refused; the others show how steeply it falls); ``moved``, the
    share of the forced assignments that its own top-k did not make.
    Without ``forced`` both are 0."""
    scores = jax.nn.sigmoid(x @ router_weight.T)
    biased = scores + bias
    _, sel = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    facts = {"scores": scores, "refused": jnp.zeros(4), "moved": _F32(0)}
    if forced is not None:
        experts = jnp.arange(scores.shape[-1])
        inside = (forced[..., None] == experts).any(1)            # (T, E)
        own = (sel[..., None] == experts).any(1)
        need = jnp.max(jnp.where(inside, -jnp.inf, biased), -1) \
            - jnp.min(jnp.where(inside, biased, jnp.inf), -1)
        facts["refused"] = jnp.mean(
            need[:, None] > eps * jnp.asarray([0.25, 0.5, 1.0, 2.0]), 0)
        facts["moved"] = jnp.mean((inside & ~own).sum(-1) / sel.shape[-1])
        sel = jnp.where((need <= eps)[:, None], forced, sel)
    w = jnp.take_along_axis(scores, sel, -1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg["route_scale"], sel, facts


def expert_ffn(p, pre, x, cfg, forced=None, eps=0.0):
    """``(what the held experts and the shared expert add, route's
    facts)``."""
    first, count = cfg["experts_held"]
    w, sel, facts = route(x, p[pre + "router_weight"], p[pre + "bias"], cfg,
                          forced, eps)

    @jax.checkpoint
    def add_expert(y, held):
        n, w1, w3, w2 = held
        mine = jnp.sum(jnp.where(sel == first + n, w, 0.0), -1)
        return y + mine[:, None] * gated_ffn(x, w1.T, w3.T, w2.T), None

    shared = gated_ffn(x, p[pre + "shared_w1_weight"],
                       p[pre + "shared_w3_weight"],
                       p[pre + "shared_w2_weight"])
    # one held expert after another (lax.scan), each on every token
    return jax.lax.scan(add_expert, shared, (
        jnp.arange(count), p[pre + "w1"], p[pre + "w3"], p[pre + "w2"]))[0], \
        facts


def _highest(fn):
    """``fn`` traced under the highest matmul precision."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


@_highest
def layer(p, x, sliding, forced=None, eps=0.0, *, cfg):
    """One block over ``x`` (S, d); ``p`` holds the layer's parameters under
    their names without ``layer<i>_``.  A dense layer is one whose ``p`` has
    ``ffn_w1_weight``.  Returns ``(x, route's facts or None)``."""
    eps_n = cfg["rms_norm_eps"]
    a = attention(p, "attn_", rms_norm(x, p["norm1_gamma"], eps_n), cfg,
                  sliding)
    x = x + rms_norm(a, p["norm2_gamma"], eps_n)
    y = rms_norm(x, p["norm3_gamma"], eps_n)
    facts = None
    if "ffn_w1_weight" in p:
        f = gated_ffn(y, p["ffn_w1_weight"], p["ffn_w3_weight"],
                      p["ffn_w2_weight"])
    else:
        f, facts = expert_ffn(p, "moe_", y, cfg, forced, eps)
    return x + rms_norm(f, p["norm4_gamma"], eps_n), facts


def _layer_vjp(p, x, g, sliding, forced=None, eps=0.0, *, cfg):
    """``(gradient of the layer's parameters, gradient of its input)`` for
    the output's gradient ``g``; the forward is computed again here.  What
    is not trained (``moe_bias``) gets no gradient."""
    fixed = {k: v for k, v in p.items() if k == "moe_bias"}
    _, pull = jax.vjp(
        lambda t, x: layer(dict(t, **fixed), x, sliding, forced, eps,
                           cfg=cfg)[0],
        {k: v for k, v in p.items() if k not in fixed}, x)
    return pull(g)


@_highest
def _head_loss(p, x, labels, eps):
    logp = jax.nn.log_softmax(
        rms_norm(x, p["norm_gamma"], eps) @ p["head_weight"].T, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def adamw(p, grads, m, v, t, recipe):
    """One AdamW step with bias correction, ``t`` 1-based, decoupled decay
    on every trained parameter: ``(params, m, v)``."""
    b1, b2 = recipe["beta1"], recipe["beta2"]
    lr, eps, wd = recipe["learning_rate"], recipe["epsilon"], recipe["wd"]
    out_p, out_m, out_v = dict(p), {}, {}
    for k, g in grads.items():
        out_m[k] = b1 * m[k] + (1 - b1) * g
        out_v[k] = b2 * v[k] + (1 - b2) * g * g
        mhat = out_m[k] / (1 - b1 ** t)
        vhat = out_v[k] / (1 - b2 ** t)
        out_p[k] = p[k] - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p[k])
    return out_p, out_m, out_v


@functools.partial(jax.jit, static_argnums=(1, 2))
def balanced_bias(scores, top_k, iterations, rate, decay):
    """The selection bias that evens out a router's load over ``scores`` (T,
    E): from zero, ``iterations`` times ``b += rate * clip((mean load -
    load) / mean load, -1, 1)``, the load being the top-k of ``scores +
    b``, the rate falling by ``decay`` each time.  It is the rule that keeps
    a trained router balanced (the bias falls for an expert with more than
    its share), run to its fixed point on one batch."""
    experts = jnp.arange(scores.shape[-1])

    def nudge(i, bias):
        _, sel = jax.lax.top_k(scores + bias, top_k)
        load = jnp.sum(sel[..., None] == experts, (0, 1)).astype(_F32)
        mean = jnp.mean(load)
        return bias + rate * decay ** i * jnp.clip((mean - load) / mean, -1,
                                                   1)

    return jax.lax.fori_loop(0, iterations, nudge,
                             jnp.zeros(scores.shape[-1], _F32))


class Blocks:
    """The jitted pieces for one ``cfg`` (and, for ``update``, one
    ``recipe``).  Made once and handed to every call, they are compiled
    once a process (and found in JAX's persistent cache by the next);
    called under an outer ``jax.jit`` they are simply part of it."""

    def __init__(self, cfg, recipe=None):
        self.fwd = jax.jit(functools.partial(layer, cfg=cfg))
        self.vjp = jax.jit(functools.partial(_layer_vjp, cfg=cfg))
        self.head = jax.jit(jax.value_and_grad(functools.partial(
            _head_loss, eps=cfg["rms_norm_eps"]), (0, 1)))
        self.update = recipe and jax.jit(
            functools.partial(adamw, recipe=recipe), donate_argnums=(0, 2, 3))


def _split(p, cfg):
    """``(the parameters outside the layers, [each layer's under their
    local names])``, all float32."""
    p = {k: v.astype(_F32) for k, v in p.items()}
    layers = []
    for i in range(len(cfg["layer_types"])):
        pre = "layer%d_" % i
        layers.append({k[len(pre):]: p.pop(k) for k in list(p)
                       if k.startswith(pre)})
    return p, layers


def _embed(outer, ids, cfg):
    x = outer["embed_weight"][ids]
    return x * math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else x


def _walk(cfg, forced, eps, batch, seq):
    """What ``Blocks.fwd`` and ``.vjp`` take after the layer's input, for
    layer ``i`` and sequence ``n``: whether it slides, the forced choices
    and the margin.  Without ``forced`` a table of zeros and a margin that
    no set meets, so that the program is the same one either way."""
    none = jnp.zeros((seq, cfg["num_experts_per_tok"]), jnp.int32)

    def arguments(i, n):
        slides = jnp.asarray(cfg["layer_types"][i] == "sliding_attention")
        if forced is None or i not in forced:
            return slides, none, _F32(-jnp.inf)
        return slides, forced[i][n * seq:(n + 1) * seq], _F32(eps)

    return arguments


def gradients(p, ids, labels, cfg, forced=None, eps=0.0, blocks=None):
    """The loss of the batch ``ids`` (B, S) (the mean of the sequences'
    mean next-token cross-entropy) and its gradient, handed out as it is
    made: a generator that yields ``(loss, facts)`` first and then ``{name:
    gradient}`` group by group: the head and the last norm, the layers from
    the last to the first, the embedding.  ``forced`` is None or ``{layer:
    (B * S, K) choices of the program compared}`` (see ``route``);
    ``facts`` is ``{"refused": [route's four shares for each expert
    layer], "moved": [the share of each expert layer]}``, empty without
    ``forced``.  A group's parameters are not read again once its gradient
    is out, so the caller may update them in place."""
    blocks = blocks or Blocks(cfg)
    outer, layers = _split(p, cfg)
    b, s = ids.shape
    after = _walk(cfg, forced, eps, b, s)
    xs = [[_embed(outer, ids[n], cfg)] for n in range(b)]
    refused, moved = {}, {}
    for i, pl in enumerate(layers):
        for n in range(b):
            x, facts = blocks.fwd(pl, xs[n][-1], *after(i, n))
            xs[n].append(x)
            if facts is not None and forced and i in forced:
                refused[i] = refused.get(i, 0) + facts["refused"] / b
                moved[i] = moved.get(i, 0) + facts["moved"] / b
    head = {k: outer[k] for k in ("norm_gamma", "head_weight")}
    value, g_head, g_x = 0.0, None, []
    for n in range(b):
        loss_n, (g, g_last) = blocks.head(head, xs[n].pop(), labels[n])
        value = value + loss_n / b
        g_head = jax.tree.map(lambda a, c: a + c / b, g_head, g) \
            if g_head else jax.tree.map(lambda c: c / b, g)
        g_x.append(g_last / b)
    yield value, {"refused": [refused[i] for i in sorted(refused)],
                  "moved": [moved[i] for i in sorted(moved)]}
    yield g_head
    for i in reversed(range(len(layers))):
        total = None
        for n in range(b):
            g, g_x[n] = blocks.vjp(layers[i], xs[n].pop(), g_x[n],
                                   *after(i, n))
            total = jax.tree.map(jnp.add, total, g) if total else g
        yield {"layer%d_%s" % (i, k): g for k, g in total.items()}
    scale = math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0
    g_embed = jnp.zeros_like(outer["embed_weight"])
    for n in range(b):
        g_embed = g_embed.at[ids[n]].add(g_x[n] * scale)
    yield {"embed_weight": g_embed}


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
        for i, pl in enumerate(layers):
            x, _ = layer(pl, x, cfg["layer_types"][i] == "sliding_attention",
                         cfg=cfg)
        total = total + _head_loss(outer, x, labels[n], cfg["rms_norm_eps"])
    return total / ids.shape[0]


def apply(p, m, v, t, group, blocks):
    """AdamW over one group of gradients, in place of the dicts ``p``, ``m``
    and ``v`` (a moment that is not there yet starts at zero); the old
    arrays are donated.  The program sees the group's arrays by position,
    so that layers of one shape share it."""
    names = sorted(group)

    def by_position(d, zero=False):
        return {str(j): d[k] if k in d or not zero else jnp.zeros_like(p[k])
                for j, k in enumerate(names)}

    new = blocks.update(by_position(p), by_position(group),
                        by_position(m, True), by_position(v, True), _F32(t))
    for old, fresh in zip((p, m, v), new):
        old.update({k: fresh[str(j)] for j, k in enumerate(names)})


def step(p, m, v, t, ids, labels, cfg, blocks):
    """One training step (``t`` 1-based) in place of ``p``, ``m``, ``v``,
    routing for itself: each group is updated as its gradient comes, so
    that no more than one group's gradient is alive.  Returns the loss
    (taken before the update)."""
    sweep = gradients(p, ids, labels, cfg, blocks=blocks)
    value, _ = next(sweep)
    for group in sweep:
        apply(p, m, v, t, group, blocks)
    return value


def balance(p, ids, cfg, iterations, rate, decay, blocks=None):
    """``{layer<i>_moe_bias: selection bias}`` that evens out each router's
    load on the batch ``ids`` at the parameters ``p``: one forward pass,
    each expert layer run once for its scores, given ``balanced_bias`` of
    them, and run again under it."""
    blocks = blocks or Blocks(cfg)
    outer, layers = _split(p, cfg)
    b, s = ids.shape
    after = _walk(cfg, None, 0.0, b, s)
    xs = [_embed(outer, ids[n], cfg) for n in range(b)]
    out = {}
    for i, pl in enumerate(layers):
        if "moe_bias" in pl:
            scores = jnp.concatenate([
                blocks.fwd(pl, xs[n], *after(i, n))[1]["scores"]
                for n in range(b)])
            pl = dict(pl, moe_bias=balanced_bias(
                scores, cfg["num_experts_per_tok"], iterations, rate, decay))
            out["layer%d_moe_bias" % i] = pl["moe_bias"]
        xs = [blocks.fwd(pl, xs[n], *after(i, n))[0] for n in range(b)]
    return out
