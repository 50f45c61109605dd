"""Plain reference of the ``kimi_linear`` decoder (Kimi-Linear-48B-A3B):
forward pass, loss, gradients and an AdamW step, in straightforward
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``.
No kernel, no sort, no chunking of the recurrence; it imports nothing of
``incubator_mxnet_tpu``.  What the family shares with the other sparse
decoders to the letter (the RMS norm, the gated feed-forward, the expert
layer with ``route(forced, eps)``, AdamW, the bias that balances a router,
the walk over the blocks that hands the gradient out group by group) is
taken from ``references/trinity_mini.py`` and ``references/glm47_flash.py``,
whose texts say who routes and why.

The equations follow ``config.json`` of the source and, for the layers,
the Kimi Linear technical report (arXiv:2510.26692, section 3) and the
family's public modelling code, as the configuration's ``assumed`` block
records it::

    h = E[ids];   a = h + Attn(N1(h));   h = a + FFN(N2(a))
    KDA(x):  q, k, v = silu(causal depthwise conv4(x Wq, x Wk, x Wv))
             q = l2norm_head(q) / sqrt(dk);  k = l2norm_head(k)
             g = -exp(A_log[h]) softplus(x Wf_a^T Wf_b^T + dt_bias)
             beta = sigmoid(x Wb^T)
             S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
                   + beta_t k_t v_t^T,  S_0 = 0;   o_t = S_t^T q_t
             y = (RMSNorm_head(o) gamma * sigmoid(x Wg_a^T Wg_b^T)) Wo^T
    MLA(x):  q = x Wq^T (heads of nope + rope);  [c | kr] = x Wkva^T
             [k_nope | v] = N(c) Wkvb^T;  k = [k_nope | kr]  (no rotary)
             o = softmax_causal(q k^T / sqrt(nope + rope)) v;  o Wo^T
    logits = N(h_L) Whead;  loss = mean_i CE(logits_i, y_i)

The recurrence runs token by token (``lax.scan``), its gradient through
segments of ``_SEGMENT`` tokens that are computed again in the backward
pass (``jax.checkpoint``), so that 16,384 tokens fit; attention runs one
head and one block of ``_QUERIES`` queries at a time.

``params`` is a flat dict of float32 arrays, matrices ``(outputs, inputs)``,
under the zoo's names without the net's prefix: ``layer<i>_kda_*`` (q, k, v,
f_a, f_b, b, g_a, g_b, o weights; ``{q,k,v}_conv_weight (C, 4)``,
``A_log (1, 1, H, 1)``, ``dt_bias (H * dk,)``, ``o_norm_gamma (dk,)``),
``layer<i>_attn_*`` (q, kv_a, kv_b, o weights, ``kv_a_norm_gamma``), the
feed-forward and expert leaves as in ``references/trinity_mini.py``.

Besides the reference itself the module declares what is the family's and
a runner needs: ``model_cfg``, ``counters``, ``GRAD_GROUPS`` and
``CONTROLS``.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp

from perfbench.references import glm47_flash as _walk
from perfbench.references.glm47_flash import _head_loss, _round8
from perfbench.references.trinity_mini import (  # noqa: F401 (apply)
    _highest, adamw, apply, expert_ffn, gated_ffn, rms_norm)

_F32 = jnp.float32
#: tokens of a segment of the recurrence that the backward pass recomputes
_SEGMENT = 128
#: queries of a block of plain attention
_QUERIES = 2048
#: heads of KDA computed at a time
_KDA_HEADS = 8


def model_cfg(config):
    """What this reference needs of the configuration: the family's keys
    under their ``config.json`` names, and the expert layer's and the block
    walk's under the names ``references/trinity_mini.py`` and
    ``references/glm47_flash.py`` read."""
    cfg = {k: config[k] for k in (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rms_norm_eps", "num_layers",
        "num_nextn_predict_layers", "linear_attn_config")}
    cfg["num_experts"] = config["num_experts"]
    cfg["num_experts_per_tok"] = config["num_experts_per_token"]
    cfg["route_norm"] = config["moe_renormalize"]
    cfg["route_scale"] = config["routed_scaling_factor"]
    cfg["experts_held"] = tuple(config["experts_held"])
    cfg["mtp_weight"] = 0.0
    return cfg


# ---------------------------------------------------------------------------
# Kimi Delta Attention
# ---------------------------------------------------------------------------

def short_conv(x, w):
    """``silu`` of the causal depthwise convolution of ``x`` (S, C) with the
    taps ``w`` (C, K), from zeros:
    ``y_t = sum_m w[:, m] x_{t-K+1+m}``."""
    taps = w.shape[-1]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    y = sum(w[:, m] * xp[m:m + x.shape[0]] for m in range(taps))
    return jax.nn.silu(y)


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: ``q, k, g`` (S, H, dk), ``v`` (S, H,
    dv), ``beta`` (S, H); ``o`` (S, H, dv).  The sequence runs in segments
    of ``_SEGMENT`` tokens, each recomputed in the backward pass."""
    s, h, dk = q.shape

    def token(S, t):
        qt, kt, vt, gt, bt = t
        S = S * jnp.exp(gt)[..., None]
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs)

    n = -(-s // _SEGMENT)
    pad = n * _SEGMENT - s

    def cut(x):
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        return x.reshape((n, _SEGMENT) + x.shape[1:])

    S0 = jnp.zeros((h, dk, v.shape[-1]), _F32)
    _, o = jax.lax.scan(segment, S0, tuple(map(cut, (q, k, v, g, beta))))
    return o.reshape((n * _SEGMENT,) + o.shape[2:])[:s]


def kda_attention(p, pre, x, cfg):
    """KDA over ``x`` (S, d), ``_KDA_HEADS`` heads at a time: the heads are
    independent up to the output projection, which sums what each group's
    columns give; each group is computed again in the backward pass, so
    that a gradient holds one group's intermediates at a time."""
    lin = cfg["linear_attn_config"]
    h, dk = lin["num_heads"], lin["head_dim"]
    n = math.gcd(h, _KDA_HEADS)
    s, d = x.shape

    def by_group(w):
        """A weight ``(H * dk, ...)`` as ``(H / n, n * dk, ...)``."""
        return w.reshape((h // n, n * dk) + w.shape[1:])

    f_a = x @ p[pre + "f_a_weight"].T
    g_a = x @ p[pre + "g_a_weight"].T
    beta = jax.nn.sigmoid(x @ p[pre + "b_weight"].T).reshape(s, h // n, n)
    groups = (
        [by_group(p[pre + m + "_weight"]) for m in ("q", "k", "v")],
        [by_group(p[pre + m + "_conv_weight"]) for m in ("q", "k", "v")],
        by_group(p[pre + "f_b_weight"]), by_group(p[pre + "g_b_weight"]),
        p[pre + "A_log"].reshape(h // n, n, 1),
        by_group(p[pre + "dt_bias"]), by_group(p[pre + "o_weight"].T),
        beta.transpose(1, 0, 2))

    @jax.checkpoint
    def group(y, one):
        w, conv, f_b, g_b, a_log, dt_bias, o_w, b = one
        q, k, v = (short_conv(x @ wm.T, cm).reshape(s, n, dk)
                   for wm, cm in zip(w, conv))
        q = l2norm(q) / math.sqrt(dk)
        k = l2norm(k)
        g = -jnp.exp(a_log) * jax.nn.softplus(
            (f_a @ f_b.T).reshape(s, n, dk) + dt_bias.reshape(n, dk))
        o = delta_rule(q, k, v, g, b)
        o = rms_norm(o, p[pre + "o_norm_gamma"], cfg["rms_norm_eps"]) \
            * jax.nn.sigmoid((g_a @ g_b.T).reshape(s, n, dk))
        return y + o.reshape(s, n * dk) @ o_w, None

    return jax.lax.scan(group, jnp.zeros((s, d), x.dtype), groups)[0]


# ---------------------------------------------------------------------------
# latent attention without position
# ---------------------------------------------------------------------------

def _one_head(q, k, v):
    """Causal softmax attention of one head, ``_QUERIES`` queries at a
    time: q, k ``(S, nope + rope)``, v ``(S, dv)``."""
    s, width = q.shape
    n = -(-s // _QUERIES)
    qs = jnp.concatenate([q, jnp.zeros((n * _QUERIES - s, width), q.dtype)])

    @jax.checkpoint
    def block(args):
        i, qb = args
        rows = i * _QUERIES + jnp.arange(_QUERIES)[:, None]
        keep = jnp.arange(s)[None, :] <= rows
        scores = jnp.where(keep, (qb @ k.T) / math.sqrt(width), -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v

    out = jax.lax.map(block, (jnp.arange(n), qs.reshape(n, _QUERIES, width)))
    return out.reshape(n * _QUERIES, -1)[:s]


def latent_attention(p, pre, x, cfg):
    s, _ = x.shape
    h, nope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                   cfg["v_head_dim"])
    latent = cfg["kv_lora_rank"]
    q = (x @ p[pre + "q_weight"].T).reshape(s, h, -1)
    kv = x @ p[pre + "kv_a_weight"].T
    ckv, kr = kv[:, :latent], kv[:, latent:]
    kv = (rms_norm(ckv, p[pre + "kv_a_norm_gamma"], cfg["rms_norm_eps"])
          @ p[pre + "kv_b_weight"].T).reshape(s, h, nope + vd)
    # the one unrotated key column block a position, beside every head's own
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(kr[:, None], (s, h, kr.shape[-1]))],
                        -1)
    out = jax.lax.map(lambda qkv: _one_head(*qkv), (
        q.transpose(1, 0, 2), k.transpose(1, 0, 2),
        kv[..., nope:].transpose(1, 0, 2)))
    return out.transpose(1, 0, 2).reshape(s, h * vd) @ p[pre + "o_weight"].T


# ---------------------------------------------------------------------------
# blocks, and what the walk over them needs
# ---------------------------------------------------------------------------

@_highest
def layer(p, x, forced=None, eps=0.0, *, cfg):
    """One block over ``x`` (S, d); ``p`` holds the layer's parameters under
    their names without ``layer<i>_``.  A KDA layer is one whose ``p`` has
    ``kda_A_log``, a dense one one whose ``p`` has ``ffn_w1_weight``.
    Returns ``(x, route's facts or None)``."""
    eps_n = cfg["rms_norm_eps"]
    y = rms_norm(x, p["norm1_gamma"], eps_n)
    # the attention and the dense feed-forward are each computed again in
    # the backward pass, so that a layer's gradient holds one of them at a
    # time (at 16,384 tokens a KDA layer's float32 intermediates are 268 MB
    # each, and a layer's whole set does not fit beside the AdamW state)
    if "kda_A_log" in p:
        a = x + jax.checkpoint(lambda p, y: kda_attention(p, "kda_", y, cfg))(
            p, y)
    else:
        a = x + jax.checkpoint(
            lambda p, y: latent_attention(p, "attn_", y, cfg))(p, y)
    y = rms_norm(a, p["norm2_gamma"], eps_n)
    if "ffn_w1_weight" in p:
        return a + jax.checkpoint(gated_ffn)(
            y, p["ffn_w1_weight"], p["ffn_w3_weight"],
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


class Blocks:
    """The jitted pieces for one ``cfg`` (and, for ``update``, one
    ``recipe``): see ``references/trinity_mini.py::Blocks``."""

    def __init__(self, cfg, recipe=None):
        eps = cfg["rms_norm_eps"]
        self.fwd = jax.jit(functools.partial(layer, cfg=cfg))
        self.vjp = jax.jit(functools.partial(_layer_vjp, cfg=cfg))
        self.head = jax.jit(jax.value_and_grad(functools.partial(
            _head_loss, eps=eps), (0, 1)))
        self.update = recipe and jax.jit(
            functools.partial(adamw, recipe=recipe), donate_argnums=(0, 2, 3))


def gradients(p, ids, labels, cfg, forced=None, eps=0.0, blocks=None):
    """The loss of the batch and its gradient, handed out group by group as
    ``references/glm47_flash.py::gradients`` walks the blocks (this family
    has no prediction module), over this family's ``Blocks``."""
    return _walk.gradients(p, ids, labels, cfg, forced, eps,
                           blocks or Blocks(cfg))


def loss_and_grads(p, ids, labels, cfg, forced=None, eps=0.0, blocks=None):
    """``(loss, {name: gradient}, facts)``: ``gradients`` gathered."""
    return _walk.loss_and_grads(p, ids, labels, cfg, forced, eps,
                                blocks or Blocks(cfg))


def step(p, m, v, t, ids, labels, cfg, blocks):
    """One training step in place of ``p``, ``m``, ``v`` (``t`` 1-based),
    as ``references/glm47_flash.py::step``; returns the loss."""
    return _walk.step(p, m, v, t, ids, labels, cfg, blocks)


#: the decay's parameters as the family's public code draws them (fla-org's
#: ``fla/layers/kda.py``): ``A`` uniform over ``_A_RANGE`` a head and
#: ``A_log = log A``; ``dt`` log-uniform over ``_DT_RANGE`` a channel, no
#: less than ``_DT_FLOOR``, and ``dt_bias = softplus^-1(dt)``
_A_RANGE, _DT_RANGE, _DT_FLOOR = (1.0, 16.0), (1e-3, 1e-1), 1e-4


@functools.partial(jax.jit, static_argnums=1)
def _decay_init(a_log, dk):
    """``(A_log, dt_bias (H * dk,))`` as ``_A_RANGE`` and ``_DT_RANGE`` say,
    from the harness's draw of ``A_log`` (Xavier, uniform over ``(-a, a)``
    with ``a = sqrt(6 / (H + 1))`` for its shape ``(1, 1, H, 1)``): its
    uniforms are that draw mapped to ``(0, 1)``, and the channels' uniforms
    come from a key folded from its bits (the harness draws ``dt_bias``,
    ending in ``_bias``, as zero)."""
    h = a_log.size
    a = math.sqrt(6.0 / (h + 1))
    u = (a_log + a) / (2 * a)
    lo, hi = _A_RANGE
    key = functools.reduce(
        jax.random.fold_in,
        jax.lax.bitcast_convert_type(a_log.ravel(), jnp.uint32),
        jax.random.PRNGKey(0))
    lo_dt, hi_dt = map(math.log, _DT_RANGE)
    dt = jnp.maximum(jnp.exp(lo_dt + (hi_dt - lo_dt) * jax.random.uniform(
        key, (h * dk,), _F32)), _DT_FLOOR)
    return jnp.log(lo + (hi - lo) * u), dt + jnp.log(-jnp.expm1(-dt))


def kda_init(p, cfg):
    """What the family's code draws otherwise than the harness's rule by
    name, for every KDA layer, from the seed's weights ``p``: ``A_log`` and
    ``dt_bias`` as ``_decay_init`` says, so that the state remembers as a
    fresh model's does (a decay of ``exp(-A dt)``, about e^-0.0016 to
    e^-1.6 a token, where the rule by name gives ``alpha`` 0.35-0.64 and a
    state of two tokens), and each short convolution's taps ``(C, K)``
    uniform over +-1/sqrt(K), as a depthwise ``Conv1d`` is drawn (the
    harness's Xavier draw over ``(C, K)``, uniform over +-sqrt(6 / (C +
    K)), scaled).  ``{name: value}`` on the host."""
    dk = cfg["linear_attn_config"]["head_dim"]
    out = {}
    for name in sorted(p):
        if name.endswith("_kda_A_log"):
            pre = name[:-len("A_log")]
            out[name], out[pre + "dt_bias"] = jax.device_get(
                _decay_init(p[name], dk))
        elif "_kda_" in name and name.endswith("_conv_weight"):
            c, k = p[name].shape
            out[name] = jax.device_get(
                p[name] * (math.sqrt(1.0 / k) / math.sqrt(6.0 / (c + k))))
    return out


def balance(p, ids, cfg, iterations, rate, decay, blocks=None):
    """What the harness holds fixed over the seed's weights ``p``: what the
    family's code draws otherwise than the rule by name (``kda_init``),
    and, at those weights, ``{layer<i>_moe_bias: selection bias}`` that
    evens out each router's load on the batch ``ids``, as
    ``references/glm47_flash.py::balance``."""
    out = kda_init(p, cfg)
    out.update(_walk.balance(dict(p, **out), ids, cfg, iterations, rate,
                             decay, blocks or Blocks(cfg)))
    return out


def loss(p, ids, labels, cfg):
    """The mean next-token loss as one straightforward composition (what
    ``jax.grad`` differentiates as a whole; ``gradients`` must agree)."""
    p = {k: v.astype(_F32) for k, v in p.items()}
    total = 0.0
    for n in range(ids.shape[0]):
        x = p["embed_weight"][ids[n]]
        for i in range(cfg["num_layers"]):
            pre = "layer%d_" % i
            x, _ = layer({k[len(pre):]: v for k, v in p.items()
                          if k.startswith(pre)}, x, cfg=cfg)
        total = total + _head_loss(p, x, labels[n], cfg["rms_norm_eps"])
    return total / ids.shape[0]


# ---------------------------------------------------------------------------
# what the kernels have to do: operations and bytes, from shapes and counts
# ---------------------------------------------------------------------------

def layer_kinds(config):
    """The kinds of the blocks the configuration builds, ``kda`` or
    ``mla``."""
    kinds = config["factory_kwargs"].get("layer_kinds")
    if kinds is None:
        kda = config["linear_attn_config"]["kda_layers"]
        kinds = ["kda" if i + 1 in kda else "mla"
                 for i in range(config["num_layers"])]
    return list(kinds)


def counters(config, loads, batch):
    """The work and byte counts the per-layer metrics read, per step on this
    chip, under the names the metric files read.  ``loads`` is ``[(expert
    layer, assignments per expert)]`` of the step's own last step.  Each
    counts the mathematics (what any implementation must do), never a
    kernel's own recomputation: a training step is the forward products
    and twice as many in the backward pass."""
    c = config
    seq, d, f = c["seq_len"], c["hidden_size"], c["moe_intermediate_size"]
    lin = c["linear_attn_config"]
    kinds = layer_kinds(c)
    n_kda, n_mla = kinds.count("kda"), kinds.count("mla")
    heads, first, count = (c["num_attention_heads"],) + tuple(
        c["experts_held"])
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    tokens = batch * seq
    # the recurrence: per token and head, the decay and read of the state
    # against k, the rank-1 write, the read by q: 3 x dk x dv forward
    kh, kd = lin["num_heads"], lin["head_dim"]
    kda_fwd_macs = tokens * kh * 3 * kd * kd * n_kda
    # q, k, v (bf16), g and beta (float32) read and o (bf16) written, once
    # forward and once backward
    kda_bytes = 2 * n_kda * tokens * (
        kh * kd * (3 * 2 + 4 + 2) + kh * 4)
    # latent attention: q k^T over nope + rope columns and p v over
    # v_head_dim, for the pairs a causal mask admits, forward
    pairs = seq * (seq + 1) // 2 * heads * batch * n_mla
    attn_fwd_macs = pairs * (width + c["v_head_dim"])
    # its projections: the query, down to the latent and the shared key
    # columns, up to the heads, and out
    mla_fwd_macs = tokens * n_mla * (
        d * heads * width + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
        + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
        + heads * c["v_head_dim"] * d)
    held = sum(float(n[first:first + count].sum()) for _, n in loads)
    total = sum(float(n.sum()) for _, n in loads)
    expert_fwd_macs = held * 3 * d * f
    weight_bytes = len(loads) * count * 3 * d * f * 2 * 3
    row_bytes = held * (d + f + f + f + f + d) * 2 * 3
    dispatch_bytes = held * d * 2 * 2 * 4
    worst = [float(n[first:first + count].max()
                   / max(n[first:first + count].mean(), 1e-30))
             for _, n in loads]
    flops_per_sample = 3 * 2 * (c["fwd_macs_per_sample"] * batch
                                + kda_fwd_macs + attn_fwd_macs
                                + expert_fwd_macs) / batch
    return {
        "flops_per_sample": flops_per_sample,
        "flops_per_module_per_chip": flops_per_sample * batch,
        "kda_flops_per_module": 3 * 2 * kda_fwd_macs,
        "kda_bytes_per_module": kda_bytes,
        "attn_flops_per_module": 3 * 2 * attn_fwd_macs,
        "mla_proj_flops_per_module": 3 * 2 * mla_fwd_macs,
        "expert_flops_per_module": 3 * 2 * expert_fwd_macs,
        "expert_bytes_per_module": weight_bytes + row_bytes,
        "dispatch_bytes_per_module": dispatch_bytes,
        "dispatch_ops_per_module": dispatch_bytes,
        "assignments_held": held,
        "assignments_routed": total,
        "moe_load_max_over_mean": sum(worst) / max(len(worst), 1),
        "assignments_dropped": tokens * c["num_experts_per_token"]
        * len(loads) - total,
    }


# ---------------------------------------------------------------------------
# what a comparison with this reference groups and must be able to tell
# ---------------------------------------------------------------------------

#: ``(group, pattern over a leaf's name)``: a leaf of the gradient belongs
#: to the first group whose pattern is found in it
GRAD_GROUPS = (
    ("kda", re.compile(r"_kda_")),
    ("attention", re.compile(r"_attn_")),
    ("experts", re.compile(r"_moe_w\d")),
    ("router", re.compile(r"_router_")),
    ("other", re.compile(r"")),
)


#: tokens of a chunk of the recurrence in the configuration's kernels
_CHUNK = 64


def _by_chunks(was):
    """``_contrib_kda`` run on every chunk of ``_CHUNK`` tokens as a sequence
    of its own: the state is not carried from one chunk to the next."""
    def run(q, k, v, g, beta, **kw):
        b, s = beta.shape[:2]
        n = -(-s // _CHUNK)

        def cut(x):
            x = jnp.pad(x, [(0, 0), (0, n * _CHUNK - s)]
                        + [(0, 0)] * (x.ndim - 2))
            return x.reshape((b * n, _CHUNK) + x.shape[2:])

        o = was(*map(cut, (q, k, v, g, beta)), **kw)
        return o.reshape((b, n * _CHUNK) + o.shape[2:])[:, :s]

    return run


#: the ways to break the step on purpose, each ``(the registered op that is
#: replaced while the step is built, what replaces it, given what it was)``.
#: ``decay``: alpha = 1 (g = 0).  ``beta``: beta = 1.  ``conv``: the short
#: convolutions dropped (silu of the projection alone).  ``chunk_state``:
#: the state not carried across chunks.  ``out_gate``: the output gate
#: dropped (sigmoid = 1).  ``float8``: the recurrence's q, k and v rounded
#: to e4m3 under a scale a tensor (the precision below the configuration's
#: bf16 compute), cotangent straight through.  No control holds the state
#: between chunks in bfloat16: at the cell's decays that moves KDA's output
#: and gradients by 0.09-0.24 %, less than the step's own bf16 q, k and v
#: do (0.24-0.29 %), so no limit above the sound step's rounding can tell
#: it (PERF.md section 4).
CONTROLS = {
    "decay": ("_contrib_kda", lambda was: lambda q, k, v, g, beta, **kw: was(
        q, k, v, jnp.zeros_like(g), beta, **kw)),
    "beta": ("_contrib_kda", lambda was: lambda q, k, v, g, beta, **kw: was(
        q, k, v, g, jnp.ones_like(beta), **kw)),
    "conv": ("_contrib_kda_conv",
             lambda was: lambda data, weight, **kw: jax.nn.silu(
                 data.astype(_F32)).astype(data.dtype)),
    "chunk_state": ("_contrib_kda", _by_chunks),
    "out_gate": ("_contrib_kda_out_norm",
                 lambda was: lambda data, gate, gamma, **kw: was(
                     data, jnp.full_like(gate, 1e4), gamma, **kw)),
    "float8": ("_contrib_kda", lambda was: lambda *a, **kw: was(
        *map(_round8, a[:3]), *a[3:], **kw)),
}
