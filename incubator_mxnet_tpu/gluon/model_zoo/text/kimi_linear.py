"""The ``kimi_linear`` decoder family (Kimi-Linear-48B-A3B) as Gluon
``HybridBlock``s.

A pre-norm decoder of two norms a block, whose attention is of one of two
kinds, layer by layer (``linear_attn_config``: ``kda_layers`` and
``full_attn_layers``, numbered from 1)::

    h = E[ids]
    a = h + Attn(N1(h));   h = a + FFN(N2(a))
    logits = N(h_L) Whead

``Attn`` is Kimi Delta Attention (KDA, :class:`KimiDeltaAttention`) or
latent attention without any position (``glm4_moe_lite.LatentAttention``
with one query projection, ``q_lora_rank`` null, and no rotary,
``mla_use_nope``: its 64 "rope" columns are used unrotated).  The first
``first_k_dense_replace`` layers have a gated-SiLU feed-forward, the others
the expert layer of ``afmoe.ExpertFFN``: sigmoid scores with a selection
bias, top-k over all the experts (one group), renormalised and scaled, one
shared expert.

KDA, for head ``h`` (``parallel/delta_rule.py`` has the recurrence)::

    q, k, v = silu(causal depthwise conv4(x Wq, x Wk, x Wv))   (per channel)
    q = l2norm(q) / sqrt(dk);   k = l2norm(k)                  (per head)
    g = -exp(A_log[h]) softplus(x Wf_a Wf_b + dt_bias)          (per channel)
    beta = sigmoid(x Wb)[h]
    S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T;  o_t = S_t^T q
    y = (RMSNorm_head(o) * sigmoid(x Wg_a Wg_b)) Wo

``experts_held``, ``vocab_rows``, ``recompute`` and ``keep_choices`` are
what they are in ``afmoe``; every parameter keeps deferred initialisation.
"""
from __future__ import annotations

import math

from ...block import HybridBlock
from .afmoe import ExpertFFN, GatedFFN, RMSNorm, _linear
from .glm4_moe_lite import LatentAttention

__all__ = ["KimiDeltaAttention", "KimiLinearLayer", "KimiLinearDecoder",
           "kimi_linear_48b", "kimi_linear_tiny"]


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention over ``heads`` heads of ``head_dim`` (keys and
    values alike): projections, short convolutions of ``conv_size`` taps,
    per-head L2 norms, the channel-wise decay and the write strength, the
    recurrence (``_contrib_kda``), the gated per-head output norm and the
    output projection.  ``rank`` is the width of the two low-rank gate
    projections.  Parameters under the published names: ``A_log`` one a
    head, shaped ``(1, 1, heads, 1)``, ``dt_bias`` one a channel, each
    convolution's taps ``(channels, conv_size)``, a matrix a convolution
    (a depthwise ``Conv1d``'s ``(channels, 1, conv_size)`` without its axis
    of one)."""

    def __init__(self, hidden, heads, head_dim, conv_size, rank, eps=1e-5,
                 **kwargs):
        super().__init__(**kwargs)
        self._heads, self._hd, self._eps = heads, head_dim, eps
        width = heads * head_dim
        with self.name_scope():
            get = self.params.get
            self.q = _linear(width, "q_")
            self.k = _linear(width, "k_")
            self.v = _linear(width, "v_")
            self.q_conv_weight = get("q_conv_weight",
                                     shape=(width, conv_size))
            self.k_conv_weight = get("k_conv_weight",
                                     shape=(width, conv_size))
            self.v_conv_weight = get("v_conv_weight",
                                     shape=(width, conv_size))
            self.f_a = _linear(rank, "f_a_")
            self.f_b = _linear(width, "f_b_")
            self.b = _linear(heads, "b_")
            self.A_log = get("A_log", shape=(1, 1, heads, 1))
            self.dt_bias = get("dt_bias", shape=(width,), init="zeros")
            self.g_a = _linear(rank, "g_a_")
            self.g_b = _linear(width, "g_b_")
            self.o_norm_gamma = get("o_norm_gamma", shape=(head_dim,),
                                    init="ones")
            self.o = _linear(hidden, "o_")

    def hybrid_forward(self, F, x, q_conv_weight, k_conv_weight,  # noqa: N803
                       v_conv_weight, A_log, dt_bias, o_norm_gamma):
        heads, kda = self._heads, F.contrib
        q = kda.kda_qk_norm(kda.kda_conv(self.q(x), q_conv_weight),
                            heads=heads, scale=1.0 / math.sqrt(self._hd))
        k = kda.kda_qk_norm(kda.kda_conv(self.k(x), k_conv_weight),
                            heads=heads)
        v = kda.kda_conv(self.v(x), v_conv_weight)
        g, beta = kda.kda_gate(self.f_b(self.f_a(x)), self.b(x), A_log,
                               dt_bias)
        o = kda.kda(q, k, v, g, beta)
        return self.o(kda.kda_out_norm(o, self.g_b(self.g_a(x)),
                                       o_norm_gamma, eps=self._eps))


class KimiLinearLayer(HybridBlock):
    """One decoder block: ``a = x + Attn(N1(x))``, ``a + FFN(N2(a))``;
    ``kind`` ``"kda"`` or ``"mla"`` chooses the attention, ``dense`` the
    gated-SiLU feed-forward, else the expert layer."""

    def __init__(self, config, kind, dense, experts_held=None,
                 keep_choices=False, **kwargs):
        super().__init__(**kwargs)
        c, eps = config, config["rms_norm_eps"]
        lin = c["linear_attn_config"]
        with self.name_scope():
            self.norm1 = RMSNorm(eps, prefix="norm1_")
            self.norm2 = RMSNorm(eps, prefix="norm2_")
            if kind == "kda":
                self.attn = KimiDeltaAttention(
                    c["hidden_size"], lin["num_heads"], lin["head_dim"],
                    lin["short_conv_kernel_size"], lin["head_dim"], eps=eps,
                    prefix="kda_")
            elif kind == "mla":
                self.attn = LatentAttention(
                    c["hidden_size"], c["num_attention_heads"],
                    c["q_lora_rank"], c["kv_lora_rank"],
                    c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"], rope_theta=c["rope_theta"], eps=eps,
                    rotary=not c["mla_use_nope"], prefix="attn_")
            else:
                raise ValueError("layer kind %r: kda or mla" % (kind,))
            if dense:
                self.ffn = GatedFFN(c["hidden_size"], c["intermediate_size"],
                                    prefix="ffn_")
            else:
                self.ffn = ExpertFFN(
                    c["hidden_size"], c["num_experts"],
                    c["num_experts_per_token"], c["moe_intermediate_size"],
                    experts_held=experts_held,
                    route_norm=c["moe_renormalize"],
                    route_scale=c["routed_scaling_factor"],
                    keep_choices=keep_choices, prefix="moe_")

    def hybrid_forward(self, F, x):  # noqa: N803
        a = x + self.attn(self.norm1(x))
        return a + self.ffn(self.norm2(a))


class KimiLinearDecoder(HybridBlock):
    """Token ids ``(B, S)`` to float32 logits ``(B, S, vocab_rows)``.
    ``config`` holds the keys of the family's ``config.json``;
    ``layer_kinds`` lists the blocks built (``"kda"`` or ``"mla"``), the
    first ``first_k_dense_replace`` of them dense; ``experts_held`` and
    ``vocab_rows`` are the chip's share; ``recompute`` has every block's
    interior recomputed in the backward pass."""

    def __init__(self, config, layer_kinds, vocab_rows, experts_held=None,
                 recompute=False, keep_choices=False, **kwargs):
        super().__init__(**kwargs)
        self._hidden, self._rows = config["hidden_size"], vocab_rows
        self._recompute = bool(recompute)
        self.layers = []
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_rows, 0),
                allow_deferred_init=True)
            for i, kind in enumerate(layer_kinds):
                layer = KimiLinearLayer(
                    config, kind, dense=i < config["first_k_dense_replace"],
                    experts_held=experts_held, keep_choices=keep_choices,
                    prefix="layer%d_" % i)
                self.layers.append(layer)
                self.register_child(layer, "layer%d" % i)
            self.norm = RMSNorm(config["rms_norm_eps"], prefix="norm_")
            self.head = _linear(vocab_rows, "head_")
        self.hybridize(False)

    def hybridize(self, active=True, **kwargs):
        super().hybridize(active, **kwargs)
        if self._recompute:
            # each block is its own region; the flag does not survive a
            # plain hybridize(), so it is set again here
            for layer in self.layers:
                layer.hybridize(active, **dict(kwargs, remat=True))

    def infer_shape(self, x, *args):
        self.embed_weight.shape = (self._rows, self._hidden)

    def hybrid_forward(self, F, ids, embed_weight):  # noqa: N803
        h = F.Embedding(ids, embed_weight, input_dim=self._rows,
                        output_dim=self._hidden)
        for layer in self.layers:
            h = layer(h)
        return self.head(self.norm(h)).astype("float32")


#: ``config.json`` of moonshotai/Kimi-Linear-48B-A3B-Instruct: what
#: ``kimi_linear_48b()`` builds when no keyword says otherwise
_KIMI_LINEAR_48B = dict(
    hidden_size=2304, num_hidden_layers=27, first_k_dense_replace=1,
    intermediate_size=9216, moe_intermediate_size=1024, moe_layer_freq=1,
    num_attention_heads=32, num_key_value_heads=32, head_dim=72,
    q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, mla_use_nope=True,
    num_experts=256, num_experts_per_token=8, num_shared_experts=1,
    num_expert_group=1, topk_group=1, use_grouped_topk=True,
    moe_renormalize=True, moe_router_activation_func="sigmoid",
    routed_scaling_factor=2.446, num_nextn_predict_layers=0,
    vocab_size=163840, rope_theta=10000.0, rope_scaling=None,
    rms_norm_eps=1e-5, tie_word_embeddings=False,
    linear_attn_config=dict(
        full_attn_layers=[4, 8, 12, 16, 20, 24, 27], head_dim=128,
        kda_layers=[1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                    22, 23, 25, 26],
        num_heads=32, short_conv_kernel_size=4))


def _build(config, num_layers=None, layer_kinds=None, experts_held=None,
           vocab_rows=None, recompute=False, keep_choices=False, **kwargs):
    unknown = set(kwargs) - set(config)
    if unknown:
        raise TypeError("not keys of the family's config.json: %s"
                        % sorted(unknown))
    config = dict(config, **kwargs)
    lin = config["linear_attn_config"]
    if layer_kinds is None:
        n = config["num_hidden_layers"] if num_layers is None else num_layers
        layer_kinds = ["kda" if i + 1 in lin["kda_layers"] else "mla"
                       for i in range(n)]
    elif num_layers is not None and num_layers != len(layer_kinds):
        raise ValueError("num_layers=%d with %d layer kinds"
                         % (num_layers, len(layer_kinds)))
    if config["num_shared_experts"] != 1:
        raise ValueError("the family has one shared expert")
    if config["num_expert_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("routing limited to groups of experts is not built")
    if config["moe_router_activation_func"] != "sigmoid" \
            or config["moe_layer_freq"] != 1:
        raise ValueError("sigmoid routing in every layer after the dense "
                         "ones is what is built")
    if config["num_nextn_predict_layers"]:
        raise ValueError("the family has no prediction module")
    return KimiLinearDecoder(
        config, list(layer_kinds),
        config["vocab_size"] if vocab_rows is None else vocab_rows,
        experts_held=experts_held, recompute=recompute,
        keep_choices=keep_choices)


def kimi_linear_48b(**kwargs):
    """Kimi-Linear-48B-A3B (moonshotai, ``model_type`` ``kimi_linear``): 27
    layers of hidden size 2304, three KDA layers (32 heads of 128, short
    convolutions of 4) to one latent attention layer (32 heads of 128 + 64
    query/key columns without position and 128 value columns over a
    key/value latent of 512, no query latent); one dense layer (9216), then
    256 experts of width 1024, 8 a token, and one shared expert; vocabulary
    163,840.  Keywords are ``config.json`` keys, plus ``num_layers`` (the
    first blocks to build, instead of the published 27) or ``layer_kinds``
    (their kinds, ``"kda"`` or ``"mla"``), ``experts_held`` = (first,
    count) and ``vocab_rows`` for a chip's share, ``recompute`` and
    ``keep_choices``."""
    return _build(_KIMI_LINEAR_48B, **kwargs)


def kimi_linear_tiny(**kwargs):
    """The same family at a size the CPU tests run in seconds: a dense KDA
    layer, then a latent attention and a KDA layer with 8 experts, 2 a
    token."""
    tiny = dict(_KIMI_LINEAR_48B, hidden_size=32, intermediate_size=48,
                moe_intermediate_size=16, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=8, v_head_dim=8, num_experts=8,
                num_experts_per_token=2, vocab_size=64,
                linear_attn_config=dict(
                    _KIMI_LINEAR_48B["linear_attn_config"], num_heads=2,
                    head_dim=16))
    kwargs.setdefault("layer_kinds", ["kda", "mla", "kda"])
    return _build(tiny, **kwargs)
