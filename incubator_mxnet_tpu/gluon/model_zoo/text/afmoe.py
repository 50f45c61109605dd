"""The ``afmoe`` decoder family (Trinity-Mini) as Gluon ``HybridBlock``s.

A pre-norm decoder with sandwich norms::

    h = E[ids] * sqrt(hidden)                        (mup_enabled)
    h = h + N2(Attn(N1(h)));   h = h + N4(FFN(N3(h)))
    logits = N(h) Whead

every ``N`` an RMS norm with a learned scale, no bias anywhere.  ``Attn`` is
gated attention over grouped heads, q and k RMS-normed per head; on
``sliding_attention`` layers rotary embedding on q and k and a causal window,
on ``full_attention`` layers neither.  The first ``num_dense_layers`` layers
have a gated-SiLU feed-forward, the others an expert layer: sigmoid scores,
top-k over all the experts with a selection-only bias, normalised and scaled
weights, one shared expert, no token dropped.

An expert layer is told which experts it holds (``experts_held = (first,
count)``): it routes over all ``num_experts`` and adds what its own experts
and the shared expert give; what absent experts would add is left out, and
nothing stands in for the chips that hold them.  ``vocab_rows`` is the slice
of the vocabulary held: ids, logits and loss are over the slice.

Every parameter keeps deferred initialisation (its input width is resolved
by the first forward, abstractly under ``shape_only_init``), so building the
published widths allocates and draws nothing.
"""
from __future__ import annotations

import math

from .... import tracing
from ...block import HybridBlock
from ...nn import Dense

__all__ = ["RMSNorm", "GatedAttention", "GatedFFN", "ExpertFFN",
           "AfmoeLayer", "AfmoeDecoder", "trinity_mini", "afmoe_tiny"]


class RMSNorm(HybridBlock):
    """``x * rsqrt(mean(x**2, -1) + eps) * gamma`` over the last axis."""

    def __init__(self, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(0,), init="ones",
                                         allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma.shape = (x.shape[-1],)

    def hybrid_forward(self, F, x, gamma):  # noqa: N803
        return F.contrib.rms_norm(x, gamma, eps=self._eps)


def _linear(units, prefix):
    return Dense(units, use_bias=False, flatten=False, prefix=prefix)


class GatedAttention(HybridBlock):
    """Causal attention over grouped heads with an output gate:
    ``(softmax(q k^T / sqrt(hd)) v * sigmoid(x Wg)) Wo``; q and k are
    RMS-normed per head; with ``window``, rotary embedding on q and k and
    keys ``i - window < j <= i`` only."""

    def __init__(self, hidden, heads, kv_heads, head_dim, window=None,
                 rope_theta=10000.0, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._hd = heads, kv_heads, head_dim
        self._window, self._theta = window, rope_theta
        with self.name_scope():
            self.q = _linear(heads * head_dim, "q_")
            self.k = _linear(kv_heads * head_dim, "k_")
            self.v = _linear(kv_heads * head_dim, "v_")
            self.g = _linear(heads * head_dim, "g_")
            self.o = _linear(hidden, "o_")
            self.qnorm = RMSNorm(eps, prefix="qnorm_")
            self.knorm = RMSNorm(eps, prefix="knorm_")

    def _heads_first(self, F, x, heads, norm=None):  # noqa: N803
        b, s = x.shape[:2]
        x = x.reshape((b, s, heads, self._hd))
        if norm is not None:
            x = norm(x)
        x = x.transpose((0, 2, 1, 3))
        if norm is not None and self._window:
            x = F.contrib.rotary(x, theta=self._theta)
        return x

    def hybrid_forward(self, F, x):  # noqa: N803
        q = self._heads_first(F, self.q(x), self._heads, self.qnorm)
        k = self._heads_first(F, self.k(x), self._kv_heads, self.knorm)
        v = self._heads_first(F, self.v(x), self._kv_heads)
        # tiles of 1024 x 1024 (cut to the sequence where it is shorter):
        # on a v5e, 8,192 tokens of 32 heads of 128 take 12.9 ms forward and
        # backward under a window of 2,048 and 20.5 ms without one, against
        # 16.8 and 31.5 ms at the kernels' own default of 256 x 512
        # (PERF.md section 6, PR 30)
        out = F.contrib.flash_attention(q, k, v, causal=True,
                                        window=self._window, block_q=1024,
                                        block_k=1024)
        b, _, s, _ = out.shape
        out = out.transpose((0, 2, 1, 3)).reshape(
            (b, s, self._heads * self._hd))
        return self.o(out * F.sigmoid(self.g(x)))


class GatedFFN(HybridBlock):
    """``(silu(x W1) * (x W3)) W2``."""

    def __init__(self, hidden, width, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.w1 = _linear(width, "w1_")
            self.w3 = _linear(width, "w3_")
            self.w2 = _linear(hidden, "w2_")

    def hybrid_forward(self, F, x):  # noqa: N803
        gate = self.w1(x)
        return self.w2(gate * F.sigmoid(gate) * self.w3(x))


class ExpertFFN(HybridBlock):
    """The expert layer of a chip that holds ``experts_held = (first,
    count)`` of ``num_experts`` experts of width ``width``, plus the shared
    expert where the family has one (``shared``).  ``score`` is the router's
    scoring rule, ``centred`` the block of tokens over whose mean the
    selection is centred (0: not), and ``act`` the experts' gate activation,
    as ``parallel/moe.py`` names them; the shared expert is gated-SiLU.  The
    block takes the experts' input and, optionally, a second tensor of the
    same shape that the ROUTER reads instead (a family whose router stands
    before the attention).  ``bias`` (selection only) and ``counts`` (the
    last step's assignments per expert, over all the experts; written
    through the trace's aux channel as BatchNorm's running statistics are)
    are not trained.  No assignment is dropped.  ``keep_choices`` adds
    ``chosen``, the last step's chosen experts of every token (tokens x
    top-k, as floats), written the same way: a comparison with a reference
    can then be made under the step's own routing decisions."""

    def __init__(self, hidden, num_experts, top_k, width, experts_held=None,
                 route_norm=True, route_scale=1.0, keep_choices=False,
                 shared=True, score="sigmoid", act="silu", centred=0,
                 **kwargs):
        super().__init__(**kwargs)
        self._held = tuple(experts_held or (0, num_experts))
        first, count = self._held
        if not 0 <= first < first + count <= num_experts:
            raise ValueError("experts_held=%r of %d experts"
                             % (experts_held, num_experts))
        self._width = width
        self._route = dict(top_k=top_k, route_norm=bool(route_norm),
                           route_scale=float(route_scale), score=score)
        if centred:
            self._route["centred"] = int(centred)
        self._act = act
        with self.name_scope():
            get = self.params.get
            self.router_weight = get("router_weight", shape=(num_experts, 0),
                                     allow_deferred_init=True)
            self.bias = get("bias", shape=(num_experts,), init="zeros",
                            grad_req="null")
            self.counts = get("counts", shape=(num_experts,), init="zeros",
                              grad_req="null")
            self.chosen = get("chosen", shape=(0, top_k), init="zeros",
                              grad_req="null", allow_deferred_init=True) \
                if keep_choices else None
            self.w1 = get("w1", shape=(count, 0, width),
                          allow_deferred_init=True)
            self.w3 = get("w3", shape=(count, 0, width),
                          allow_deferred_init=True)
            self.w2 = get("w2", shape=(count, width, 0),
                          allow_deferred_init=True)
            self.shared = GatedFFN(hidden, width, prefix="shared_") \
                if shared else None

    def infer_shape(self, x, *args):
        d, count = x.shape[-1], self._held[1]
        self.router_weight.shape = (self.router_weight.shape[0], d)
        self.w1.shape = self.w3.shape = (count, d, self._width)
        self.w2.shape = (count, self._width, d)
        if self.chosen is not None:
            self.chosen.shape = (math.prod(x.shape[:-1]),
                                 self.chosen.shape[1])

    def hybrid_forward(self, F, x, route_on=None, *, router_weight, bias,  # noqa: N803
                       counts, w1, w3, w2, chosen=None):
        tokens = x.reshape((-1, x.shape[-1]))
        weights, sel, load = F.contrib.moe_router(
            tokens if route_on is None
            else route_on.reshape((-1, route_on.shape[-1])),
            router_weight, bias, **self._route)
        rows, sizes, row, order = F.contrib.moe_dispatch(
            tokens, sel, experts_held=self._held)
        ys = F.contrib.moe_experts(rows, w1, w3, w2, sizes, act=self._act)
        y = F.contrib.moe_combine(ys, weights, sizes, row, order)
        tc = tracing.current_trace()
        if tc is not None and tc.training:
            tc.write_aux(self.counts, load._data)
            if self.chosen is not None:
                tc.write_aux(self.chosen, sel._data.astype("float32"))
        y = y.reshape(x.shape)
        return y if self.shared is None else y + self.shared(x)


class AfmoeLayer(HybridBlock):
    """One decoder block: attention and feed-forward, each between two RMS
    norms, each added to the residual stream.  ``dense`` chooses the
    gated-SiLU feed-forward, else the expert layer; ``sliding`` the window
    and rotary embedding."""

    def __init__(self, config, dense, sliding, experts_held=None,
                 keep_choices=False, **kwargs):
        super().__init__(**kwargs)
        c, eps = config, config["rms_norm_eps"]
        with self.name_scope():
            self.norm1 = RMSNorm(eps, prefix="norm1_")
            self.norm2 = RMSNorm(eps, prefix="norm2_")
            self.norm3 = RMSNorm(eps, prefix="norm3_")
            self.norm4 = RMSNorm(eps, prefix="norm4_")
            self.attn = GatedAttention(
                c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"], c["head_dim"],
                window=c["sliding_window"] if sliding else None,
                rope_theta=c["rope_theta"], eps=eps, prefix="attn_")
            if dense:
                self.ffn = GatedFFN(c["hidden_size"], c["intermediate_size"],
                                    prefix="ffn_")
            else:
                self.ffn = ExpertFFN(
                    c["hidden_size"], c["num_experts"],
                    c["num_experts_per_tok"], c["moe_intermediate_size"],
                    experts_held=experts_held, route_norm=c["route_norm"],
                    route_scale=c["route_scale"], keep_choices=keep_choices,
                    prefix="moe_")

    def hybrid_forward(self, F, x):  # noqa: N803
        x = x + self.norm2(self.attn(self.norm1(x)))
        return x + self.norm4(self.ffn(self.norm3(x)))


class AfmoeDecoder(HybridBlock):
    """Token ids ``(B, S)`` to float32 logits ``(B, S, vocab_rows)``.
    ``config`` holds the keys of the family's ``config.json``;
    ``layer_types`` lists the layers that are built, ``experts_held`` and
    ``vocab_rows`` are the chip's share.  ``recompute`` has every block's
    interior recomputed in the backward pass (``hybridize(remat=True)`` on
    the block: ``jax.checkpoint``)."""

    def __init__(self, config, layer_types, vocab_rows, experts_held=None,
                 recompute=False, keep_choices=False, **kwargs):
        super().__init__(**kwargs)
        self._scale = math.sqrt(config["hidden_size"]) \
            if config["mup_enabled"] else 1.0
        self._hidden, self._rows = config["hidden_size"], vocab_rows
        self._recompute = bool(recompute)
        self.layers = []
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_rows, 0),
                allow_deferred_init=True)
            for i, kind in enumerate(layer_types):
                if kind not in ("sliding_attention", "full_attention"):
                    raise ValueError("layer_types[%d]=%r" % (i, kind))
                layer = AfmoeLayer(
                    config, dense=i < config["num_dense_layers"],
                    sliding=kind == "sliding_attention",
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
                        output_dim=self._hidden) * self._scale
        for layer in self.layers:
            h = layer(h)
        return self.head(self.norm(h)).astype("float32")


#: ``config.json`` of arcee-ai/Trinity-Mini: what ``trinity_mini()`` builds
#: when no keyword says otherwise
_TRINITY_MINI = dict(
    hidden_size=2048, num_hidden_layers=32, num_dense_layers=2,
    global_attn_every_n_layers=4, intermediate_size=6144,
    moe_intermediate_size=1024, num_attention_heads=32,
    num_key_value_heads=4, head_dim=128, num_experts=128,
    num_experts_per_tok=8, num_shared_experts=1, vocab_size=200192,
    sliding_window=2048, rope_theta=10000.0, rms_norm_eps=1e-5,
    route_norm=True, route_scale=2.826, mup_enabled=True)


def _build(config, layer_types=None, experts_held=None, vocab_rows=None,
           recompute=False, keep_choices=False, **kwargs):
    unknown = set(kwargs) - set(config)
    if unknown:
        raise TypeError("not keys of the family's config.json: %s"
                        % sorted(unknown))
    config = dict(config, **kwargs)
    if layer_types is None:
        every = config["global_attn_every_n_layers"]
        layer_types = ["full_attention" if (i + 1) % every == 0
                       else "sliding_attention"
                       for i in range(config["num_hidden_layers"])]
    if config["num_shared_experts"] != 1:
        raise ValueError("the family has one shared expert")
    return AfmoeDecoder(
        config, list(layer_types),
        config["vocab_size"] if vocab_rows is None else vocab_rows,
        experts_held=experts_held, recompute=recompute,
        keep_choices=keep_choices)


def trinity_mini(**kwargs):
    """Trinity-Mini (arcee-ai, ``model_type`` ``afmoe``): 32 layers of hidden
    size 2048, 32 query heads over 4 key/value heads of 128, three
    sliding-window (2048) layers to one full layer, two dense layers
    (6144), then 128 experts of width 1024, 8 a token, and one shared
    expert; vocabulary 200,192.  Keywords are ``config.json`` keys, plus
    ``layer_types`` (the layers to build, instead of the published 32),
    ``experts_held`` = (first, count) and ``vocab_rows`` for a chip's share,
    ``recompute`` and ``keep_choices``."""
    return _build(_TRINITY_MINI, **kwargs)


def afmoe_tiny(**kwargs):
    """The same family at a size the CPU tests run in seconds: one dense
    sliding layer, then a sliding and a full layer with 8 experts, 2 a
    token."""
    tiny = dict(_TRINITY_MINI, hidden_size=32, intermediate_size=48,
                moe_intermediate_size=16, num_attention_heads=4,
                num_key_value_heads=2, head_dim=8, num_experts=8,
                num_experts_per_tok=2, vocab_size=64, sliding_window=8,
                num_dense_layers=1)
    kwargs.setdefault("layer_types", ["sliding_attention"] * 2
                      + ["full_attention"])
    return _build(tiny, **kwargs)
