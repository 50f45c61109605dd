"""The ``smallthinker`` decoder family (SmallThinker-21BA3B) as Gluon
``HybridBlock``s.

A pre-norm decoder of two norms a block whose router stands BEFORE the
attention::

    h = E[ids]
    r = x Wr^T;   sel = top-k(r);   w = softmax(r[sel])
    a = x + Attn(N1(x));   y = a + sum_{e in sel} w_e Expert_e(N2(a))
    logits = N(h_L) Whead

``x`` the block's input: the router reads it as it is, un-normed, while the
experts read the normed state after the attention, so a block's routing
depends on nothing its attention computes.  Every ``N`` is an RMS norm with
a learned scale, no bias anywhere.  ``Attn`` is plain causal attention over
grouped heads (no gate, no per-head norm); layer ``i`` has a window where
``sliding_window_layout[i]`` is 1 and rotary embedding on q and k where
``rope_layout[i]`` is 1 (in the published model the same layers: a full
layer has no position signal of its own).  Every layer is an expert layer:
ReLU-gated experts ``(relu(x Wg) * (x Wu)) Wd``, no shared expert; the
weights are a softmax over the chosen logits
(``moe_primary_router_apply_softmax``, the only rule the family builds).

``experts_held``, ``vocab_rows``, ``recompute`` and ``keep_choices`` are
what they are in ``afmoe``; every parameter keeps deferred initialisation.

``centred_selection`` = n is a device of TRAINING the published model does
not have and no factory turns on by itself: ``sel = top-k(r - mean of r over
the block of n consecutive tokens the token lies in + b)`` (a sequence a
multiple of n tokens), ``b`` the experts' non-trained selection bias (zero
unless set).  This router reads the un-normed stream, and what neighbouring
tokens share there (a full layer without positions hands each the running
mean of the values, a window layer the mean over its window) soon
outweighs, in an expert's logit, what tells them apart; the shared part
moves as the weights move, and with it the load of every expert.  A trained
router holds its load even as it goes; centring the selection takes the
shared part out of the choice at every step, as a bias set once does only
for the first.  The weights ``softmax(r[sel])`` never see it.
"""
from __future__ import annotations

from ...block import HybridBlock
from .afmoe import ExpertFFN, RMSNorm, _linear

__all__ = ["GroupedAttention", "SmallThinkerLayer", "SmallThinkerDecoder",
           "smallthinker_21b", "smallthinker_tiny"]


class GroupedAttention(HybridBlock):
    """Causal attention over grouped heads: ``softmax(q k^T / sqrt(hd)) v``
    then ``Wo``; with ``rope_theta`` rotary embedding on q and k, with
    ``window`` the keys ``i - window < j <= i`` only."""

    #: tiles of the flash kernels (cut to the sequence where it is shorter),
    #: as the other family of grouped heads of 128 has them
    BLOCK_Q, BLOCK_K = 1024, 1024

    def __init__(self, hidden, heads, kv_heads, head_dim, window=None,
                 rope_theta=None, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._hd = heads, kv_heads, head_dim
        self._window, self._theta = window, rope_theta
        with self.name_scope():
            self.q = _linear(heads * head_dim, "q_")
            self.k = _linear(kv_heads * head_dim, "k_")
            self.v = _linear(kv_heads * head_dim, "v_")
            self.o = _linear(hidden, "o_")

    def _heads_first(self, x, heads):
        b, s = x.shape[:2]
        return x.reshape((b, s, heads, self._hd)).transpose((0, 2, 1, 3))

    def hybrid_forward(self, F, x):  # noqa: N803
        q = self._heads_first(self.q(x), self._heads)
        k = self._heads_first(self.k(x), self._kv_heads)
        v = self._heads_first(self.v(x), self._kv_heads)
        if self._theta is not None:
            q = F.contrib.rotary(q, theta=self._theta)
            k = F.contrib.rotary(k, theta=self._theta)
        out = F.contrib.flash_attention(
            q, k, v, causal=True, window=self._window, block_q=self.BLOCK_Q,
            block_k=self.BLOCK_K, use_pallas=True)
        b, _, s, _ = out.shape
        return self.o(out.transpose((0, 2, 1, 3)).reshape(
            (b, s, self._heads * self._hd)))


class SmallThinkerLayer(HybridBlock):
    """One decoder block: ``a = x + Attn(N1(x))``, ``a + Experts(N2(a))``
    under the routing of ``x`` itself.  ``sliding`` chooses the window,
    ``rotary`` the rotary embedding."""

    def __init__(self, config, sliding, rotary, experts_held=None,
                 keep_choices=False, centred_selection=0, **kwargs):
        super().__init__(**kwargs)
        c, eps = config, config["rms_norm_eps"]
        with self.name_scope():
            self.norm1 = RMSNorm(eps, prefix="norm1_")
            self.norm2 = RMSNorm(eps, prefix="norm2_")
            self.attn = GroupedAttention(
                c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"], c["head_dim"],
                window=c["sliding_window_size"] if sliding else None,
                rope_theta=c["rope_theta"] if rotary else None,
                prefix="attn_")
            self.ffn = ExpertFFN(
                c["hidden_size"], c["moe_num_primary_experts"],
                c["moe_num_active_primary_experts"], c["moe_ffn_hidden_size"],
                experts_held=experts_held, keep_choices=keep_choices,
                shared=False, score="softmax", act="relu",
                centred=centred_selection, prefix="moe_")

    def hybrid_forward(self, F, x):  # noqa: N803
        a = x + self.attn(self.norm1(x))
        return a + self.ffn(self.norm2(a), x)


class SmallThinkerDecoder(HybridBlock):
    """Token ids ``(B, S)`` to float32 logits ``(B, S, vocab_rows)``.
    ``config`` holds the keys of the family's ``config.json``; the first
    ``num_layers`` of its layers are built, each as ``sliding_window_layout``
    and ``rope_layout`` say; ``experts_held`` and ``vocab_rows`` are the
    chip's share; ``recompute`` has every block's interior recomputed in the
    backward pass; ``centred_selection``: the module's text."""

    def __init__(self, config, num_layers, vocab_rows, experts_held=None,
                 recompute=False, keep_choices=False,
                 centred_selection=0, **kwargs):
        super().__init__(**kwargs)
        self._hidden, self._rows = config["hidden_size"], vocab_rows
        self._recompute = bool(recompute)
        self.layers = []
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_rows, 0),
                allow_deferred_init=True)
            for i in range(num_layers):
                layer = SmallThinkerLayer(
                    config, sliding=bool(config["sliding_window_layout"][i]),
                    rotary=bool(config["rope_layout"][i]),
                    experts_held=experts_held, keep_choices=keep_choices,
                    centred_selection=centred_selection,
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


#: ``config.json`` of PowerInfer/SmallThinker-21BA3B-Instruct: what
#: ``smallthinker_21b()`` builds when no keyword says otherwise
_SMALLTHINKER_21B = dict(
    hidden_size=2560, num_hidden_layers=52, num_attention_heads=28,
    num_key_value_heads=4, head_dim=128, moe_ffn_hidden_size=768,
    moe_num_primary_experts=64, moe_num_active_primary_experts=6,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    sliding_window_size=4096, sliding_window_layout=[0, 1, 1, 1] * 13,
    rope_layout=[0, 1, 1, 1] * 13, rope_theta=1500000, rope_scaling=None,
    rms_norm_eps=1e-6, tie_word_embeddings=False, vocab_size=151936)


def _build(config, num_layers=None, experts_held=None, vocab_rows=None,
           recompute=False, keep_choices=False, centred_selection=0,
           **kwargs):
    unknown = set(kwargs) - set(config)
    if unknown:
        raise TypeError("not keys of the family's config.json: %s"
                        % sorted(unknown))
    config = dict(config, **kwargs)
    if num_layers is None:
        num_layers = config["num_hidden_layers"]
    for key in ("sliding_window_layout", "rope_layout"):
        if len(config[key]) < num_layers:
            raise ValueError("%s names %d layers, %d are built"
                             % (key, len(config[key]), num_layers))
    if config["tie_word_embeddings"]:
        raise ValueError("the family's head is its own matrix")
    if config["rope_scaling"] is not None:
        raise ValueError("scaled rotary embedding is not built")
    if not config["moe_primary_router_apply_softmax"]:
        raise ValueError("a router without the softmax over the chosen "
                         "logits is not built")
    return SmallThinkerDecoder(
        config, num_layers,
        config["vocab_size"] if vocab_rows is None else vocab_rows,
        experts_held=experts_held, recompute=recompute,
        keep_choices=keep_choices, centred_selection=centred_selection)


def smallthinker_21b(**kwargs):
    """SmallThinker-21BA3B-Instruct (PowerInfer): 52 layers of hidden size
    2560, 28 query heads over 4 key/value heads of 128, a full layer
    without rotary embedding then three layers under a window of 4096 with
    it; every layer 64 ReLU-gated experts of width 768, 6 a token by a
    router that reads the block's input, softmax over the chosen, no shared
    expert; vocabulary 151,936, head untied.  Keywords are ``config.json``
    keys, plus ``num_layers`` (the first blocks to build, instead of the
    published 52), ``experts_held`` = (first, count) and ``vocab_rows`` for
    a chip's share, ``recompute``, ``keep_choices`` and
    ``centred_selection`` (0: the published router)."""
    return _build(_SMALLTHINKER_21B, **kwargs)


def smallthinker_tiny(**kwargs):
    """The same family at a size the CPU tests run in seconds: a full layer
    and two window layers, 8 experts, 2 a token."""
    tiny = dict(_SMALLTHINKER_21B, hidden_size=32, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                moe_ffn_hidden_size=16, moe_num_primary_experts=8,
                moe_num_active_primary_experts=2, sliding_window_size=8,
                sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
                vocab_size=64)
    kwargs.setdefault("num_layers", 3)
    return _build(tiny, **kwargs)
