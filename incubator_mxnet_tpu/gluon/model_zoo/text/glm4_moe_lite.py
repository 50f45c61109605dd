"""The ``glm4_moe_lite`` decoder family (GLM-4.7-Flash) as Gluon
``HybridBlock``s.

A pre-norm decoder of two norms a block::

    h = E[ids]
    a = h + Attn(N1(h));   h = a + FFN(N2(a))
    logits = N(h_L) Whead

every ``N`` an RMS norm with a learned scale, no bias anywhere.  ``Attn`` is
latent attention (MLA): the query and the key/value each go through a
low-rank latent with an RMS norm on it, a head's query and key are
``qk_nope_head_dim`` columns without position and ``qk_rope_head_dim`` with
rotary embedding, and the rotary part of the key is ONE vector a position
for all the heads (its gradient is the sum over heads).  The first
``first_k_dense_replace`` layers have a gated-SiLU feed-forward, the others
the expert layer of ``afmoe.ExpertFFN`` with this family's numbers.

With ``num_nextn_predict_layers`` 1 a prediction module follows the last
block, in training and in inference alike::

    u_i = [Nh(h_L,i) | Ne(E[t_{i+1}])] Wp;   g = Block(u)
    mtp_logits = Nm(g) Whead

``h_L`` the last block's output before the final norm, ``E`` and ``Whead``
the model's own embedding and head (the same parameters, not copies).  The
net gets ``ids`` only: ``t_{i+1}`` is ``ids`` rolled left by one, so the
last position wraps and belongs to no loss
(``gluon.loss.MultiTokenCrossEntropyLoss`` masks it; attention is causal, so
it reaches no other position).  The net then returns ``(logits,
mtp_logits)``.

``experts_held``, ``vocab_rows``, ``recompute`` and ``keep_choices`` are
what they are in ``afmoe``; every parameter keeps deferred initialisation.
"""
from __future__ import annotations

import math

from ...block import HybridBlock
from .afmoe import ExpertFFN, GatedFFN, RMSNorm, _linear

__all__ = ["LatentAttention", "Glm4MoeLiteLayer", "MTPModule",
           "Glm4MoeLiteDecoder", "glm47_flash", "glm4_moe_lite_tiny"]


class LatentAttention(HybridBlock):
    """Causal attention whose query and key/value come out of low-rank
    latents: ``cq = Nq(x Wdq)``, ``q = cq Wuq`` (heads of ``[nope | rope]``);
    ``[ckv | kr] = x Wdkv``, ``Nkv(ckv) Wukv`` gives each head ``[k_nope |
    v]``; rotary embedding on every head's ``q_rope`` and on ``kr``, which
    all the heads share; ``k_h = [k_nope_h | kr]``; softmax scale
    ``1 / sqrt(nope + rope)``.  With ``q_lora_rank`` None the query is one
    projection, ``q = x Wq``; with ``rotary`` False the ``rope`` columns go
    unrotated (a family whose latent attention has no position at all)."""

    #: tiles of the flash kernels at a head size of 256 (cut to the sequence
    #: where it is shorter); read on the chip, PERF.md section 6, PR 35: the
    #: forward kernel takes 5.54 ms a layer with them and 5.98 at 512 x 1024,
    #: the backward kernel 11.2 at either
    BLOCK_Q, BLOCK_K = 1024, 1024

    def __init__(self, hidden, heads, q_lora_rank, kv_lora_rank, nope, rope,
                 v_head_dim, rope_theta=10000.0, eps=1e-5, rotary=True,
                 **kwargs):
        super().__init__(**kwargs)
        self._heads, self._latent = heads, kv_lora_rank
        self._nope, self._rope, self._vd = nope, rope, v_head_dim
        self._theta = rope_theta if rotary else None
        self._direct_q = q_lora_rank is None
        with self.name_scope():
            if q_lora_rank is None:
                self.q = _linear(heads * (nope + rope), "q_")
            else:
                self.q_a = _linear(q_lora_rank, "q_a_")
                self.q_a_norm = RMSNorm(eps, prefix="q_a_norm_")
                self.q_b = _linear(heads * (nope + rope), "q_b_")
            self.kv_a = _linear(kv_lora_rank + rope, "kv_a_")
            self.kv_a_norm = RMSNorm(eps, prefix="kv_a_norm_")
            self.kv_b = _linear(heads * (nope + v_head_dim), "kv_b_")
            self.o = _linear(hidden, "o_")

    def _heads_first(self, x, width):
        b, s = x.shape[:2]
        return x.reshape((b, s, self._heads, width)).transpose((0, 2, 1, 3))

    def hybrid_forward(self, F, x):  # noqa: N803
        b, s = x.shape[:2]
        nope, rope, heads = self._nope, self._rope, self._heads
        if self._direct_q:
            q = self._heads_first(self.q(x), nope + rope)
        else:
            q = self._heads_first(self.q_b(self.q_a_norm(self.q_a(x))),
                                  nope + rope)
        if self._theta is not None:
            q = F.concat(
                F.slice_axis(q, axis=-1, begin=0, end=nope),
                F.contrib.rotary(F.slice_axis(q, axis=-1, begin=nope,
                                              end=None),
                                 theta=self._theta), dim=-1)
        kv = self.kv_a(x)
        kr = F.slice_axis(kv, axis=-1, begin=self._latent, end=None) \
            .reshape((b, 1, s, rope))
        if self._theta is not None:
            kr = F.contrib.rotary(kr, theta=self._theta)
        kv = self._heads_first(self.kv_b(self.kv_a_norm(
            F.slice_axis(kv, axis=-1, begin=0, end=self._latent))),
            nope + self._vd)
        # kr is written beside every head's key (heads x S x rope values a
        # layer): the kernels then see plain heads of nope + rope, and the
        # broadcast's transpose sums kr's gradient over the heads
        k = F.concat(F.slice_axis(kv, axis=-1, begin=0, end=nope),
                     F.broadcast_axis(kr, axis=1, size=heads), dim=-1)
        v = F.slice_axis(kv, axis=-1, begin=nope, end=None)
        out = F.contrib.flash_attention(
            q, k, v, causal=True, scale=1.0 / math.sqrt(nope + rope),
            block_q=self.BLOCK_Q, block_k=self.BLOCK_K, use_pallas=True)
        return self.o(out.transpose((0, 2, 1, 3)).reshape(
            (b, s, heads * self._vd)))


class Glm4MoeLiteLayer(HybridBlock):
    """One decoder block: ``a = x + Attn(N1(x))``, ``a + FFN(N2(a))``;
    ``dense`` chooses the gated-SiLU feed-forward, else the expert layer."""

    def __init__(self, config, dense, experts_held=None, keep_choices=False,
                 **kwargs):
        super().__init__(**kwargs)
        c, eps = config, config["rms_norm_eps"]
        with self.name_scope():
            self.norm1 = RMSNorm(eps, prefix="norm1_")
            self.norm2 = RMSNorm(eps, prefix="norm2_")
            self.attn = LatentAttention(
                c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
                c["kv_lora_rank"], c["qk_nope_head_dim"],
                c["qk_rope_head_dim"], c["v_head_dim"],
                rope_theta=c["rope_theta"], eps=eps, prefix="attn_")
            if dense:
                self.ffn = GatedFFN(c["hidden_size"], c["intermediate_size"],
                                    prefix="ffn_")
            else:
                self.ffn = ExpertFFN(
                    c["hidden_size"], c["n_routed_experts"],
                    c["num_experts_per_tok"], c["moe_intermediate_size"],
                    experts_held=experts_held,
                    route_norm=c["norm_topk_prob"],
                    route_scale=c["routed_scaling_factor"],
                    keep_choices=keep_choices, prefix="moe_")

    def hybrid_forward(self, F, x):  # noqa: N803
        a = x + self.attn(self.norm1(x))
        return a + self.ffn(self.norm2(a))


class MTPModule(HybridBlock):
    """The prediction module: ``head(Nm(Block([Nh(h) | Ne(e)] Wp)))`` for
    the last hidden state ``h`` and the next token's embedding ``e``.
    ``head`` is the decoder's own output head; it is called here and is no
    child of this block, so its parameters are counted once."""

    def __init__(self, config, head, experts_held=None, keep_choices=False,
                 **kwargs):
        super().__init__(**kwargs)
        eps = config["rms_norm_eps"]
        self._head = (head,)    # in a tuple: not registered as a child
        with self.name_scope():
            self.hnorm = RMSNorm(eps, prefix="hnorm_")
            self.enorm = RMSNorm(eps, prefix="enorm_")
            self.eh_proj = _linear(config["hidden_size"], "eh_proj_")
            self.block = Glm4MoeLiteLayer(
                config, dense=False, experts_held=experts_held,
                keep_choices=keep_choices, prefix="")
            self.head_norm = RMSNorm(eps, prefix="head_norm_")

    def hybrid_forward(self, F, h, e):  # noqa: N803
        u = self.eh_proj(F.concat(self.hnorm(h), self.enorm(e), dim=-1))
        return self._head[0](self.head_norm(self.block(u)))


class Glm4MoeLiteDecoder(HybridBlock):
    """Token ids ``(B, S)`` to float32 logits ``(B, S, vocab_rows)``, and
    with a prediction module to ``(logits, mtp_logits)``.  ``config`` holds
    the keys of the family's ``config.json``; ``num_layers`` blocks are
    built, the first ``first_k_dense_replace`` of them dense;
    ``experts_held`` and ``vocab_rows`` are the chip's share; ``recompute``
    has every block's interior recomputed in the backward pass."""

    def __init__(self, config, num_layers, vocab_rows, experts_held=None,
                 recompute=False, keep_choices=False, **kwargs):
        super().__init__(**kwargs)
        self._hidden, self._rows = config["hidden_size"], vocab_rows
        self._recompute = bool(recompute)
        share = dict(experts_held=experts_held, keep_choices=keep_choices)
        self.layers = []
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_rows, 0),
                allow_deferred_init=True)
            for i in range(num_layers):
                layer = Glm4MoeLiteLayer(
                    config, dense=i < config["first_k_dense_replace"],
                    prefix="layer%d_" % i, **share)
                self.layers.append(layer)
                self.register_child(layer, "layer%d" % i)
            self.norm = RMSNorm(config["rms_norm_eps"], prefix="norm_")
            self.head = _linear(vocab_rows, "head_")
            # the module is the published checkpoint's layer
            # ``num_hidden_layers``: one past the last block here
            self.mtp = MTPModule(config, self.head,
                                 prefix="layer%d_" % num_layers, **share) \
                if config["num_nextn_predict_layers"] else None
        self.hybridize(False)

    def hybridize(self, active=True, **kwargs):
        super().hybridize(active, **kwargs)
        if self._recompute:
            # each block is its own region; the flag does not survive a
            # plain hybridize(), so it is set again here
            blocks = self.layers + ([self.mtp.block] if self.mtp else [])
            for block in blocks:
                block.hybridize(active, **dict(kwargs, remat=True))

    def infer_shape(self, x, *args):
        self.embed_weight.shape = (self._rows, self._hidden)

    def hybrid_forward(self, F, ids, embed_weight):  # noqa: N803
        def embed(tokens):
            return F.Embedding(tokens, embed_weight, input_dim=self._rows,
                               output_dim=self._hidden)

        h = embed(ids)
        for layer in self.layers:
            h = layer(h)
        logits = self.head(self.norm(h)).astype("float32")
        if self.mtp is None:
            return logits
        return logits, self.mtp(
            h, embed(F.roll(ids, shift=-1, axis=1))).astype("float32")


#: ``config.json`` of zai-org/GLM-4.7-Flash: what ``glm47_flash()`` builds
#: when no keyword says otherwise
_GLM47_FLASH = dict(
    hidden_size=2048, num_hidden_layers=47, first_k_dense_replace=1,
    intermediate_size=10240, moe_intermediate_size=1536,
    num_attention_heads=20, num_key_value_heads=20, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
    v_head_dim=256, n_routed_experts=64, n_shared_experts=1,
    num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=1.8,
    n_group=1, topk_group=1, num_nextn_predict_layers=1, vocab_size=154880,
    rope_theta=1000000.0, rms_norm_eps=1e-5)


def _build(config, num_layers=None, experts_held=None, vocab_rows=None,
           recompute=False, keep_choices=False, **kwargs):
    unknown = set(kwargs) - set(config)
    if unknown:
        raise TypeError("not keys of the family's config.json: %s"
                        % sorted(unknown))
    config = dict(config, **kwargs)
    if config["n_shared_experts"] != 1:
        raise ValueError("the family has one shared expert")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("routing limited to groups of experts is not built")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has a key and a value a head")
    if config["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("one prediction module or none")
    return Glm4MoeLiteDecoder(
        config,
        config["num_hidden_layers"] if num_layers is None else num_layers,
        config["vocab_size"] if vocab_rows is None else vocab_rows,
        experts_held=experts_held, recompute=recompute,
        keep_choices=keep_choices)


def glm47_flash(**kwargs):
    """GLM-4.7-Flash (zai-org, ``model_type`` ``glm4_moe_lite``): 47 layers
    of hidden size 2048; latent attention, 20 heads of 192 + 64 (query,
    key) and 256 (value) over latents of 768 and 512; one dense layer
    (10240), then 64 experts of width 1536, 4 a token, and one shared
    expert; one prediction module; vocabulary 154,880.  Keywords are
    ``config.json`` keys, plus ``num_layers`` (the blocks to build, instead
    of the published 47), ``experts_held`` = (first, count) and
    ``vocab_rows`` for a chip's share, ``recompute`` and ``keep_choices``."""
    return _build(_GLM47_FLASH, **kwargs)


def glm4_moe_lite_tiny(**kwargs):
    """The same family at a size the CPU tests run in seconds: one dense
    layer, then two with 8 experts, 2 a token, and the prediction module."""
    tiny = dict(_GLM47_FLASH, hidden_size=32, intermediate_size=48,
                moe_intermediate_size=16, num_attention_heads=4,
                num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts=8, num_experts_per_tok=2, vocab_size=64)
    kwargs.setdefault("num_layers", 3)
    return _build(tiny, **kwargs)
