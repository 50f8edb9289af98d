"""Solar Open 2 style decoder (``model_type`` ``solar_open2``): periods of
one gated softmax-attention layer and three Kimi Delta Attention layers
(Kimi Linear arXiv:2510.26692), every layer followed by a mixture of routed
experts plus a shared expert, RMSNorm before every mixer and mixture, no
positional encoding, an untied output head.

``h`` is a (T, hidden) sequence, matrices are stored (out, in), no bias but
the convolutions':

    x = E[ids]
    layer i:  x = x + mixer_i(RMSNorm(x));   x = x + MoE(RMSNorm(x))
    logits = RMSNorm(x) W_headᵀ

    softmax mixer (i in gqa_layers): H_q query heads over H_kv key/value
        heads of d, no positions, causal,
        a = softmax(q kᵀ / sqrt(d)) v;   out = W_o (sigmoid(W_g h) ⊙ a)

    KDA mixer (``blocks.KimiDeltaAttention``), per head of d:
        q = l2norm(silu(conv(W_q h))) / sqrt(d);  k = l2norm(silu(conv(W_k h)))
        v = silu(conv(W_v h))
        g_t = −exp(A_log) · softplus(W_a↑ W_a↓ h_t + dt_bias)      in R^d, ≤ 0
        β_t = 2 · sigmoid(w_β · h_t)           (1 · without negative eigenvalues)
        S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ,  S_0 = 0
        o_t = S_tᵀ q_t
        out = W_o (RMSNorm_head(o_t) ⊙ sigmoid(W_o↑ W_o↓ h_t))

    MoE: s = sigmoid(W_r h) over ALL routed experts (float32, highest);
        top-k of s;  w_e = s_e / Σ_topk s · routed_scaling_factor
        y = Σ_{e in top-k, e held here} w_e · W2_e (silu(W1_e h) ⊙ W3_e h)
            + the shared expert, of the same form

The model is built for ONE HOLDER'S SHARE of a deployment: it is told how
many heads, which routed experts and how many vocabulary rows it holds.
The router keeps all its outputs; what an absent expert or head would add
is left out and nothing stands in for the absent chips (no all-reduce, no
exchange).  Every size is given at construction; the decoder layers are the
block's ``remat_layers``; each layer's per-expert assignment count is
added to the auxiliary state of ``blocks.RoutedExpertState``.
"""
from __future__ import annotations

import jax

from ...block import HybridBlock
from ...nn import HybridSequential, RMSNorm
from .blocks import (GroupedQueryAttention, KimiDeltaAttention,
                     RoutedExpertState, SparseExperts, dense)

__all__ = ["SolarDecoderLayer", "SolarOpen2", "solar_open2"]


class SolarDecoderLayer(HybridBlock):
    """``x + mixer(RMSNorm(x))`` then ``x + MoE(RMSNorm(x))``; returns the
    mixture's load beside ``x``."""

    def __init__(self, mixer, experts, hidden_size, epsilon=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.input_norm = RMSNorm(hidden_size, epsilon,
                                      prefix="input_norm_")
            self.mixer = mixer(prefix="mixer_")
            self.post_norm = RMSNorm(hidden_size, epsilon,
                                     prefix="post_norm_")
            self.moe = experts(prefix="moe_")

    def hybrid_forward(self, F, x):
        h = self.input_norm(x)
        if isinstance(self.mixer, GroupedQueryAttention):
            with jax.named_scope("solar/attention"):
                x = x + self.mixer(h)
        else:                   # the KDA mixer names its own scopes
            x = x + self.mixer(h)
        y, load, rows = self.moe(self.post_norm(x))
        return x + y, load, rows


class SolarOpen2(RoutedExpertState, HybridBlock):
    """Token ids ``(batch, T)`` to logits ``(batch, T, vocab_size)``.

    ``layer_types`` names each layer's mixer, ``"attention"`` or ``"kda"``.
    ``num_heads``, ``num_kv_heads``, ``kda_heads``, ``experts_held`` and
    ``vocab_size`` are what this holder has of the published counts (the
    first rows of both vocabulary tables: ids, logits and loss are over the
    slice); ``experts_total`` is the router's width.
    """

    def __init__(self, vocab_size, hidden_size, layer_types, num_heads,
                 num_kv_heads, head_dim, kda_heads, kda_head_dim,
                 expert_width, experts_total, experts_held, top_k,
                 first_expert=0, shared_experts=1, routed_scaling=1.0,
                 norm_topk=True, attention_gate=True, kda_conv=4,
                 kda_low_rank=None, kda_chunk=64, kda_neg_eigval=True,
                 expert_tile=256, epsilon=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._vocab, self._hidden = vocab_size, hidden_size
        mixers = {
            "kda": lambda prefix: KimiDeltaAttention(
                hidden_size, kda_heads, kda_head_dim, kda_conv, kda_low_rank,
                kda_chunk, kda_neg_eigval, epsilon, scope="solar/kda",
                prefix=prefix),
            "attention": lambda prefix: GroupedQueryAttention(
                hidden_size, num_heads, num_kv_heads, head_dim,
                head_dim ** -0.5, gate=attention_gate, prefix=prefix),
        }

        def experts(prefix):
            return SparseExperts(
                hidden_size, expert_width, experts_total, experts_held,
                first_expert, top_k, shared_experts, routed_scaling,
                norm_topk, expert_tile, scope="solar/moe", prefix=prefix)

        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for kind in layer_types:
                    self.layers.add(SolarDecoderLayer(
                        mixers[kind], experts, hidden_size, epsilon))
            self.final_norm = RMSNorm(hidden_size, epsilon,
                                      prefix="final_norm_")
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, hidden_size))
            self._declare_expert_state(experts_held)

    @property
    def remat_layers(self):
        """The blocks a train step with ``remat=True`` checkpoints one by
        one (``gluon.block.remat_scope``)."""
        return list(self.layers)

    @property
    def expert_blocks(self):
        return [layer.moe for layer in self.layers]

    def hybrid_forward(self, F, ids, embed_weight, head_weight, expert_load,
                       expert_rows):
        x = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                        output_dim=self._hidden)
        notes = []
        for layer in self.layers:
            x, *note = layer(x)
            notes.append(note)
        self._write_expert_state(F, notes, expert_load, expert_rows,
                                 ids.context)
        with jax.named_scope("solar/head"):
            return dense(F, self.final_norm(x), head_weight, self._vocab)


def solar_open2(config, **kwargs):
    """A :class:`SolarOpen2` from the keys of a published ``config.json``
    (``solar_open2``) in which the counts are one holder's share:
    ``num_attention_heads``, ``num_key_value_heads``,
    ``linear_attn_config.num_heads``, ``n_routed_experts`` and
    ``vocab_size`` are what is held here, the first ``num_hidden_layers``
    layers are built, and ``published.n_routed_experts`` (when the file
    has it) is the router's width.  ``first_routed_expert``,
    ``kda_chunk_size``, ``kda_low_rank_dim`` and ``expert_tile_rows`` are this repo's keys for
    what the published file does not carry."""
    for key, want in (("first_k_dense_replace", 0), ("use_rope", False),
                      ("kda_use_full_proj", False),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"solar_open2: {key}={config[key]!r} is not "
                             "supported")
    linear = config["linear_attn_config"]
    softmax_layers = set(config["gqa_layers"])
    return SolarOpen2(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=["attention" if i in softmax_layers else "kda"
                     for i in range(config["num_hidden_layers"])],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], kda_heads=linear["num_heads"],
        kda_head_dim=linear["head_dim"],
        expert_width=config["moe_intermediate_size"],
        experts_total=config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"]),
        experts_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        first_expert=config.get("first_routed_expert", 0),
        shared_experts=config["n_shared_experts"],
        routed_scaling=config["routed_scaling_factor"],
        norm_topk=config["norm_topk_prob"],
        attention_gate=config["use_gqa_gate"],
        kda_conv=linear["short_conv_kernel_size"],
        kda_low_rank=config.get("kda_low_rank_dim"),
        kda_chunk=config.get("kda_chunk_size", 64),
        kda_neg_eigval=config["kda_allow_neg_eigval"],
        expert_tile=config.get("expert_tile_rows", 256),
        epsilon=config["rms_norm_eps"], **kwargs)
