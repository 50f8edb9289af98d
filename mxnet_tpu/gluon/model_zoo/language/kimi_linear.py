"""Kimi Linear style decoder (``model_type`` ``kimi_linear``, Moonshot AI,
arXiv:2510.26692): Kimi Delta Attention layers with a multi-head latent
attention layer among them (3 : 1), the first ``first_k_dense_replace``
layers followed by a dense gated MLP and every later one by a mixture of
routed experts plus a shared expert behind a sigmoid router whose
selection bias balances the load; RMSNorm before every mixer and
feed-forward, no positions anywhere, an untied output head.

``h`` is a (T, hidden) sequence, matrices are stored (out, in), no bias:

    x = E[ids]
    layer i:  x = x + mixer_i(RMSNorm(x));   x = x + ffn_i(RMSNorm(x))
    logits = RMSNorm(x) W_headᵀ

    mixer: KDA (``blocks.KimiDeltaAttention``, β = sigmoid, convolutions
        without a bias) where ``linear_attn_config.kda_layers`` names the
        layer, MLA (``blocks.MultiHeadLatentAttention``, no positions)
        where ``full_attn_layers`` does; both lists count from 1
    ffn: ``GatedMLP`` for i < first_k_dense_replace, else
        s = sigmoid(W_r h) over ALL routed experts (float32, highest);
        the chosen = top-k of s + b (b the selection bias: it chooses and
        never weighs);  w_e = scaling · s_e / Σ_chosen s
        y = Σ_{e chosen, e held here} w_e · W2_e (silu(W1_e h) ⊙ W3_e h)
            + the shared expert, of the same form

    after the layers, in training mode only:
        b_e ← b_e + u · sign(mean(c) − c_e),  c the step's assignments to
        each of ALL experts (``blocks.balanced_bias``)

The model is built for ONE HOLDER'S SHARE of a deployment, as
``solar_open2`` is: it is told which routed experts and how many
vocabulary rows it holds, the router and its bias keep all their outputs,
what an absent expert would add is left out and nothing stands in for the
absent chips.  Every size is given at construction; the layers are the
block's ``remat_layers``.  Each expert layer READS its ``select_bias``
inside its rematerialisation boundary; the model WRITES it, and adds to
``expert_load`` and ``expert_rows``, after the layers
(``blocks.RoutedExpertState``).
"""
from __future__ import annotations

import jax

from ...block import HybridBlock
from ...nn import HybridSequential, RMSNorm
from .blocks import (GatedMLP, KimiDeltaAttention, MultiHeadLatentAttention,
                     RoutedExpertState, SparseExperts, dense)

__all__ = ["KimiDecoderLayer", "KimiLinear", "kimi_linear"]


class KimiDecoderLayer(HybridBlock):
    """``x + mixer(RMSNorm(x))`` then ``x + ffn(RMSNorm(x))``.  A layer
    whose feed-forward is ``routed`` returns the mixture's notes beside
    ``x``: load, rows, and the count over all experts."""

    def __init__(self, mixer, ffn, routed, hidden_size, epsilon=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.routed = routed
        with self.name_scope():
            self.input_norm = RMSNorm(hidden_size, epsilon,
                                      prefix="input_norm_")
            self.mixer = mixer(prefix="mixer_")
            self.post_norm = RMSNorm(hidden_size, epsilon,
                                     prefix="post_norm_")
            self.ffn = ffn(prefix="ffn_")

    def hybrid_forward(self, F, x):
        x = x + self.mixer(self.input_norm(x))   # the mixers name their scopes
        if self.routed:
            y, *notes = self.ffn(self.post_norm(x))
            return (x + y, *notes)
        with jax.named_scope("kimi/mlp"):
            return x + self.ffn(self.post_norm(x))


class KimiLinear(RoutedExpertState, HybridBlock):
    """Token ids ``(batch, T)`` to logits ``(batch, T, vocab_size)``.

    ``layer_types`` names each layer's mixer, ``"kda"`` or ``"mla"``; the
    first ``dense_layers`` layers have a dense MLP of ``dense_width``, the
    rest routed experts.  ``experts_held`` and ``vocab_size`` are what
    this holder has of the published counts (experts ``first_expert ..``;
    the first rows of both vocabulary tables: ids, logits and loss are
    over the slice); ``experts_total`` is the width of the router and of
    its bias.  ``bias_update_rate`` is the balancing rule's ``u``.
    """

    def __init__(self, vocab_size, hidden_size, layer_types, dense_layers,
                 dense_width, kda_heads, kda_head_dim, mla_heads, nope_dim,
                 rope_dim, v_dim, kv_rank, expert_width, experts_total,
                 experts_held, top_k, first_expert=0, shared_experts=1,
                 routed_scaling=1.0, norm_topk=True, kda_conv=4,
                 kda_low_rank=None, kda_chunk=64, expert_tile=256,
                 bias_update_rate=1e-3, epsilon=1e-5, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if set(layer_types) - {"kda", "mla"}:
            raise ValueError(f"kimi_linear: layer types {layer_types} other "
                             "than 'kda' and 'mla'")
        self._vocab, self._hidden = vocab_size, hidden_size
        mixers = {
            "kda": lambda prefix: KimiDeltaAttention(
                hidden_size, kda_heads, kda_head_dim, kda_conv, kda_low_rank,
                kda_chunk, neg_eigval=False, epsilon=epsilon,
                conv_bias=False, scope="kimi/kda", prefix=prefix),
            "mla": lambda prefix: MultiHeadLatentAttention(
                hidden_size, mla_heads, nope_dim, rope_dim, v_dim, kv_rank,
                epsilon, scope="kimi/attention", prefix=prefix),
        }

        def mlp(prefix):
            return GatedMLP(hidden_size, dense_width, prefix=prefix)

        def experts(prefix):
            return SparseExperts(
                hidden_size, expert_width, experts_total, experts_held,
                first_expert, top_k, shared_experts, routed_scaling,
                norm_topk, expert_tile, select_bias=True, scope="kimi/moe",
                prefix=prefix)

        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for i, kind in enumerate(layer_types):
                    routed = i >= dense_layers
                    self.layers.add(KimiDecoderLayer(
                        mixers[kind], experts if routed else mlp, routed,
                        hidden_size, epsilon))
            self.final_norm = RMSNorm(hidden_size, epsilon,
                                      prefix="final_norm_")
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, hidden_size))
            self._declare_expert_state(experts_held, bias_update_rate)

    @property
    def remat_layers(self):
        """The blocks a train step with ``remat=True`` checkpoints one by
        one (``gluon.block.remat_scope``)."""
        return list(self.layers)

    @property
    def expert_blocks(self):
        return [layer.ffn for layer in self.layers if layer.routed]

    def hybrid_forward(self, F, ids, embed_weight, head_weight,
                       expert_load=None, expert_rows=None):
        x = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                        output_dim=self._hidden)
        notes = []
        for layer in self.layers:
            if layer.routed:
                x, *note = layer(x)
                notes.append(note)
            else:
                x = layer(x)
        self._write_expert_state(F, notes, expert_load, expert_rows,
                                 ids.context)
        with jax.named_scope("kimi/head"):
            return dense(F, self.final_norm(x), head_weight, self._vocab)


def layer_kinds(config):
    """Each built layer's mixer, ``"kda"`` or ``"mla"``, from the config's
    two lists of layers, which count from 1."""
    linear = config["linear_attn_config"]
    full, kda = set(linear["full_attn_layers"]), set(linear["kda_layers"])
    kinds = []
    for number in range(1, config["num_hidden_layers"] + 1):
        if (number in full) == (number in kda):
            raise ValueError(f"kimi_linear: layer {number} is in "
                             f"{'both' if number in full else 'neither'} "
                             "of full_attn_layers and kda_layers")
        kinds.append("mla" if number in full else "kda")
    return kinds


def kimi_linear(config, **kwargs):
    """A :class:`KimiLinear` from the keys of a published ``config.json``
    (``kimi_linear``) in which the counts are one holder's share:
    ``num_experts`` and ``vocab_size`` are what is held here, the first
    ``num_hidden_layers`` layers are built, and ``published.num_experts``
    (when the file has it) is the router's width.  ``first_routed_expert``,
    ``kda_chunk_size``, ``kda_low_rank_dim``, ``expert_tile_rows`` and
    ``router_bias_update_rate`` are this repo's keys for what the published
    file does not carry."""
    for key, want in (("q_lora_rank", None), ("mla_use_nope", True),
                      ("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("moe_router_activation_func", "sigmoid"),
                      ("num_expert_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"kimi_linear: {key}={config[key]!r} is not "
                             "supported")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("kimi_linear: latent attention expands a key and a "
                         "value head for every query head")
    linear = config["linear_attn_config"]
    return KimiLinear(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=layer_kinds(config),
        dense_layers=config["first_k_dense_replace"],
        dense_width=config["intermediate_size"],
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        mla_heads=config["num_attention_heads"],
        nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        kv_rank=config["kv_lora_rank"],
        expert_width=config["moe_intermediate_size"],
        experts_total=config.get("published", {}).get(
            "num_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        top_k=config["num_experts_per_token"],
        first_expert=config.get("first_routed_expert", 0),
        shared_experts=config["num_shared_experts"],
        routed_scaling=config["routed_scaling_factor"],
        norm_topk=config["moe_renormalize"],
        kda_conv=linear["short_conv_kernel_size"],
        kda_low_rank=config.get("kda_low_rank_dim"),
        kda_chunk=config.get("kda_chunk_size", 64),
        expert_tile=config.get("expert_tile_rows", 256),
        bias_update_rate=config.get("router_bias_update_rate", 1e-3),
        epsilon=config["rms_norm_eps"], **kwargs)
