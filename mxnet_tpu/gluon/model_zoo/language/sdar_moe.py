"""SDAR style mixture-of-experts decoder (``model_type`` ``sdar_moe``: the
Qwen3-MoE block trained and served by diffusion over blocks, Cheng et al.
SDAR arXiv:2510.06303): every layer is grouped-query attention with a
per-head RMSNorm of queries and keys and rotary positions, followed by a
mixture of routed gated-SiLU experts behind a softmax router, RMSNorm
before each, no bias, no shared expert, an untied output head.

``h`` is a (L, hidden) sequence, matrices are stored (out, in):

    x = E[ids]
    layer i:  a = RMSNorm(x);  q, k, v = W_q a, W_k a, W_v a  in heads of d
              q, k = rope(RMSNorm_d(q), p), rope(RMSNorm_d(k), p)
              x = x + W_o softmax(q kᵀ / sqrt(d) + M) v
              m = RMSNorm(x);  s = softmax(W_r m) over ALL experts
              the chosen = top-k of s;  w_e = s_e / Σ_chosen s
              x = x + Σ_{e chosen, e held here} w_e · W2_e (silu(W1_e m) ⊙ W3_e m)
    logits = RMSNorm(x) W_headᵀ

Two layouts of the one block, chosen by ``layout``:

``training`` (block-diffusion training, Arriola et al. arXiv:2503.09573):
    ``ids = [x0 ; xt]``, the clean sequence of T ids and its noised copy
    side by side, positions ``p = [0..T−1 ; 0..T−1]``, the mask
    ``block_diffusion(block_length, T)`` of ``ops.pallas_attention.Mask``
    (a clean row sees clean keys of its own and earlier blocks, a noisy row
    the noisy keys of its own block and the clean keys of earlier blocks,
    no clean row a noisy key); the logits are of the noisy half alone,
    (batch, T, vocab).  The noise is DATA: which positions of ``xt`` hold
    the mask id and what each weighs in the loss is drawn by whoever owns
    the batch, and the weights reach the loss as ``TrainStep``'s third
    batch array.
``denoising`` (one step of generation by blocks): ``ids = [clean prefix ;
    one noisy block]``, positions ``0..L−1``, the mask
    ``block_causal(block_length)``; logits of every position, of which the
    last block's are the denoiser's.

The model is built for ONE HOLDER'S SHARE of a deployment, as
``solar_open2`` is: it is told which routed experts and how many
vocabulary rows it holds; the router keeps all its outputs, what an absent
expert would add is left out and nothing stands in for the absent chips.
Every size is given at construction; the layers are the block's
``remat_layers``; each layer's per-expert assignment count is added to
the auxiliary state of ``blocks.RoutedExpertState``.
"""
from __future__ import annotations

import contextlib

import jax

from ...block import HybridBlock
from ...nn import HybridSequential, RMSNorm
from .blocks import (GroupedQueryAttention, RoutedExpertState,
                     SparseExperts, dense)

__all__ = ["SDARDecoderLayer", "SDARMoE", "sdar_moe"]


class SDARDecoderLayer(HybridBlock):
    """``x + attention(RMSNorm(x), positions)`` then ``x + MoE(RMSNorm(x))``;
    returns the mixture's load beside ``x``."""

    def __init__(self, attention, experts, hidden_size, epsilon=1e-6,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.input_norm = RMSNorm(hidden_size, epsilon,
                                      prefix="input_norm_")
            self.attention = attention(prefix="attention_")
            self.post_norm = RMSNorm(hidden_size, epsilon,
                                     prefix="post_norm_")
            self.moe = experts(prefix="moe_")

    def hybrid_forward(self, F, x, positions):
        with jax.named_scope("sdar/attention"):
            x = x + self.attention(self.input_norm(x), positions)
        y, load, rows = self.moe(self.post_norm(x))
        return x + y, load, rows


class SDARMoE(RoutedExpertState, HybridBlock):
    """Token ids to logits, in the ``layout`` the block stands in (see the
    module's head): ``training`` takes ``(batch, 2T)`` and returns
    ``(batch, T, vocab_size)``, ``denoising`` takes and returns
    ``(batch, L, ...)``.

    ``experts_held`` and ``vocab_size`` are what this holder has of the
    published counts (experts ``first_expert ..``; the first rows of both
    vocabulary tables: ids, logits and loss are over the slice);
    ``experts_total`` is the router's width.
    """

    def __init__(self, vocab_size, hidden_size, num_layers, num_heads,
                 num_kv_heads, head_dim, expert_width, experts_total,
                 experts_held, top_k, first_expert=0, norm_topk=True,
                 rope_theta=1e6, block_length=4, expert_tile=256,
                 epsilon=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._vocab, self._hidden = vocab_size, hidden_size
        self._block = block_length
        self.layout = "training"

        def attention(prefix):
            return GroupedQueryAttention(
                hidden_size, num_heads, num_kv_heads, head_dim,
                head_dim ** -0.5, rotary=rope_theta, qk_norm=epsilon,
                mask="block_diffusion", mask_block=block_length,
                prefix=prefix)

        def experts(prefix):
            return SparseExperts(
                hidden_size, expert_width, experts_total, experts_held,
                first_expert, top_k, shared_experts=0, norm_topk=norm_topk,
                tile=expert_tile, score_function="softmax",
                scope="sdar/moe", prefix=prefix)

        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for _ in range(num_layers):
                    self.layers.add(SDARDecoderLayer(
                        attention, experts, hidden_size, epsilon))
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

    @contextlib.contextmanager
    def denoising(self):
        """Inside, the block stands in the ``denoising`` layout: calls take
        ``[clean prefix ; one noisy block]`` under ``block_causal``."""
        self._stand_in("denoising", "block_causal")
        try:
            yield self
        finally:
            self._stand_in("training", "block_diffusion")

    def _stand_in(self, layout, mask):
        self.layout = layout
        for layer in self.layers:
            layer.attention.mask = mask
        self._clear_cached_op()     # a trace holds the layout it was made in

    def hybrid_forward(self, F, ids, embed_weight, head_weight, expert_load,
                       expert_rows):
        length = ids.shape[1]
        if self.layout == "training":
            if length % (2 * self._block):
                raise ValueError(
                    f"sdar_moe: the training layout is [x0 ; xt], two "
                    f"copies of whole blocks of {self._block}; {length} ids "
                    "given")
            half = F.arange(length // 2, dtype="int32")
            positions = F.concat(half, half, dim=0)
        else:
            positions = F.arange(length, dtype="int32")
        x = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                        output_dim=self._hidden)
        notes = []
        for layer in self.layers:
            x, *note = layer(x, positions)
            notes.append(note)
        self._write_expert_state(F, notes, expert_load, expert_rows,
                                 ids.context)
        if self.layout == "training":       # the noisy half carries the loss
            x = F.slice_axis(x, axis=1, begin=length // 2, end=None)
        with jax.named_scope("sdar/head"):
            return dense(F, self.final_norm(x), head_weight, self._vocab)


def sdar_moe(config, **kwargs):
    """A :class:`SDARMoE` from the keys of a published ``config.json``
    (``sdar_moe``) in which the counts are one holder's share:
    ``num_experts`` and ``vocab_size`` are what is held here, the first
    ``num_hidden_layers`` layers are built, and ``published.num_experts``
    (when the file has it) is the router's width.  ``block_length``,
    ``first_routed_expert`` and ``expert_tile_rows`` are this repo's keys
    for what the published file does not carry."""
    for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("rope_scaling", None), ("use_sliding_window", False),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"sdar_moe: {key}={config[key]!r} is not "
                             "supported")
    return SDARMoE(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_width=config["moe_intermediate_size"],
        experts_total=config.get("published", {}).get(
            "num_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        first_expert=config.get("first_routed_expert", 0),
        norm_topk=config["norm_topk_prob"],
        rope_theta=float(config["rope_theta"]),
        block_length=config.get("block_length", 4),
        expert_tile=config.get("expert_tile_rows", 256),
        epsilon=config["rms_norm_eps"], **kwargs)
