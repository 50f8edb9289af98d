"""Granite 4.0-H style hybrid decoder (``model_type`` ``granitemoehybrid``
with no routed experts): Mamba-2 mixers with a grouped-query attention
layer among them, each followed by a shared gated MLP, RMSNorm before
every mixer and MLP, scaled residuals, no positional encoding, the
embedding tied to the output head.

    x = E[ids] · embedding_multiplier
    per layer:  x = x + residual_multiplier · mixer(RMSNorm(x))
                x = x + residual_multiplier · MLP(RMSNorm(x))
    logits = RMSNorm(x) Eᵀ / logits_scaling

Every size is given at construction, so no parameter waits for a first
batch (``parallel.spmd.functionalize`` then makes no eager dry run).  The
model declares its decoder layers as ``remat_layers``: a train step built
with ``remat=True`` checkpoints each of them and keeps its input only.
"""
from __future__ import annotations

import jax

from ...block import HybridBlock
from ...nn import HybridSequential, RMSNorm
from .blocks import GatedMLP, GroupedQueryAttention, Mamba2Mixer, dense

__all__ = ["HybridDecoderLayer", "GraniteHybrid", "granite_hybrid"]


class HybridDecoderLayer(HybridBlock):
    """``x + r · mixer(RMSNorm(x))`` then ``x + r · MLP(RMSNorm(x))``."""

    def __init__(self, mixer, hidden_size, intermediate_size,
                 residual_multiplier, epsilon=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._residual = float(residual_multiplier)
        with self.name_scope():
            self.input_norm = RMSNorm(hidden_size, epsilon,
                                      prefix="input_norm_")
            self.mixer = mixer(prefix="mixer_")
            self.post_norm = RMSNorm(hidden_size, epsilon,
                                     prefix="post_norm_")
            self.mlp = GatedMLP(hidden_size, intermediate_size,
                                prefix="mlp_")

    def hybrid_forward(self, F, x):
        x = x + self._residual * self.mixer(self.input_norm(x))
        return x + self._residual * self.mlp(self.post_norm(x))


class GraniteHybrid(HybridBlock):
    """Token ids ``(batch, T)`` to logits ``(batch, T, vocab_size)``.

    ``layer_types`` names each layer's mixer, ``"mamba"`` or
    ``"attention"``.  ``vocab_size`` may be a slice of the published
    table (its first rows): ids, logits and loss are then over the slice.
    """

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 layer_types, num_heads, num_kv_heads, attention_multiplier,
                 mamba_heads, mamba_head_dim, mamba_state, mamba_groups=1,
                 mamba_conv=4, mamba_chunk=256, embedding_multiplier=1.0,
                 residual_multiplier=1.0, logits_scaling=1.0, epsilon=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._vocab, self._hidden = vocab_size, hidden_size
        self._embedding_multiplier = float(embedding_multiplier)
        self._logits_scaling = float(logits_scaling)
        mixers = {
            "mamba": lambda prefix: Mamba2Mixer(
                hidden_size, mamba_heads, mamba_head_dim, mamba_state,
                mamba_groups, mamba_conv, mamba_chunk, epsilon,
                prefix=prefix),
            "attention": lambda prefix: GroupedQueryAttention(
                hidden_size, num_heads, num_kv_heads,
                hidden_size // num_heads, attention_multiplier,
                prefix=prefix),
        }
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for kind in layer_types:
                    self.layers.add(HybridDecoderLayer(
                        mixers[kind], hidden_size, intermediate_size,
                        residual_multiplier, epsilon))
            self.final_norm = RMSNorm(hidden_size, epsilon,
                                      prefix="final_norm_")

    @property
    def remat_layers(self):
        """The blocks a train step with ``remat=True`` checkpoints one by
        one (``gluon.block.remat_scope``)."""
        return list(self.layers)

    def hybrid_forward(self, F, ids, embed_weight):
        x = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                        output_dim=self._hidden) * self._embedding_multiplier
        for layer in self.layers:
            x = layer(x)
        with jax.named_scope("granite/head"):
            return dense(F, self.final_norm(x), embed_weight,
                         self._vocab) * (1.0 / self._logits_scaling)


def granite_hybrid(config, **kwargs):
    """A :class:`GraniteHybrid` from the keys of a published
    ``config.json`` (``granitemoehybrid`` without routed experts); the
    first ``num_hidden_layers`` entries of ``layer_types`` are built."""
    if config.get("num_local_experts", 0):
        raise ValueError("granite_hybrid: routed experts are not supported")
    return GraniteHybrid(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["shared_intermediate_size"],
        layer_types=config["layer_types"][:config["num_hidden_layers"]],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        attention_multiplier=config["attention_multiplier"],
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        mamba_state=config["mamba_d_state"],
        mamba_groups=config["mamba_n_groups"],
        mamba_conv=config["mamba_d_conv"],
        mamba_chunk=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        epsilon=config["rms_norm_eps"], **kwargs)
