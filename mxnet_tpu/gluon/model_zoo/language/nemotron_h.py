"""Nemotron-H style hybrid decoder (``model_type`` ``nemotron_h``): every
layer is ONE mixer behind a pre-norm, chosen per layer by a character of a
pattern string: ``M`` a Mamba-2 mixer, ``E`` a mixture of routed relu²
experts with a shared expert behind a sigmoid router whose selection bias
balances the load, ``*`` grouped-query attention.  RMSNorm before every
mixer, no positional encoding, no embedding scale, an untied output head.

``h`` is a (T, hidden) sequence, matrices are stored (out, in), no bias but
the convolution's:

    x = E[ids]
    layer i:  x = x + mixer_i(RMSNorm(x))
    logits = RMSNorm(x) W_headᵀ

    M: Mamba-2 (``Mamba2Mixer``) with ``n_groups`` B/C groups; its gated
        norm is over each group's channels alone
    E: s = sigmoid(W_r h) over ALL routed experts (float32, highest);
        the chosen = top-k of s + b;  w_e = scaling · s_e / Σ_chosen s
        y = Σ_{e chosen, e held here} w_e · W2_e relu(W1_e h)²
            + W2_sh relu(W1_sh h)²           (the shared expert, its own width)
    *: causal grouped-query attention, scale 1/sqrt(head_dim), no gate

    after the layers, in training mode only:
        b_e ← b_e + u · sign(mean(c) − c_e),  c the step's assignments to
        each of ALL experts (auxiliary-loss-free balancing, Wang et al.
        arXiv:2408.15664)

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
from .blocks import (GroupedQueryAttention, Mamba2Mixer, RoutedExpertState,
                     SparseExperts, dense)

__all__ = ["NemotronLayer", "NemotronH", "nemotron_h"]

KINDS = "ME*"       # a pattern's characters
# the scope a mixer is traced under (the shared blocks' own scopes nest
# inside); the mixture is given its own
SCOPES = {"M": "nemotron/mamba", "*": "nemotron/attention"}


class NemotronLayer(HybridBlock):
    """``x + mixer(RMSNorm(x))``.  An expert layer (``kind`` ``E``) returns
    the mixture's notes beside ``x``: load, rows, and the count over all
    experts."""

    def __init__(self, mixer, kind, hidden_size, epsilon=1e-5, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self.kind = kind
        with self.name_scope():
            self.norm = RMSNorm(hidden_size, epsilon, prefix="norm_")
            self.mixer = mixer(prefix="mixer_")

    def hybrid_forward(self, F, x):
        h = self.norm(x)
        if self.kind == "E":
            y, *notes = self.mixer(h)
            return (x + y, *notes)
        with jax.named_scope(SCOPES[self.kind]):
            return x + self.mixer(h)


class NemotronH(RoutedExpertState, HybridBlock):
    """Token ids ``(batch, T)`` to logits ``(batch, T, vocab_size)``.

    ``pattern`` names each layer's mixer by a character: ``M``, ``E`` or
    ``*``.  ``experts_held`` and ``vocab_size`` are what this holder has of
    the published counts (experts ``first_expert ..``; the first rows of
    both vocabulary tables: ids, logits and loss are over the slice);
    ``experts_total`` is the width of the router and of its bias.
    ``bias_update_rate`` is the balancing rule's ``u``.
    """

    def __init__(self, vocab_size, hidden_size, pattern, num_heads,
                 num_kv_heads, head_dim, mamba_heads, mamba_head_dim,
                 mamba_state, mamba_groups, expert_width, shared_width,
                 experts_total, experts_held, top_k, first_expert=0,
                 routed_scaling=1.0, norm_topk=True, mamba_conv=4,
                 mamba_chunk=128, expert_tile=128, bias_update_rate=1e-3,
                 epsilon=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if set(pattern) - set(KINDS):
            raise ValueError(f"nemotron_h: pattern {pattern!r} has layers "
                             f"other than {KINDS!r}")
        self._vocab, self._hidden = vocab_size, hidden_size
        mixers = {
            "M": lambda prefix: Mamba2Mixer(
                hidden_size, mamba_heads, mamba_head_dim, mamba_state,
                mamba_groups, mamba_conv, mamba_chunk, epsilon,
                prefix=prefix),
            "*": lambda prefix: GroupedQueryAttention(
                hidden_size, num_heads, num_kv_heads, head_dim,
                head_dim ** -0.5, prefix=prefix),
            "E": lambda prefix: SparseExperts(
                hidden_size, expert_width, experts_total, experts_held,
                first_expert, top_k, scaling=routed_scaling,
                norm_topk=norm_topk, tile=expert_tile, form="relu2",
                shared_width=shared_width, select_bias=True,
                scope="nemotron/moe", prefix=prefix),
        }
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for kind in pattern:
                    self.layers.add(NemotronLayer(
                        mixers[kind], kind, hidden_size, epsilon))
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
    def expert_layers(self):
        return [layer for layer in self.layers if layer.kind == "E"]

    @property
    def expert_blocks(self):
        return [layer.mixer for layer in self.expert_layers]

    def hybrid_forward(self, F, ids, embed_weight, head_weight,
                       expert_load=None, expert_rows=None):
        x = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                        output_dim=self._hidden)
        notes = []
        for layer in self.layers:
            if layer.kind == "E":
                x, *note = layer(x)
                notes.append(note)
            else:
                x = layer(x)
        self._write_expert_state(F, notes, expert_load, expert_rows,
                                 ids.context)
        with jax.named_scope("nemotron/head"):
            return dense(F, self.final_norm(x), head_weight, self._vocab)


def nemotron_h(config, **kwargs):
    """A :class:`NemotronH` from the keys of a published ``config.json``
    (``nemotron_h``) in which the counts are one holder's share:
    ``n_routed_experts`` and ``vocab_size`` are what is held here, the
    first ``num_hidden_layers`` characters of ``hybrid_override_pattern``
    are built, and ``published.n_routed_experts`` (when the file has it)
    is the router's width.  ``first_routed_expert``, ``expert_tile_rows``
    and ``router_bias_update_rate`` are this repo's keys for what the
    published file does not carry."""
    for key, want in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                      ("n_group", 1), ("topk_group", 1),
                      ("tie_word_embeddings", False), ("use_conv_bias", True),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("mlp_bias", False), ("use_bias", False)):
        if config.get(key, want) != want:
            raise ValueError(f"nemotron_h: {key}={config[key]!r} is not "
                             "supported")
    return NemotronH(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        pattern=config["hybrid_override_pattern"][
            :config["num_hidden_layers"]],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        mamba_state=config["ssm_state_size"],
        mamba_groups=config["n_groups"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["n_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
        experts_total=config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"]),
        experts_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        first_expert=config.get("first_routed_expert", 0),
        routed_scaling=config["routed_scaling_factor"],
        norm_topk=config["norm_topk_prob"],
        mamba_conv=config["conv_kernel"], mamba_chunk=config["chunk_size"],
        expert_tile=config.get("expert_tile_rows", 128),
        bias_update_rate=config.get("router_bias_update_rate", 1e-3),
        epsilon=config["layer_norm_epsilon"], **kwargs)
