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

    KDA mixer, per head of d:
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
added to auxiliary state (``expert_load``, ``expert_rows``), written in the
step the way BatchNorm writes its running statistics.
"""
from __future__ import annotations

import jax

from .... import initializer as init_mod
from ...block import HybridBlock
from ...nn import HybridSequential, RMSNorm
from .granite import (GatedMLP, GroupedQueryAttention, MambaALog,
                      MambaDtBias, Relu2MLP, _dense)

__all__ = ["KimiDeltaAttention", "SparseExperts", "balanced_bias",
           "SolarDecoderLayer", "SolarOpen2", "solar_open2"]


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention over ``num_heads`` heads of ``head_dim``: the
    gated delta rule with a decay per key channel (op
    ``_contrib_kda_scan``, chunks of ``chunk_size``) between short causal
    convolutions and a per-head RMSNorm with a low-rank sigmoid gate."""

    def __init__(self, hidden_size, num_heads, head_dim, conv_kernel=4,
                 low_rank=None, chunk_size=64, neg_eigval=True, epsilon=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._head_dim = num_heads, head_dim
        self._inner = inner = num_heads * head_dim
        self._hidden, self._chunk = hidden_size, chunk_size
        self._rank = rank = head_dim if low_rank is None else low_rank
        self._beta_scale = 2.0 if neg_eigval else 1.0
        conv_init = init_mod.Uniform(conv_kernel ** -0.5)
        with self.name_scope():
            for name in "qkv":
                setattr(self, name + "_weight", self.params.get(
                    name + "_weight", shape=(inner, hidden_size)))
                setattr(self, name + "_conv_weight", self.params.get(
                    name + "_conv_weight", shape=(inner, conv_kernel),
                    init=conv_init))
                setattr(self, name + "_conv_bias", self.params.get(
                    name + "_conv_bias", shape=(inner,), init="zeros"))
            self.a_down_weight = self.params.get(
                "a_down_weight", shape=(rank, hidden_size))
            self.a_up_weight = self.params.get(
                "a_up_weight", shape=(inner, rank))
            self.A_log = self.params.get(
                "A_log", shape=(num_heads,), init=MambaALog())
            self.dt_bias = self.params.get(
                "dt_bias", shape=(inner,), init=MambaDtBias())
            self.beta_weight = self.params.get(
                "beta_weight", shape=(num_heads, hidden_size))
            self.g_down_weight = self.params.get(
                "g_down_weight", shape=(rank, hidden_size))
            self.g_up_weight = self.params.get(
                "g_up_weight", shape=(inner, rank))
            self.norm = RMSNorm(head_dim, epsilon, prefix="norm_")
            self.o_weight = self.params.get(
                "o_weight", shape=(hidden_size, inner))

    def hybrid_forward(self, F, h, q_weight, q_conv_weight, q_conv_bias,
                       k_weight, k_conv_weight, k_conv_bias, v_weight,
                       v_conv_weight, v_conv_bias, a_down_weight,
                       a_up_weight, A_log, dt_bias, beta_weight,
                       g_down_weight, g_up_weight, o_weight):
        inner = self._inner

        def heads(x):      # (batch, T, H·d) -> (batch, T, H, d)
            return F.reshape(x, shape=(0, 0, self._heads, self._head_dim))

        def unit(x):       # each head's vector to length 1
            return x * F.rsqrt(F.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        with jax.named_scope("solar/kda/proj"):
            q, k, v = (_dense(F, h, w, inner)
                       for w in (q_weight, k_weight, v_weight))
        with jax.named_scope("solar/kda/conv"):
            q, k, v = (heads(F.Activation(
                F.contrib.causal_conv1d(x, w, b), act_type="silu"))
                for x, w, b in ((q, q_conv_weight, q_conv_bias),
                                (k, k_conv_weight, k_conv_bias),
                                (v, v_conv_weight, v_conv_bias)))
            q = unit(q) * self._head_dim ** -0.5
            k = unit(k)
        with jax.named_scope("solar/kda/gates"):
            step = F.Activation(F.broadcast_add(
                _dense(F, _dense(F, h, a_down_weight, self._rank),
                       a_up_weight, inner),
                F.reshape(dt_bias, shape=(1, 1, -1))), act_type="softrelu")
            g = F.broadcast_mul(
                heads(step), -F.exp(F.reshape(A_log, shape=(1, 1, -1, 1))))
            beta = self._beta_scale * F.sigmoid(
                _dense(F, h, beta_weight, self._heads))
            gate = heads(F.sigmoid(_dense(
                F, _dense(F, h, g_down_weight, self._rank), g_up_weight,
                inner)))
        with jax.named_scope("solar/kda/scan"):
            o = F.contrib.kda_scan(q, k, v, g, beta, chunk_size=self._chunk)
        with jax.named_scope("solar/kda/out"):
            o = F.reshape(self.norm(o) * gate, shape=(0, 0, -1))
            return _dense(F, o, o_weight, self._hidden)


class SparseExperts(HybridBlock):
    """One holder's share of a mixture of ``experts_total`` routed experts,
    ``top_k`` a token: the router scores ALL experts, the experts
    ``first_expert .. first_expert + experts_held − 1`` are held and
    computed here for the rows routed to them (op
    ``_contrib_routed_experts``: nothing is dropped), and the shared expert
    is added.  ``form`` is the experts' (and the shared expert's):
    ``"gated_silu"``, three matrices, or ``"relu2"``, two; the shared
    expert is ``shared_width`` wide (by default ``shared_experts × width``;
    with ``shared_experts`` 0 there is none); ``score_function`` is the
    router's, ``"sigmoid"`` or ``"softmax"`` over all experts;
    ``scope`` is the ``jax.named_scope`` its parts are traced under.
    Returns ``(y, load, rows)``: the assignments each held expert received
    and the rows the grouped products ran.

    With ``select_bias`` the block holds a bias per expert
    (``select_bias``, no gradient) that is added to the scores to CHOOSE
    the top k and never weighs them, and returns a fourth output, the
    assignments to each of all ``experts_total`` experts: what the rule
    that balances the bias reads (``balanced_bias``).  The block reads
    the bias and does not write it: whoever owns the step applies the
    rule, outside any rematerialisation boundary.  ``bias_init`` is the
    bias's initializer (by default the one the block is initialised with).

    ``router`` is by default one matrix inside the op (``router_weight``).
    Given a block's constructor, ``router(prefix=...)``, that block is the
    router: called ``router(h, *state)`` with whatever else the experts
    were called with, it returns the logits ``(..., experts_total)`` and
    its state after them, which the experts return after their own
    outputs (a router that carries a state from layer to layer)."""

    def __init__(self, hidden_size, width, experts_total, experts_held,
                 first_expert, top_k, shared_experts=1, scaling=1.0,
                 norm_topk=True, tile=256, form="gated_silu",
                 shared_width=None, select_bias=False, scope="solar/moe",
                 score_function="sigmoid", router=None, bias_init=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._traced_as = scope
        self._attrs = dict(
            experts_total=experts_total, top_k=top_k,
            first_expert=first_expert, routed_scaling_factor=scaling,
            norm_topk_prob=norm_topk, tile=tile, expert_form=form,
            select_bias=select_bias, score_function=score_function)
        if shared_width is None:
            shared_width = shared_experts * width
        shared = {"gated_silu": GatedMLP, "relu2": Relu2MLP}[form]
        with self.name_scope():
            if router is None:
                self.router = None
                self.router_weight = self.params.get(
                    "router_weight", shape=(experts_total, hidden_size))
            else:
                self.router = router(prefix="router_")
                self._attrs["router"] = "logits"
            self.w1 = self.params.get(
                "w1", shape=(experts_held, width, hidden_size))
            if form == "gated_silu":
                self.w3 = self.params.get(
                    "w3", shape=(experts_held, width, hidden_size))
            self.w2 = self.params.get(
                "w2", shape=(experts_held, hidden_size, width))
            if select_bias:
                self.select_bias = self.params.get(
                    "select_bias", shape=(experts_total,), grad_req="null",
                    init=bias_init)
            self.shared = shared(hidden_size, shared_width,
                                 prefix="shared_") if shared_width else None

    def hybrid_forward(self, F, h, *state, w1, w2, router_weight=None,
                       w3=None, select_bias=None):
        routing = router_weight     # the matrix, or a block's logits
        if self.router is not None:
            with jax.named_scope(self._traced_as + "/router"):
                routing, *state = self.router(h, *state)
        inputs = [v for v in (h, routing, w1, w3, w2, select_bias)
                  if v is not None]
        with jax.named_scope(self._traced_as):      # the op's own scopes nest
            y, *notes = F.contrib.routed_experts(*inputs, **self._attrs)
        if self.shared is None:
            return (y, *notes, *state)
        with jax.named_scope(self._traced_as + "/shared"):
            shared = self.shared(h)
        with jax.named_scope(self._traced_as + "/combine"):
            return (y + shared, *notes, *state)


def balanced_bias(F, bias, counts, rate):
    """The selection bias after one step of the auxiliary-loss-free
    balancing rule (Wang et al. arXiv:2408.15664): an expert that received
    fewer assignments than the mean is raised by ``rate``, one that
    received more is lowered: ``b + rate · sign(mean(c) − c)``."""
    return bias + rate * F.sign(F.mean(counts, axis=-1, keepdims=True)
                                - counts)


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


class SolarOpen2(HybridBlock):
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
                kda_chunk, kda_neg_eigval, epsilon, prefix=prefix),
            "attention": lambda prefix: GroupedQueryAttention(
                hidden_size, num_heads, num_kv_heads, head_dim,
                head_dim ** -0.5, gate=attention_gate, prefix=prefix),
        }

        def experts(prefix):
            return SparseExperts(
                hidden_size, expert_width, experts_total, experts_held,
                first_expert, top_k, shared_experts, routed_scaling,
                norm_topk, expert_tile, prefix=prefix)

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
            # auxiliary state, one row a layer: no gradient, no optimizer
            self.expert_load = self.params.get(
                "expert_load", shape=(len(layer_types), experts_held),
                init="zeros", grad_req="null")
            self.expert_rows = self.params.get(
                "expert_rows", shape=(len(layer_types),), init="zeros",
                grad_req="null")

    @property
    def remat_layers(self):
        """The blocks a train step with ``remat=True`` checkpoints one by
        one (``gluon.block.remat_scope``)."""
        return list(self.layers)

    def hybrid_forward(self, F, ids, embed_weight, head_weight, expert_load,
                       expert_rows):
        x = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                        output_dim=self._hidden)
        loads, rows = [], []
        for layer in self.layers:
            x, load, row = layer(x)
            loads.append(load)
            rows.append(row)
        # outside the layers' remat boundaries: the step returns these as
        # the forward's mutated state, with the loss, in the same program.
        # Added, not overwritten: the state is the sum over the forwards
        # made since it was last zero (whole numbers, exact in float32 up
        # to 2**24 an entry)
        with jax.named_scope("step/aux_state"):
            expert_load._set_data(
                (expert_load + F.stack(*loads, axis=0))._data)
            expert_rows._set_data(
                (expert_rows + F.concat(*rows, dim=0))._data)
        with jax.named_scope("solar/head"):
            return _dense(F, self.final_norm(x), head_weight, self._vocab)

    def record_expert_load(self, arrays=None, steps=1):
        """Set the ``mxnet_moe_*`` gauges from the auxiliary state, which
        the forward adds to: the sums over the ``steps`` steps made since
        it was zero.  ``arrays`` is ``{parameter name: array}`` of a train
        step that owns the state (``dict(zip(step.param_names,
        step.params))``), by default this block's own parameters.  One read
        of two small arrays, made when somebody asks, never in the step.
        Returns the two sums."""
        import numpy as np

        from .... import telemetry
        load, rows = (
            np.asarray(arrays[p.name]) if arrays is not None
            else p.data().asnumpy()
            for p in (self.expert_load, self.expert_rows))
        telemetry.record_moe_load(load, rows, steps)
        return load, rows


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
