"""ZAYA1 style decoder (``model_type`` ``zaya``): every layer is a
compressed convolutional attention sublayer (CCA, Zyphra arXiv:2510.04476:
queries, keys and values live in a latent narrower than the hidden state,
two causal convolutions mix the q/k latents over time, each value head is
half this token's and half the previous one's, rotary positions on the
leading half of a head) followed by a mixture of routed gated-SiLU experts,
ONE a token, behind a small MLP router that carries its state from layer to
layer (ZAYA1 report, arXiv:2511.17127); RMSNorm before each, a learned
scale and bias on both branches of every residual, no bias in the
projections, the embedding tied to the output head.

``x`` is a (T, hidden) sequence, matrices are stored (out, in), ``H`` query
heads over ``G`` key/value heads of ``d``, ``g = H/G``:

    x = E[ids];  r = 0  (T, router width)
    layer i:
      a = RMSNorm(x);  q̃ = W_q a  (T, H·d);  k̃ = W_k a  (T, G·d)
      c = conv1(conv0([q̃ ; k̃])):  conv0 depthwise, conv1 in H + G groups of
          d channels (a head's channels mix), both causal with a bias
      q' = c_q + ½ (q̃ + k̃↑);  k' = c_k + ½ (q̃↓ + k̃)
          (k̃↑ a key head repeated over its g query heads, q̃↓ their mean)
      q̂ = √d · q' / ‖q'‖₂;  k̂ = τ · √d · k' / ‖k'‖₂   (τ a key head, learned)
      q̂, k̂ = rope(·) over the first ``rotary_dim`` channels of a head
      v_t = [W_v1 a_t ; W_v2 a_{t−1}]  per key head (a_{−1} = 0)
      y = W_o softmax(q̂ k̂ᵀ / √d + causal) v
      x = s₁ ⊙ (x + b₁) + s₂ ⊙ (y + b₂)
      m = RMSNorm(x)
      r = W_down m + b_down + γ · r              (float32, highest)
      ℓ = W₃ gelu(W₂ gelu(W₁ RMSNorm(r) + b₁') + b₂') + b₃';  s = softmax(ℓ)
      e* = argmax(s + β);  y = s_{e*} · expert_{e*}(m) if e* is held here
      x = s₃ ⊙ (x + b₃) + s₄ ⊙ (y + b₄)
    logits = RMSNorm(x) Eᵀ

    after the layers, in training mode only:
        β ← β + u · sign(mean(c) − c),  c the step's assignments to each of
        ALL experts (``blocks.balanced_bias``, as ``nemotron_h``)

The model is built for ONE HOLDER'S SHARE of a deployment, as
``solar_open2`` is: it is told which routed experts and how many vocabulary
rows it holds, the router and its bias keep all their outputs, what an
absent expert would add is left out and nothing stands in for the absent
chips.  Every size is given at construction; the layers are the block's
``remat_layers``, and TWO activations cross each of their boundaries: the
hidden state and the router's state.  Each layer's ``select_bias`` is
read inside its boundary and written after the layers, with
``expert_load`` and ``expert_rows`` (``blocks.RoutedExpertState``).
"""
from __future__ import annotations

import jax
import numpy as np

from .... import initializer as init_mod
from .... import ndarray as nd
from ...block import HybridBlock
from ...nn import HybridSequential, RMSNorm
from .blocks import RoutedExpertState, SparseExperts, dense

__all__ = ["CompressedConvAttention", "ZayaRouter", "ZayaDecoderLayer",
           "Zaya", "zaya"]


@init_mod.register
class CurrentTapOne(init_mod.Initializer):
    """A depthwise causal convolution that starts near the identity:
    ``N(0, sigma)`` on every tap and 1 more on the last, which multiplies
    the current step."""

    def __init__(self, sigma=0.02):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        now = np.zeros(arr.shape, np.float32)
        now[:, -1] = 1.0
        arr[:] = nd.random.normal(0.0, self.sigma, arr.shape,
                                  dtype=arr.dtype, ctx=arr.ctx) \
            + nd.array(now, ctx=arr.ctx, dtype=arr.dtype)


class CompressedConvAttention(HybridBlock):
    """Compressed convolutional attention over ``num_heads`` query heads
    and ``num_kv_heads`` key/value heads of ``head_dim`` (the module's head
    has the equations): the projections go from ``hidden_size`` to the
    latents (``H·d`` and ``G·d`` channels) and the output projection comes
    back from ``H·d``; ``conv_taps`` are the taps of the depthwise and of
    the grouped convolution; ``rotary_dim`` leading channels of a head are
    turned (base ``rope_theta``), by positions ``0..T−1``; causal, through
    the flash kernel (op ``_contrib_flash_attention``)."""

    BLOCK = 512     # the flash kernel's tiles, as GroupedQueryAttention's
    EPS = 1e-6      # under the root of a head's squared length

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 conv_taps=(2, 2), rotary_dim=None, rope_theta=5e6,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % num_kv_heads or head_dim % 2:
            raise ValueError(f"cca: {num_heads} query heads over "
                             f"{num_kv_heads} key/value heads of {head_dim}")
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._head_dim, self._hidden = head_dim, hidden_size
        self._rotary = head_dim if rotary_dim is None else int(rotary_dim)
        self._theta = float(rope_theta)
        q, kv = num_heads * head_dim, num_kv_heads * head_dim
        with self.name_scope():
            self.q_weight = self.params.get(
                "q_weight", shape=(q, hidden_size))
            self.k_weight = self.params.get(
                "k_weight", shape=(kv, hidden_size))
            self.v1_weight = self.params.get(
                "v1_weight", shape=(kv // 2, hidden_size))
            self.v2_weight = self.params.get(
                "v2_weight", shape=(kv // 2, hidden_size))
            self.o_weight = self.params.get(
                "o_weight", shape=(hidden_size, q))
            self.conv0_weight = self.params.get(
                "conv0_weight", shape=(q + kv, conv_taps[0]),
                init=CurrentTapOne())
            self.conv0_bias = self.params.get(
                "conv0_bias", shape=(q + kv,), init="zeros")
            self.conv1_weight = self.params.get(
                "conv1_weight", shape=(q + kv, head_dim, conv_taps[1]))
            self.conv1_bias = self.params.get(
                "conv1_bias", shape=(q + kv,), init="zeros")
            self.temperature = self.params.get(
                "temperature", shape=(num_kv_heads,), init="ones")

    def hybrid_forward(self, F, a, q_weight, k_weight, v1_weight, v2_weight,
                       o_weight, conv0_weight, conv0_bias, conv1_weight,
                       conv1_bias, temperature):
        from .... import telemetry
        heads, groups, d = self._heads, self._kv_heads, self._head_dim
        q_width, kv_width = heads * d, groups * d
        telemetry.record_cca_latent_channels(q_width, kv_width)

        def by_group(x, n):     # (batch, T, G·n·d) -> (batch, T, G, n, d)
            return F.reshape(x, shape=(0, 0, groups, n, d))

        def unit(x):            # each head's vector to length √d
            return x * F.rsqrt(F.mean(x * x, axis=-1, keepdims=True)
                               + self.EPS / d)

        def in_heads(x, n):     # (batch, T, ..., d) -> (batch, n, T, d)
            return F.transpose(F.reshape(x, shape=(0, 0, n, d)),
                               axes=(0, 2, 1, 3))

        with jax.named_scope("zaya/attention/proj"):
            q = dense(F, a, q_weight, q_width)
            k = dense(F, a, k_weight, kv_width)
            v_now = dense(F, a, v1_weight, kv_width // 2)
            v_before = dense(F, a, v2_weight, kv_width // 2)
        with jax.named_scope("zaya/attention/mix"):
            c = F.contrib.causal_conv1d(
                F.contrib.causal_conv1d(F.concat(q, k, dim=-1),
                                        conv0_weight, conv0_bias),
                conv1_weight, conv1_bias)
            q, k = by_group(q, heads // groups), by_group(k, 1)
            q_new = by_group(F.slice_axis(c, axis=-1, begin=0, end=q_width),
                             heads // groups) \
                + 0.5 * F.broadcast_add(q, k)
            k_new = by_group(F.slice_axis(c, axis=-1, begin=q_width,
                                          end=None), 1) \
                + 0.5 * (F.mean(q, axis=3, keepdims=True) + k)
            q = in_heads(unit(q_new), heads)
            k = in_heads(F.broadcast_mul(
                unit(k_new), F.reshape(temperature, shape=(1, 1, -1, 1, 1))),
                groups)
            # a key head's first half from this token, its second half from
            # the token before (zeros before the sequence)
            v_before = F.concat(
                F.zeros_like(F.slice_axis(v_before, axis=1, begin=0, end=1)),
                F.slice_axis(v_before, axis=1, begin=0, end=-1), dim=1)
            v = in_heads(F.concat(
                F.reshape(v_now, shape=(0, 0, groups, d // 2)),
                F.reshape(v_before, shape=(0, 0, groups, d // 2)), dim=-1),
                groups)
        with jax.named_scope("zaya/attention/rope"):
            positions = F.arange(a.shape[1], dtype="int32")
            q, k = (F.contrib.rotary_embedding(
                x, positions, base=self._theta, rotary_dim=self._rotary)
                for x in (q, k))
        with jax.named_scope("zaya/attention"):
            out = F.contrib.flash_attention(
                q, k, v, mask="causal", sm_scale=d ** -0.5,
                block_q=self.BLOCK, block_k=self.BLOCK)
        with jax.named_scope("zaya/attention/out"):
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(0, 0, -1))
            return dense(F, out, o_weight, self._hidden)


class ZayaRouter(HybridBlock):
    """The router of one layer, a network with a state: ``r`` (..., width)
    comes from the layer before (zeros into the first), is decayed by the
    learned ``gamma`` and added to this layer's down-projection of the
    hidden state (exponential depth averaging); an RMSNorm and an MLP of
    two exact-erf GELU layers turn it into ``experts_total`` logits.
    Returns ``(logits, r)``.  All of it in float32 at the highest
    precision whatever the step's: near-ties among the scores must fall
    the same way wherever they are computed."""

    def __init__(self, hidden_size, width, experts_total, epsilon=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._width, self._total = width, experts_total
        with self.name_scope():
            self.down_weight = self.params.get(
                "down_weight", shape=(width, hidden_size))
            self.down_bias = self.params.get(
                "down_bias", shape=(width,), init="zeros")
            self.gamma = self.params.get("gamma", shape=(1,), init="zeros")
            self.norm = RMSNorm(width, epsilon, prefix="norm_")
            for name, units in (("fc1", width), ("fc2", width),
                                ("out", experts_total)):
                setattr(self, name + "_weight", self.params.get(
                    name + "_weight", shape=(units, width)))
                setattr(self, name + "_bias", self.params.get(
                    name + "_bias", shape=(units,), init="zeros"))

    def hybrid_forward(self, F, m, r, down_weight, down_bias, gamma,
                       fc1_weight, fc1_bias, fc2_weight, fc2_bias,
                       out_weight, out_bias):
        def f32(v):
            return F.cast(v, dtype="float32")

        def affine(x, weight, bias, units):
            return F.FullyConnected(x, f32(weight), f32(bias), flatten=False,
                                    num_hidden=units)

        with jax.default_matmul_precision("highest"):
            r = affine(f32(m), down_weight, down_bias, self._width) \
                + F.broadcast_mul(f32(r), f32(gamma))
            z = f32(self.norm(r))
            for weight, bias in ((fc1_weight, fc1_bias),
                                 (fc2_weight, fc2_bias)):
                z = F.LeakyReLU(affine(z, weight, bias, self._width),
                                act_type="gelu")
            return affine(z, out_weight, out_bias, self._total), r


class ResidualScale(HybridBlock):
    """``s_skip ⊙ (x + b_skip) + s_out ⊙ (y + b_out)``: the residual sum
    with a learned scale and bias on the skip and on the sublayer's output;
    scales 1 and biases 0 at initialisation."""

    def __init__(self, hidden_size, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            for name in ("skip", "out"):
                setattr(self, name + "_scale", self.params.get(
                    name + "_scale", shape=(hidden_size,), init="ones"))
                setattr(self, name + "_bias", self.params.get(
                    name + "_bias", shape=(hidden_size,), init="zeros"))

    def hybrid_forward(self, F, x, y, skip_scale, skip_bias, out_scale,
                       out_bias):
        with jax.named_scope("zaya/residual_scale"):
            return F.broadcast_mul(F.broadcast_add(x, skip_bias),
                                   skip_scale) \
                + F.broadcast_mul(F.broadcast_add(y, out_bias), out_scale)


class ZayaDecoderLayer(HybridBlock):
    """``(x, r) → (x, r, load, rows, counts)``: the attention sublayer,
    then the expert sublayer whose router takes the state ``r`` of the
    layer before and hands on its own; the mixture's notes beside them."""

    def __init__(self, attention, experts, hidden_size, epsilon=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.input_norm = RMSNorm(hidden_size, epsilon,
                                      prefix="input_norm_")
            self.attention = attention(prefix="attention_")
            self.attention_residual = ResidualScale(
                hidden_size, prefix="attention_residual_")
            self.post_norm = RMSNorm(hidden_size, epsilon,
                                     prefix="post_norm_")
            self.moe = experts(prefix="moe_")
            self.moe_residual = ResidualScale(hidden_size,
                                              prefix="moe_residual_")

    def hybrid_forward(self, F, x, r):
        x = self.attention_residual(x, self.attention(self.input_norm(x)))
        y, load, rows, counts, r = self.moe(self.post_norm(x), r)
        return self.moe_residual(x, y), r, load, rows, counts


class Zaya(RoutedExpertState, HybridBlock):
    """Token ids ``(batch, T)`` to logits ``(batch, T, vocab_size)``.

    ``experts_held`` and ``vocab_size`` are what this holder has of the
    published counts (experts ``first_expert ..``; the first rows of the
    tied table: ids, logits and loss are over the slice); ``experts_total``
    is the width of the router's output and of its bias, ``router_width``
    of its state.  ``bias_update_rate`` is the balancing rule's ``u``;
    the selection bias starts at 0, as a checkpoint's, or is drawn from
    ``N(0, bias_sigma)``.
    """

    def __init__(self, vocab_size, hidden_size, num_layers, num_heads,
                 num_kv_heads, head_dim, expert_width, experts_total,
                 experts_held, router_width, top_k=1, first_expert=0,
                 conv_taps=(2, 2), rotary_dim=None, rope_theta=5e6,
                 expert_tile=256, bias_update_rate=1e-3, bias_sigma=0.0,
                 epsilon=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._vocab, self._hidden = vocab_size, hidden_size
        self._router_width = router_width

        def attention(prefix):
            return CompressedConvAttention(
                hidden_size, num_heads, num_kv_heads, head_dim, conv_taps,
                rotary_dim, rope_theta, prefix=prefix)

        def router(prefix):
            return ZayaRouter(hidden_size, router_width, experts_total,
                              epsilon, prefix=prefix)

        def experts(prefix):
            return SparseExperts(
                hidden_size, expert_width, experts_total, experts_held,
                first_expert, top_k, shared_experts=0, norm_topk=False,
                tile=expert_tile, select_bias=True, scope="zaya/moe",
                score_function="softmax", router=router,
                bias_init=init_mod.Normal(bias_sigma) if bias_sigma
                else "zeros", prefix=prefix)

        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for _ in range(num_layers):
                    self.layers.add(ZayaDecoderLayer(
                        attention, experts, hidden_size, epsilon))
            self.final_norm = RMSNorm(hidden_size, epsilon,
                                      prefix="final_norm_")
            self._declare_expert_state(experts_held, bias_update_rate)

    @property
    def remat_layers(self):
        """The blocks a train step with ``remat=True`` checkpoints one by
        one (``gluon.block.remat_scope``)."""
        return list(self.layers)

    @property
    def expert_blocks(self):
        return [layer.moe for layer in self.layers]

    def hybrid_forward(self, F, ids, embed_weight, expert_load, expert_rows):
        x = F.Embedding(ids, embed_weight, input_dim=self._vocab,
                        output_dim=self._hidden)
        r = F.zeros(ids.shape + (self._router_width,), ctx=ids.context)
        notes = []
        for layer in self.layers:
            x, r, *note = layer(x, r)
            notes.append(note)
        self._write_expert_state(F, notes, expert_load, expert_rows,
                                 ids.context)
        with jax.named_scope("zaya/head"):
            return dense(F, self.final_norm(x), embed_weight, self._vocab)

    def record_expert_load(self, arrays=None, steps=1):
        """``RoutedExpertState.record_expert_load``, and
        ``mxnet_router_eda_gamma_abs_mean`` from the routers' ``gamma`` as
        the last step left them."""
        from .... import telemetry

        load, rows = super().record_expert_load(arrays, steps)
        telemetry.record_router_eda_gamma(np.stack(
            [self._host(experts.router.gamma, arrays)
             for experts in self.expert_blocks]))
        return load, rows


def zaya(config, **kwargs):
    """A :class:`Zaya` from the keys of a published ``config.json``
    (``zaya``) in which the counts are one holder's share: ``num_experts``
    and ``vocab_size`` are what is held here, the first
    ``num_hidden_layers`` layers are built, and ``published.num_experts``
    (when the file has it) is the router's width.  ``first_routed_expert``,
    ``expert_tile_rows``, ``router_bias_update_rate`` and
    ``router_bias_init_sigma`` are this repo's keys for what the published
    file does not carry."""
    for key, want in (("sliding_window", None), ("attention_bias", False),
                      ("lm_head_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", True)):
        if config.get(key, want) != want:
            raise ValueError(f"zaya: {key}={config[key]!r} is not supported")
    layers = config["num_hidden_layers"]
    kinds = set(config["layer_types"][:layers])
    if kinds - {"hybrid"}:
        raise ValueError(f"zaya: layer_types {sorted(kinds)} has layers "
                         "other than 'hybrid'")
    rope = config["rope_parameters"]["hybrid"]
    return Zaya(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=layers, num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_width=config["moe_intermediate_size"],
        experts_total=config.get("published", {}).get(
            "num_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        router_width=config["router_hidden_size"],
        top_k=config["num_experts_per_tok"],
        first_expert=config.get("first_routed_expert", 0),
        conv_taps=(config["cca_time0"], config["cca_time1"]),
        rotary_dim=int(config["head_dim"] * rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        expert_tile=config.get("expert_tile_rows", 256),
        bias_update_rate=config.get("router_bias_update_rate", 1e-3),
        bias_sigma=config.get("router_bias_init_sigma", 0.0),
        epsilon=config["rms_norm_eps"], **kwargs)
