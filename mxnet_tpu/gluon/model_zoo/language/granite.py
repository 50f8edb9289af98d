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

from .... import initializer as init_mod
from .... import ndarray as nd
from ...block import HybridBlock
from ...nn import HybridSequential, RMSNorm

__all__ = ["Mamba2Mixer", "GroupedQueryAttention", "GatedMLP", "Relu2MLP",
           "HybridDecoderLayer", "GraniteHybrid", "granite_hybrid"]


@init_mod.register
class MambaALog(init_mod.Initializer):
    """``A_log = log U(1, 16)``: Mamba-2's default for the per-head decay."""

    def _init_weight(self, _, arr):
        arr[:] = nd.log(nd.random.uniform(1.0, 16.0, arr.shape,
                                          dtype=arr.dtype, ctx=arr.ctx))


@init_mod.register
class MambaDtBias(init_mod.Initializer):
    """The inverse softplus of a step size drawn log-uniformly from
    ``[dt_min, dt_max]``: Mamba-2's default for ``dt_bias``."""

    def __init__(self, dt_min=1e-3, dt_max=1e-1):
        super().__init__(dt_min=dt_min, dt_max=dt_max)
        self.dt_min, self.dt_max = dt_min, dt_max

    def _init_weight(self, _, arr):
        import math
        dt = nd.exp(nd.random.uniform(
            math.log(self.dt_min), math.log(self.dt_max), arr.shape,
            dtype=arr.dtype, ctx=arr.ctx))
        arr[:] = dt + nd.log(-nd.expm1(-dt))


def _dense(F, x, weight, units):
    """``x Wᵀ`` over the trailing axis, no bias; ``weight`` (units, in)."""
    return F.FullyConnected(x, weight, no_bias=True, flatten=False,
                            num_hidden=units)


class Mamba2Mixer(HybridBlock):
    """Mamba-2 (Dao & Gu arXiv:2405.21060): ``[z, xBC, dt] = W_in h``;
    ``xBC = silu(conv1d_causal(xBC))`` split into x (heads × head_dim) and
    the groups' B and C (state_size each); ``Δ = softplus(dt + dt_bias)``,
    ``a = −exp(A_log)``; the selective scan (op ``_contrib_ssd_scan``, in
    chunks of ``chunk_size``); ``RMSNorm(y · silu(z))``, over each of the
    ``n_groups`` groups of channels alone; ``W_out``."""

    def __init__(self, hidden_size, num_heads, head_dim, state_size,
                 n_groups=1, conv_kernel=4, chunk_size=256, epsilon=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._head_dim = num_heads, head_dim
        self._groups, self._state = n_groups, state_size
        self._chunk, self._hidden = chunk_size, hidden_size
        self._inner = num_heads * head_dim
        conv_dim = self._inner + 2 * n_groups * state_size
        with self.name_scope():
            self.in_proj_weight = self.params.get(
                "in_proj_weight",
                shape=(self._inner + conv_dim + num_heads, hidden_size))
            # a depthwise Conv1d's own default, U(±1/√K), not the
            # matrices' N(0, 0.02): the signal keeps its size through it
            self.conv_weight = self.params.get(
                "conv_weight", shape=(conv_dim, conv_kernel),
                init=init_mod.Uniform(conv_kernel ** -0.5))
            self.conv_bias = self.params.get(
                "conv_bias", shape=(conv_dim,), init="zeros")
            self.A_log = self.params.get(
                "A_log", shape=(num_heads,), init=MambaALog())
            self.D = self.params.get("D", shape=(num_heads,), init="ones")
            self.dt_bias = self.params.get(
                "dt_bias", shape=(num_heads,), init=MambaDtBias())
            self.norm = RMSNorm(self._inner, epsilon, num_groups=n_groups,
                                prefix="norm_")
            self.out_proj_weight = self.params.get(
                "out_proj_weight", shape=(hidden_size, self._inner))

    def hybrid_forward(self, F, h, in_proj_weight, conv_weight, conv_bias,
                       A_log, D, dt_bias, out_proj_weight):
        inner, bc = self._inner, self._groups * self._state
        with jax.named_scope("granite/mamba/in_proj"):
            zxbcdt = _dense(F, h, in_proj_weight,
                            2 * inner + 2 * bc + self._heads)
            z = F.slice_axis(zxbcdt, axis=-1, begin=0, end=inner)
            xbc = F.slice_axis(zxbcdt, axis=-1, begin=inner,
                               end=2 * inner + 2 * bc)
            dt = F.slice_axis(zxbcdt, axis=-1, begin=2 * inner + 2 * bc,
                              end=None)
        with jax.named_scope("granite/mamba/conv"):
            xbc = F.Activation(
                F.contrib.causal_conv1d(xbc, conv_weight, conv_bias),
                act_type="silu")
        with jax.named_scope("granite/mamba/ssd"):
            x = F.reshape(
                F.slice_axis(xbc, axis=-1, begin=0, end=inner),
                shape=(0, 0, self._heads, self._head_dim))
            b = F.reshape(
                F.slice_axis(xbc, axis=-1, begin=inner, end=inner + bc),
                shape=(0, 0, self._groups, self._state))
            c = F.reshape(
                F.slice_axis(xbc, axis=-1, begin=inner + bc, end=None),
                shape=(0, 0, self._groups, self._state))
            dt = F.Activation(
                F.broadcast_add(dt, F.reshape(dt_bias, shape=(1, 1, -1))),
                act_type="softrelu")
            y = F.contrib.ssd_scan(x, dt, -F.exp(A_log), b, c, D,
                                   chunk_size=self._chunk)
        with jax.named_scope("granite/mamba/gated_norm"):
            y = self.norm(F.reshape(y, shape=(0, 0, -1)), z)
        with jax.named_scope("granite/mamba/out_proj"):
            return _dense(F, y, out_proj_weight, self._hidden)


class GroupedQueryAttention(HybridBlock):
    """Self-attention with ``num_kv_heads`` key/value heads under
    ``num_heads`` query heads, no bias: ``softmax(q kᵀ · scale) v`` through
    the flash kernel (op ``_contrib_flash_attention``), then the output
    projection.  With ``gate`` the heads' outputs are multiplied
    elementwise by ``sigmoid(W_g h)`` before it.

    By default causal, with no positional encoding.  ``qk_norm`` (an
    epsilon) norms every query and key head by an RMSNorm with a learned
    weight of ``head_dim``; ``rotary`` (the base θ) then turns them by the
    positions the block is CALLED with, ``block(h, positions)``
    (op ``_contrib_rotary_embedding``).  ``mask`` and ``mask_block`` name
    the kernel's mask (``ops.pallas_attention.Mask``); they are plain
    attributes that a model may set between traces, and a
    ``block_diffusion`` mask takes its ``half`` from the sequence it is
    traced at."""

    # the flash kernel's tiles: (512, 64) query rows against (512, 64)
    # keys keep the grid at 8 × 8 steps a head at 4096 positions
    BLOCK = 512

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 scale, gate=False, rotary=None, qk_norm=None, mask="causal",
                 mask_block=1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._head_dim, self._scale = head_dim, float(scale)
        self._hidden, self._rotary = hidden_size, rotary
        self.mask, self.mask_block = mask, mask_block
        with self.name_scope():
            if gate:
                self.g_weight = self.params.get(
                    "g_weight", shape=(num_heads * head_dim, hidden_size))
            self.q_weight = self.params.get(
                "q_weight", shape=(num_heads * head_dim, hidden_size))
            self.k_weight = self.params.get(
                "k_weight", shape=(num_kv_heads * head_dim, hidden_size))
            self.v_weight = self.params.get(
                "v_weight", shape=(num_kv_heads * head_dim, hidden_size))
            self.o_weight = self.params.get(
                "o_weight", shape=(hidden_size, num_heads * head_dim))
            self.q_norm = self.k_norm = None
            if qk_norm is not None:
                self.q_norm = RMSNorm(head_dim, qk_norm, prefix="q_norm_")
                self.k_norm = RMSNorm(head_dim, qk_norm, prefix="k_norm_")

    def hybrid_forward(self, F, h, positions=None, *, q_weight, k_weight,
                       v_weight, o_weight, g_weight=None):
        def heads(w, n, norm=None, turned=False):
            """(batch, T, n·d) -> (batch, n, T, d)"""
            y = F.reshape(_dense(F, h, w, n * self._head_dim),
                          shape=(0, 0, n, self._head_dim))
            if norm is not None:
                with jax.named_scope("qk_norm"):
                    y = norm(y)
            y = F.transpose(y, axes=(0, 2, 1, 3))
            if turned and self._rotary is not None:
                with jax.named_scope("rope"):
                    y = F.contrib.rotary_embedding(y, positions,
                                                   base=self._rotary)
            return y

        # a block_diffusion mask's two copies are the halves of the sequence
        half = h.shape[1] // 2 if self.mask == "block_diffusion" else 0
        with jax.named_scope("granite/attention"):
            out = F.contrib.flash_attention(
                heads(q_weight, self._heads, self.q_norm, turned=True),
                heads(k_weight, self._kv_heads, self.k_norm, turned=True),
                heads(v_weight, self._kv_heads),
                mask=self.mask, mask_block=self.mask_block, mask_half=half,
                sm_scale=self._scale, block_q=self.BLOCK, block_k=self.BLOCK)
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(0, 0, -1))
            if g_weight is not None:
                out = out * F.sigmoid(_dense(F, h, g_weight,
                                             self._heads * self._head_dim))
            return _dense(F, out, o_weight, self._hidden)


class GatedMLP(HybridBlock):
    """``W_out (silu(g) ⊙ u)`` with ``[g, u] = W_in h``, no bias."""

    def __init__(self, hidden_size, intermediate_size, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._width, self._hidden = intermediate_size, hidden_size
        with self.name_scope():
            self.in_weight = self.params.get(
                "in_weight", shape=(2 * intermediate_size, hidden_size))
            self.out_weight = self.params.get(
                "out_weight", shape=(hidden_size, intermediate_size))

    def hybrid_forward(self, F, h, in_weight, out_weight):
        with jax.named_scope("granite/mlp"):
            gu = _dense(F, h, in_weight, 2 * self._width)
            g = F.slice_axis(gu, axis=-1, begin=0, end=self._width)
            u = F.slice_axis(gu, axis=-1, begin=self._width, end=None)
            return _dense(F, F.Activation(g, act_type="silu") * u,
                          out_weight, self._hidden)


class Relu2MLP(HybridBlock):
    """``W_out relu(W_in h)²``, no gate, no bias."""

    def __init__(self, hidden_size, intermediate_size, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._width, self._hidden = intermediate_size, hidden_size
        with self.name_scope():
            self.in_weight = self.params.get(
                "in_weight", shape=(intermediate_size, hidden_size))
            self.out_weight = self.params.get(
                "out_weight", shape=(hidden_size, intermediate_size))

    def hybrid_forward(self, F, h, in_weight, out_weight):
        with jax.named_scope("relu2_mlp"):
            u = F.relu(_dense(F, h, in_weight, self._width))
            return _dense(F, u * u, out_weight, self._hidden)


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
            return _dense(F, self.final_norm(x), embed_weight,
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
