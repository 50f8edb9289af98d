"""The blocks more than one language model of the zoo is built from, and
the routed-expert auxiliary state every model with routed experts keeps.

Here: ``dense`` (``x Wᵀ``, no bias); the Mamba-2 initializers
(``MambaALog``, ``MambaDtBias``) and ``Mamba2Mixer``;
``KimiDeltaAttention``; ``GroupedQueryAttention``;
``MultiHeadLatentAttention``; the two MLPs (``GatedMLP``, ``Relu2MLP``);
``SparseExperts`` and the balancing rule of its selection bias
(``balanced_bias``); ``RoutedExpertState``, the one owner of a model's
``expert_load`` and ``expert_rows``.

The rule: a model file imports from this module only, never from another
model's file, and only ``__init__.py`` imports the model files.  What one
model alone uses stays in its file; a block a second model needs moves
here in the change that needs it.  A block keeps the ``jax.named_scope``
names it was first traced under (``granite/...``): the trace readers and
the tests key on them; a block two models trace under their own names
takes its ``scope`` from the model.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from .... import autograd
from .... import initializer as init_mod
from .... import ndarray as nd
from ...block import HybridBlock
from ...nn import RMSNorm

__all__ = ["Mamba2Mixer", "KimiDeltaAttention", "GroupedQueryAttention",
           "MultiHeadLatentAttention", "GatedMLP", "Relu2MLP",
           "SparseExperts", "balanced_bias"]


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
        dt = nd.exp(nd.random.uniform(
            math.log(self.dt_min), math.log(self.dt_max), arr.shape,
            dtype=arr.dtype, ctx=arr.ctx))
        arr[:] = dt + nd.log(-nd.expm1(-dt))


def dense(F, x, weight, units):
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
            zxbcdt = dense(F, h, in_proj_weight,
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
            return dense(F, y, out_proj_weight, self._hidden)


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692) over
    ``num_heads`` heads of ``head_dim``: the gated delta rule with a decay
    per key channel (op ``_contrib_kda_scan``, chunks of ``chunk_size``)
    between short causal convolutions and a per-head RMSNorm with a
    low-rank sigmoid gate.  Per head of d:

        q = l2norm(silu(conv(W_q h))) / sqrt(d);  k = l2norm(silu(conv(W_k h)))
        v = silu(conv(W_v h))
        g_t = −exp(A_log) · softplus(W_a↑ W_a↓ h_t + dt_bias)      ≤ 0
        β_t = 2 · sigmoid(w_β · h_t)   (1 · without ``neg_eigval``)
        S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ
        o_t = S_tᵀ q_t;  out = W_o (RMSNorm_head(o_t) ⊙ sigmoid(W_g↑ W_g↓ h_t))

    The convolutions carry a bias unless ``conv_bias`` is false; ``scope``
    names the ``jax.named_scope`` its parts are traced under."""

    def __init__(self, hidden_size, num_heads, head_dim, conv_kernel=4,
                 low_rank=None, chunk_size=64, neg_eigval=True, epsilon=1e-5,
                 conv_bias=True, scope="kda", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._head_dim = num_heads, head_dim
        self._inner = inner = num_heads * head_dim
        self._hidden, self._chunk = hidden_size, chunk_size
        self._rank = rank = head_dim if low_rank is None else low_rank
        self._beta_scale = 2.0 if neg_eigval else 1.0
        self._traced_as = scope
        conv_init = init_mod.Uniform(conv_kernel ** -0.5)
        with self.name_scope():
            for name in "qkv":
                setattr(self, name + "_weight", self.params.get(
                    name + "_weight", shape=(inner, hidden_size)))
                setattr(self, name + "_conv_weight", self.params.get(
                    name + "_conv_weight", shape=(inner, conv_kernel),
                    init=conv_init))
                if conv_bias:
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

    def hybrid_forward(self, F, h, *, q_weight, q_conv_weight, k_weight,
                       k_conv_weight, v_weight, v_conv_weight, a_down_weight,
                       a_up_weight, A_log, dt_bias, beta_weight,
                       g_down_weight, g_up_weight, o_weight,
                       q_conv_bias=None, k_conv_bias=None, v_conv_bias=None):
        inner, scope = self._inner, self._traced_as

        def heads(x):      # (batch, T, H·d) -> (batch, T, H, d)
            return F.reshape(x, shape=(0, 0, self._heads, self._head_dim))

        def unit(x):       # each head's vector to length 1
            return x * F.rsqrt(F.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        def conv(x, w, b):
            if b is None:
                return F.contrib.causal_conv1d(x, w, no_bias=True)
            return F.contrib.causal_conv1d(x, w, b)

        with jax.named_scope(scope + "/proj"):
            q, k, v = (dense(F, h, w, inner)
                       for w in (q_weight, k_weight, v_weight))
        with jax.named_scope(scope + "/conv"):
            q, k, v = (heads(F.Activation(conv(x, w, b), act_type="silu"))
                       for x, w, b in ((q, q_conv_weight, q_conv_bias),
                                       (k, k_conv_weight, k_conv_bias),
                                       (v, v_conv_weight, v_conv_bias)))
            q = unit(q) * self._head_dim ** -0.5
            k = unit(k)
        with jax.named_scope(scope + "/gates"):
            step = F.Activation(F.broadcast_add(
                dense(F, dense(F, h, a_down_weight, self._rank),
                      a_up_weight, inner),
                F.reshape(dt_bias, shape=(1, 1, -1))), act_type="softrelu")
            g = F.broadcast_mul(
                heads(step), -F.exp(F.reshape(A_log, shape=(1, 1, -1, 1))))
            beta = self._beta_scale * F.sigmoid(
                dense(F, h, beta_weight, self._heads))
            gate = heads(F.sigmoid(dense(
                F, dense(F, h, g_down_weight, self._rank), g_up_weight,
                inner)))
        with jax.named_scope(scope + "/scan"):
            o = F.contrib.kda_scan(q, k, v, g, beta, chunk_size=self._chunk)
        with jax.named_scope(scope + "/out"):
            o = F.reshape(self.norm(o) * gate, shape=(0, 0, -1))
            return dense(F, o, o_weight, self._hidden)


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
            y = F.reshape(dense(F, h, w, n * self._head_dim),
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
                out = out * F.sigmoid(dense(F, h, g_weight,
                                            self._heads * self._head_dim))
            return dense(F, out, o_weight, self._hidden)


class MultiHeadLatentAttention(HybridBlock):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
    with every head's keys and values expanded from ONE latent a token,
    without positions (Kimi Linear's ``mla_use_nope``), causal, no bias.
    ``h`` is (T, hidden), matrices are stored (out, in), H heads:

        [q_nope ; q_pe] = W_q h                a head: nope_dim + rope_dim
        [c_kv ; k_pe]   = W_kva h              kv_rank + rope_dim, k_pe ONE
                                               for all heads
        [k_nope ; v]    = W_kvb RMSNorm(c_kv)  a head: nope_dim + v_dim
        q = [q_nope ; q_pe];  k = [k_nope ; k_pe]
        out = W_o softmax(q kᵀ / sqrt(nope_dim + rope_dim) + causal) v

    through the flash kernels, whose values keep their own head size
    (op ``_contrib_flash_attention``).  ``scope`` names the
    ``jax.named_scope`` the kernel call is traced under, with its parts
    ``proj``, ``latent`` and ``out`` inside it."""

    BLOCK = GroupedQueryAttention.BLOCK     # the flash kernel's tiles

    def __init__(self, hidden_size, num_heads, nope_dim, rope_dim, v_dim,
                 kv_rank, epsilon=1e-5, scope="mla", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._hidden = num_heads, hidden_size
        self._nope, self._rope, self._v = nope_dim, rope_dim, v_dim
        self._rank, self._traced_as = kv_rank, scope
        with self.name_scope():
            self.q_weight = self.params.get(
                "q_weight", shape=(num_heads * (nope_dim + rope_dim),
                                   hidden_size))
            self.kv_a_weight = self.params.get(
                "kv_a_weight", shape=(kv_rank + rope_dim, hidden_size))
            self.latent_norm = RMSNorm(kv_rank, epsilon,
                                       prefix="latent_norm_")
            self.kv_b_weight = self.params.get(
                "kv_b_weight", shape=(num_heads * (nope_dim + v_dim),
                                      kv_rank))
            self.o_weight = self.params.get(
                "o_weight", shape=(hidden_size, num_heads * v_dim))

    def hybrid_forward(self, F, h, q_weight, kv_a_weight, kv_b_weight,
                       o_weight):
        from .... import telemetry
        heads, nope, rope, scope = (self._heads, self._nope, self._rope,
                                    self._traced_as)
        d_qk = nope + rope
        telemetry.record_mla_latent_channels(self._rank, rope)

        def in_heads(x):        # (batch, T, H, n) -> (batch, H, T, n)
            return F.transpose(x, axes=(0, 2, 1, 3))

        with jax.named_scope(scope + "/proj"):
            q = in_heads(F.reshape(dense(F, h, q_weight, heads * d_qk),
                                   shape=(0, 0, heads, d_qk)))
            latent = dense(F, h, kv_a_weight, self._rank + rope)
        with jax.named_scope(scope + "/latent"):
            kv = F.reshape(dense(
                F, self.latent_norm(F.slice_axis(
                    latent, axis=-1, begin=0, end=self._rank)),
                kv_b_weight, heads * (nope + self._v)),
                shape=(0, 0, heads, nope + self._v))
            k_pe = F.broadcast_to(F.reshape(
                F.slice_axis(latent, axis=-1, begin=self._rank, end=None),
                shape=(0, 0, 1, rope)), shape=(0, 0, heads, rope))
            k = in_heads(F.concat(
                F.slice_axis(kv, axis=-1, begin=0, end=nope), k_pe, dim=-1))
            v = in_heads(F.slice_axis(kv, axis=-1, begin=nope, end=None))
        with jax.named_scope(scope):
            out = F.contrib.flash_attention(
                q, k, v, mask="causal", sm_scale=d_qk ** -0.5,
                block_q=self.BLOCK, block_k=self.BLOCK)
        with jax.named_scope(scope + "/out"):
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(0, 0, -1))
            return dense(F, out, o_weight, self._hidden)


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
            gu = dense(F, h, in_weight, 2 * self._width)
            g = F.slice_axis(gu, axis=-1, begin=0, end=self._width)
            u = F.slice_axis(gu, axis=-1, begin=self._width, end=None)
            return dense(F, F.Activation(g, act_type="silu") * u,
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
            u = F.relu(dense(F, h, in_weight, self._width))
            return dense(F, u * u, out_weight, self._hidden)


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
                 shared_width=None, select_bias=False, scope="moe",
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


class RoutedExpertState:
    """The auxiliary state of a model with routed experts, for a
    ``HybridBlock`` that derives from this class and states its
    ``SparseExperts`` blocks, in layer order, as ``expert_blocks``.

    ``expert_load`` (expert layers, experts held) and ``expert_rows``
    (expert layers,) are parameters of the model itself, with no gradient
    and no optimizer: a train step carries them and its checkpoints hold
    them.  Every forward adds each expert layer's assignments and rows to
    them (whole numbers, exact in float32 up to 2**24 an entry), so they
    are the sums over the forwards made since they were last zero, the way
    BatchNorm writes its running statistics.  A training forward also
    moves each expert block's ``select_bias`` by ``balanced_bias``, after
    the layers have read it: the next step reads what is written here.
    Both writes are made after the layers, under ``step/aux_state``,
    outside every rematerialisation boundary; the step returns them as
    the forward's mutated state, with the loss, in the same program."""

    def _declare_expert_state(self, experts_held, bias_update_rate=0.0):
        """Declare the two sums, once the layers are built, inside the
        model's name scope; none where the model has no expert layer.
        ``bias_update_rate`` is the balancing rule's ``u``."""
        self._bias_rate = float(bias_update_rate)
        layers = len(self.expert_blocks)
        if layers:
            self.expert_load = self.params.get(
                "expert_load", shape=(layers, experts_held), init="zeros",
                grad_req="null")
            self.expert_rows = self.params.get(
                "expert_rows", shape=(layers,), init="zeros",
                grad_req="null")

    def _write_expert_state(self, F, notes, expert_load, expert_rows, ctx):
        """Add the layers' ``notes`` to the sums and, in training, move the
        selection biases.  ``notes`` holds each expert layer's outputs
        after ``y``: ``(load, rows)``, and the counts over all experts
        where its block holds a selection bias."""
        if not notes:
            return
        loads, rows, *counts = zip(*notes)
        with jax.named_scope("step/aux_state"):
            expert_load._set_data(
                (expert_load + F.stack(*loads, axis=0))._data)
            expert_rows._set_data(
                (expert_rows + F.concat(*rows, dim=0))._data)
            if counts and autograd.is_training():
                for experts, count in zip(self.expert_blocks, counts[0]):
                    bias = experts.select_bias.data(ctx)
                    bias._set_data(balanced_bias(
                        F, bias, count, self._bias_rate)._data)

    @staticmethod
    def _host(param, arrays):
        """A host copy of ``param``: from ``arrays`` ``{parameter name:
        array}`` where given, else from the parameter's own data."""
        return np.asarray(arrays[param.name], np.float32) \
            if arrays is not None else param.data().asnumpy()

    def record_expert_load(self, arrays=None, steps=1):
        """Set the ``mxnet_moe_*`` gauges from the auxiliary state: the
        two counts sum over the ``steps`` steps made since they were zero,
        the selection biases (where the experts hold one) are as the last
        step left them.  ``arrays`` is ``{parameter name: array}`` of a
        train step that owns the state (``dict(zip(step.param_names,
        step.params))``), by default this block's own parameters.  One
        read of a few small arrays, made when somebody asks, never in the
        step.  Returns the two sums."""
        from .... import telemetry

        load, rows = (self._host(p, arrays)
                      for p in (self.expert_load, self.expert_rows))
        bias = [self._host(experts.select_bias, arrays)
                for experts in self.expert_blocks
                if hasattr(experts, "select_bias")]
        telemetry.record_moe_load(load, rows, steps,
                                  bias=np.stack(bias) if bias else None)
        return load, rows
