"""State-space sequence ops: the chunked selective scan of Mamba-2 (state
space duality, Dao & Gu arXiv:2405.21060 §6), the causal 1-D convolution
in front of it (depthwise, or grouped), and RMSNorm (plain and SiLU-gated).

No reference analog: MXNet 1.x has no state-space layer.  The scan is
written in the chunked form so that XLA places its work on the MXU:
inside a chunk the recurrence is the masked matrix product
``(L ⊙ C Bᵀ) X``, between chunks a P×N state is carried.  Every decay is
``exp`` of a DIFFERENCE of one cumulative sum of ``Δ·a ≤ 0``, masked to
the causal half before it is exponentiated, so nothing positive ever is.
Gradients are ``jax.vjp`` through this form (registry default); a Pallas
kernel for it is a later optimisation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ._op_nn import _wb_names
from .registry import register
from ..base import MXNetError


# --- RMSNorm ----------------------------------------------------------------
@register("RMSNorm", alias=("_contrib_rms_norm",),
          input_names=("data", "gamma"))
def _rms_norm(attrs, x, gamma, *maybe_gate):
    """``x · rsqrt(mean(x², -1) + eps) · gamma`` over the trailing axis,
    statistics in float32.  With a third input ``gate`` the input is first
    multiplied by ``silu(gate)`` (Mamba-2's gated norm).  With
    ``num_groups`` G the mean is over each of the G equal groups of
    consecutive channels (Mamba-2 with several B/C groups norms each
    group's channels alone)."""
    eps = float(attrs.get("eps", 1e-5))
    groups = int(attrs.get("num_groups", 1))
    if groups < 1 or x.shape[-1] % groups:
        raise MXNetError(f"RMSNorm: {x.shape[-1]} channels in {groups} "
                         "groups")
    h = x.astype(jnp.float32)
    if maybe_gate:
        h = h * jax.nn.silu(maybe_gate[0].astype(jnp.float32))
    by_group = h.reshape(h.shape[:-1] + (groups, -1))
    h = (by_group * lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
         ).reshape(h.shape)
    return (h * gamma.astype(jnp.float32)).astype(x.dtype)


# --- rotary positions ---------------------------------------------------------
@register("_contrib_rotary_embedding", alias=("rotary_embedding",),
          input_names=("data", "positions"))
def _rotary_embedding(attrs, x, positions):
    """Rotary position embedding, rotate-half form (Su et al.
    arXiv:2104.09864 as GPT-NeoX lays it out): with ``d`` the number of
    LEADING channels of a head that are turned (``rotary_dim``, by default
    the whole trailing axis), ``θ_i = base^(−2i/d)`` for ``i < d/2`` and
    ``[x1, x2]`` the two halves of those ``d`` channels,

        out = [x1 cos(pθ) − x2 sin(pθ),  x2 cos(pθ) + x1 sin(pθ)]

    and the channels after them pass untouched (a partial rotary factor).
    ``x`` (batch, heads, T, head size); ``positions`` (T,) or (batch, T),
    an INPUT and not ``0..T−1``: positions may repeat (block-diffusion
    training lays two copies of a sequence side by side under the same
    positions).  Angles, sines and cosines are taken in float32."""
    base = float(attrs.get("base", 10000.0))
    d = int(attrs.get("rotary_dim", x.shape[-1]))
    if d % 2 or not 0 < d <= x.shape[-1] \
            or positions.shape[-1] != x.shape[-2]:
        raise MXNetError(f"rotary_embedding: data {x.shape}, rotary_dim "
                         f"{d}, positions {positions.shape}")
    if d < x.shape[-1]:
        turned = _rotary_embedding({"base": base}, x[..., :d], positions)
        return jnp.concatenate([turned, x[..., d:]], axis=-1)
    inv_freq = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    if angle.ndim == 3:                 # (batch, T, d/2): over the heads
        angle = angle[:, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# --- causal convolution over time: depthwise, or grouped ------------------------
@register("_contrib_causal_conv1d", alias=("causal_conv1d",),
          input_names=_wb_names)
def _causal_conv1d(attrs, x, weight, bias=None):
    """``y[b, t, c] = bias[c] + Σ_k weight[c, k] · x[b, t − (K−1) + k, c]``
    with zeros before the sequence: ``x`` (batch, time, channels),
    ``weight`` (channels, K) as a depthwise ``Conv1d`` stores it (its last
    tap multiplies the current step).  With ``no_bias`` there is no
    ``bias`` input and no term for it.

    A 3-d ``weight`` (channels, channels/groups, K), as a grouped
    ``Conv1d`` stores it, mixes the channels of a group: output channel
    ``c`` of group ``g`` reads that group's ``channels/groups`` input
    channels at every tap,

        y[b, t, c] = bias[c] + Σ_k Σ_i weight[c, i, k]
                               · x[b, t − (K−1) + k, g · channels/groups + i]

    ``groups`` is read from the shapes."""
    if weight.ndim == 3:
        return _grouped_causal_conv1d(x, weight, bias)
    k = weight.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    y = None if bias is None else bias.astype(x.dtype)
    for j in range(k):
        tap = xp[:, j:j + t, :] * weight[:, j].astype(x.dtype)
        y = tap if y is None else y + tap
    return y


def _grouped_causal_conv1d(x, weight, bias):
    """K shifted grouped products, one a tap: each is a batched matrix
    product over the groups, (time, in a group) × (in a group, out a
    group), which XLA places on the MXU like any other."""
    channels, per_group, k = weight.shape
    if x.shape[-1] != channels or channels % per_group:
        raise MXNetError(f"causal_conv1d: data {x.shape}, grouped weight "
                         f"{weight.shape}")
    groups, t = channels // per_group, x.shape[1]
    xp = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    w = weight.astype(x.dtype).reshape(groups, per_group, per_group, k)
    y = None if bias is None else bias.astype(x.dtype)
    for j in range(k):
        tap = xp[:, j:j + t, :].reshape(x.shape[:2] + (groups, per_group))
        tap = jnp.einsum("btgi,goi->btgo", tap, w[..., j]).reshape(x.shape)
        y = tap if y is None else y + tap
    return y


# --- the chunked scan ---------------------------------------------------------
def ssd_scan(x, dt, a, b, c, d, chunk_size):
    """Selective state-space scan, per head ``h`` with a P×N state:

        S_t = exp(Δ_t a_h) S_{t-1} + Δ_t x_t B_tᵀ,   y_t = S_t C_t + D_h x_t

    x (batch, T, H, P); dt (batch, T, H), Δ > 0; a (H,), a < 0;
    b, c (batch, T, G, N) with H a multiple of G; d (H,).  Returns y like
    x.  T need not be a multiple of ``chunk_size``: the tail is padded
    with Δ = 0 steps, which neither decay nor feed the state.
    """
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    q = int(chunk_size)
    if h % g or q < 1:
        raise MXNetError(f"ssd_scan: {h} heads over {g} groups, chunk {q}")
    f32 = jnp.float32
    pad = -t % q
    if pad:
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // q
    xc = x.astype(f32).reshape(bsz, nc, q, h, p)
    dtc = dt.astype(f32).reshape(bsz, nc, q, h)
    # heads share their group's B and C: the group axis is kept and the
    # heads of a group ride along as their own axis
    r = h // g
    bc = b.astype(f32).reshape(bsz, nc, q, g, n)
    cc = c.astype(f32).reshape(bsz, nc, q, g, n)

    cs = jnp.cumsum(dtc * a.astype(f32), axis=2)          # (b, nc, q, h) ≤ 0
    xdt = (xc * dtc[..., None]).reshape(bsz, nc, q, g, r, p)
    csg = cs.reshape(bsz, nc, q, g, r)

    # inside a chunk: y_t += Σ_{s≤t} exp(cs_t − cs_s) (C_t·B_s) Δ_s x_s
    seg = csg[:, :, :, None] - csg[:, :, None, :]          # (b, nc, t, s, g, r)
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bctsg", cc, bc)
    y = jnp.einsum("bctsgr,bcsgrp->bctgrp", decay * cb[..., None], xdt)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(csg[:, :, -1:] - csg)                 # (b, nc, q, g, r)
    states = jnp.einsum("bcsgn,bcsgrp->bcgrpn", bc, xdt * to_end[..., None])

    # between chunks: carry the state, decayed over the whole chunk
    chunk_decay = jnp.exp(csg[:, :, -1])                   # (b, nc, g, r)

    def carry(s, inp):
        own, dec = inp
        return s * dec[..., None, None] + own, s

    _, entering = lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                # (b, nc, g, r, p, n)

    # what the state entering the chunk still gives at step t
    y = y + jnp.einsum("bctgn,bcgrpn->bctgrp", cc, entering) \
        * jnp.exp(csg)[..., None]
    y = y.reshape(bsz, nc * q, h, p)[:, :t]
    y = y + x.astype(f32)[:, :t] * d.astype(f32)[:, None]
    return y.astype(x.dtype)


@register("_contrib_ssd_scan", alias=("ssd_scan",),
          input_names=("data", "dt", "A", "B", "C", "D"))
def _ssd_scan(attrs, x, dt, a, b, c, d):
    return ssd_scan(x, dt, a, b, c, d, int(attrs.get("chunk_size", 256)))
