"""Linear-attention sequence ops: the chunked gated delta rule with a decay
per key channel (Kimi Delta Attention, Kimi Linear arXiv:2510.26692 §3).

No reference analog: MXNet 1.x has no linear-attention layer.  Per head,
with a d_k × d_v state ``S`` (``S_0 = 0``):

    S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t

The scan is written in the chunked form so that XLA places its work on the
MXU.  With ``G`` the cumulative sum of ``g`` inside a chunk and ``S_0`` the
state entering it, ``S_t = Diag(exp G_t) S_0 + Σ_{s≤t} Diag(exp(G_t − G_s))
k_s u_sᵀ``, where the rows ``u`` solve the unit lower-triangular system

    (I + Diag(β) A) U = β ⊙ V − β ⊙ (K ⊙ exp G) S_0,
    A[t, s] = Σ_c k_tc k_sc exp(G_tc − G_sc)   for s < t

so inside a chunk the delta rule is one triangular solve and matrix
products, and between chunks the state is carried.  Every decay is ``exp``
of a DIFFERENCE of the one cumulative sum ``G`` (``g ≤ 0``), masked to the
causal half before it is exponentiated, so nothing positive ever is: the
per-channel difference ``G_t − G_s`` is taken directly inside sub-blocks of
``SUB`` steps, and between sub-blocks it is split at the later block's
first step ``r`` into ``(G_t − G_r) + (G_r − G_s)``, both ≤ 0.  Gradients
are ``jax.vjp`` through this form (registry default).

The op ``_contrib_kda_scan`` runs the same mathematics as two Pallas
kernels that walk the chunks on a grid axis (``pallas_kda``) wherever their
shape rule admits the heads and the chunk (``pallas_kda.kernel_takes``), and
this form elsewhere; ``kda_scan`` here is the plain reference.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from . import pallas_kda
from .registry import register
from ..base import MXNetError
from ..telemetry import record_kda_scan_lowered

# steps whose per-channel decays are differenced directly, (SUB, SUB, d_k)
# a sub-block; a chunk is a whole number of them
SUB = 16


def _intra_chunk(q, k, cs, sub):
    """``(Σ_c k_tc k_sc e^{G_tc − G_sc}, Σ_c q_tc k_sc e^{G_tc − G_sc})`` for
    ``s ≤ t``, zero above the diagonal: q, k, cs (..., Q, d) -> 2 × (..., Q, Q)."""
    lead, (qn, d) = q.shape[:-2], q.shape[-2:]
    ns = qn // sub
    blocks = lambda v: v.reshape(lead + (ns, sub, d))  # noqa: E731
    qb, kb, gb = blocks(q), blocks(k), blocks(cs)
    # G just before each sub-block's first step (0 for the first)
    ref = jnp.concatenate(
        [jnp.zeros(lead + (1, d), cs.dtype), gb[..., :-1, -1, :]], axis=-2)
    # earlier sub-blocks: rows decayed back to their block's start, keys
    # decayed forward to it; a key not before block i is masked to zero
    to_start = jnp.exp(gb - ref[..., :, None, :])             # (., ns, sub, d)
    before = (jnp.arange(qn)[None, :]
              < (jnp.arange(ns) * sub)[:, None])[..., None]  # (ns, Q, 1)
    k_fwd = k[..., None, :, :] * jnp.exp(jnp.where(
        before, ref[..., :, None, :] - cs[..., None, :, :], -jnp.inf))
    off_kk = jnp.einsum("...iad,...isd->...ias", kb * to_start, k_fwd)
    off_qk = jnp.einsum("...iad,...isd->...ias", qb * to_start, k_fwd)
    # the sub-block's own steps: the difference itself, causal half only
    seg = gb[..., :, None, :] - gb[..., None, :, :]           # (., ns, a, b, d)
    causal = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    own_kk = jnp.einsum("...iad,...ibd,...iabd->...iab", kb, kb, decay)
    own_qk = jnp.einsum("...iad,...ibd,...iabd->...iab", qb, kb, decay)
    eye = jnp.eye(ns, dtype=q.dtype)[:, None, :, None]        # (i, 1, j, 1)

    def whole(off, own):
        full = off.reshape(lead + (ns, sub, ns, sub)) \
            + own[..., :, :, None, :] * eye
        return full.reshape(lead + (qn, qn))

    return whole(off_kk, own_kk), whole(off_qk, own_qk)


def kda_scan(q, k, v, g, beta, chunk_size):
    """Gated delta-rule scan with a decay per key channel, per head:

        S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ
        o_t = S_tᵀ q_t,   S_0 = 0

    q, k, g (batch, T, H, d_k) with g ≤ 0; v (batch, T, H, d_v); beta
    (batch, T, H).  Returns o like v.  T need not be a multiple of
    ``chunk_size``: the tail is padded with steps of β = 0, g = 0 and zero
    q, k, v, which neither decay nor feed the state.  Every decay is the
    ``exp`` of a masked difference of one cumulative sum of ``g`` (see the
    module's head), so no positive number is exponentiated.
    """
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    qn = int(chunk_size)
    sub = SUB if qn % SUB == 0 else qn
    if qn < 1 or k.shape != q.shape or g.shape != q.shape \
            or beta.shape != q.shape[:3] or v.shape[:3] != q.shape[:3]:
        raise MXNetError(
            f"kda_scan: q {q.shape} k {k.shape} v {v.shape} g {g.shape} "
            f"beta {beta.shape}, chunk {qn}")
    f32 = jnp.float32
    pad = -t % qn
    nc = (t + pad) // qn

    def chunks(x):      # (b, T, H, ...) -> (b, nc, H, Q, ...)
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((bsz, nc, qn) + x.shape[2:])
        return jnp.moveaxis(x, 2, 3)

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g)
    bc = chunks(beta)[..., None]                              # (b, nc, H, Q, 1)
    cs = jnp.cumsum(gc, axis=3)                               # ≤ 0
    a_kk, a_qk = _intra_chunk(qc, kc, cs, sub)
    strict = jnp.tril(jnp.ones((qn, qn), f32), -1)
    system = jnp.eye(qn, dtype=f32) + bc * a_kk * strict
    from_start = jnp.exp(cs)
    solved = solve_triangular(
        system, jnp.concatenate([bc * kc * from_start, bc * vc], axis=-1),
        lower=True, unit_diagonal=True)
    w, u_own = solved[..., :dk], solved[..., dk:]
    to_end = kc * jnp.exp(cs[..., -1:, :] - cs)               # (b, nc, H, Q, dk)
    chunk_decay = from_start[..., -1, :]                      # (b, nc, H, dk)

    def carry(s, inp):          # s (b, H, dk, dv): the state entering
        w_c, u_c, end_c, dec_c = inp
        u = u_c - jnp.einsum("bhqk,bhkv->bhqv", w_c, s)
        new = s * dec_c[..., None] + jnp.einsum("bhqk,bhqv->bhkv", end_c, u)
        return new, (s, u)

    _, (entering, u) = lax.scan(
        carry, jnp.zeros((bsz, h, dk, dv), f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (w, u_own, to_end, chunk_decay)))
    entering, u = jnp.moveaxis(entering, 0, 1), jnp.moveaxis(u, 0, 1)
    o = jnp.einsum("bchqk,bchkv->bchqv", qc * from_start, entering) \
        + jnp.einsum("bchqs,bchsv->bchqv", a_qk, u)
    o = jnp.moveaxis(o, 3, 2).reshape(bsz, nc * qn, h, dv)[:, :t]
    return o.astype(v.dtype)


@register("_contrib_kda_scan", alias=("kda_scan",),
          input_names=("q", "k", "v", "g", "beta"))
def _kda_scan(attrs, q, k, v, g, beta):
    chunk = int(attrs.get("chunk_size", 64))
    if pallas_kda.kernel_takes(q.shape, v.shape, chunk, SUB):
        record_kda_scan_lowered("pallas")
        return pallas_kda.kda_scan(q, k, v, g, beta, chunk, SUB)
    record_kda_scan_lowered("xla")
    return kda_scan(q, k, v, g, beta, chunk)
