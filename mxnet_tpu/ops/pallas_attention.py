"""Blockwise (flash) attention — the framework's first Pallas TPU kernel.

Reference capability anchor: src/operator/contrib/transformer-inl.h ships
interleaved-matmul self-attention ops that materialise the (S, S) score
matrix in HBM; SURVEY.md §7 step 8 calls for the TPU-native replacement.
This kernel computes softmax(q·kᵀ)·v with the online-softmax recurrence:
scores never leave VMEM, HBM traffic is O(S·D) instead of O(S²), and the
MXU sees (BLOCK_Q × D) @ (D × BLOCK_K) tiles.

Design (canonical TPU flash pattern):
  grid = (batch·heads, S/BLOCK_Q, S/BLOCK_K); the innermost grid axis is
  sequential on TPU, so f32 scratch (acc, running max m, running sum l)
  persists across the K sweep — initialised at k==0, finalised (acc/l)
  at the last k block.  Causal masking compares global q/k indices from
  broadcasted_iota; fully-masked k blocks are skipped with @pl.when.

Grouped queries: ``k`` and ``v`` may carry fewer heads than ``q`` (a
divisor of its head count); query head ``i`` reads key/value head
``i // (h_q / h_kv)``.  The forward kernel does that in the key/value
blocks' index maps, so no head is ever repeated in HBM.

Backward: custom_vjp that recomputes attention in plain XLA one block of
``BWD_BLOCK_Q`` query rows at a time (``lax.scan`` over the blocks, dk
and dv accumulated in the carry): the scores alive at once are
(heads, BWD_BLOCK_Q, S), never (heads, S, S).  Rematerialisation trades
FLOPs for HBM, same recipe as jax.checkpoint; a dedicated Pallas backward
kernel is a later optimisation.

Where the program is lowered for anything but a tpu the same kernel runs
under the Pallas interpreter (_pallas_rows.per_platform), so unit tests
exercise the identical code path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_rows import per_platform
from .registry import register

_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                 acc_ref, m_ref, l_ref, *,
                 block_q, block_k, s_actual, sm_scale, causal):
    """One (q-block, k-block) grid step of online-softmax attention."""
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = pl.program_id(1) * block_q
    k_start = kb * block_k

    # causal: a k block strictly above the diagonal contributes nothing
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (BQ, D)
        k = k_ref[0].astype(jnp.float32)            # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (BQ, BK)

        q_ids = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_ids < s_actual                      # padded keys
        if causal:
            mask &= k_ids <= q_ids
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                        # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # (BQ, BK)
        correction = jnp.exp(m_prev - m_new)         # (BQ, 1)
        l_new = l_ref[:, :1] * correction + jnp.sum(p, axis=1,
                                                    keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        # padded q rows have l == 0; emit 0 there rather than NaN
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = l_ref[:]


def _round_up(x, m):
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale",
                                             "block_q", "block_k"))
def _flash_fwd(q, k, v, *, causal, sm_scale, block_q, block_k):
    import math
    b, h, s, d = q.shape
    group = h // k.shape[1]       # query heads per key/value head
    bq = min(block_q, _round_up(s, 128))
    bk = min(block_k, _round_up(s, 128))
    # pad to a common multiple of BOTH block sizes — a floor-divided grid
    # would silently drop tail key blocks
    s_pad = _round_up(s, math.lcm(bq, bk))
    if s_pad != s:
        pad = [(0, 0), (0, 0), (0, s_pad - s), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    bh = b * h
    qf = q.reshape(bh, s_pad, d)
    kf = k.reshape(bh // group, s_pad, d)
    vf = v.reshape(bh // group, s_pad, d)

    kernel = functools.partial(
        _attn_kernel, block_q=bq, block_k=bk, s_actual=s,
        sm_scale=sm_scale, causal=causal)
    grid = (bh, s_pad // bq, s_pad // bk)
    scratch_shapes = [
        pltpu.VMEM((bq, d), jnp.float32),       # acc
        pltpu.VMEM((bq, 128), jnp.float32),     # running max (lane-bcast)
        pltpu.VMEM((bq, 128), jnp.float32),     # running sum (lane-bcast)
    ]

    q_spec = pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0))
    # flat q index = batch * h + head, so // group is batch * h_kv + kv head
    kv_spec = pl.BlockSpec((1, bk, d),
                           lambda bh_, qi, ki: (bh_ // group, ki, 0))
    stat_spec = pl.BlockSpec((1, bq, 128), lambda bh_, qi, ki: (bh_, qi, 0))

    def call(interpret, qf, kf, vf):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=(q_spec, stat_spec, stat_spec),
            out_shape=(
                jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
                jax.ShapeDtypeStruct((bh, s_pad, 128), jnp.float32),
                jax.ShapeDtypeStruct((bh, s_pad, 128), jnp.float32),
            ),
            scratch_shapes=scratch_shapes,
            interpret=interpret,
            name="mx_flash_attention_fwd",
        )(qf, kf, vf)

    out, m_out, l_out = per_platform(call, qf, kf, vf)
    out = out.reshape(b, h, s_pad, d)[:, :, :s, :]
    m_out = m_out[:, :, 0].reshape(b, h, s_pad)[:, :, :s]
    l_out = l_out[:, :, 0].reshape(b, h, s_pad)[:, :, :s]
    return out, m_out, l_out


def _check_heads(q, k, v):
    if k.shape != v.shape or q.shape[1] % k.shape[1] or \
            (q.shape[0], q.shape[2], q.shape[3]) != \
            (k.shape[0], k.shape[2], k.shape[3]):
        raise ValueError(
            f"flash_attention: q {q.shape} against k {k.shape}, v {v.shape}: "
            "batch, length and head size must agree and the key/value head "
            "count must divide the query head count")


def _reference_attention(q, k, v, causal, sm_scale, q_start=0):
    """Plain XLA attention of the query rows ``q`` (global positions
    ``q_start`` onward) over all keys; used by the recompute backward.
    k, v: (batch, h_kv, S, d) with h_kv dividing q's head count."""
    b, h, rows, d = q.shape
    h_kv, s = k.shape[1], k.shape[2]
    qg = q.astype(jnp.float32).reshape(b, h_kv, h // h_kv, rows, d)
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qg,
                        k.astype(jnp.float32)) * sm_scale
    if causal:
        qi = q_start + jnp.arange(rows)[:, None]
        ki = jnp.arange(s)[None, :]
        logits = jnp.where(ki <= qi, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return out.reshape(b, h, rows, d).astype(q.dtype)


def _static_sm_scale(sm_scale, head_dim):
    """Resolve the softmax scale to a static python float.

    The scale parameterizes the kernel (a jit static argument), so a
    traced value here is a contract violation — rejecting it with a
    TypeError replaces the suppressed ``float(sm_scale)`` host escape
    of the original kernel (a concretization that graftlint's
    trace-host-escape rule rightly flagged)."""
    if sm_scale is None:
        return head_dim ** -0.5
    if not isinstance(sm_scale, (int, float)):
        raise TypeError(
            "flash_attention: sm_scale must be a static python scalar "
            f"(got {type(sm_scale).__name__}); it is baked into the "
            "kernel grid, not traced")
    return sm_scale


def reference_attention(q, k, v, causal=False, sm_scale=None):
    """Public plain-XLA attention with flash_attention's signature —
    the kernel registry's reference implementation."""
    _check_heads(q, k, v)
    return _reference_attention(q, k, v, causal,
                                _static_sm_scale(sm_scale, q.shape[-1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=128,
                    block_k=128):
    """softmax(q kᵀ / √d) v with O(S·D) memory.

    q: (batch, heads, seq, head_dim); k, v the same or with fewer heads
    (grouped queries: a divisor of q's head count).  sm_scale defaults
    to 1/sqrt(head_dim).

    ``sm_scale`` is a STATIC kernel parameter (baked into the pallas
    grid function), so it must be a python scalar, never a traced
    array — the old ``float(sm_scale)`` host conversion would silently
    concretize a tracer inside jit/shard_map bodies.
    """
    sm_scale = _static_sm_scale(sm_scale, q.shape[-1])
    _check_heads(q, k, v)
    out, _, _ = _flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                           block_q=block_q, block_k=block_k)
    return out


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k):
    out = flash_attention(q, k, v, causal, sm_scale, block_q, block_k)
    return out, (q, k, v)


# query rows recomputed at once in the backward: the scores alive are
# (batch, heads, BWD_BLOCK_Q, S) float32 — 268 MB at 32 heads × 4096 keys
BWD_BLOCK_Q = 512


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, res, g):
    q, k, v = res
    sm_scale = _static_sm_scale(sm_scale, q.shape[-1])
    s = q.shape[2]
    rows = min(BWD_BLOCK_Q, s)
    pad = -s % rows
    # rows padded past the sequence carry a zero cotangent: they add
    # nothing to dk and dv, and their dq is cut off again
    padding = [(0, 0), (0, 0), (0, pad), (0, 0)]
    blocks = [jnp.moveaxis(jnp.pad(a, padding).reshape(
        a.shape[0], a.shape[1], -1, rows, a.shape[3]), 2, 0) for a in (q, g)]

    def one_block(acc, inp):
        q_i, g_i, start = inp
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference_attention(
                q_, k_, v_, causal, sm_scale, q_start=start), q_i, k, v)
        dq_i, dk_i, dv_i = vjp(g_i)
        return (acc[0] + dk_i, acc[1] + dv_i), dq_i

    (dk, dv), dq = jax.lax.scan(
        one_block, (jnp.zeros_like(k), jnp.zeros_like(v)),
        (blocks[0], blocks[1], jnp.arange(0, s + pad, rows)))
    dq = jnp.moveaxis(dq, 0, 2).reshape(q.shape[:2] + (s + pad, q.shape[3]))
    return dq[:, :, :s], dk, dv


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@register("_contrib_flash_attention", alias=("flash_attention",))
def _contrib_flash_attention(attrs, q, k, v):
    causal = bool(attrs.get("causal", False))
    sm_scale = attrs.get("sm_scale")
    sm_scale = float(sm_scale) if sm_scale is not None else None
    return flash_attention(q, k, v, causal, sm_scale,
                           int(attrs.get("block_q", 128)),
                           int(attrs.get("block_k", 128)))
