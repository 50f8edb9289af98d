"""Ring attention — sequence/context parallelism over the mesh 'sp' axis.

The reference's only long-sequence tooling is bucketing + truncated BPTT
(SURVEY.md §5 long-context: "not present — design fresh").  This is the
fresh design: the sequence axis is sharded over 'sp'; each device holds a
contiguous (S/sp)-block of q, k, v.  K/V blocks rotate around the ring
with ``lax.ppermute`` while each device folds the visiting block into an
online-softmax partial (o, m, l) — attention over unbounded context with
per-device memory O(S/sp · D), communication overlapped with compute by
XLA's async collective scheduling.

The per-step local attention is the Pallas flash kernel (forward) with a
custom_vjp that recomputes the block in plain XLA, so the whole ring —
scan + ppermute + merges — is differentiable end to end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from ..ops.pallas_attention import _flash_fwd, _NEG_INF
from .mesh import DeviceMesh

__all__ = ["ring_attention_local", "ring_self_attention"]


def _ref_attn_stats(q, k, v, causal, sm_scale):
    """Differentiable XLA local attention returning (o, m, l) — the
    backward rule for the Pallas forward, and the source of m/l
    cotangents for the ring merge."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    if causal:
        s = q.shape[2]
        qi = jnp.arange(s)[:, None]
        ki = jnp.arange(s)[None, :]
        logits = jnp.where(ki <= qi, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    o = (o / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(q.dtype)
    return o, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _local_attn_stats(q, k, v, causal, sm_scale):
    return _flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                      block_q=128, block_k=128)


def _local_attn_stats_fwd(q, k, v, causal, sm_scale):
    return _local_attn_stats(q, k, v, causal, sm_scale), (q, k, v)


def _local_attn_stats_bwd(causal, sm_scale, res, cts):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref_attn_stats(q_, k_, v_, causal, sm_scale),
        q, k, v)
    return vjp(cts)


_local_attn_stats.defvjp(_local_attn_stats_fwd, _local_attn_stats_bwd)


def _merge(o1, m1, l1, o2, m2, l2):
    """Combine two normalized online-softmax partials."""
    m = jnp.maximum(m1, m2)

    def coeff(mi, li):
        safe = jnp.where(li > 0.0, mi - m, 0.0)
        return jnp.where(li > 0.0, jnp.exp(safe) * li, 0.0)

    c1, c2 = coeff(m1, l1), coeff(m2, l2)
    l = c1 + c2
    denom = jnp.where(l == 0.0, 1.0, l)[..., None]
    o = (o1.astype(jnp.float32) * c1[..., None]
         + o2.astype(jnp.float32) * c2[..., None]) / denom
    return o.astype(o1.dtype), m, l


def ring_attention_local(q, k, v, sp, axis="sp", causal=False,
                         sm_scale=None):
    """Ring attention body — call INSIDE shard_map with q/k/v holding the
    local contiguous sequence block (B, H, S/sp, D).

    sp must be the static size of ``axis``.  Per ring step the resident
    k/v block is folded into the partial and then forwarded to the right
    neighbour (lax.ppermute).  Causal masking is by global block index:
    visiting block after mine -> skipped, before mine -> full, mine ->
    triangular (the Pallas kernel's causal mode).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    # graftlint: disable=trace-host-escape -- sm_scale is a static python-float hyperparameter by contract, trace-time Python
    sm_scale = float(sm_scale)
    idx = lax.axis_index(axis)
    perm = [(j, (j + 1) % sp) for j in range(sp)]
    b, h, sl, d = q.shape

    def diag(q_, k_, v_):
        return _local_attn_stats(q_, k_, v_, True, sm_scale)

    def full(q_, k_, v_):
        return _local_attn_stats(q_, k_, v_, False, sm_scale)

    def skip(q_, k_, v_):
        return (jnp.zeros_like(q_),
                jnp.full((b, h, sl), _NEG_INF, jnp.float32),
                jnp.zeros((b, h, sl), jnp.float32))

    def fold(carry, k_cur, v_cur, i):
        o_acc, m_acc, l_acc = carry
        src = (idx - i) % sp          # global block index k_cur came from
        if causal:
            case = jnp.where(src == idx, 0, jnp.where(src < idx, 1, 2))
            o_i, m_i, l_i = lax.switch(case, (diag, full, skip),
                                       q, k_cur, v_cur)
        else:
            o_i, m_i, l_i = full(q, k_cur, v_cur)
        return _merge(o_acc, m_acc, l_acc, o_i, m_i, l_i)

    # fold the resident block, then sp-1 rotate->fold steps (no wasted
    # final ppermute)
    carry0 = fold((jnp.zeros_like(q),
                   jnp.full((b, h, sl), _NEG_INF, jnp.float32),
                   jnp.zeros((b, h, sl), jnp.float32)), k, v, 0)

    def step(carry, i):
        acc, k_cur, v_cur = carry
        k_cur = lax.ppermute(k_cur, axis, perm)
        v_cur = lax.ppermute(v_cur, axis, perm)
        acc = fold(acc, k_cur, v_cur, i)
        return (acc, k_cur, v_cur), None

    ((o, _, _), _, _), _ = lax.scan(step, (carry0, k, v),
                                    jnp.arange(1, sp))
    return o


def ring_self_attention(mesh, q, k, v, causal=False, axis="sp",
                        sm_scale=None):
    """Sequence-parallel attention: q/k/v (B, H, S, D) sharded over the
    sequence axis; returns output with the same sharding."""
    if not isinstance(mesh, DeviceMesh):
        raise MXNetError("mesh must be a parallel.DeviceMesh")
    sp = mesh.size(axis)
    if q.shape[2] % sp:
        raise MXNetError(f"sequence {q.shape[2]} not divisible by "
                         f"sp={sp}")
    spec = P(None, None, axis, None)

    @functools.partial(shard_map, mesh=mesh.jax_mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    def run(q_, k_, v_):
        return ring_attention_local(q_, k_, v_, sp, axis=axis,
                                    causal=causal, sm_scale=sm_scale)

    return run(q, k, v)
