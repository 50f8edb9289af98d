"""Blockwise (flash) attention — the framework's first Pallas TPU kernel.

Reference capability anchor: src/operator/contrib/transformer-inl.h ships
interleaved-matmul self-attention ops that materialise the (S, S) score
matrix in HBM; SURVEY.md §7 step 8 calls for the TPU-native replacement.
This kernel computes softmax(q·kᵀ)·v with the online-softmax recurrence:
scores never leave VMEM, HBM traffic is O(S·D) instead of O(S²), and the
MXU sees (BLOCK_Q × D) @ (D × BLOCK_K) tiles.

Design (canonical TPU flash pattern, over a table of tiles):
  What a query may see is ONE description, a ``Mask``: an element
  predicate over global q/k ids and a tile predicate.  The mask, the
  length and the tiles of a call are static, so the host knows every
  (query tile, key tile) pair's kind before the program is lowered:
  ``tile_table`` lists the COMPUTED pairs in sweep order, each with
  whether it opens and closes its sweep and whether it is FULL (no
  barred pair, no padded key).  The table goes to each ``pallas_call`` as
  its scalar-prefetch operand (SMEM); grid = (batch·heads, table's length),
  the index maps read a step's tiles from it, and the innermost grid
  axis is sequential on TPU, so f32 scratch (acc, running max m, running
  sum l) persists across a query tile's key sweep: initialised on the
  sweep's first entry, finalised (acc/l) on its last.  An empty tile has
  no grid step; a full tile runs no mask arithmetic; a partial one masks
  by the element predicate (compared from broadcasted_iota), which the
  XLA reference shares.  ``causal`` is one kind of mask (docs/kernels.md
  has the others and how to add one).

Grouped queries: ``k`` and ``v`` may carry fewer heads than ``q`` (a
divisor of its head count); query head ``i`` reads key/value head
``i // (h_q / h_kv)``.  The forward kernel does that in the key/value
blocks' index maps, so no head is ever repeated in HBM.

A value head may be narrower or wider than a query/key head (multi-head
latent attention: keys of 192 channels over values of 128): q, k, dQ and
dK take the query/key size, v, the output, its accumulator, dO and dV
the value's, and nothing is padded to the larger.

Backward: custom_vjp whose forward rule keeps ``(q, k, v, out, lse)``
with ``lse = m + log(l)``, one float32 a query row, and whose backward is
two more Pallas kernels over the same tiles.  Both recompute a tile's
scores as the forward computes them, so ``P = exp(S - lse)`` is the
forward's softmax, and form ``dS = P * (dO vᵀ - delta)`` with
``delta = rowsum(dO * out)``; scores stay in VMEM and the tables hold
the forward's tiles.
  ``mx_flash_attention_bwd_dq``: the forward's grid and table,
  ``dQ += dS k`` in float32 scratch across the key sweep.
  ``mx_flash_attention_bwd_dkv``: grid (batch·kv heads, table's length),
  the table ordered by key tile, then query head of the group, then
  query tile; the tile is laid out (key, query) so that ``dV += Pᵀ dO``
  and ``dK += dSᵀ q`` need no transpose, and a key/value head's
  gradients accumulate over its whole group of query heads in scratch.
``out`` and ``lse`` carry the names ``FLASH_RESIDUALS``, which a
rematerialisation boundary's policy keeps: its backward recomputes q, k
and v but not the forward kernel.

Where the program is lowered for anything but a tpu the same kernel runs
under the Pallas interpreter (_pallas_rows.per_platform), so unit tests
exercise the identical code path.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import (record_flash_attention_bwd_lowered,
                         record_flash_attention_grid_steps,
                         record_flash_attention_tiles)
from ._pallas_rows import per_platform
from .registry import register

_NEG_INF = -1e30

# the names of the forward rule's ``out`` and ``lse``: a rematerialisation
# boundary keeps these two (``gluon.block.remat_scope``,
# ``parallel.spmd.remat_wrap``), so its backward reads them and does not run
# the forward kernel again
FLASH_RESIDUALS = ("mx_flash_out", "mx_flash_lse")
_TRACED = threading.local()


def traced_calls():
    """``flash_attention`` calls this thread has staged into a jaxpr so far.
    A rematerialisation boundary stages its layer, so the difference across
    one is the calls inside it; a call differentiated outside every boundary
    runs its forward rule alone and is not counted."""
    return getattr(_TRACED, "calls", 0)


@dataclasses.dataclass(frozen=True)
class Mask:
    """Which keys a query may see, by global position ids (a query's and a
    key's index on the sequence axis).  The one description the kernels,
    their tile tables and ``_reference_attention`` share; no (S, S)
    array is ever built from it.

    ``none``: every key.  ``causal``: ``k <= q``.  ``block_causal``: with
    ``b(i) = i // block``, ``b(k) <= b(q)`` (a block sees itself whole and
    what lies before it).  ``block_diffusion``: the sequence is two copies
    of ``half`` positions, ids below ``half`` the clean copy and the rest
    the noisy one, ``b(i) = (i mod half) // block``; a clean query sees the
    clean keys with ``b(k) <= b(q)``, a noisy query the noisy keys of its
    own block and the clean keys with ``b(k) < b(q)``, and no clean query
    sees a noisy key (block-diffusion training: Arriola et al.
    arXiv:2503.09573).

    ``allowed`` is written over ``//``, comparisons and ``&``/``|``
    alone, so it takes the iota arrays of a partial tile inside a kernel
    and numpy arrays alike; ``tile`` runs on the host only, over numpy
    values, as a call's tile table is built."""

    kind: str = "none"
    block: int = 1
    half: int = 0

    KINDS = ("none", "causal", "block_causal", "block_diffusion")

    def __post_init__(self):
        if self.kind not in self.KINDS or self.block < 1 or (
                self.kind in ("none", "causal") and self.block != 1) or (
                self.kind == "block_diffusion"
                and (self.half < 1 or self.half % self.block)):
            raise ValueError(f"flash_attention: no such mask: {self}")

    @classmethod
    def of(cls, spec):
        """A ``Mask`` from what a caller may pass as ``causal``: a Mask, or
        a bool (``causal`` or ``none``)."""
        if isinstance(spec, cls):
            return spec
        return cls("causal" if spec else "none")

    def _blocks(self, ids):
        return ids if self.block == 1 else ids // self.block

    def allowed(self, q_ids, k_ids):
        """Elementwise: may query ``q_ids`` see key ``k_ids``; None for
        the mask that allows everything."""
        if self.kind == "none":
            return None
        if self.kind != "block_diffusion":
            return self._blocks(k_ids) <= self._blocks(q_ids)
        q_noisy, k_noisy = q_ids >= self.half, k_ids >= self.half
        qb = self._blocks(q_ids - q_noisy * self.half)
        kb = self._blocks(k_ids - k_noisy * self.half)
        return ((~k_noisy & (kb <= qb) & ~(q_noisy & (kb == qb)))
                | (q_noisy & k_noisy & (kb == qb)))

    def tile(self, q0, q1, k0, k1):
        """``(some, every)``: whether any, and whether every, pair of the
        queries ``q0..q1`` and keys ``k0..k1`` (inclusive ids, numpy
        values) is allowed.  A tile with ``some`` false is empty and has
        no grid step."""
        if self.kind == "none":
            return True, True
        if self.kind != "block_diffusion":
            return (self._blocks(k0) <= self._blocks(q1),
                    self._blocks(k1) <= self._blocks(q0))
        t, blocks = self.half, self._blocks
        # the clean and the noisy part of each range, as positions; a
        # part that is not there has lo > hi and is guarded by has_*
        has_qc, has_qn, has_kc, has_kn = q0 < t, q1 >= t, k0 < t, k1 >= t
        qc1, kc1 = np.minimum(q1, t - 1), np.minimum(k1, t - 1)
        qn0, kn0 = np.maximum(q0, t) - t, np.maximum(k0, t) - t
        qn1, kn1 = q1 - t, k1 - t
        cc, nc, nn = has_qc & has_kc, has_qn & has_kc, has_qn & has_kn
        some = ((cc & (blocks(k0) <= blocks(qc1)))
                | (nc & (blocks(k0) < blocks(qn1)))
                | (nn & (blocks(kn0) <= blocks(qn1))
                   & (blocks(qn0) <= blocks(kn1))))
        no = np.logical_not
        every = (no(has_qc & has_kn)
                 & (no(cc) | (blocks(kc1) <= blocks(q0)))
                 & (no(nc) | (blocks(kc1) < blocks(qn0)))
                 & (no(nn) | ((blocks(kn0) == blocks(qn1))
                              & (blocks(qn0) == blocks(kn1)))))
        return some, every

    def tile_kinds(self, s, bq, bk):
        """``(some, every)`` of every (query tile, key tile) pair of one
        head at ``s`` positions: two (query tiles, key tiles) arrays."""
        s_pad = _round_up(s, math.lcm(bq, bk))
        q0 = np.arange(0, s_pad, bq)[:, None]
        k0 = np.arange(0, s_pad, bk)[None, :]
        some, every = (np.broadcast_to(a, (q0.size, k0.size)) for a in
                       self.tile(q0, q0 + bq - 1, k0, k0 + bk - 1))
        # a tile that holds padded keys is masked there whatever the kind
        return some, every & (k0 + bk <= s)

    def tile_counts(self, s, bq, bk):
        """``{"empty", "partial", "full"}``: the (query tile, key tile)
        pairs of one head at ``s`` positions, on the host."""
        some, every = self.tile_kinds(s, bq, bk)
        return {"empty": int((~some).sum()), "full": int(every.sum()),
                "partial": int((some & ~every).sum())}


# A table entry is one int32 word: three flags (the tile opens its sweep:
# zero the accumulators; closes it: write the output; holds no barred pair
# and no padded key), then the query tile, the key tile and the query head
# of the group.  One word a step keeps a call's tables small in SMEM
# (1 MiB on a v5e: 262,144 steps)
_FIRST, _LAST, _FULL = 1, 2, 4
_TILE_BITS, _HEAD_BITS = 10, 8


def _unpack(word):
    """``(query tile, key tile, head of the group, flags)`` of table
    entries: a numpy array on the host, a scalar read from SMEM in a
    kernel or an index map."""
    tile = (1 << _TILE_BITS) - 1
    return ((word >> 3) & tile, (word >> (3 + _TILE_BITS)) & tile,
            word >> (3 + 2 * _TILE_BITS), word & 7)


@functools.lru_cache(maxsize=128)
def tile_table(mask, s, bq, bk, by_key=False, group=1):
    """The computed tiles of a call, in the order its grid walks them:
    one packed int32 a step (``_unpack``).  ``by_key`` false (forward,
    ``bwd_dq``): a head's tiles by query tile, then key tile ascending: a
    sweep is a query tile's keys.  ``by_key`` true (``bwd_dkv``): a
    key/value head's tiles by key tile, then the ``group`` query heads
    that read it, then query tile ascending: a sweep is a key tile's
    queries over its whole group.  Built from ``Mask.tile`` alone, on the
    host."""
    some, every = mask.tile_kinds(s, bq, bk)
    if max(some.shape) > 1 << _TILE_BITS or group > 1 << _HEAD_BITS:
        raise ValueError(
            f"flash_attention: {some.shape} tiles of ({bq}, {bk}) over a "
            f"group of {group} heads are more than a table entry can name "
            f"({1 << _TILE_BITS} a side, {1 << _HEAD_BITS} heads): take "
            "larger blocks")
    if by_key:
        some, every = some.T, every.T
    swept = np.broadcast_to(some[:, None, :],
                            (some.shape[0], group, some.shape[1]))
    outer, head, inner = np.nonzero(swept)      # row-major: the sweep order
    turns = outer[1:] != outer[:-1]
    flags = (_FIRST * np.r_[True, turns] + _LAST * np.r_[turns, True]
             + _FULL * every[outer, inner])
    q_tile, k_tile = (inner, outer) if by_key else (outer, inner)
    table = (flags | q_tile << 3 | k_tile << (3 + _TILE_BITS)
             | head << (3 + 2 * _TILE_BITS)).astype(np.int32)
    table.setflags(write=False)
    return table


def _visible(mask, q_ids, k_ids, s_actual):
    """The element mask of a tile: padded keys, and what ``mask`` bars."""
    seen = k_ids < s_actual
    allowed = mask.allowed(q_ids, k_ids)
    return seen if allowed is None else seen & allowed


def _step(table_ref, block_q, block_k):
    """This grid step's entry of the table: the first position of its
    query and key tile, and whether it is the sweep's first, its last, and
    a full tile."""
    q_tile, k_tile, _, flags = _unpack(table_ref[pl.program_id(1)])
    return (q_tile * block_q, k_tile * block_k, (flags & _FIRST) != 0,
            (flags & _LAST) != 0, (flags & _FULL) != 0)


def _attn_kernel(table_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref,
                 l_out_ref, acc_ref, m_ref, l_ref, *, block_q, block_k,
                 s_actual, sm_scale, mask):
    """One computed (q-block, k-block) tile of online-softmax attention."""
    q_start, k_start, first, last, full = _step(table_ref, block_q, block_k)

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # A row whose keys so far were all barred has m = -1e30 and sums
    # garbage; its first visible key brings a finite m and the correction
    # exp(-1e30 - m) = 0 wipes that, and every row sees its own key at
    # the latest
    def compute(masked):
        q = q_ref[0].astype(jnp.float32)            # (BQ, D)
        k = k_ref[0].astype(jnp.float32)            # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (BQ, BK)
        if masked:
            q_ids = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(_visible(mask, q_ids, k_ids, s_actual), s,
                          _NEG_INF)

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

    pl.when(full)(functools.partial(compute, False))
    pl.when(~full)(functools.partial(compute, True))

    @pl.when(last)
    def _finalize():
        # padded q rows have l == 0; emit 0 there rather than NaN
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[:]
        l_out_ref[0] = l_ref[:]


def _round_up(x, m):
    return (x + m - 1) // m * m


def _tiles(s, block_q, block_k):
    """``(bq, bk, s_pad)``: the tiles of a call at ``s`` positions and the
    length its operands are padded to."""
    bq = min(block_q, _round_up(s, 128))
    bk = min(block_k, _round_up(s, 128))
    # pad to a common multiple of BOTH block sizes — a floor-divided grid
    # would silently drop tail key blocks
    return bq, bk, _round_up(s, math.lcm(bq, bk))


def _pad_seq(s_pad, *arrays):
    """Zero rows up to ``s_pad`` on the sequence axis of (b, h, s, …)."""
    pad = s_pad - arrays[0].shape[2]
    if not pad:
        return arrays
    return tuple(jnp.pad(a, [(0, 0), (0, 0), (0, pad)]
                         + [(0, 0)] * (a.ndim - 3)) for a in arrays)


def _table_call(kernel, table, heads, in_specs, out_specs, out_shape,
                scratch_shapes, name, operands):
    """One ``pallas_call`` on the grid ``(heads, the table's length)``:
    ``table`` is its scalar-prefetch operand (SMEM), which an index map
    gets after the two grid indices and the kernel before its blocks."""
    def call(interpret, *operands):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(heads, table.size),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch_shapes),
            out_shape=out_shape,
            interpret=interpret,
            name=name,
        )(jnp.asarray(table), *operands)

    return per_platform(call, *operands)


def _tile_of(axis):
    """An index map's reader: the query (0), key (1) tile or the head of
    the group (2) of grid step ``t``."""
    return lambda t, table: _unpack(table[t])[axis]


_q_tile, _k_tile, _head = _tile_of(0), _tile_of(1), _tile_of(2)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale",
                                             "block_q", "block_k"))
def _flash_fwd(q, k, v, *, causal, sm_scale, block_q, block_k):
    """``causal``, here and below: a bool or a ``Mask``."""
    mask = Mask.of(causal)
    b, h, s, d = q.shape
    d_v = v.shape[-1]
    group = h // k.shape[1]       # query heads per key/value head
    bq, bk, s_pad = _tiles(s, block_q, block_k)
    q, k, v = _pad_seq(s_pad, q, k, v)
    bh = b * h
    qf = q.reshape(bh, s_pad, d)
    kf = k.reshape(bh // group, s_pad, d)
    vf = v.reshape(bh // group, s_pad, d_v)

    q_spec = pl.BlockSpec((1, bq, d), lambda i, *t: (i, _q_tile(*t), 0))
    o_spec = pl.BlockSpec((1, bq, d_v), lambda i, *t: (i, _q_tile(*t), 0))
    # flat q index = batch * h + head, so // group is batch * h_kv + kv head
    k_spec, v_spec = (pl.BlockSpec((1, bk, n),
                                   lambda i, *t: (i // group, _k_tile(*t), 0))
                      for n in (d, d_v))
    stat_spec = pl.BlockSpec((1, bq, 128), lambda i, *t: (i, _q_tile(*t), 0))
    out, m_out, l_out = _table_call(
        functools.partial(_attn_kernel, block_q=bq, block_k=bk, s_actual=s,
                          sm_scale=sm_scale, mask=mask),
        tile_table(mask, s, bq, bk), bh,
        [q_spec, k_spec, v_spec], (o_spec, stat_spec, stat_spec),
        (jax.ShapeDtypeStruct((bh, s_pad, d_v), q.dtype),
         jax.ShapeDtypeStruct((bh, s_pad, 128), jnp.float32),
         jax.ShapeDtypeStruct((bh, s_pad, 128), jnp.float32)),
        [pltpu.VMEM((bq, d_v), jnp.float32),     # acc
         pltpu.VMEM((bq, 128), jnp.float32),     # running max (lane-bcast)
         pltpu.VMEM((bq, 128), jnp.float32)],    # running sum (lane-bcast)
        "mx_flash_attention_fwd", (qf, kf, vf))
    out = out.reshape(b, h, s_pad, d_v)[:, :, :s, :]
    m_out = m_out[:, :, 0].reshape(b, h, s_pad)[:, :, :s]
    l_out = l_out[:, :, 0].reshape(b, h, s_pad)[:, :, :s]
    return out, m_out, l_out


def _check_heads(q, k, v):
    if k.shape[:3] != v.shape[:3] or q.shape[1] % k.shape[1] or \
            (q.shape[0], q.shape[2], q.shape[3]) != \
            (k.shape[0], k.shape[2], k.shape[3]):
        raise ValueError(
            f"flash_attention: q {q.shape} against k {k.shape}, v {v.shape}: "
            "batch and length must agree, q and k their head size, k and v "
            "their head count, and the key/value head count must divide the "
            "query head count")


def _reference_attention(q, k, v, causal, sm_scale):
    """Plain XLA attention: the (S, S) scores in HBM.  k: (batch, h_kv, S,
    d) with h_kv dividing q's head count; v: (batch, h_kv, S, d_v)."""
    mask = Mask.of(causal)
    b, h, s, d = q.shape
    h_kv, d_v = k.shape[1], v.shape[-1]
    qg = q.astype(jnp.float32).reshape(b, h_kv, h // h_kv, s, d)
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qg,
                        k.astype(jnp.float32)) * sm_scale
    allowed = mask.allowed(jnp.arange(s)[:, None], jnp.arange(s)[None, :])
    if allowed is not None:
        logits = jnp.where(allowed, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return out.reshape(b, h, s, d_v).astype(q.dtype)


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

    q: (batch, heads, seq, head_dim); k the same or with fewer heads
    (grouped queries: a divisor of q's head count); v k's heads with a
    head size of its own, ``d_v``, which the output takes: (batch, heads,
    seq, d_v).  sm_scale defaults to 1/sqrt(head_dim).  ``causal`` is a
    bool or a ``Mask`` (static: the kernels are built for it).

    ``sm_scale`` is a STATIC kernel parameter (baked into the pallas
    grid function), so it must be a python scalar, never a traced
    array — the old ``float(sm_scale)`` host conversion would silently
    concretize a tracer inside jit/shard_map bodies.
    """
    _TRACED.calls = traced_calls() + 1
    return _checked_fwd(q, k, v, causal, sm_scale, block_q, block_k)[0]


def _record_grid_steps(kernel, mask, s, block_q, block_k, by_key=False,
                       group=1):
    """Set the gauge of the grid steps a query head ``kernel`` is built
    with: the length of the table its ``pallas_call`` gets."""
    bq, bk, _ = _tiles(s, block_q, block_k)
    record_flash_attention_grid_steps(
        mask.kind, kernel,
        tile_table(mask, s, bq, bk, by_key, group).size // group)


def _checked_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    _check_heads(q, k, v)
    mask = Mask.of(causal)
    # facts about the program, taken as it is traced
    record_flash_attention_tiles(mask.kind, mask.tile_counts(
        q.shape[2], *_tiles(q.shape[2], block_q, block_k)[:2]))
    _record_grid_steps("fwd", mask, q.shape[2], block_q, block_k)
    return _flash_fwd(q, k, v, causal=mask,
                      sm_scale=_static_sm_scale(sm_scale, q.shape[-1]),
                      block_q=block_q, block_k=block_k)


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k):
    out, m, l = _checked_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    # named for a boundary's policy (FLASH_RESIDUALS), the identity outside
    # one; the primal output is the saved ``out`` itself, so the output
    # projection's pullback reads it too.  Every row sees a key (its own,
    # under every kind of mask), so l > 0
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    lse = checkpoint_name(m + jnp.log(l), FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


_NT = (((1,), (1,)), ((), ()))    # a bᵀ: contract both last dimensions
_NN = (((1,), (0,)), ((), ()))    # a b


def _tile_p_ds(q, k, v, do, lse, delta, q_start, k_start, *, q_axis,
               masked, s_actual, sm_scale, mask):
    """``P`` and ``P * (dP - delta)`` of one tile, laid out (query, key)
    for ``q_axis`` 0 and (key, query) for 1; ``lse`` and ``delta`` hold
    one value a query and broadcast along the key axis.  The scores are
    the forward kernel's: float32 operands, the scale after the product,
    the same mask on a tile that is not full (``masked``)."""
    def over_heads(of_q, of_k):
        """(query, key) or (key, query) products over the head dimension."""
        return jax.lax.dot_general(
            *((of_q, of_k) if q_axis == 0 else (of_k, of_q)), _NT,
            preferred_element_type=jnp.float32)

    s = over_heads(q.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    if masked:
        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   q_axis)
        k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   1 - q_axis)
        s = jnp.where(_visible(mask, q_ids, k_ids, s_actual), s, _NEG_INF)
    p = jnp.exp(s - lse)
    # Mosaic's product of float32 operands is one bfloat16 pass, and what
    # dO loses to it is one error for a query's whole row of dP, which the
    # sums over the keys do not average out: a float32 dO goes in as two
    # bfloat16 parts.  On the chip dQ and dK then read 3.7e-3 from the
    # exact gradient; with one pass 4.1e-3, farther than the XLA backward
    # this replaced (4.0e-3, its delta taken from the same dP): PERF.md §6
    parts = (do,)
    if do.dtype == jnp.float32:
        high = do.astype(jnp.bfloat16).astype(jnp.float32)
        parts = (high, do - high)
    dp = sum(over_heads(part, v) for part in parts)
    return p, p * (dp - delta)


def _bwd_dq_kernel(table_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, acc_ref, *, block_q, block_k, s_actual,
                   sm_scale, mask):
    """One computed (q-block, k-block) tile of ``dQ = scale · Σ_k dS k``."""
    q_start, k_start, first, last, full = _step(table_ref, block_q, block_k)

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def compute(masked):
        k = k_ref[0]
        _, ds = _tile_p_ds(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0][:, :1],
            delta_ref[0][:, :1], q_start, k_start, q_axis=0, masked=masked,
            s_actual=s_actual, sm_scale=sm_scale, mask=mask)
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    pl.when(full)(functools.partial(compute, False))
    pl.when(~full)(functools.partial(compute, True))

    @pl.when(last)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(table_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, block_q,
                    block_k, s_actual, sm_scale, mask):
    """One computed (k-block, query head of the group, q-block) tile of
    ``dV = Σ Pᵀ dO`` and ``dK = scale · Σ dSᵀ q``, the tile transposed."""
    q_start, k_start, first, last, full = _step(table_ref, block_q, block_k)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute(masked):
        q, do = q_ref[0], do_ref[0]
        p, ds = _tile_p_ds(
            q, k_ref[0], v_ref[0], do, lse_ref[0, 0], delta_ref[0, 0],
            q_start, k_start, q_axis=1, masked=masked, s_actual=s_actual,
            sm_scale=sm_scale, mask=mask)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    pl.when(full)(functools.partial(compute, False))
    pl.when(~full)(functools.partial(compute, True))

    @pl.when(last)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale",
                                             "block_q", "block_k"))
def _flash_bwd(q, k, v, out, lse, do, *, causal, sm_scale, block_q, block_k):
    """``(dq, dk, dv)`` with the sequence still padded to the tiles: rows
    past ``s`` are zero and the caller cuts them off."""
    mask = Mask.of(causal)
    b, h, s, d = q.shape
    h_kv, d_v = k.shape[1], v.shape[-1]
    group = h // h_kv
    bq, bk, s_pad = _tiles(s, block_q, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    # padded query rows: zero cotangent and delta, lse 0, so P stays finite
    # and dS is zero; padded keys are masked, so P is zero there
    q, k, v, do, lse, delta = _pad_seq(s_pad, q, k, v, do, lse, delta)
    bh = b * h
    qf, dof = q.reshape(bh, s_pad, d), do.reshape(bh, s_pad, d_v)
    kf, vf = k.reshape(b * h_kv, s_pad, d), v.reshape(b * h_kv, s_pad, d_v)
    params = dict(block_q=bq, block_k=bk, s_actual=s, sm_scale=sm_scale,
                  mask=mask)

    # dq: the forward's table; statistics one value a row, broadcast over a
    # lane tile as the forward writes its own
    q_spec, do_spec = (pl.BlockSpec((1, bq, n),
                                    lambda i, *t: (i, _q_tile(*t), 0))
                       for n in (d, d_v))
    k_spec, v_spec = (pl.BlockSpec((1, bk, n),
                                   lambda i, *t: (i // group, _k_tile(*t), 0))
                      for n in (d, d_v))
    col_spec = pl.BlockSpec((1, bq, 128), lambda i, *t: (i, _q_tile(*t), 0))
    cols = [jnp.broadcast_to(a.reshape(bh, s_pad, 1), (bh, s_pad, 128))
            for a in (lse, delta)]
    dq = _table_call(
        functools.partial(_bwd_dq_kernel, **params),
        tile_table(mask, s, bq, bk), bh,
        [q_spec, k_spec, v_spec, do_spec, col_spec, col_spec], q_spec,
        jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        [pltpu.VMEM((bq, d), jnp.float32)],
        "mx_flash_attention_bwd_dq", (qf, kf, vf, dof, *cols))

    # dk, dv: a key tile's sweep runs over its whole group of query heads
    # (flat query head = (batch · h_kv + kv head) · group + head of the
    # group), so its block and both accumulators stay where they are
    q_spec, do_spec = (pl.BlockSpec(
        (1, bq, n), lambda i, *t: (i * group + _head(*t), _q_tile(*t), 0))
        for n in (d, d_v))
    k_spec, v_spec = (pl.BlockSpec((1, bk, n),
                                   lambda i, *t: (i, _k_tile(*t), 0))
                      for n in (d, d_v))
    # one row of bq statistics a block: the block's last two dimensions are
    # the array's, whatever bq is
    row_spec = pl.BlockSpec(
        (1, 1, 1, bq),
        lambda i, *t: (i * group + _head(*t), _q_tile(*t), 0, 0))
    rows = [a.reshape(bh, s_pad // bq, 1, bq) for a in (lse, delta)]
    dk, dv = _table_call(
        functools.partial(_bwd_dkv_kernel, **params),
        tile_table(mask, s, bq, bk, True, group), b * h_kv,
        [q_spec, k_spec, v_spec, do_spec, row_spec, row_spec],
        (k_spec, v_spec),
        (jax.ShapeDtypeStruct(kf.shape, k.dtype),
         jax.ShapeDtypeStruct(vf.shape, v.dtype)),
        [pltpu.VMEM((bk, d), jnp.float32),
         pltpu.VMEM((bk, d_v), jnp.float32)],
        "mx_flash_attention_bwd_dkv", (qf, kf, vf, dof, *rows))
    return (dq.reshape(b, h, s_pad, d), dk.reshape(b, h_kv, s_pad, d),
            dv.reshape(b, h_kv, s_pad, d_v))


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    record_flash_attention_bwd_lowered("pallas")
    mask, s = Mask.of(causal), q.shape[2]
    _record_grid_steps("bwd_dq", mask, s, block_q, block_k)
    _record_grid_steps("bwd_dkv", mask, s, block_q, block_k, True,
                       q.shape[1] // k.shape[1])
    grads = _flash_bwd(
        q, k, v, out, lse, g, causal=causal,
        sm_scale=_static_sm_scale(sm_scale, q.shape[-1]),
        block_q=block_q, block_k=block_k)
    return tuple(a[:, :, :q.shape[2]] for a in grads)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@register("_contrib_flash_attention", alias=("flash_attention",))
def _contrib_flash_attention(attrs, q, k, v):
    # ``causal=True`` is the alias of ``mask="causal"``
    mask = Mask(str(attrs["mask"]), int(attrs.get("mask_block", 1)),
                int(attrs.get("mask_half", 0))) if "mask" in attrs \
        else Mask.of(bool(attrs.get("causal", False)))
    sm_scale = attrs.get("sm_scale")
    sm_scale = float(sm_scale) if sm_scale is not None else None
    return flash_attention(q, k, v, mask, sm_scale,
                           int(attrs.get("block_q", 128)),
                           int(attrs.get("block_k", 128)))
