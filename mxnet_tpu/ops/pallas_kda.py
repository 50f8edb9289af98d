"""The chunked gated delta rule (``_contrib_kda_scan``) as two Pallas
kernels that walk a head's chunks in order on a grid axis.

The mathematics is ``_op_linear_attention.kda_scan``'s, one chunk of ``Q``
steps at a time (that module's head has the derivation and the rule that no
positive number is exponentiated).  Per chunk, with ``G`` the cumulative sum
of ``g`` inside it and ``S`` the d_k × d_v state entering it:

    A_kk[t, s] = Σ_c k_tc k_sc e^{G_tc − G_sc}   (s < t),  A_qk the same
                 with q_t (s ≤ t), from masked differences of G in
                 sub-blocks of ``sub`` steps
    [W, U] = (I + Diag(β) A_kk)⁻¹ [β ⊙ k ⊙ e^G, β ⊙ v]
    u = U − W S
    o = (q ⊙ e^G) S + A_qk u
    S ← Diag(e^{G_Q}) S + (k ⊙ e^{G_Q − G})ᵀ u

**Forward** (``mx_kda_fwd``): grid ``(batch · heads / hg, chunks)``; the
first axis is parallel, the chunk axis is walked in order.  A step takes
``hg`` heads, a sublane tile of 8 (all of them when the head count is no
multiple of 8), as blocks ``(Q, hg, d)`` of q, k, g, v and o read and written
in place in the ``(batch, T, heads, d)`` layout the op receives: one head's
``(Q, d)`` alone is no legal block of that layout, and any other layout is
a relayout copy in HBM of every operand and cotangent.  The step walks its
heads in a rolled loop, one head's chunk a trip.  β comes as rows of a chunk,
``(batch · heads / hg, chunks, hg, Q)``.  Each head's ``S`` is float32 VMEM
scratch, ``(hg, d_k, d_v)``, zeroed at chunk 0; the kernel writes o and the
state ENTERING each chunk, ``(batch · heads, chunks, d_k, d_v)`` float32: the
backward's residual.

**Backward** (``mx_kda_bwd``): the same grid with the chunk axis walked in
reverse (index map ``c → chunks − 1 − c``).  ``dS``, the gradient of the
state leaving the chunk, is float32 VMEM scratch zeroed at the last chunk.
A step recomputes its chunk's intra-chunk products from the inputs and the
stored state, and writes dq, dk, dv, dg and dβ of that chunk.

Both bodies are one chunk of one head long: the only Python loop is over
the ``log2(Q)`` levels of the triangular inverse, so lowering a call costs
the same at any length, head count or batch.  Products run as
``_op_linear_attention.kda_scan``'s do at the TPU's default: bfloat16
passes with float32 sums, except the cumulative sums and the triangular
inverse with the products through it, which take float32
(``Precision.HIGHEST``, as XLA's triangular solve does).

**Kept across a rematerialisation boundary**: the forward's ``o``,
``(batch, T', heads, d_v)`` float32, and its entering states carry the
names ``KDA_RESIDUALS``, which a boundary's policy keeps
(``gluon.block.remat_scope``, ``parallel.spmd.remat_wrap``), so its
backward recomputes q, k, v, g and β through the layer's projections,
convolutions and gates but does not run ``mx_kda_fwd`` again.  They cost
``4 · batch · T' · heads · d_v · (1 + d_k / Q)`` bytes a layer: 0.40 GB at
8192 steps, 32 heads of 128 and chunks of 64 (0.13 GB of ``o``, 0.27 GB of
states).

Where the program is lowered for anything but a tpu the same kernels run
under the Pallas interpreter (``_pallas_rows.per_platform``).
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_rows import per_platform

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))

# the names of the forward rule's ``o`` and entering states: a
# rematerialisation boundary keeps these two, so its backward reads them and
# does not run the forward kernel again
KDA_RESIDUALS = ("mx_kda_out", "mx_kda_entering")
_TRACED = threading.local()


def traced_calls():
    """``kda_scan`` calls this thread has staged into a jaxpr so far: the
    difference across a rematerialisation boundary is the calls inside it
    (``pallas_attention.traced_calls`` counts the flash kernel's)."""
    return getattr(_TRACED, "calls", 0)


def kernel_takes(q_shape, v_shape, chunk, sub):
    """The shape rule: q/k and v heads of 128 or 256 channels (whole lane
    tiles) and chunks of 16 to 64 steps in whole sub-blocks, the sizes the
    kernels are compiled for and tested at."""
    return (all(d % 128 == 0 and d <= 256 for d in (q_shape[-1], v_shape[-1]))
            and chunk % sub == 0 and sub % 8 == 0 and chunk <= 64)


def _dot(a, b, dims=_NN, batch=((), ()), precision=None):
    return jax.lax.dot_general(a, b, (dims, batch), precision=precision,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _diagonal(shape):
    return _iota(shape, 0) == _iota(shape, 1)


def _row_to_col(row):
    """``(1, n)`` -> ``(n, 1)``, exactly."""
    n = row.shape[1]
    return jnp.sum(jnp.where(_diagonal((n, n)), row, 0.0), axis=1,
                   keepdims=True)


def _col_to_row(col):
    """``(n, 1)`` -> ``(1, n)``, exactly."""
    n = col.shape[0]
    return jnp.sum(jnp.where(_diagonal((n, n)), col, 0.0), axis=0,
                   keepdims=True)


class _Chunk:
    """What one chunk's steps share, forward and backward: the cumulative
    sum ``G`` and the decays of the intra-chunk products, each the ``exp``
    of a masked difference of ``G`` (≤ 0).  Sub-block ``i`` of ``sub``
    steps takes as its reference ``R_i`` the G just before its first step
    (0 for the first): ``to_ref`` is ``e^{G_t − R_i}`` for its own steps,
    ``from_ref[i]`` is ``e^{R_i − G_s}`` for the steps of earlier
    sub-blocks (0 elsewhere), and ``own[i, t, j]`` is ``e^{G_t − G_j}``
    inside the sub-block for ``j ≤ t`` (0 above)."""

    def __init__(self, q, k, g, sub):
        qn, dk = q.shape
        ns = qn // sub
        self.qn, self.dk, self.ns, self.sub = qn, dk, ns, sub
        t, s = _iota((qn, qn), 0), _iota((qn, qn), 1)
        self.t, self.s = t, s
        self.cs = _dot(jnp.where(s <= t, 1.0, 0.0), g, precision=_HI)
        cs4 = self.cs.reshape(ns, sub, dk)
        ref = jnp.zeros((1, 1, dk), _F32)
        if ns > 1:
            ref = jnp.concatenate([ref, cs4[:-1, sub - 1:sub, :]], axis=0)
        self.to_ref = jnp.exp(
            self.cs - jnp.broadcast_to(ref, (ns, sub, dk)).reshape(qn, dk))
        earlier = _iota((ns, qn, dk), 1) < _iota((ns, qn, dk), 0) * sub
        self.from_ref = jnp.exp(jnp.where(earlier, ref - self.cs[None],
                                          -jnp.inf))
        causal = (_iota((ns, sub, sub, dk), 2)
                  <= _iota((ns, sub, sub, dk), 1))
        self.own = jnp.exp(jnp.where(
            causal, cs4[:, :, None, :] - cs4[:, None, :, :], -jnp.inf))
        self.q4, self.k4 = q.reshape(ns, sub, dk), k.reshape(ns, sub, dk)
        # rows of sub-block i: its keys' and its queries' products with the
        # keys of earlier sub-blocks, (ns, 2 sub, Q)
        self.lhs = jnp.concatenate([(k * self.to_ref).reshape(ns, sub, dk),
                                    (q * self.to_ref).reshape(ns, sub, dk)],
                                   axis=1)
        self.rhs = self.from_ref * k[None]
        self.block = t // sub == s // sub

    def blockwise(self, own):
        """``(ns, sub, sub)`` products of each sub-block with itself ->
        the block diagonal of ``(Q, Q)``."""
        return jnp.where(self.block, jnp.tile(own.reshape(self.qn, self.sub),
                                              (1, self.ns)), 0.0)

    def own_part(self, full):
        """The block diagonal of ``full (Q, Q)`` as ``(ns, sub, sub)``."""
        fold = jnp.where(_iota((self.qn, self.sub), 0) % self.sub
                         == _iota((self.qn, self.sub), 1), 1.0, 0.0)
        return _dot(jnp.where(self.block, full, 0.0), fold,
                    precision=_HI).reshape(self.ns, self.sub, self.sub)

    def products(self):
        """``(A_kk, A_qk)``: strictly lower and lower ``(Q, Q)``."""
        qn, sub = self.qn, self.sub
        off = _dot(self.lhs, self.rhs, ((2,), (2,)), ((0,), (0,)))
        k4, q4 = self.k4, self.q4
        own_kk = jnp.sum(k4[:, :, None, :] * k4[:, None, :, :] * self.own, -1)
        own_qk = jnp.sum(q4[:, :, None, :] * k4[:, None, :, :] * self.own, -1)
        a_kk = off[:, :sub].reshape(qn, qn) + self.blockwise(own_kk)
        a_qk = off[:, sub:].reshape(qn, qn) + self.blockwise(own_qk)
        return jnp.where(self.s < self.t, a_kk, 0.0), a_qk

    def products_vjp(self, d_kk, d_qk):
        """The cotangents ``d_kk``, ``d_qk`` of ``products()`` -> those of
        q, k and of ``G``, each ``(Q, d_k)``."""
        qn, dk, ns, sub = self.qn, self.dk, self.ns, self.sub
        # earlier sub-blocks: two products a sub-block, through the
        # references
        d_off = jnp.concatenate([d_kk.reshape(ns, sub, qn),
                                 d_qk.reshape(ns, sub, qn)], axis=1)
        rows = _dot(d_off, self.rhs, ((2,), (1,)), ((0,), (0,)))
        rows = rows.reshape(ns, 2, sub, dk)
        by_kk = rows[:, 0].reshape(qn, dk) * self.to_ref
        by_qk = rows[:, 1].reshape(qn, dk) * self.to_ref
        cols = jnp.sum(_dot(d_off, self.lhs, ((1,), (1,)), ((0,), (0,)))
                       * self.from_ref, axis=0)
        # each sub-block with itself: the decays elementwise
        z_kk = self.own_part(d_kk)[..., None] * self.own
        z_qk = self.own_part(d_qk)[..., None] * self.own
        k4, q4 = self.k4, self.q4
        by_kk += jnp.sum(z_kk * k4[:, None, :, :], axis=2).reshape(qn, dk)
        by_qk += jnp.sum(z_qk * k4[:, None, :, :], axis=2).reshape(qn, dk)
        cols += jnp.sum(z_kk * k4[:, :, None, :] + z_qk * q4[:, :, None, :],
                        axis=1).reshape(qn, dk)
        k, q = k4.reshape(qn, dk), q4.reshape(qn, dk)
        return by_qk, by_kk + cols, k * by_kk + q * by_qk - k * cols


def _unit_lower_inverse(n):
    """``(I + n)⁻¹`` for ``n`` strictly lower ``(Q, Q)``: the inverses of
    the diagonal blocks of 1, 2, 4, … merged pairwise,
    ``[[A, 0], [C, B]]⁻¹ = [[A⁻¹, 0], [−B⁻¹ C A⁻¹, B⁻¹]]``, in float32."""
    qn = n.shape[0]
    t, s = _iota((qn, qn), 0), _iota((qn, qn), 1)
    x = jnp.where(t == s, 1.0, 0.0)
    width = 1
    while width < qn:
        c = jnp.where((t // (2 * width) == s // (2 * width))
                      & (t // width != s // width), n, 0.0)
        if width == 1:
            x = x - c
        else:
            x = x - _dot(_dot(x, c, precision=_HI), x, precision=_HI)
        width *= 2
    return x


def _solve(ch, beta, k, v):
    """The chunk's triangular system: ``(A_kk, A_qk, (I + Diag(β) A_kk)⁻¹,
    its right-hand side, [W, U])``."""
    a_kk, a_qk = ch.products()
    inv = _unit_lower_inverse(beta * a_kk)
    rhs = jnp.concatenate([beta * k * jnp.exp(ch.cs), beta * v], axis=1)
    return a_kk, a_qk, inv, rhs, _dot(inv, rhs, precision=_HI)


def _heads(n, body):
    """``body(j)`` for the ``n`` heads of a block, in a rolled loop: the
    body is one head long whatever ``n`` is."""
    def step(j, carry):
        body(j)
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, entering_ref,
                s_ref, *, sub):
    """One chunk of a block of heads, forward."""
    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    def head(j):
        q, k, v, g = (r[:, j, :] for r in (q_ref, k_ref, v_ref, g_ref))
        beta = _row_to_col(b_ref[pl.ds(j, 1), :])
        qn, dk = q.shape
        ch = _Chunk(q, k, g, sub)
        _, a_qk, _, _, x = _solve(ch, beta, k, v)
        s = s_ref[j]
        entering_ref[j] = s
        u = x[:, dk:] - _dot(x[:, :dk], s)
        o_ref[:, j, :] = _dot(q * jnp.exp(ch.cs), s) + _dot(a_qk, u)
        last = ch.cs[qn - 1:qn, :]
        s_ref[j] = (_row_to_col(jnp.exp(last)) * s
                    + _dot(k * jnp.exp(last - ch.cs), u, _TN))

    _heads(s_ref.shape[0], head)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, entering_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, sub):
    """One chunk of a block of heads, backward: ``ds_ref`` holds the
    cotangents of the states leaving the chunk and leaves with those of
    the states entering it."""
    @pl.when(pl.program_id(1) == 0)
    def _start():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def head(j):
        grads = _bwd_head(
            q_ref[:, j, :], k_ref[:, j, :], v_ref[:, j, :], g_ref[:, j, :],
            b_ref[pl.ds(j, 1), :], entering_ref[j], do_ref[:, j, :],
            ds_ref[j], sub)
        for ref, grad in zip((dq_ref, dk_ref, dv_ref, dg_ref), grads):
            ref[:, j, :] = grad
        db_ref[pl.ds(j, 1), :] = grads[4]
        ds_ref[j] = grads[5]

    _heads(ds_ref.shape[0], head)


def _bwd_head(q, k, v, g, beta_row, s, do, d_next, sub):
    """One chunk of one head, backward: the cotangents of q, k, v, g, β
    (a row) and of the state entering the chunk, from those of o and of
    the state leaving it (``d_next``)."""
    beta = _row_to_col(beta_row)
    qn, dk = q.shape
    ch = _Chunk(q, k, g, sub)
    a_kk, a_qk, inv, rhs, x = _solve(ch, beta, k, v)
    w = x[:, :dk]
    u = x[:, dk:] - _dot(w, s)
    eg = jnp.exp(ch.cs)
    last = ch.cs[qn - 1:qn, :]
    to_end = jnp.exp(last - ch.cs)
    k_end = k * to_end

    # o = (q ⊙ e^G) S + A_qk u;  S' = Diag(e^{G_Q}) S + k_endᵀ u
    d_qe = _dot(do, s, _NT)
    d_a_qk = jnp.where(ch.s <= ch.t, _dot(do, u, _NT), 0.0)
    du = _dot(a_qk, do, _TN) + _dot(k_end, d_next)
    d_k_end = _dot(u, d_next, _NT)
    d_s = (_dot(q * eg, do, _TN) + _row_to_col(jnp.exp(last)) * d_next)
    d_last = (jnp.exp(last) * _col_to_row(jnp.sum(s * d_next, axis=1,
                                                  keepdims=True))
              + jnp.sum(d_k_end * k_end, axis=0, keepdims=True))
    # u = U − W S
    d_s -= _dot(w, du, _TN)
    dx = jnp.concatenate([-_dot(du, s, _NT), du], axis=1)
    # [W, U] = (I + Diag(β) A_kk)⁻¹ rhs
    d_rhs = _dot(inv, dx, _TN, precision=_HI)
    d_n = jnp.where(ch.s < ch.t, -_dot(d_rhs, x, _NT, precision=_HI), 0.0)
    d_w_rhs, d_u_rhs = d_rhs[:, :dk], d_rhs[:, dk:]
    d_beta = jnp.sum(d_n * a_kk, axis=1, keepdims=True) + jnp.sum(
        d_w_rhs * k * eg, axis=1, keepdims=True) + jnp.sum(
        d_u_rhs * v, axis=1, keepdims=True)
    dq, dk_, d_cs = ch.products_vjp(beta * d_n, d_a_qk)
    dq += d_qe * eg
    dk_ += beta * d_w_rhs * eg + d_k_end * to_end
    d_cs += (d_qe * q + beta * d_w_rhs * k) * eg - d_k_end * k_end
    d_cs += jnp.where(_iota((qn, dk), 0) == qn - 1, d_last, 0.0)
    return (dq, dk_, beta * d_u_rhs,
            _dot(jnp.where(ch.s >= ch.t, 1.0, 0.0), d_cs, precision=_HI),
            _col_to_row(d_beta), d_s)


def _block_heads(heads):
    """Heads a grid step takes: a sublane tile of them (8), or all."""
    return 8 if heads % 8 == 0 else heads


def _specs(heads, qn, dk, dv, reverse, nc):
    """Block specs of a grid step ``(i, c)``: the ``hg`` heads
    ``i · hg …`` of ``batch · heads`` (``_block_heads``) and chunk ``c``,
    walked backwards when ``reverse``; q, k, v, g, o and their
    cotangents in the op's own ``(batch, T, heads, d)`` layout."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    hg = _block_heads(heads)
    groups = heads // hg

    def head(d):
        return pl.BlockSpec((None, qn, hg, d),
                            lambda i, c: (i // groups, at(c), i % groups, 0))

    row = pl.BlockSpec((None, None, hg, qn), lambda i, c: (i, at(c), 0, 0))
    state = pl.BlockSpec((hg, None, dk, dv), lambda i, c: (i, at(c), 0, 0))
    return head(dk), head(dv), row, state


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          operands):
    def call(interpret, *operands):
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret, name=name)(*operands)

    return per_platform(call, *operands)


# jitted, so that a program's calls at one shape share one traced and
# lowered kernel: lowering a kernel body is what a call costs in set-up
@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(q, k, v, g, beta, qn, sub):
    b, tp, heads, dk = q.shape
    dv = v.shape[-1]
    nc, hg = tp // qn, _block_heads(heads)
    kspec, vspec, row, state = _specs(heads, qn, dk, dv, False, nc)
    return _call(
        functools.partial(_fwd_kernel, sub=sub), "mx_kda_fwd",
        (b * heads // hg, nc), [kspec, kspec, vspec, kspec, row],
        (vspec, state),
        (jax.ShapeDtypeStruct(v.shape, _F32),
         jax.ShapeDtypeStruct((b * heads, nc, dk, dv), _F32)),
        [pltpu.VMEM((hg, dk, dv), _F32)], (q, k, v, g, beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(q, k, v, g, beta, qn, sub):
    return _forward(q, k, v, g, beta, qn, sub)[0]


def _scan_fwd(q, k, v, g, beta, qn, sub):
    o, entering = _forward(q, k, v, g, beta, qn, sub)
    # named for a boundary's policy (KDA_RESIDUALS), the identity outside
    # one; the primal output is the saved ``o`` itself, so the output norm's
    # pullback reads it too
    o = checkpoint_name(o, KDA_RESIDUALS[0])
    entering = checkpoint_name(entering, KDA_RESIDUALS[1])
    return o, (q, k, v, g, beta, entering)


def _scan_bwd(qn, sub, res, do):
    return _backward(*res, do, qn, sub)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _backward(q, k, v, g, beta, entering, do, qn, sub):
    b, tp, heads, dk = q.shape
    dv = v.shape[-1]
    nc, hg = tp // qn, _block_heads(heads)
    kspec, vspec, row, state = _specs(heads, qn, dk, dv, True, nc)
    return _call(
        functools.partial(_bwd_kernel, sub=sub), "mx_kda_bwd",
        (b * heads // hg, nc),
        [kspec, kspec, vspec, kspec, row, state, vspec],
        (kspec, kspec, vspec, kspec, row),
        tuple(jax.ShapeDtypeStruct(a.shape, _F32) for a in (q, k, v, g, beta)),
        [pltpu.VMEM((hg, dk, dv), _F32)], (q, k, v, g, beta, entering, do))


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_scan(q, k, v, g, beta, chunk_size, sub):
    """``_op_linear_attention.kda_scan`` through the kernels: the same
    arguments, float32 inside, the tail padded with steps of β = 0, g = 0,
    the output in v's dtype.  ``kernel_takes`` must admit the shapes."""
    _TRACED.calls = traced_calls() + 1
    bsz, t, h, _ = q.shape
    qn = int(chunk_size)
    pad = -t % qn
    nc, hg = (t + pad) // qn, _block_heads(h)

    def padded(x):
        x = x.astype(_F32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return x

    # β as rows of a chunk, a block's heads together:
    # (b, T, H) -> (b · H / hg, chunks, hg, Q)
    rows = padded(beta).reshape(bsz, nc, qn, h // hg, hg)
    rows = rows.transpose(0, 3, 1, 4, 2).reshape(bsz * h // hg, nc, hg, qn)
    o = _scan(padded(q), padded(k), padded(v), padded(g), rows, qn, sub)
    return o[:, :t].astype(v.dtype)
