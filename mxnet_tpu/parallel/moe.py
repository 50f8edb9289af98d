"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

Greenfield TPU capability (SURVEY §2.4 checklist: the reference has no
MoE / expert parallelism at all; this completes the dp/fsdp/tp/sp/pp/ep
strategy set). Design is the GShard/Switch recipe mapped to shard_map:

  * top-1 gating with a per-device capacity C = ceil(cf * n_local / E);
    overflow tokens are dropped (their combine weight is zero) — the
    standard static-shape trick that keeps everything XLA-compilable.
  * dispatch/combine are dense einsums against a (n, E, C) one-hot
    mask — MXU-friendly, no gathers.
  * expert parallelism = two ``lax.all_to_all`` collectives over the
    ``ep`` axis: tokens travel source-device-major to the device owning
    their expert, run that device's local expert FFNs, and travel back.
    Tokens are data-sharded over the SAME axis, so dp and ep share the
    mesh dimension (the usual deployment: experts spread across the
    data-parallel group).
  * the router is differentiable through the gate VALUE (softmax prob
    of the chosen expert); the argmax route itself is not, per the
    literature. An auxiliary load-balancing loss (Switch style:
    E * sum_e fraction_tokens_e * mean_gate_e) is returned for the
    trainer to add.

``moe_ffn`` is the single-device reference; ``moe_ffn_ep`` is the
sharded version — numerically identical when capacity admits every
token (tested on the 8-device CPU mesh).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..base import MXNetError


def init_moe_params(key, d_model, d_hidden, num_experts, dtype=jnp.float32):
    """Router + stacked expert FFN parameters."""
    kg, k1, k2 = jax.random.split(key, 3)
    s1 = 1.0 / math.sqrt(d_model)
    s2 = 1.0 / math.sqrt(d_hidden)
    return {
        "wg": (jax.random.normal(kg, (d_model, num_experts)) * s1
               ).astype(dtype),
        "w1": (jax.random.normal(k1, (num_experts, d_model, d_hidden))
               * s1).astype(dtype),
        "b1": jnp.zeros((num_experts, d_hidden), dtype),
        "w2": (jax.random.normal(k2, (num_experts, d_hidden, d_model))
               * s2).astype(dtype),
        "b2": jnp.zeros((num_experts, d_model), dtype),
    }


def _route(x, wg, capacity):
    """Top-1 routing: returns (dispatch (n,E,C), combine (n,E,C),
    aux_loss scalar)."""
    n, _ = x.shape
    logits = x @ wg                         # (n, E)
    gates = jax.nn.softmax(logits, axis=-1)
    num_experts = gates.shape[-1]
    expert = jnp.argmax(gates, axis=-1)     # (n,)
    onehot = jax.nn.one_hot(expert, num_experts, dtype=x.dtype)  # (n, E)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0              # (n, E)
    keep = (pos >= 0) & (pos < capacity)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=x.dtype)                       # (n, E, C)
    dispatch = pos_oh * keep.astype(x.dtype)[..., None]
    gate_val = jnp.sum(gates * onehot, axis=-1)                  # (n,)
    combine = dispatch * gate_val[:, None, None]
    # Switch-style load balancing: experts should see equal traffic
    frac = onehot.mean(axis=0)
    mean_gate = gates.mean(axis=0)
    aux = num_experts * jnp.sum(frac * mean_gate)
    return dispatch, combine, aux


def moe_ffn(params, x, capacity_factor=2.0):
    """Single-device MoE FFN (the dense reference).

    x: (n, d_model) tokens. Returns (y, aux_loss)."""
    n = x.shape[0]
    num_experts = params["wg"].shape[-1]
    capacity = max(1, math.ceil(capacity_factor * n / num_experts))
    dispatch, combine, aux = _route(x, params["wg"], capacity)
    xe = jnp.einsum("nec,nd->ecd", dispatch, x)          # (E, C, d)
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", xe, params["w1"])
                    + params["b1"][:, None, :])
    ye = jnp.einsum("ech,ehd->ecd", h, params["w2"]) \
        + params["b2"][:, None, :]
    y = jnp.einsum("nec,ecd->nd", combine, ye)
    return y, aux


def moe_ffn_local(params, xl, *, axis, ep, capacity, num_experts):
    """Per-device MoE FFN body, for use INSIDE an enclosing shard_map.

    ``params`` are this device's slices (wg replicated, experts' leading
    dim already E/ep local); ``xl`` is this device's (n_loc, d) tokens.
    Issues the two ``lax.all_to_all`` collectives over ``axis`` — callers
    composing MoE with other axes (pipeline stages, dp) just call this
    from their own shard_map body.  Returns (y_local, pmean'd aux loss).
    """
    dispatch, combine, aux = _route(xl, params["wg"], capacity)  # (n,E,C)
    xe = jnp.einsum("nec,nd->ecd", dispatch, xl)         # (E, C, d)
    # regroup expert dim by owning device, swap with the device axis:
    # (ep, E_loc, C, d) -> all_to_all -> (ep, E_loc, C, d) where the
    # leading dim is now the SOURCE device of the token slots
    e_loc = xe.shape[0] // ep
    xe = xe.reshape(ep, e_loc, capacity, xe.shape[-1])
    xe = lax.all_to_all(xe, axis, split_axis=0, concat_axis=0,
                        tiled=False)
    # (ep, E_loc, C, d): local experts, slots from every source dev
    h = jax.nn.relu(jnp.einsum("secd,edh->sech", xe, params["w1"])
                    + params["b1"][None, :, None, :])
    ye = jnp.einsum("sech,ehd->secd", h, params["w2"]) \
        + params["b2"][None, :, None, :]
    ye = lax.all_to_all(ye, axis, split_axis=0, concat_axis=0,
                        tiled=False)
    ye = ye.reshape(num_experts, capacity, ye.shape[-1])
    y = jnp.einsum("nec,ecd->nd", combine, ye)
    # aux loss averages over devices (each routed its own tokens)
    return y, lax.pmean(aux, axis)


def moe_ffn_ep(params, x, mesh, axis="ep", capacity_factor=2.0):
    """Expert-parallel MoE FFN over ``axis``.

    Tokens (n, d) are sharded over ``axis``; experts are sharded over
    the same axis (E must divide by the axis size). Two all_to_all
    collectives move token slots to the expert owners and back — the
    bandwidth-optimal EP schedule on ICI.
    """
    ep = mesh.size(axis)
    num_experts = params["wg"].shape[-1]
    if num_experts % ep:
        raise MXNetError(
            f"num_experts {num_experts} must divide over {axis}={ep}")
    n = x.shape[0]
    if n % ep:
        raise MXNetError(f"token count {n} must divide over {axis}={ep}")
    n_loc = n // ep
    capacity = max(1, math.ceil(capacity_factor * n_loc / num_experts))

    def local(wg, w1, b1, w2, b2, xl):
        return moe_ffn_local({"wg": wg, "w1": w1, "b1": b1,
                              "w2": w2, "b2": b2},
                             xl, axis=axis, ep=ep, capacity=capacity,
                             num_experts=num_experts)

    pspec_tokens = P(axis)
    pspec_experts = P(axis)
    return shard_map(
        local, mesh=mesh.jax_mesh,
        in_specs=(P(), pspec_experts, pspec_experts, pspec_experts,
                  pspec_experts, pspec_tokens),
        out_specs=(pspec_tokens, P()),
        check_vma=False,
    )(params["wg"], params["w1"], params["b1"], params["w2"],
      params["b2"], x)
