"""Tensor parallelism over the mesh 'tp' axis via shard_map.

The reference has no tensor parallelism (SURVEY.md §2.4 checklist: "not
present anywhere"); its closest artifacts are cross-device batchnorm
stats (sync_batch_norm-inl.h) and context-group model parallelism.  This
module is the greenfield TPU capability SURVEY §7 step 8 plans: Megatron-
style column/row-parallel projections written as *explicit* shard_map
programs — activations stay replicated over 'tp', weights are sharded,
and exactly one psum per row-parallel cut rides the ICI.

Layout for one pre-LN transformer block (E = embed, F = ffn, H = heads):

  wq/wk/wv (E, E)  column-sharded  P(None, 'tp')   heads split H/tp
  wo       (E, E)  row-sharded     P('tp', None)   psum after
  w1       (E, F)  column-sharded  P(None, 'tp')
  w2       (F, E)  row-sharded     P('tp', None)   psum after
  biases of column-parallel layers shard with the output features;
  biases of row-parallel layers are replicated and added AFTER the psum.

Attention inside the block is the Pallas flash kernel
(ops/pallas_attention.py) running on each shard's local heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from ..ops.pallas_attention import flash_attention
from .mesh import DeviceMesh

__all__ = ["column_parallel_dense", "row_parallel_dense",
           "init_transformer_params", "transformer_block_ref",
           "transformer_block_tp", "shard_transformer_params"]


def column_parallel_dense(x, w_local, b_local=None):
    """Inside shard_map: w column-sharded -> output features sharded.
    No communication."""
    y = jnp.matmul(x, w_local)
    if b_local is not None:
        y = y + b_local
    return y


def row_parallel_dense(x_local, w_local, b=None, axis="tp"):
    """Inside shard_map: x feature-sharded, w row-sharded -> full output
    via one psum over ``axis``; replicated bias added after the psum.
    axis=None skips the psum (single-device reference path)."""
    y = jnp.matmul(x_local, w_local)
    if axis is not None:
        y = jax.lax.psum(y, axis)
    if b is not None:
        y = y + b
    return y


def _layernorm(x, gamma, beta, eps=1e-5):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


def init_transformer_params(key, embed, ffn, num_heads, dtype=jnp.float32):
    """Parameter dict for one pre-LN transformer block."""
    if embed % num_heads:
        raise MXNetError("embed must be divisible by num_heads")
    ks = jax.random.split(key, 6)
    sd = embed ** -0.5

    def rnd(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    return {
        "wq": rnd(ks[0], (embed, embed), sd),
        "wk": rnd(ks[1], (embed, embed), sd),
        "wv": rnd(ks[2], (embed, embed), sd),
        "wo": rnd(ks[3], (embed, embed), sd),
        "w1": rnd(ks[4], (embed, ffn), sd),
        "w2": rnd(ks[5], (ffn, embed), ffn ** -0.5),
        "bq": jnp.zeros((embed,), dtype), "bk": jnp.zeros((embed,), dtype),
        "bv": jnp.zeros((embed,), dtype), "bo": jnp.zeros((embed,), dtype),
        "b1": jnp.zeros((ffn,), dtype), "b2": jnp.zeros((embed,), dtype),
        "ln1_g": jnp.ones((embed,), dtype),
        "ln1_b": jnp.zeros((embed,), dtype),
        "ln2_g": jnp.ones((embed,), dtype),
        "ln2_b": jnp.zeros((embed,), dtype),
    }


_PARAM_SPECS = {
    "wq": P(None, "tp"), "wk": P(None, "tp"), "wv": P(None, "tp"),
    "bq": P("tp"), "bk": P("tp"), "bv": P("tp"),
    "wo": P("tp", None), "bo": P(),
    "w1": P(None, "tp"), "b1": P("tp"),
    "w2": P("tp", None), "b2": P(),
    "ln1_g": P(), "ln1_b": P(), "ln2_g": P(), "ln2_b": P(),
}


def _block_math(x, p, *, num_heads, causal, tp_axis):
    """The block body; runs replicated (tp_axis=None) or as the per-shard
    program inside shard_map (tp_axis='tp') — same code, so the TP test
    is an exact-math comparison."""
    b, s, e = x.shape
    n_local_heads = p["wq"].shape[1] // (e // num_heads)
    dh = e // num_heads

    h = _layernorm(x, p["ln1_g"], p["ln1_b"])
    q = column_parallel_dense(h, p["wq"], p["bq"])
    k = column_parallel_dense(h, p["wk"], p["bk"])
    v = column_parallel_dense(h, p["wv"], p["bv"])

    def split(t):
        return t.reshape(b, s, n_local_heads, dh).transpose(0, 2, 1, 3)

    attn = flash_attention(split(q), split(k), split(v), causal)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, n_local_heads * dh)
    x = x + row_parallel_dense(attn, p["wo"], p["bo"], axis=tp_axis)

    h2 = _layernorm(x, p["ln2_g"], p["ln2_b"])
    y = jax.nn.gelu(column_parallel_dense(h2, p["w1"], p["b1"]))
    return x + row_parallel_dense(y, p["w2"], p["b2"], axis=tp_axis)


def transformer_block_ref(params, x, num_heads, causal=False):
    """Single-device reference forward of the block."""
    return _block_math(x, params, num_heads=num_heads, causal=causal,
                       tp_axis=None)


def shard_transformer_params(mesh, params):
    """device_put each param with its TP NamedSharding."""
    if not isinstance(mesh, DeviceMesh):
        raise MXNetError("mesh must be a parallel.DeviceMesh")
    out = {}
    for name, arr in params.items():
        spec = _PARAM_SPECS[name]
        out[name] = jax.device_put(arr, mesh.sharding(*spec))  # graftlint: disable=per-param-collective -- one placement per weight at model setup, not a per-step loop
    return out


def transformer_block_tp(mesh, params, x, num_heads, causal=False,
                         axis="tp"):
    """TP forward: one shard_map program over mesh['tp'].

    x replicated, weights sharded per _PARAM_SPECS, two psums (after wo
    and after w2).  num_heads must divide by mesh.size('tp').
    """
    tp = mesh.size(axis)
    if num_heads % tp:
        raise MXNetError(f"num_heads {num_heads} not divisible by "
                         f"tp={tp}")
    names = sorted(params)
    in_specs = (P(),) + tuple(_PARAM_SPECS[n] for n in names)

    @functools.partial(
        shard_map, mesh=mesh.jax_mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False)
    def run(x_, *flat):
        p = dict(zip(names, flat))
        return _block_math(x_, p, num_heads=num_heads, causal=causal,
                           tp_axis=axis)

    return run(x, *(params[n] for n in names))
