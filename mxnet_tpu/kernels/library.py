"""The registered kernels: the three seed-era Pallas one-offs promoted
into the registry contract.

Each spec pairs the Pallas implementation (parameterized by its tunable
config) with the pure-XLA reference that doubles as the numerics oracle
and the ``MXNET_KERNELS=reference`` executable.  The references are the
SAME functions the op layer runs with kernels off (plain_layer_norm /
plain_softmax_ce) — that identity is what makes reference-mode fits
bitwise-identical to kernels-off.
"""
from __future__ import annotations

import numpy as np

from ..ops import pallas_attention, pallas_norm, pallas_softmax_ce
from ..ops._pallas_rows import ROW_TILES, pick_block_rows
from .registry import KernelSpec, register_kernel


def _rows(shape):
    return int(np.prod(shape[:-1])) if len(shape) > 1 else int(shape[0])


# -- layernorm ----------------------------------------------------------------
def _ln_make(config):
    br = int(config["block_rows"])

    def impl(x, gamma, beta, eps=1e-5):
        return pallas_norm.fused_layer_norm(x, gamma, beta, eps=eps,
                                            block_rows=br)
    return impl


def _ln_reference(x, gamma, beta, eps=1e-5):
    return pallas_norm.plain_layer_norm(x, gamma, beta, eps=eps, axis=-1)


def _ln_space(shape, dtype):
    n = _rows(shape)
    cfgs = [{"block_rows": b} for b in ROW_TILES if b <= n and n % b == 0]
    return cfgs or [_ln_default(shape, dtype)]


def _ln_default(shape, dtype):
    return {"block_rows": pick_block_rows(_rows(shape), shape[-1], dtype)}


def _ln_inputs(shape, dtype, rng):
    import jax.numpy as jnp
    d = int(shape[-1])
    x = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
    gamma = jnp.asarray((1.0 + 0.1 * rng.randn(d)).astype(np.float32), dtype)
    beta = jnp.asarray((0.1 * rng.randn(d)).astype(np.float32), dtype)
    return (x, gamma, beta), {}


def _row_kernel_tol(dtype):
    import jax.numpy as jnp
    if jnp.dtype(dtype).itemsize < 4:
        # bf16/f16: the KERNEL keeps row stats in f32 while the
        # reference accumulates in-dtype, so most of the gap here is
        # reference rounding (~5% of gradient scale observed for bf16
        # LayerNorm bwd); still tight enough to catch O(1) math bugs
        return (2e-1, 2e-1)
    return (2e-5, 2e-5)


register_kernel(KernelSpec(
    name="layernorm",
    doc="fused trailing-axis LayerNorm (pallas_norm.py); config = row "
        "tile {block_rows}; fwd pallas, bwd analytic custom_vjp",
    reference=_ln_reference,
    make=_ln_make,
    config_space=_ln_space,
    default_config=_ln_default,
    example_inputs=_ln_inputs,
    grad_argnums=(0, 1, 2),
    tolerance=_row_kernel_tol,
))


# -- softmax cross-entropy ----------------------------------------------------
def _smce_make(config):
    br = int(config["block_rows"])

    def impl(logits, labels):
        return pallas_softmax_ce.softmax_ce_kernel(logits, labels,
                                                   block_rows=br)
    return impl


def _smce_space(shape, dtype):
    n = int(shape[0])
    cfgs = [{"block_rows": b} for b in ROW_TILES if b <= n and n % b == 0]
    return cfgs or [_smce_default(shape, dtype)]


def _smce_default(shape, dtype):
    return {"block_rows": pick_block_rows(int(shape[0]), shape[1], dtype)}


def _smce_inputs(shape, dtype, rng):
    import jax.numpy as jnp
    n, d = int(shape[0]), int(shape[1])
    logits = jnp.asarray(rng.randn(n, d).astype(np.float32), dtype)
    # include the -1 ignore/padding label so the gate proves the
    # zero-loss / zero-gradient convention, not just the happy path
    labels = rng.randint(0, d, size=n).astype(np.int32)
    if n > 1:
        labels[0] = -1
    return (logits, jnp.asarray(labels)), {}


def _smce_tol(dtype):
    import jax.numpy as jnp
    if jnp.dtype(dtype).itemsize < 4:
        return (2e-2, 2e-2)
    return (2e-5, 2e-5)


register_kernel(KernelSpec(
    name="softmax_ce",
    doc="fused per-row softmax + cross-entropy (pallas_softmax_ce.py); "
        "config = row tile {block_rows}; fwd pallas, bwd analytic "
        "(softmax - onehot) custom_vjp",
    reference=pallas_softmax_ce.plain_softmax_ce,
    make=_smce_make,
    config_space=_smce_space,
    default_config=_smce_default,
    example_inputs=_smce_inputs,
    grad_argnums=(0,),
    tolerance=_smce_tol,
))


# -- flash attention ----------------------------------------------------------
_ATTN_SPACE = ({"block_q": 128, "block_k": 128},
               {"block_q": 64, "block_k": 64},
               {"block_q": 64, "block_k": 128},
               {"block_q": 128, "block_k": 64},
               {"block_q": 256, "block_k": 128},
               {"block_q": 128, "block_k": 256})


def _attn_make(config):
    bq, bk = int(config["block_q"]), int(config["block_k"])

    def impl(q, k, v, causal=True, sm_scale=None):
        return pallas_attention.flash_attention(q, k, v, causal, sm_scale,
                                                bq, bk)
    return impl


def _attn_reference(q, k, v, causal=True, sm_scale=None):
    return pallas_attention.reference_attention(q, k, v, causal, sm_scale)


def _attn_space(shape, dtype):
    return [dict(c) for c in _ATTN_SPACE]


def _attn_default(shape, dtype):
    return {"block_q": 128, "block_k": 128}


def _attn_inputs(shape, dtype, rng):
    import jax.numpy as jnp
    q, k, v = (jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
               for _ in range(3))
    # causal is the serving configuration (GenerationEngine prefill) and
    # the harder masking case — gate what we ship
    return (q, k, v), {"causal": True}


def _attn_tol(dtype):
    import jax.numpy as jnp
    if jnp.dtype(dtype).itemsize < 4:
        return (4e-2, 4e-2)
    return (2e-4, 2e-4)   # online softmax reassociates the reduction


register_kernel(KernelSpec(
    name="attention",
    doc="blockwise (flash) causal attention (pallas_attention.py); "
        "config = MXU tiles {block_q, block_k} of all three kernels; fwd "
        "pallas online softmax that keeps (out, lse), bwd two pallas "
        "kernels (dq; dk, dv over the whole query group) that recompute "
        "the scores a tile at a time in VMEM",
    reference=_attn_reference,
    make=_attn_make,
    config_space=_attn_space,
    default_config=_attn_default,
    example_inputs=_attn_inputs,
    grad_argnums=(0, 1, 2),
    tolerance=_attn_tol,
))
