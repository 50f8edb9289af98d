"""What the row kernels (pallas_norm, pallas_softmax_ce) and the
attention kernel share: where a ``pallas_call`` is compiled, and how a
row count is tiled.

**Mosaic or the interpreter** is decided when a program is LOWERED, from
the platform it is lowered for (``jax.lax.platform_dependent``), never
from ``jax.default_backend()``: operands committed to ``mx.cpu()`` in a
process whose default backend is the chip interpret, operands on a
``tpu`` device compile under Mosaic, and one traced function serves
both.

**Row tiles** satisfy Mosaic's block rule (second-to-last block dim a
multiple of the dtype's sublane packing: 8 rows of 32-bit, 16 of 16-bit,
32 of 8-bit) by PADDING the rows to a tile multiple — as attention pads
its sequence — instead of shrinking the tile below it.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

# one f32 copy of a (block_rows, d) tile may take this much VMEM: the
# kernels hold ~6 such temporaries beside double-buffered in/out blocks,
# which stays under Mosaic's 16 MiB default scoped limit on every
# generation
_TILE_F32_BYTES = 1 << 20
ROW_TILES = (256, 128, 64, 32, 16, 8)


def per_platform(call, *operands):
    """``call(interpret, *operands)``, lowered as ``call(False, …)`` for
    tpu and as ``call(True, …)`` for every other platform."""
    return jax.lax.platform_dependent(
        *operands, tpu=functools.partial(call, False),
        default=functools.partial(call, True))


def min_rows(dtype):
    """Sublane packing of ``dtype``: the smallest legal row tile."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def row_tile_fits(d, dtype):
    """The shape rule behind the ops' ``auto`` gates: a row kernel
    supports trailing width ``d`` when its smallest tile fits the VMEM
    budget.  Anything wider takes plain XLA; a compiler refusal INSIDE
    this rule is an error the caller sees."""
    return min_rows(dtype) * int(d) * 4 <= _TILE_F32_BYTES


def kernel_wanted(flag, d, dtype):
    """An op's ``MXNET_FUSED_*`` gate: 1/true/on forces the kernel,
    0/false/off forces plain XLA, ``auto`` (the default) is the shape
    rule.  No probe: Mosaic refusing a shape the rule admits is an error
    the caller sees, never a quiet plain-XLA run."""
    value = os.environ.get(flag, "auto").lower()
    if value in ("1", "true", "on"):
        return True
    if value in ("0", "false", "off"):
        return False
    return row_tile_fits(d, dtype)


def pick_block_rows(n, d, dtype):
    """Largest power-of-two row tile that divides ``n`` and fits the
    VMEM budget, never below the dtype's sublane packing (the rows are
    then padded up to it)."""
    floor = min_rows(dtype)
    cap = max(floor, _TILE_F32_BYTES // (int(d) * 4))
    for b in ROW_TILES:
        if floor <= b <= cap and n % b == 0:
            return b
    return floor


def resolve_block_rows(n, d, dtype, block_rows):
    """A tuned tile applies only when it tiles THIS ``n`` exactly (a
    shard_map body sees the shard-local row count, not the tuned one)."""
    if block_rows and n % block_rows == 0:
        return block_rows
    return pick_block_rows(n, d, dtype)


def pad_rows(x, block_rows, value=0):
    """Pad the leading dim of ``x`` to a multiple of ``block_rows``."""
    pad = -x.shape[0] % block_rows
    if not pad:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1),
                   constant_values=value)
