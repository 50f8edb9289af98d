"""The flash attention kernels, forward and backward, compiled by Mosaic for
a DESCRIBED TPU v5e (no chip attached): what the interpreter cannot show,
a block shape, a layout, a VMEM budget or a tile table SMEM cannot hold
that the compiler refuses.  Nothing runs; the shapes are the token
cells' and the suite's awkward ones.
All compiles live in this one file and the topology is described inside a
fixture, so only the worker that is given the file loads the TPU's
library."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops.pallas_attention import Mask, flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a chip that is not attached cannot be read back
    # from the persistent cache: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (query heads, key/value heads, positions, head size or (query/key, value
# head size), dtype, block_q, block_k, causal: a bool or a Mask)
@pytest.mark.parametrize("h,h_kv,s,d,dtype,block_q,block_k,causal", [
    (32, 2, 8192, 128, "float32", 512, 512, True),     # Nemotron's layer
    (32, 32, 8192, (192, 128), "float32", 512, 512, True),  # Kimi's MLA
    (8, 1, 8192, 128, "float32", 512, 512, True),      # Solar's
    (8, 2, 8192, 128, "float32", 512, 512, True),      # ZAYA1's CCA layer
    (32, 8, 4096, 64, "float32", 512, 512, True),      # granite's
    (32, 2, 8192, 128, "bfloat16", 512, 512, True),
    (8, 8, 1024, 128, "bfloat16", 128, 128, True),     # chip_smoke's
    (1, 1, 128, 8, "float32", 128, 128, True),
    (4, 2, 200, 16, "float32", 128, 128, True),        # a tail, heads of 16
    (4, 2, 200, 32, "bfloat16", 128, 128, False),
    (2, 2, 256, 32, "float32", 64, 64, True),          # the tuner's pairs
    (2, 2, 256, 32, "float32", 64, 128, True),
    (2, 2, 512, 32, "float32", 256, 128, False),
    (2, 2, 256, 32, "float32", 96, 128, True),         # no lane multiple
    # SDAR's layer: [x0 ; xt] of 8192 tokens under the block-diffusion mask
    (32, 4, 16384, 128, "float32", 512, 512,
     Mask("block_diffusion", 4, 8192)),
    (4, 2, 272, 16, "float32", 128, 128, Mask("block_diffusion", 4, 136)),
    (4, 2, 200, 16, "bfloat16", 128, 64, Mask("block_causal", 4)),
    # the tile tables in SMEM: bq != bk with tiles that straddle the two
    # copies; no empty tile, so the table is the whole rectangle: the
    # suite's largest (3 x 4 of (128, 96)), the cells' length (256 steps),
    # and 65,536 steps, a quarter of the chip's SMEM
    (4, 2, 272, 16, "float32", 64, 128, Mask("block_diffusion", 4, 136)),
    (1, 1, 256, 32, "float32", 128, 96, False),
    (32, 2, 8192, 128, "float32", 512, 512, False),
    (2, 1, 32768, 64, "float32", 128, 128, False),
])
def test_forward_and_backward_compile_for_a_v5e(one_chip, h, h_kv, s, d,
                                                dtype, block_q, block_k,
                                                causal):
    d_qk, d_v = d if isinstance(d, tuple) else (d, d)

    def spec(heads, size):
        return jax.ShapeDtypeStruct((1, heads, s, size), jnp.dtype(dtype),
                                    sharding=one_chip)

    def out_and_grads(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: flash_attention(
            *a, causal, None, block_q, block_k), q, k, v)
        return (out,) + vjp(do)

    text = jax.jit(out_and_grads).lower(
        spec(h, d_qk), spec(h_kv, d_qk), spec(h_kv, d_v),
        spec(h, d_v)).compile().as_text()
    for kernel in ("mx_flash_attention_fwd", "mx_flash_attention_bwd_dq",
                   "mx_flash_attention_bwd_dkv"):
        assert kernel in text


# -- the KDA kernels (ops.pallas_kda) ------------------------------------------------
def _kda_bodies(one_chip, monkeypatch, heads, t, compile_too):
    """The Mosaic module of each KDA kernel (forward and backward of one
    call of chunk 64) lowered for the described chip at ``heads`` heads of
    128 and ``t`` positions, its numbers and value names masked: the grid's
    extent and the shapes are written into it, the operations are what
    must not grow."""
    from jax._src.pallas.mosaic import lowering
    from mxnet_tpu.ops import pallas_kda

    bodies = {}
    lower = lowering.lower_jaxpr_to_module

    def spy(ctx, grid_mapping, jaxpr, **kw):
        module = lower(ctx, grid_mapping, jaxpr, **kw)
        # value names carry constants' values too (%c8_i32_3)
        text = re.sub(r"%[\w.#]+", "%v", str(module))
        bodies[jaxpr.debug_info.func_name] = re.sub(r"\d+", "N", text)
        return module

    monkeypatch.setattr(lowering, "lower_jaxpr_to_module", spy)
    jax.clear_caches()

    def out_and_grads(q, k, v, g, beta, do):
        out, vjp = jax.vjp(lambda *a: pallas_kda.kda_scan(*a, 64, 16),
                           q, k, v, g, beta)
        return (out,) + vjp(do)

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    lowered = jax.jit(out_and_grads).lower(
        *[spec(1, t, heads, 128)] * 4, spec(1, t, heads),
        spec(1, t, heads, 128))
    if compile_too:
        text = lowered.compile().as_text()
        assert "mx_kda_fwd" in text and "mx_kda_bwd" in text
    monkeypatch.undo()
    return bodies


@pytest.mark.parametrize("heads,t", [
    (8, 8192),      # Solar's KDA layer
    (32, 8192),     # Kimi Linear's
    (32, 1024),
])
def test_kda_kernels_compile_for_a_v5e_and_their_bodies_do_not_grow(
        one_chip, monkeypatch, heads, t):
    got = _kda_bodies(one_chip, monkeypatch, heads, t, True)
    want = _kda_bodies(one_chip, monkeypatch, 8, 1024, False)
    assert sorted(got) == sorted(want) == ["mx_kda_bwd", "mx_kda_fwd"]
    for kernel in want:
        assert got[kernel] == want[kernel], kernel
