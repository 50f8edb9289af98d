"""Block-diffusion training of the SDAR style decoder
(gluon.model_zoo.language.sdar_moe) at a small size on the CPU: the three
flash attention kernels under every kind of ``Mask`` against an attention
with the mask written out, values and all three gradients, in the Pallas
interpreter; ``causal`` unchanged to the bit against what the kernels gave
before the mask description (recorded here); the tile predicate against
the element predicate; the rotary operator (``contrib.rotary_embedding``)
against its definition, with repeated positions; ``TrainStep`` with a third
batch array; the model's logits, weighted loss and every gradient against
the benchmark's plain reference; THE LEAK TEST: the training layout's
logits of a block equal the denoising forward on that block behind its
clean prefix, in the reference and in the program, and do not see the
clean copy of their own block; and the share test: the eight holders'
routed outputs add up to the uncut reference's layer."""
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.gluon.model_zoo.language import SparseExperts
from mxnet_tpu.ops.pallas_attention import (_FIRST, _FULL, _LAST, Mask,
                                            _reference_attention, _tiles,
                                            _unpack, flash_attention,
                                            reference_attention, tile_table)
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "harness"))
import benchcore  # noqa: E402

CELL = benchcore.Cell("sdar-30b-a3b-spmd-bd4-seq8192-bs1")
REF = CELL.config_module()
# width 64; 4 query heads over 2 key/value heads of 16; experts 4-7 of 16
# held, top-3, tiles of 4 rows; blocks of 4; 36 tokens, so 72 positions
SMALL = dict(
    CELL.config, hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, moe_intermediate_size=32, num_experts=4,
    published={"num_experts": 16}, first_routed_expert=4,
    num_experts_per_tok=3, expert_tile_rows=4, vocab_size=64,
    mask_token_id=63, num_classes=64, image=[36], num_hidden_layers=3)
T, B = 36, 4


# -- (a) the mask description ------------------------------------------------------
def _written_out(mask, s):
    """The (s, s) mask by the issue's words, a pair at a time."""
    allowed = np.zeros((s, s), bool)
    for i in range(s):
        for j in range(s):
            if mask.kind == "none":
                allowed[i, j] = True
            elif mask.kind != "block_diffusion":
                allowed[i, j] = j // mask.block <= i // mask.block
            else:
                t = mask.half
                bi, bj = (i % t) // mask.block, (j % t) // mask.block
                if i < t:
                    allowed[i, j] = j < t and bj <= bi
                else:
                    allowed[i, j] = bj == bi if j >= t else bj < bi
    return allowed


def _explicit(q, k, v, allowed):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# 272 = two copies of 136 positions: not a multiple of any tile, so tiles
# straddle the two copies; (64, 128) tiles make 6 x 3 of them
MASKS = [(Mask("none"), 200), (Mask("causal"), 200),
         (Mask("block_causal", 4), 200), (Mask("block_causal", 8), 272),
         (Mask("block_diffusion", 4, 100), 200),
         (Mask("block_diffusion", 4, 136), 272),
         (Mask("block_diffusion", 8, 136), 272)]


def _qkv(s, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((2, h, s, 16)),
                             jnp.float32) for h in (4, 2, 2, 4))


@pytest.mark.parametrize("blocks", [(128, 128), (64, 128)])
@pytest.mark.parametrize("mask,s", MASKS, ids=lambda v: str(v))
def test_kernels_match_the_written_out_mask(mask, s, blocks):
    q, k, v, do = _qkv(s)
    allowed = _written_out(mask, s)
    got, vjp = jax.vjp(lambda *a: flash_attention(*a, mask, None, *blocks),
                       q, k, v)
    with jax.default_matmul_precision("highest"):
        want, ref_vjp = jax.vjp(lambda *a: _explicit(*a, allowed), q, k, v)
        xla = _reference_attention(q, k, v, mask, 0.25)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(xla, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip("qkv", vjp(do), ref_vjp(do)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


# what the parent commit's kernels gave for these inputs (crc32 of out,
# dq, dk, dv in the interpreter): the mask description changes no bit
RECORDED = {
    (True, (128, 128)): [3463852165, 275095769, 628240721, 1240925623],
    (True, (64, 128)): [3463852165, 275095769, 1703205426, 2248986524],
    (False, (128, 128)): [3596482852, 2339856826, 3337438391, 2177339079]}


@pytest.mark.parametrize("causal,blocks", sorted(RECORDED))
def test_causal_and_none_are_unchanged_to_the_bit(causal, blocks):
    rng = np.random.default_rng(38)
    q, k, v, do = (jnp.asarray(rng.standard_normal((2, h, 200, 16)),
                               jnp.float32) for h in (4, 2, 2, 4))
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, causal, None, *blocks),
                       q, k, v)
    assert [zlib.crc32(np.asarray(a).tobytes())
            for a in (out,) + vjp(do)] == RECORDED[causal, blocks]


@pytest.mark.parametrize("mask,s", MASKS, ids=lambda v: str(v))
def test_tile_predicate_is_the_element_predicate_over_a_tile(mask, s):
    allowed = _written_out(mask, s)
    ids = np.arange(s)
    got = mask.allowed(ids[:, None], ids[None, :])
    np.testing.assert_array_equal(
        allowed, np.ones((s, s), bool) if got is None else got)
    rng = np.random.default_rng(1)
    for _ in range(300):
        q0, k0 = rng.integers(0, s, 2)
        q1, k1 = rng.integers(q0, s), rng.integers(k0, s)
        some, every = mask.tile(q0, q1, k0, k1)
        part = allowed[q0:q1 + 1, k0:k1 + 1]
        assert (bool(some), bool(every)) == (part.any(), part.all()), \
            (q0, q1, k0, k1)


def test_tile_counts_at_the_timed_shape():
    """The issue's arithmetic: at T = 8192 in tiles of 512, 288 of 1,024
    tile pairs a head are not empty (136 clean → clean, 136 noisy → clean,
    16 noisy → noisy), 32 + 16 of them partial; the allowed pairs are
    T² + T·B; causal at 8192 keeps 136 of 256."""
    mask = Mask("block_diffusion", 4, 8192)
    assert mask.tile_counts(16384, 512, 512) == {
        "empty": 736, "partial": 48, "full": 240}
    assert Mask("causal").tile_counts(8192, 512, 512) == {
        "empty": 120, "partial": 16, "full": 120}
    assert Mask("none").tile_counts(200, 128, 128) == {
        "empty": 0, "partial": 2, "full": 2}     # the padded keys' tiles
    assert _written_out(Mask("block_diffusion", B, T), 2 * T).sum() \
        == T * T + T * B == REF.allowed_pairs(SMALL)
    # tiles that straddle the copies and the padding: the gauge is what
    # the call's mask counts, and no tile with an allowed pair is empty
    mask = Mask("block_diffusion", 4, 136)
    flash_attention(*_qkv(272)[:3], mask, None, 64, 128)
    gauge = telemetry.REGISTRY.get("mxnet_flash_attention_tiles")
    counts = mask.tile_counts(272, 64, 128)
    assert {kind: gauge.value({"mask": "block_diffusion", "kind": kind})
            for kind in counts} == counts
    assert sum(counts.values()) == 6 * 3 and counts["empty"] >= 4
    allowed = np.pad(_written_out(mask, 272), [(0, 112), (0, 112)])
    needed = allowed.reshape(6, 64, 3, 128).any(axis=(1, 3))
    assert counts["empty"] <= (~needed).sum()
    some = np.array([[bool(mask.tile(qi * 64, qi * 64 + 63, ki * 128,
                                     ki * 128 + 127)[0])
                      for ki in range(3)] for qi in range(6)])
    assert (some | ~needed).all()


# -- (a') the tile tables the three kernels walk -------------------------------------
TILINGS = [(128, 128), (64, 128), (128, 64)]


def _brute_force(mask, s, bq, bk):
    """``(some, full)`` of every (query tile, key tile) pair, an element at
    a time: ``Mask.allowed`` over the padded ids, and no padded key."""
    bq, bk, s_pad = _tiles(s, bq, bk)
    ids = np.arange(s_pad)
    allowed = mask.allowed(ids[:, None], ids[None, :])
    allowed = np.ones((s_pad, s_pad), bool) if allowed is None else allowed
    by_tile = allowed.reshape(s_pad // bq, bq, s_pad // bk, bk)
    full = by_tile.all(axis=(1, 3)) & ((np.arange(s_pad // bk) + 1) * bk <= s)
    return by_tile.any(axis=(1, 3)), full, (bq, bk)


@pytest.mark.parametrize("by_key,group", [(False, 1), (True, 1), (True, 2)])
@pytest.mark.parametrize("blocks", TILINGS)
@pytest.mark.parametrize("mask,s", MASKS, ids=lambda v: str(v))
def test_tile_table_is_the_brute_force_classification(mask, s, blocks,
                                                      by_key, group):
    some, full, (bq, bk) = _brute_force(mask, s, *blocks)
    q_tile, k_tile, head, flags = _unpack(
        tile_table(mask, s, bq, bk, by_key, group))
    # the computed tiles, each once a head of the group, in sweep order
    want = [(qi, ki, g) for ki in range(some.shape[1]) for g in range(group)
            for qi in range(some.shape[0]) if some[qi, ki]] if by_key else \
        [(qi, ki, 0) for qi, ki in zip(*np.nonzero(some))]
    assert list(zip(q_tile, k_tile, head)) == want
    np.testing.assert_array_equal((flags & _FULL) != 0, full[q_tile, k_tile])
    # every query tile and every key tile has an entry: each output block
    # of each kernel is written
    assert set(q_tile) == set(range(some.shape[0]))
    assert set(k_tile) == set(range(some.shape[1]))
    # the flags bracket each sweep: first on its first entry alone, last
    # on its last alone
    swept = k_tile if by_key else q_tile
    turns = swept[1:] != swept[:-1]
    np.testing.assert_array_equal((flags & _FIRST) != 0, np.r_[True, turns])
    np.testing.assert_array_equal((flags & _LAST) != 0, np.r_[turns, True])
    assert (np.diff(swept) >= 0).all()
    assert mask.tile_counts(s, bq, bk) == {
        "empty": int((~some).sum()), "full": int(full.sum()),
        "partial": int((some & ~full).sum())}


def test_tile_table_at_the_timed_shapes():
    """288 steps a head where the rectangle had 1,024 (SDAR), 136 of 256
    (Nemotron, Solar), 36 of 64 (granite); ``bwd_dkv`` walks a key tile's
    sweep once a head of the group; a table no entry can name is refused."""
    sdar = Mask("block_diffusion", 4, 8192)
    assert tile_table(sdar, 16384, 512, 512).size == 288
    assert tile_table(sdar, 16384, 512, 512, True, 8).size == 288 * 8
    assert tile_table(Mask("causal"), 8192, 512, 512).size == 136
    assert tile_table(Mask("causal"), 4096, 512, 512, True, 4).size == 36 * 4
    assert tile_table(Mask("none"), 200, 128, 128).size == 4
    flags = _unpack(tile_table(sdar, 16384, 512, 512))[3]
    assert ((flags & _FULL) != 0).sum() == 240
    with pytest.raises(ValueError, match="more than a table entry can name"):
        tile_table(Mask("causal"), 1025 * 8, 8, 8)
    with pytest.raises(ValueError, match="more than a table entry can name"):
        tile_table(Mask("causal"), 256, 128, 128, True, 257)


@pytest.mark.parametrize("mask,s", MASKS, ids=lambda v: str(v))
def test_table_walk_matches_the_reference_over_a_group_of_four(mask, s):
    """bq != bk, a padded tail, four query heads on ONE key/value head:
    values and the three gradients against ``reference_attention``."""
    rng = np.random.default_rng(39)
    q, k, v, do = (jnp.asarray(rng.standard_normal((2, h, s, 16)),
                               jnp.float32) for h in (4, 1, 1, 4))
    got, vjp = jax.vjp(lambda *a: flash_attention(*a, mask, None, 128, 64),
                       q, k, v)
    with jax.default_matmul_precision("highest"):
        want, ref_vjp = jax.vjp(lambda *a: reference_attention(*a, mask),
                                q, k, v)
        grads = ref_vjp(do)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip("qkv", vjp(do), grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("blocks", TILINGS[:2])
@pytest.mark.parametrize("mask,s", MASKS, ids=lambda v: str(v))
def test_no_grid_step_is_taken_for_an_empty_tile(mask, s, blocks):
    """The engagement gauge: each kernel's grid steps a query head, as the
    call is traced, equal the mask's partial + full tiles; and the grids
    of the three ``pallas_call``s in the program are those tables' long."""
    q, k, v, do = _qkv(s)

    def both(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: flash_attention(*a, mask, None,
                                                      *blocks), q, k, v)
        return (out,) + vjp(do)

    jaxpr = jax.make_jaxpr(both)(q, k, v, do)
    counts = mask.tile_counts(s, *_tiles(s, *blocks)[:2])
    computed = counts["partial"] + counts["full"]
    gauge = telemetry.REGISTRY.get("mxnet_flash_attention_grid_steps")
    assert {kernel: gauge.value({"mask": mask.kind, "kernel": kernel})
            for kernel in ("fwd", "bwd_dq", "bwd_dkv")} == {
        "fwd": computed, "bwd_dq": computed, "bwd_dkv": computed}
    grids = {}
    for name, grid in _pallas_grids(jaxpr.jaxpr):
        grids.setdefault(name, set()).add(grid)
    # batch 2: 8 query heads, 4 key/value heads with a group of 2 each
    assert grids == {"mx_flash_attention_fwd": {(8, computed)},
                     "mx_flash_attention_bwd_dq": {(8, computed)},
                     "mx_flash_attention_bwd_dkv": {(4, 2 * computed)}}


def _pallas_grids(jaxpr):
    """``(kernel's name, grid)`` of every ``pallas_call`` under ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], tuple(eqn.params["grid_mapping"].grid)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_grids(sub)


def test_masks_that_cannot_be_are_refused():
    for bad in (("windowed",), ("causal", 4), ("block_causal", 0),
                ("block_diffusion", 4, 0), ("block_diffusion", 4, 10)):
        with pytest.raises(ValueError, match="no such mask"):
            Mask(*bad)
    assert Mask.of(True) == Mask("causal") and Mask.of(False) == Mask()
    q, k, v = (nd.array(np.asarray(a)) for a in _qkv(72)[:3])
    got = nd.contrib.flash_attention(q, k, v, mask="block_diffusion",
                                     mask_block=4, mask_half=36)
    want = flash_attention(*_qkv(72)[:3], Mask("block_diffusion", 4, 36))
    np.testing.assert_array_equal(got.asnumpy(), want)


# -- (b) the rotary operator -------------------------------------------------------
def _rotary(x, positions, base):
    """The definition, a pair of channels at a time: channel i of the
    first half turns with channel i of the second by p · base^(−2i/d)."""
    d = x.shape[-1]
    out = np.empty_like(x, dtype=np.float64)
    for i in range(d // 2):
        angle = positions * float(base) ** (-2.0 * i / d)
        a, b = x[..., i], x[..., i + d // 2]
        out[..., i] = a * np.cos(angle) - b * np.sin(angle)
        out[..., i + d // 2] = b * np.cos(angle) + a * np.sin(angle)
    return out


@pytest.mark.parametrize("per_row", [False, True])
def test_rotary_embedding_forward_and_gradient(per_row):
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 3, 10, 8)).astype(np.float32)
    # repeated positions, as the two copies of the training layout carry
    positions = np.array([0, 1, 2, 3, 4] * 2, np.int32)
    if per_row:
        positions = np.stack([positions, positions[::-1] + 7])
    data = nd.array(x)
    data.attach_grad()
    with autograd.record():
        out = nd.contrib.rotary_embedding(data, nd.array(positions),
                                          base=100.0)
        (out * out * nd.array(x[::-1])).sum().backward()
    pos = positions[:, None, :] if per_row else positions
    np.testing.assert_allclose(out.asnumpy(), _rotary(x, pos, 100.0),
                               rtol=1e-5, atol=1e-6)
    # a rotation: lengths are kept, and the same position turns alike
    np.testing.assert_allclose(np.linalg.norm(out.asnumpy(), axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    cos_sin = jnp.asarray(pos, jnp.float32)[..., None] * 100.0 ** (
        -jnp.arange(0, 8, 2) / 8)

    def plain(v):
        a, b = v[..., :4], v[..., 4:]
        c, s = jnp.cos(cos_sin), jnp.sin(cos_sin)
        o = jnp.concatenate([a * c - b * s, b * c + a * s], -1)
        return (o * o * x[::-1]).sum()
    np.testing.assert_allclose(data.grad.asnumpy(), jax.grad(plain)(x),
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(mx.MXNetError, match="rotary_embedding"):
        nd.contrib.rotary_embedding(data, nd.array(positions[..., :9]))


# -- (c) TrainStep with a third batch array ----------------------------------------
def test_train_step_hands_the_third_array_to_the_loss():
    """A dense layer under softmax cross-entropy: with weights the loss
    is the weighted mean, its update the weighted gradient; the counters
    count the positions and the masked among them; a step built on two
    arrays refuses a third and the other way round."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 6, 8)).astype(np.float32)
    y = rng.integers(0, 5, (4, 6)).astype(np.float32)
    w = (rng.random((4, 6, 1)) * (rng.random((4, 6, 1)) < 0.5)
         ).astype(np.float32)
    mesh = make_mesh(devices=jax.devices()[:1], dp=1)

    def build(batch):
        mx.random.seed(0)
        net = gluon.nn.Dense(5, flatten=False, in_units=8)
        net.initialize(mx.initializer.Normal(0.5))
        return TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 1.0}, mesh,
                         example_batch=tuple(nd.array(a) for a in batch))

    step = build((x, y, w))
    weight, bias = (np.asarray(a) for a in step.params)

    def loss(p):
        logp = jax.nn.log_softmax(x @ p[0].T + p[1], axis=-1)
        picked = jnp.take_along_axis(logp, y[..., None].astype(int), -1)
        return -jnp.mean(w * picked)

    want, grads = jax.value_and_grad(loss)((weight, bias))
    telemetry.enable()
    try:
        before = [telemetry.REGISTRY.get(k).value() for k in (
            "mxnet_diffusion_positions_total",
            "mxnet_diffusion_masked_positions_total")]
        got = float(step(x, y, w))
        after = [telemetry.REGISTRY.get(k).value() for k in (
            "mxnet_diffusion_positions_total",
            "mxnet_diffusion_masked_positions_total")]
        record = [r for r in telemetry.span_records()
                  if r["name"] == "spmd/step/shard_batch"][-1]
    finally:
        telemetry.disable()
    assert got == pytest.approx(float(want), rel=1e-5)
    for new, old, g in zip(step.params, (weight, bias), grads):
        np.testing.assert_allclose(old - np.asarray(new), g, rtol=1e-4,
                                   atol=1e-6)
    assert [b - a for a, b in zip(before, after)] == [24, (w != 0).sum()]
    assert record["counts"]["mxnet_diffusion_positions_total"] == 24
    assert record["counts"]["mxnet_io_stage_bytes_total"] == \
        x.nbytes + y.nbytes + w.nbytes
    with pytest.raises(mx.MXNetError, match="3 batch arrays"):
        step(x, y)
    two = build((x, y))
    assert float(two(x, y)) == pytest.approx(float(jax.value_and_grad(
        lambda p: -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(
            x @ p[0].T + p[1], -1), y[..., None].astype(int), -1)))(
                (weight, bias))[0]), rel=1e-5)
    with pytest.raises(mx.MXNetError, match="2 batch arrays"):
        two(x, y, w)
    with pytest.raises(mx.MXNetError, match="sample_weight"):
        TrainStep(gluon.nn.Dense(5, in_units=8), lambda p, l: p.sum(), "sgd",
                  {}, mesh, example_batch=(x, y, w))


# -- (d) the model against the plain reference ------------------------------------
def _model(cfg, seed=0):
    mx.random.seed(seed)
    net = REF.build(cfg, "gluon")
    net.initialize(mx.initializer.Normal(0.02))
    names = REF.canonical(cfg, "gluon", net)
    params = {names[k]: p.data().asnumpy()
              for k, p in net.collect_params().items()}
    return net, names, params


def _batch(cfg, batch=2, seed=0):
    """``([x0 ; xt], x0, weights)`` by the cell's own driver."""
    driver = CELL.driver_module()
    x0 = np.random.default_rng(seed).integers(
        0, cfg["mask_token_id"], (batch, cfg["image"][0])).astype(np.int32)
    xt, w = driver.noised_pool(seed, x0, cfg["block_length"],
                               cfg["mask_token_id"], 0.001)
    return np.concatenate([x0, xt], axis=-1), x0, w


SHAPES = REF.param_shapes(SMALL)
TRAINED = sorted(REF.trained(SHAPES))


@pytest.fixture(scope="module")
def trained():
    """One SGD step at learning rate 1 through ``TrainStep`` with the
    weight array, with and without remat: the update IS the gradient."""
    out = {}
    for remat in (False, True):
        net, names, params = _model(SMALL)
        x, y, w = _batch(SMALL)
        step = TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 1.0},
            make_mesh(devices=jax.devices()[:1], dp=1),
            example_batch=tuple(nd.array(a) for a in (x, y, w[..., None])),
            remat=remat)
        with step.mesh.jax_mesh:
            logits = np.asarray(jax.jit(lambda ps, a: step._apply(
                jax.random.PRNGKey(0), ps, (a,))[0][0])(step.params, x))
        loss = float(step(x, y, w[..., None]))
        out[remat] = dict(
            params=params, x=x, y=y, w=w, logits=logits, loss=loss,
            after={names[n]: np.asarray(a)
                   for n, a in zip(step.param_names, step.params)},
            boundaries=step.remat_boundaries,
            aux=sorted(names[step.param_names[i]] for i in step._aux_idx))
    with jax.default_matmul_precision("highest"):
        t = out[True]
        out["ref_logits"], out["ref_margin"], out["ref_counts"] = (
            np.asarray(a) for a in REF.reference(SMALL, routing=True)(
                t["params"], t["x"]))
        out["ref_loss"], out["ref_grads"] = jax.value_and_grad(
            REF.loss(SMALL))(t["params"], t["x"], t["y"], t["w"])
    return out


def test_every_size_is_given_and_the_layouts_are_checked():
    net = REF.build(SMALL, "gluon")
    net.initialize(mx.initializer.Normal(0.02))
    assert all(p._data is not None for p in net.collect_params().values())
    names = REF.canonical(SMALL, "gluon", net)
    assert {names[k]: tuple(p.shape)
            for k, p in net.collect_params().items()} == {
                k: tuple(s) for k, s in SHAPES.items()}
    with pytest.raises(ValueError, match="whole blocks"):
        net(nd.array(np.zeros((1, 2 * T + 2), np.int32)))


@pytest.mark.parametrize("remat", [False, True])
def test_logits_and_loss_match_the_reference(trained, remat):
    t = trained[remat]
    assert t["logits"].shape == (2, T, 64)
    np.testing.assert_allclose(t["logits"], trained["ref_logits"],
                               rtol=2e-4, atol=2e-5)
    assert t["loss"] == pytest.approx(float(trained["ref_loss"]), rel=1e-5)
    # about half the positions are masked, and the weights are 1/t
    assert 0.2 < (t["w"] > 0).mean() < 0.8 and t["w"].max() > 1.0


@pytest.mark.parametrize("name", TRAINED)
def test_gradient_of_every_parameter_matches_the_reference(trained, name):
    """learning rate 1, no momentum: before − after = the gradient, to
    within the float32 spacing of the parameter it was taken from."""
    t = trained[True]
    got = t["params"][name] - t["after"][name]
    want = np.asarray(trained["ref_grads"][name])
    assert np.abs(want).max() > 0, "the reference never reads it"
    spacing = float(np.spacing(np.abs(t["params"][name]).max()))
    np.testing.assert_allclose(
        got, want, rtol=2e-3,
        atol=2e-4 * float(np.abs(want).max()) + spacing)


def test_remat_holds_a_boundary_per_layer_and_changes_nothing(trained):
    assert trained[True]["boundaries"] == SMALL["num_hidden_layers"] == 3
    assert trained[False]["boundaries"] == 0
    for name, a in trained[True]["after"].items():
        np.testing.assert_allclose(a, trained[False]["after"][name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)
    t = trained[True]
    assert t["aux"] == sorted(REF.AUX)
    # the step's own count of its first forward is the reference's
    np.testing.assert_array_equal(t["after"]["expert_load"],
                                  trained["ref_counts"])
    assert trained["ref_margin"].shape == (3, 2, 2 * T)


def test_the_driver_keeps_a_token_by_its_block(trained):
    """A noisy position's margin is the least over the layers and the
    noisy positions of its block: they alone see its key."""
    driver = CELL.driver_module()
    margin = trained["ref_margin"]
    least = margin.min(axis=0)
    got = driver.token_margin(margin, B)
    assert got.shape == (2, T)
    for i in (0, 5, T - 1):
        block = slice(T + i // B * B, T + i // B * B + B)
        assert got[1, i] == least[1, block].min()


def test_named_scopes_are_in_the_step_program():
    net, _names, _params = _model(SMALL)
    x, y, w = _batch(SMALL)
    step = TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {"learning_rate": 1e-3},
        make_mesh(devices=jax.devices()[:1], dp=1),
        example_batch=tuple(nd.array(a) for a in (x, y, w[..., None])),
        remat=True)
    text = step._step.lower(
        jax.random.PRNGKey(0), step._train_params, step._aux_params,
        step.opt_state, x, y, w[..., None]).as_text(debug_info=True)
    for scope in ("sdar/attention", "qk_norm", "rope",
                  "op/_contrib_rotary_embedding",
                  "op/_contrib_flash_attention", "sdar/moe",
                  "routed_experts/router", "sdar/head", "step/loss",
                  "step/optimizer"):
        assert scope in text, scope
    assert "sdar/moe/shared" not in text        # there is no shared expert


# -- (e) the leak test -------------------------------------------------------------
def _denoise_inputs(x, b):
    """``[x0 blocks < b ; xt block b]`` of the training batch ``x``."""
    x0, xt = x[:, :T], x[:, T:]
    return np.concatenate([x0[:, :b * B], xt[:, b * B:(b + 1) * B]], axis=-1)


def test_leak_in_the_reference(trained):
    """For EVERY block b the training layout's logits of block b are the
    denoising forward's on ``[x0 blocks < b ; xt block b]``, and changing
    ``x0`` inside block b (or after it) changes neither."""
    t = trained[True]
    denoise = REF.denoise(SMALL)
    with jax.default_matmul_precision("highest"):
        for b in range(T // B):
            got = np.asarray(denoise(t["params"], _denoise_inputs(t["x"], b)))
            np.testing.assert_allclose(
                got[:, -B:], trained["ref_logits"][:, b * B:(b + 1) * B],
                rtol=2e-4, atol=2e-5, err_msg=f"block {b}")
        changed = t["x"].copy()
        changed[:, 3 * B:4 * B] = (changed[:, 3 * B:4 * B] + 7) % 63
        moved = np.asarray(REF.reference(SMALL)(t["params"], changed))
    np.testing.assert_array_equal(moved[:, :4 * B],
                                  trained["ref_logits"][:, :4 * B])
    assert np.abs(moved[:, 4 * B:] - trained["ref_logits"][:, 4 * B:]
                  ).max() > 1e-4


def test_leak_in_the_program(trained):
    t = trained[True]
    net, _names, _params = _model(SMALL)
    whole = net(nd.array(t["x"])).asnumpy()
    np.testing.assert_allclose(whole, t["logits"], rtol=1e-5, atol=1e-6)
    with net.denoising():
        assert net.layout == "denoising"
        for b in range(T // B):
            got = net(nd.array(_denoise_inputs(t["x"], b))).asnumpy()
            np.testing.assert_allclose(
                got[:, -B:], whole[:, b * B:(b + 1) * B], rtol=2e-4,
                atol=2e-5, err_msg=f"block {b}")
    assert net.layout == "training"
    assert net.layers[0].attention.mask == "block_diffusion"
    changed = t["x"].copy()
    changed[:, 3 * B:4 * B] = (changed[:, 3 * B:4 * B] + 7) % 63
    moved = net(nd.array(changed)).asnumpy()
    np.testing.assert_array_equal(moved[:, :4 * B], whole[:, :4 * B])
    assert np.abs(moved[:, 4 * B:] - whole[:, 4 * B:]).max() > 1e-4


# -- (f) the shares add up to the uncut layer --------------------------------------
def _set(block, values):
    block.initialize()
    for name, value in values.items():
        getattr(block, name).set_data(nd.array(value))


def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 holders of two, softmax top-3 renormalised, no
    shared expert: the eight shares' outputs add up to the uncut
    reference's mixture, and the reference's own shares do too."""
    cfg = dict(SMALL, num_experts=16, first_routed_expert=0)
    at = "layers.1.moe."
    rng = np.random.default_rng(4)
    p = {k[len(at):]: (rng.standard_normal(s) * 0.3).astype(np.float32)
         for k, s in REF.param_shapes(cfg).items() if k.startswith(at)}
    h = rng.standard_normal((2, 21, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF._moe(p, "", jnp.asarray(h), cfg))
        ref_shares = sum(np.asarray(REF._moe(
            {"router": p["router"], **{k: p[k][first:first + 2]
                                       for k in ("w1", "w3", "w2")}},
            "", jnp.asarray(h), cfg, held=(first, 2)))
            for first in range(0, 16, 2))
    np.testing.assert_allclose(ref_shares, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()))
    total, loads = 0.0, []
    for first in range(0, 16, 2):
        held = slice(first, first + 2)
        block = SparseExperts(64, 32, 16, 2, first, 3, shared_experts=0,
                              tile=4, score_function="softmax")
        assert block.shared is None
        _set(block, {"router_weight": p["router"], "w1": p["w1"][held],
                     "w3": p["w3"][held], "w2": p["w2"][held]})
        y, load, _rows = block(nd.array(h))
        total = total + y.asnumpy()
        loads.append(load.asnumpy())
    np.testing.assert_allclose(total, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()))
    # every one of the 42 positions' 3 choices was computed by some holder
    assert np.concatenate(loads).sum() == 42 * 3
