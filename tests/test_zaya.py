"""The ZAYA1 style decoder (gluon.model_zoo.language.zaya) at a small size
on the CPU: the grouped causal convolution against explicit sums (value and
gradient) and the depthwise call bit-equal to the parent's code; a partial
rotary turn against the reference and the whole-head call bit-equal; the
routed-expert op fed logits against the same op fed the router's matrix,
and top-1's gradient into the router against a masked loop; compressed
convolutional attention, forward and every parameter's gradient, against
the benchmark's plain reference at two head layouts and two pairs of taps;
the leak test (no output before ``t`` hears token ``t``, the value's second
half at ``t + 1`` does); the router with and without its state; the share
test (the holders of experts 0–7 and 8–15 add up to the uncut layer); the
whole model against the reference (logits, loss, every parameter's
gradient) with remat on and off, TWO activations across each boundary; the
selection bias and the load as auxiliary state through ``state_dict`` and
back; the two gauges."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon.model_zoo.language import (
    CompressedConvAttention, SparseExperts, ZayaRouter)
from mxnet_tpu.ops import _op_ssm
from mxnet_tpu.ops._op_moe import routed_experts
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "harness"))
import benchcore  # noqa: E402

CELL = benchcore.Cell("zaya1-8b-spmd-seq8192-bs1")
REF = CELL.config_module()
# hidden 32; 4 query heads over 2 key/value heads of 8, the first 4 channels
# of a head turned; experts 4-7 of 8 held, ONE a token, tiles of 4 rows; a
# router of width 12; 3 layers; 21 positions: a tail in the tiles
SMALL = dict(
    CELL.config, hidden_size=32, head_dim=8, num_attention_heads=4,
    num_key_value_heads=2, moe_intermediate_size=16, num_experts=4,
    published={"num_experts": 8}, first_routed_expert=4,
    router_hidden_size=12, expert_tile_rows=4, vocab_size=64, num_classes=64,
    image=[22], num_hidden_layers=3, router_bias_init_sigma=1e-3)


def _f(rng, *shape, scale=1.0):
    return (rng.standard_normal(shape) * scale).astype(np.float32)


# -- (a) the grouped causal convolution -------------------------------------------
def _conv_by_hand(x, w, b):
    """Every output one by one: the definition."""
    bsz, t, channels = x.shape
    per, k = w.shape[1], w.shape[2]
    y = np.zeros(x.shape, np.float64)
    for n in range(bsz):
        for s in range(t):
            for c in range(channels):
                first = (c // per) * per
                acc = float(b[c])
                for j in range(k):
                    at = s - (k - 1) + j
                    if at >= 0:
                        acc += float(np.dot(
                            w[c, :, j].astype(np.float64),
                            x[n, at, first:first + per]))
                y[n, s, c] = acc
    return y


@pytest.mark.parametrize("groups,per,k", [(3, 4, 2), (2, 8, 3), (5, 2, 1)])
def test_grouped_conv_is_the_sum_it_says(groups, per, k):
    rng = np.random.default_rng(0)
    channels = groups * per
    x, w, b = _f(rng, 3, 9, channels), _f(rng, channels, per, k), \
        _f(rng, channels)
    got = nd.contrib.causal_conv1d(nd.array(x), nd.array(w), nd.array(b))
    np.testing.assert_allclose(got.asnumpy(), _conv_by_hand(x, w, b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["data", "weight", "bias"])
def test_grouped_conv_gradient_is_the_reference_s(wrt):
    rng = np.random.default_rng(1)
    args = (_f(rng, 2, 7, 12), _f(rng, 12, 4, 2), _f(rng, 12))
    weight = _f(rng, 2, 7, 12)

    def through(fn):
        return jax.grad(lambda *a: (fn(*a) * weight).sum(), argnums=wrt)(
            *map(jnp.asarray, args))

    got = through(lambda x, w, b: _op_ssm._causal_conv1d({}, x, w, b))
    want = through(REF._grouped_conv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _parent_depthwise(x, weight, bias):
    """The operator's body at the parent commit, word for word."""
    k = weight.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    y = bias.astype(x.dtype)
    for j in range(k):
        y = y + xp[:, j:j + t, :] * weight[:, j].astype(x.dtype)
    return y


def test_depthwise_conv_lowers_as_the_parent_s():
    rng = np.random.default_rng(2)
    args = tuple(map(jnp.asarray, (_f(rng, 2, 11, 6), _f(rng, 6, 4),
                                   _f(rng, 6))))
    ours = jax.jit(lambda *a: _op_ssm._causal_conv1d({}, *a))
    theirs = jax.jit(lambda *a: _parent_depthwise(*a))
    assert ours.lower(*args).as_text() == theirs.lower(*args).as_text()
    np.testing.assert_array_equal(ours(*args), theirs(*args))
    with pytest.raises(mx.MXNetError, match="grouped weight"):
        nd.contrib.causal_conv1d(nd.ones((1, 4, 6)), nd.ones((6, 4, 2)),
                                 nd.ones((6,)))


# -- (b) rotary positions on the leading channels of a head -------------------------
def _parent_rotary(x, positions, base):
    """The operator's body at the parent commit, word for word."""
    d = x.shape[-1]
    inv_freq = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    if angle.ndim == 3:
        angle = angle[:, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


@pytest.mark.parametrize("rotary", [4, 8, 16])
def test_rotary_dim_turns_the_leading_channels_alone(rotary):
    rng = np.random.default_rng(3)
    x = _f(rng, 2, 3, 10, 16)
    got = nd.contrib.rotary_embedding(
        nd.array(x), nd.arange(10, dtype="int32"), base=5e6,
        rotary_dim=rotary).asnumpy()
    want = REF._rope(jnp.asarray(x), rotary, 5e6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[..., rotary:], x[..., rotary:])
    assert np.abs(got[:, :, 1:, :rotary] - x[:, :, 1:, :rotary]).max() > 0.1


def test_rotary_default_lowers_as_the_parent_s():
    rng = np.random.default_rng(4)
    x, pos = jnp.asarray(_f(rng, 2, 3, 10, 16)), jnp.arange(10)
    ours = jax.jit(lambda a, p: _op_ssm._rotary_embedding(
        {"base": 1e6}, a, p))
    theirs = jax.jit(lambda a, p: _parent_rotary(a, p, 1e6))
    assert ours.lower(x, pos).as_text() == theirs.lower(x, pos).as_text()
    np.testing.assert_array_equal(ours(x, pos), theirs(x, pos))
    for bad in (6 + 1, 0, 18):
        with pytest.raises(mx.MXNetError, match="rotary_dim"):
            nd.contrib.rotary_embedding(
                nd.array(np.asarray(x)), nd.arange(10, dtype="int32"),
                rotary_dim=bad)


# -- (c) the routed experts fed logits ------------------------------------------------
def _expert_inputs(total=8, held=4, hidden=16, width=12):
    rng = np.random.default_rng(5)
    return (_f(rng, 2, 19, hidden), _f(rng, total, hidden, scale=0.5),
            _f(rng, held, width, hidden, scale=0.3),
            _f(rng, held, width, hidden, scale=0.3),
            _f(rng, held, hidden, width, scale=0.3), _f(rng, total,
                                                        scale=0.05))


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
@pytest.mark.parametrize("top_k,norm", [(1, False), (2, True)])
@pytest.mark.parametrize("bias", [False, True])
def test_router_logits_give_what_the_router_weight_gives(bias, top_k, norm,
                                                         score):
    h, router, w1, w3, w2, b = _expert_inputs()
    logits = np.asarray(jnp.matmul(h, router.T, precision="highest"))
    attrs = dict(experts_total=8, top_k=top_k, first_expert=2, tile=4,
                 norm_topk_prob=norm, select_bias=bias, score_function=score)
    tail = [nd.array(v) for v in (w1, w3, w2)] \
        + ([nd.array(b)] if bias else [])
    want = nd.contrib.routed_experts(nd.array(h), nd.array(router), *tail,
                                     **attrs)
    got = nd.contrib.routed_experts(nd.array(h), nd.array(logits), *tail,
                                    router="logits", **attrs)
    assert len(got) == len(want) == 3 + bias
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.asnumpy(), w.asnumpy())
    assert want[1].asnumpy().sum() > 0


def test_router_logits_refuse_what_does_not_fit():
    h, router, w1, w3, w2, _ = _expert_inputs()
    tail = [nd.array(v) for v in (w1, w3, w2)]
    with pytest.raises(mx.MXNetError, match="router"):
        nd.contrib.routed_experts(nd.array(h), nd.array(router), *tail,
                                  top_k=1, router="logits")
    with pytest.raises(mx.MXNetError, match="router"):
        nd.contrib.routed_experts(nd.array(h), nd.array(router), *tail,
                                  top_k=1, router="matrix")
    with pytest.raises(mx.MXNetError, match="experts_total"):
        nd.contrib.routed_experts(
            nd.array(h), nd.array(np.zeros((2, 19, 8), np.float32)), *tail,
            top_k=1, router="logits", experts_total=16)


def _top1_loop(h, logits, w1, w3, w2, first, bias=None):
    """Top-1 of softmax, no renorming: the one weight IS the score."""
    x = h.reshape(-1, h.shape[-1])
    scores = jax.nn.softmax(logits.reshape(-1, logits.shape[-1]), -1)
    expert = jnp.argmax(scores if bias is None else scores + bias, -1)
    weight = jnp.take_along_axis(scores, expert[:, None], -1)[:, 0]
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        mid = jax.nn.silu(x @ w1[e].T) * (x @ w3[e].T)
        y = y + jnp.where(expert == first + e, weight, 0.0)[:, None] \
            * (mid @ w2[e].T)
    return y.reshape(h.shape)


@pytest.mark.parametrize("bias", [False, True])
def test_top1_learns_its_router_through_the_one_weight(bias):
    """Top-1 without renorming: the router's only gradient is the walk's
    ``dweight``; it is the masked loop's, and not zero."""
    h, router, w1, w3, w2, b = map(jnp.asarray, _expert_inputs())
    b = b if bias else None
    logits = jnp.matmul(h, router.T, precision="highest")
    weight = jnp.asarray(_f(np.random.default_rng(6), *h.shape))
    got = jax.grad(lambda l: (routed_experts(
        h, l, w1, w3, w2, 1, 2, norm_topk=False, tile=4,
        select_bias=b, score_function="softmax", router="logits")[0]
        * weight).sum())(logits)
    want = jax.grad(lambda l: (_top1_loop(h, l, w1, w3, w2, 2, b)
                               * weight).sum())(logits)
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()))


# -- (d) compressed convolutional attention -------------------------------------------
def _set(block, values):
    block.initialize()
    for name, value in values.items():
        getattr(block, name).set_data(nd.array(value))


ATTN = {"q": "q_weight", "k": "k_weight", "v1": "v1_weight",
        "v2": "v2_weight", "o": "o_weight", "conv0_w": "conv0_weight",
        "conv0_b": "conv0_bias", "conv1_w": "conv1_weight",
        "conv1_b": "conv1_bias", "temp": "temperature"}


def _cca(heads, kv_heads, taps, seed=7, hidden=24, d=8, rotary=4):
    cfg = dict(SMALL, hidden_size=hidden, head_dim=d,
               num_attention_heads=heads, num_key_value_heads=kv_heads,
               cca_time0=taps[0], cca_time1=taps[1], num_hidden_layers=1,
               rope_parameters={"hybrid": {
                   "partial_rotary_factor": rotary / d, "rope_theta": 5e6}})
    rng = np.random.default_rng(seed)
    at = "layers.0.attn."
    p = {k[len(at):]: _f(rng, *s, scale=0.4)
         for k, s in REF.param_shapes(cfg).items() if k.startswith(at)}
    block = CompressedConvAttention(hidden, heads, kv_heads, d, taps, rotary,
                                    5e6)
    _set(block, {ATTN[k]: v for k, v in p.items()})
    return cfg, p, block


LAYOUTS = [(8, 2, (2, 2)), (4, 4, (2, 2)), (8, 2, (3, 1)), (4, 4, (3, 1))]


@pytest.mark.parametrize("heads,kv_heads,taps", LAYOUTS)
def test_cca_forward_and_every_gradient_match_the_reference(heads, kv_heads,
                                                            taps):
    cfg, p, block = _cca(heads, kv_heads, taps)
    rng = np.random.default_rng(8)
    a, weight = _f(rng, 2, 13, 24), _f(rng, 2, 13, 24)
    x = nd.array(a)
    x.attach_grad()
    with autograd.record():
        out = block(x)
        (out * nd.array(weight)).sum().backward()
    with jax.default_matmul_precision("highest"):
        want = REF._cca(p, "", jnp.asarray(a), cfg)
        grads = jax.grad(lambda q, v: (REF._cca(q, "", v, cfg)
                                       * weight).sum(), argnums=(0, 1))(
            p, jnp.asarray(a))
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(x.grad.asnumpy(), grads[1], rtol=1e-3,
                               atol=1e-4 * float(np.abs(grads[1]).max()))
    for name, g in grads[0].items():
        got = getattr(block, ATTN[name]).grad().asnumpy()
        assert np.abs(g).max() > 0, name
        np.testing.assert_allclose(
            got, g, rtol=1e-3, atol=1e-4 * float(np.abs(g).max()),
            err_msg=name)


def test_cca_hears_nothing_from_the_future_and_the_value_is_shifted():
    """Changing token ``t`` changes no output before ``t``; the value's
    second half at ``t + 1`` is the changed token's."""
    cfg, p, block = _cca(8, 2, (2, 2))
    rng = np.random.default_rng(9)
    a = _f(rng, 1, 16, 24)
    b = a.copy()
    t = 9
    b[0, t] += _f(rng, 24)
    out_a, out_b = (block(nd.array(v)).asnumpy() for v in (a, b))
    np.testing.assert_array_equal(out_a[0, :t], out_b[0, :t])
    assert np.abs(out_a[0, t:] - out_b[0, t:]).min(axis=-1).min() > 0
    # the reference's values: (batch, heads, T, d), halves of 4
    v = lambda z: np.concatenate([                      # noqa: E731
        (z @ p["v1"].T).reshape(1, 16, 2, 4),
        (np.pad(z, [(0, 0), (1, 0), (0, 0)])[:, :-1] @ p["v2"].T
         ).reshape(1, 16, 2, 4)], -1)
    va, vb = v(a), v(b)
    changed = np.abs(va - vb).max(axis=(0, 2))          # (T, d)
    assert changed[t, :4].min() > 0 and changed[t, 4:].max() == 0
    assert changed[t + 1, 4:].min() > 0 and changed[t + 1, :4].max() == 0
    assert changed[np.r_[:t, t + 2:16]].max() == 0
    # and the block computes those values: with W_v1 = 0 the output at t
    # no longer hears a change of token t through v, at t + 1 it does
    block.v1_weight.set_data(nd.zeros(p["v1"].shape))
    block.q_weight.set_data(nd.zeros(p["q"].shape))
    block.k_weight.set_data(nd.zeros(p["k"].shape))
    out_a, out_b = (block(nd.array(z)).asnumpy() for z in (a, b))
    np.testing.assert_array_equal(out_a[0, :t + 1], out_b[0, :t + 1])
    assert np.abs(out_a[0, t + 1] - out_b[0, t + 1]).max() > 0


def test_cca_refuses_heads_that_do_not_group():
    with pytest.raises(ValueError, match="query heads"):
        CompressedConvAttention(32, 6, 4, 8)


# -- (e) the router and its state -----------------------------------------------------
ROUTER = {"down_w": "down_weight", "down_b": "down_bias", "gamma": "gamma",
          "w1": "fc1_weight", "b1": "fc1_bias", "w2": "fc2_weight",
          "b2": "fc2_bias", "w3": "out_weight", "b3": "out_bias"}


def _router(gamma, seed=10):
    rng = np.random.default_rng(seed)
    at = "layers.0.router."
    p = {k[len(at):]: _f(rng, *s, scale=0.5)
         for k, s in REF.param_shapes(SMALL).items() if k.startswith(at)}
    p["gamma"] = np.array([gamma], np.float32)
    block = ZayaRouter(32, 12, 8)
    _set(block, {ROUTER[k]: v for k, v in p.items() if k != "norm"})
    block.norm.gamma.set_data(nd.array(p["norm"]))
    return p, block


@pytest.mark.parametrize("gamma", [0.0, 0.7])
def test_router_carries_its_state_from_the_layer_before(gamma):
    p, block = _router(gamma)
    rng = np.random.default_rng(11)
    m, r0, r1 = _f(rng, 2, 9, 32), _f(rng, 2, 9, 12), _f(rng, 2, 9, 12)
    logits, r = block(nd.array(m), nd.array(r0))
    with jax.default_matmul_precision("highest"):
        scores, want_r = REF._router(p, "", jnp.asarray(m), jnp.asarray(r0),
                                     SMALL)
    np.testing.assert_allclose(r.asnumpy(), want_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        jax.nn.softmax(logits.asnumpy(), -1), scores, rtol=1e-5, atol=1e-7)
    other = block(nd.array(m), nd.array(r1))[0].asnumpy()
    if gamma:       # the layer before is heard
        assert np.abs(other - logits.asnumpy()).max() > 1e-3
    else:           # a stateless router: whatever came in, the same logits
        np.testing.assert_array_equal(other, logits.asnumpy())
        np.testing.assert_array_equal(
            r.asnumpy(), block(nd.array(m), nd.array(0 * r1))[1].asnumpy())


def test_router_computes_in_float32_whatever_it_is_given():
    _p, block = _router(0.3)
    rng = np.random.default_rng(12)
    m, r = _f(rng, 2, 5, 32), _f(rng, 2, 5, 12)
    m16 = nd.array(m).astype("bfloat16")
    logits, state = block(m16, nd.array(r))
    assert logits.dtype == state.dtype == np.float32
    want = block(m16.astype("float32"), nd.array(r))[0].asnumpy()
    np.testing.assert_array_equal(logits.asnumpy(), want)


# -- (f) the shares add up to the uncut layer -----------------------------------------
def test_expert_shares_add_up_to_the_uncut_layer():
    """8 experts over 2 holders of 4, one a token over s + β: the two
    shares' outputs are the uncut reference's mixture; both holders carry
    the same router state on and count the same assignments over all 8."""
    cfg = dict(SMALL, num_experts=8, first_routed_expert=0)
    rng = np.random.default_rng(13)
    p = {}
    for at in ("layers.0.router.", "layers.0.moe."):
        p.update({k[len("layers.0."):]: _f(rng, *s, scale=0.5)
                  for k, s in REF.param_shapes(cfg).items()
                  if k.startswith(at)})
    p["moe.bias"] *= 0.02
    m, r0 = _f(rng, 2, 21, 32), _f(rng, 2, 21, 12)
    with jax.default_matmul_precision("highest"):
        scores, want_r = REF._router(p, "router.", jnp.asarray(m),
                                     jnp.asarray(r0), cfg)
        want = REF._moe(p, "moe.", jnp.asarray(m), scores, cfg)
    total, loads, counts = 0.0, [], []
    for first in (0, 4):
        held = slice(first, first + 4)
        block = SparseExperts(
            32, 16, 8, 4, first, 1, shared_experts=0, norm_topk=False,
            tile=4, select_bias=True, score_function="softmax",
            router=lambda prefix: ZayaRouter(32, 12, 8, prefix=prefix))
        _set(block, {"w1": p["moe.w1"][held], "w3": p["moe.w3"][held],
                     "w2": p["moe.w2"][held], "select_bias": p["moe.bias"]})
        _set(block.router, {ROUTER[k[len("router."):]]: v
                            for k, v in p.items()
                            if k.startswith("router.") and k != "router.norm"})
        block.router.norm.gamma.set_data(nd.array(p["router.norm"]))
        y, load, _rows, count, r = block(nd.array(m), nd.array(r0))
        np.testing.assert_allclose(r.asnumpy(), want_r, rtol=1e-5, atol=1e-6)
        total = total + y.asnumpy()
        loads.append(load.asnumpy())
        counts.append(count.asnumpy())
    np.testing.assert_allclose(total, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()))
    # every one of the 42 tokens' one choice was computed by one holder
    assert np.concatenate(loads).sum() == 42 and min(
        load.sum() for load in loads) > 0
    for count in counts:
        np.testing.assert_array_equal(count, np.concatenate(loads))


def test_the_default_router_keeps_its_parameters():
    """Solar's, Nemotron's and SDAR's mixture builds as before: one matrix
    inside the op; a block as router brings its own and no matrix."""
    def names(block):
        return sorted(k.split("_", 1)[1] for k in block.collect_params())
    assert "router_weight" in names(SparseExperts(32, 16, 8, 4, 0, 2))
    with_block = names(SparseExperts(
        32, 16, 8, 4, 0, 1, shared_experts=0,
        router=lambda prefix: ZayaRouter(32, 12, 8, prefix=prefix)))
    assert "router_weight" not in with_block
    assert "router_down_weight" in with_block and "w1" in with_block


# -- (g) the model against the plain reference ---------------------------------------
def _model(cfg, seed=0):
    mx.random.seed(seed)
    net = REF.build(cfg, "gluon")
    net.initialize(mx.initializer.Normal(0.02))
    # a model some steps into training: the parameters that start at 0 or
    # 1 (depth averaging, temperature, residual scaling, biases) moved, so
    # that a wrong reading of any of them shows
    rng = np.random.default_rng(seed + 100)
    for name, p in net.collect_params().items():
        if p.grad_req != "null" and not name.endswith(
                ("_weight", "gamma")) or name.endswith("router_gamma"):
            p.set_data(p.data() + nd.array(_f(rng, *p.shape, scale=0.1)))
    names = REF.canonical(cfg, "gluon", net)
    params = {names[k]: p.data().asnumpy()
              for k, p in net.collect_params().items()}
    return net, names, params


def _batch(cfg, batch=2, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, cfg["image"][0])).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _step(net, x, y, remat, optimizer="sgd", params=None):
    mesh = make_mesh(devices=jax.devices()[:1], dp=1)
    return TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer,
                     params or {"learning_rate": 1.0, "momentum": 0.9}, mesh,
                     example_batch=(nd.array(x), nd.array(y)), remat=remat)


def _state(step, names):
    return {names[n]: np.asarray(a)
            for n, a in zip(step.param_names, step.params)}


SHAPES = REF.param_shapes(SMALL)
TRAINED = sorted(REF.trained(SHAPES))
BIAS = sorted(k for k in SHAPES if k.endswith("moe.bias"))


@pytest.fixture(scope="module")
def trained():
    """One SGD step from zero momentum at learning rate 1, with and
    without remat: the update IS the gradient."""
    out = {}
    for remat in (False, True):
        net, names, params = _model(SMALL)
        x, y = _batch(SMALL)
        step = _step(net, x, y, remat)
        with step.mesh.jax_mesh:
            logits = np.asarray(jax.jit(lambda ps, a: step._apply(
                jax.random.PRNGKey(0), ps, (a,))[0][0])(step.params, x))
        loss = float(step(x, y))
        out[remat] = dict(params=params, x=x, y=y, logits=logits, loss=loss,
                          after=_state(step, names),
                          boundaries=step.remat_boundaries,
                          aux=sorted(names[step.param_names[i]]
                                     for i in step._aux_idx))
    with jax.default_matmul_precision("highest"):
        t = out[True]
        out["ref_logits"] = np.asarray(
            REF.reference(SMALL)(t["params"], t["x"]))
        out["ref_loss"], out["ref_grads"] = jax.value_and_grad(
            REF.loss(SMALL))(t["params"], t["x"], t["y"])
        out["ref_bias"] = REF.updated_bias(SMALL)(t["params"], t["x"])
    return out


def test_every_size_is_given_so_nothing_waits_for_a_batch():
    net = REF.build(SMALL, "gluon")
    net.initialize(mx.initializer.Normal(0.02))
    assert all(p._data is not None for p in net.collect_params().values())
    shapes = {k: tuple(p.shape) for k, p in net.collect_params().items()}
    names = REF.canonical(SMALL, "gluon", net)
    assert {names[k]: s for k, s in shapes.items()} == \
        {k: tuple(s) for k, s in SHAPES.items()}
    # what starts somewhere else than N(0, 0.02)
    layer = net.layers[0]
    conv0 = layer.attention.conv0_weight.data().asnumpy()
    assert np.abs(conv0[:, -1] - 1).max() < 0.1 > np.abs(conv0[:, :-1]).max()
    assert np.abs(conv0[:, :-1]).max() > 0
    for p, start in ((layer.attention.temperature, 1.0),
                     (layer.moe.router.gamma, 0.0),
                     (layer.attention_residual.skip_scale, 1.0),
                     (layer.moe_residual.out_bias, 0.0),
                     (layer.attention.conv1_bias, 0.0),
                     (layer.moe.router.down_bias, 0.0)):
        assert (p.data().asnumpy() == start).all(), p.name
    bias = layer.moe.select_bias.data().asnumpy()
    assert 0 < np.abs(bias).max() < 5e-3        # N(0, 1e-3), not N(0, 0.02)
    zero = REF.build(dict(SMALL, router_bias_init_sigma=0.0), "gluon")
    zero.initialize(mx.initializer.Normal(0.02))
    assert not zero.layers[0].moe.select_bias.data().asnumpy().any()


@pytest.mark.parametrize("key,value,match", [
    ("sliding_window", 4096, "sliding_window"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("layer_types", ["hybrid", "hybrid_sliding", "hybrid"], "layer_types")])
def test_zaya_refuses_what_it_does_not_build(key, value, match):
    with pytest.raises(ValueError, match=match):
        REF.build(dict(SMALL, **{key: value}), "gluon")


@pytest.mark.parametrize("remat", [False, True])
def test_logits_and_loss_match_the_reference(trained, remat):
    np.testing.assert_allclose(trained[remat]["logits"],
                               trained["ref_logits"], rtol=1e-4, atol=1e-5)
    assert abs(trained[remat]["loss"] - float(trained["ref_loss"])) < 1e-5


@pytest.mark.parametrize("name", TRAINED)
def test_gradient_of_every_parameter_matches_the_reference(trained, name):
    """learning rate 1, momentum from zero: before − after = the gradient,
    to within the float32 spacing of the parameter it was taken from."""
    t = trained[True]
    got = t["params"][name] - t["after"][name]
    want = np.asarray(trained["ref_grads"][name])
    if name == "layers.0.router.gamma":     # it decays the zeros before it
        assert not want.any() and not got.any()
        return
    assert np.abs(want).max() > 0, "the reference never reads it"
    spacing = float(np.spacing(np.abs(t["params"][name]).max()))
    np.testing.assert_allclose(
        got, want, rtol=2e-3,
        atol=2e-4 * float(np.abs(want).max()) + spacing)


def test_remat_carries_two_arrays_across_every_boundary(trained):
    assert trained[True]["boundaries"] == SMALL["num_hidden_layers"] == 3
    assert trained[False]["boundaries"] == 0
    for name, a in trained[True]["after"].items():
        np.testing.assert_allclose(a, trained[False]["after"][name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)
    # the router's state is heard across a boundary: layer 1's gamma has a
    # gradient only through layer 0's state
    gamma = "layers.1.router.gamma"
    assert trained[True]["params"][gamma] != trained[True]["after"][gamma]


def test_the_tied_table_hears_the_embedding_and_the_head(trained):
    """One parameter read twice: its gradient is the sum of the gather's
    and the product's.  Rows no id of the batch names hear the head alone,
    and they do."""
    t = trained[True]
    moved = t["params"]["embed"] - t["after"]["embed"]
    unseen = np.setdiff1d(np.arange(64), t["x"])
    assert len(unseen) and np.abs(moved[unseen]).max() > 0
    assert "head" not in SHAPES


def test_the_bias_and_the_load_leave_the_step_as_auxiliary_state(trained):
    t = trained[True]
    assert t["aux"] == sorted(BIAS + list(REF.AUX))
    for name in BIAS:
        np.testing.assert_allclose(t["after"][name],
                                   trained["ref_bias"][name], rtol=0,
                                   atol=1e-7)
        moved = t["after"][name] - t["params"][name]
        assert set(np.round(moved / 1e-3).tolist()) <= {-1.0, 0.0, 1.0}
        assert np.abs(moved).max() > 0
    load, rows = t["after"]["expert_load"], t["after"]["expert_rows"]
    assert load.shape == (3, 4) and rows.shape == (3,)
    np.testing.assert_array_equal(rows, (np.ceil(load / 4) * 4).sum(axis=1))
    # one expert a token: a layer's held load is at most its tokens
    assert 0 < load.sum(axis=1).max() <= 2 * 21


def test_the_reference_notes_its_routing(trained):
    t = trained[True]
    with jax.default_matmul_precision("highest"):
        logits, margin, counts = REF.reference(SMALL, routing=True)(
            t["params"], t["x"])
    np.testing.assert_array_equal(logits, trained["ref_logits"])
    np.testing.assert_array_equal(counts, t["after"]["expert_load"])
    assert margin.shape == (3,) + t["x"].shape and (margin >= 0).all()


def test_state_dict_carries_the_bias_and_the_slots_and_back():
    net, names, _ = _model(SMALL)
    x, y = _batch(SMALL)
    adamw = {"learning_rate": 1e-2, "beta1": 0.9, "beta2": 0.95,
             "epsilon": 1e-8, "wd": 1e-3}
    step = _step(net, x, y, True, "adamw", adamw)
    for _ in range(2):
        step(x, y)
    saved = {k: np.asarray(v) for k, v in step.state_dict().items()}
    bias = [n for n in step.param_names if names[n] in BIAS]
    assert len(bias) == 3 and all(f"param:{n}" in saved for n in bias)
    assert not any(k.startswith(f"opt:{n}:") for n in bias for k in saved)
    assert np.abs(saved[f"param:{bias[0]}"]).max() > 0
    # a fresh step (other seed: other weights, other bias, zero slots); the
    # two nets' parameters differ by their blocks' prefixes only
    net2, _names, _ = _model(SMALL, seed=1)
    fresh = _step(net2, x, y, True, "adamw", adamw)
    rename = dict(zip(fresh.param_names, step.param_names))

    def theirs(key):
        kind, name, *slot = key.split(":")
        return saved[":".join([kind, rename[name]] + slot)]

    fresh.load_state_dict({k: theirs(k) for k in fresh.state_dict()})
    assert float(fresh(x, y)) == float(step(x, y))
    for a, b in zip(fresh.params, step.params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- (h) the gauges -------------------------------------------------------------------
def test_the_latent_widths_are_a_gauge_set_as_a_call_is_traced():
    from mxnet_tpu import telemetry
    _cfg, _p, block = _cca(8, 2, (2, 2))
    block(nd.ones((1, 5, 24)))
    gauge = telemetry.REGISTRY.get("mxnet_cca_latent_channels")
    assert gauge.value({"part": "q"}) == 64
    assert gauge.value({"part": "kv"}) == 16


def test_the_depth_averaging_coefficient_is_a_gauge_read_from_the_state():
    from mxnet_tpu import telemetry
    net, _names, _ = _model(SMALL)
    for layer, value in zip(net.layers, (0.5, -0.25, 0.0)):
        layer.moe.router.gamma.set_data(nd.array([value]))
    load, rows = net.record_expert_load(steps=1)
    assert load.shape == (3, 4) and rows.shape == (3,)
    assert telemetry.REGISTRY.get(
        "mxnet_router_eda_gamma_abs_mean").value() == pytest.approx(0.25)
    bias = np.stack([layer.moe.select_bias.data().asnumpy()
                     for layer in net.layers])
    assert telemetry.REGISTRY.get(
        "mxnet_moe_router_bias_abs_mean").value() == pytest.approx(
            float(np.abs(bias).mean()))
