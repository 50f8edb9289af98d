"""The Kimi Linear style decoder (gluon.model_zoo.language.kimi_linear) at a
small size on the CPU: multi-head latent attention, forward and every
parameter's gradient, against the benchmark's plain reference (a direct
masked softmax over keys expanded for every head) at two head layouts; the
leak test; Kimi Delta Attention without a convolution bias and with a plain
sigmoid beta against the reference's scan over single steps; the causal
convolution without a bias against the same call with a zero bias; the
share test (the holders of experts 0-3 and 4-7, the shared expert counted
once, add up to the uncut layer); the layer kinds read from the
configuration's 1-based lists; the whole model against the reference
(logits, loss, every parameter's gradient, the selection bias's rule) with
remat on and off; the latent gauge."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon.model_zoo.language import (
    KimiDeltaAttention, MultiHeadLatentAttention, SparseExperts, kimi_linear)
from mxnet_tpu.gluon.model_zoo.language.kimi_linear import layer_kinds
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "harness"))
import benchcore  # noqa: E402

CELL = benchcore.Cell("kimi-linear-spmd-seq8192-bs1")
REF = CELL.config_module()
# hidden 32; 2 KDA heads of 8 in chunks of 4; 4 latent attention heads with
# keys of 8 + 4 over values of 6 from a latent of 10; a dense MLP of 48;
# experts 4-7 of 8 held, top-3, tiles of 4 rows; 3 layers (KDA with the
# dense MLP, MLA, KDA with experts); 21 positions: a tail in the chunks and
# the tiles
SMALL = dict(
    CELL.config, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16,
    linear_attn_config={"full_attn_layers": [2, 6], "kda_layers": [1, 3, 4, 5],
                        "head_dim": 8, "num_heads": 2,
                        "short_conv_kernel_size": 4},
    num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=6, kv_lora_rank=10, kda_chunk_size=4,
    num_experts=4, published={"num_experts": 8}, first_routed_expert=4,
    num_experts_per_token=3, num_experts_per_tok=3, expert_tile_rows=4,
    vocab_size=64, num_classes=64, image=[22], num_hidden_layers=3)


def _f(rng, *shape, scale=1.0):
    return (rng.standard_normal(shape) * scale).astype(np.float32)


def _set(block, values):
    block.initialize()
    for name, value in values.items():
        getattr(block, name).set_data(nd.array(value))


def _layer_params(cfg, at, seed):
    """The reference's parameters under ``at`` (its prefix cut off), drawn
    at random; norm weights near 1."""
    rng = np.random.default_rng(seed)
    return {k[len(at):]: (1 + _f(rng, *s, scale=0.1) if k.endswith("norm")
                          else _f(rng, *s, scale=0.4))
            for k, s in REF.param_shapes(cfg).items() if k.startswith(at)}


def _check_block(block, names, p, ref, hidden, seed):
    """The block's output and every gradient against ``ref(p, h)``."""
    rng = np.random.default_rng(seed)
    a, weight = _f(rng, 2, 13, hidden), _f(rng, 2, 13, hidden)
    x = nd.array(a)
    x.attach_grad()
    with autograd.record():
        out = block(x)
        (out * nd.array(weight)).sum().backward()
    with jax.default_matmul_precision("highest"):
        want = ref(p, jnp.asarray(a))
        grads = jax.grad(lambda q, v: (ref(q, v) * weight).sum(),
                         argnums=(0, 1))(p, jnp.asarray(a))
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(x.grad.asnumpy(), grads[1], rtol=1e-3,
                               atol=1e-4 * float(np.abs(grads[1]).max()))
    for name, g in grads[0].items():
        got = names[name](block).grad().asnumpy()
        assert np.abs(g).max() > 0, name
        np.testing.assert_allclose(
            got, g, rtol=1e-3, atol=1e-4 * float(np.abs(g).max()),
            err_msg=name)


# -- (a) multi-head latent attention --------------------------------------------------
MLA = {"q": lambda b: b.q_weight, "kv_a": lambda b: b.kv_a_weight,
       "kv_b": lambda b: b.kv_b_weight, "o": lambda b: b.o_weight,
       "kv_norm": lambda b: b.latent_norm.gamma}


def _mla(heads, nope, rope, v, rank, seed=1):
    cfg = dict(SMALL, num_attention_heads=heads, qk_nope_head_dim=nope,
               qk_rope_head_dim=rope, v_head_dim=v, kv_lora_rank=rank)
    p = _layer_params(cfg, "layers.1.mla.", seed)
    block = MultiHeadLatentAttention(32, heads, nope, rope, v, rank)
    block.initialize()
    for name, value in p.items():
        MLA[name](block).set_data(nd.array(value))
    return cfg, p, block


@pytest.mark.parametrize("heads,nope,rope,v,rank", [
    (4, 8, 4, 6, 10),           # values narrower than the keys
    (2, 4, 8, 16, 12)])         # and wider
def test_mla_forward_and_every_gradient_match_the_reference(heads, nope, rope,
                                                            v, rank):
    cfg, p, block = _mla(heads, nope, rope, v, rank)
    _check_block(block, MLA, p, lambda q, h: REF._mla(q, "", h, cfg), 32, 2)


def test_mla_is_a_softmax_over_keys_that_share_their_positional_part():
    """The reference written out once more, head by head in numpy: the
    joint latent's norm, k_pe the same for every head, scale 1/sqrt(12)."""
    cfg, p, block = _mla(4, 8, 4, 6, 10)
    h = _f(np.random.default_rng(3), 1, 9, 32)
    got = block(nd.array(h)).asnumpy()[0]
    x = h[0].astype(np.float64)
    q = (x @ p["q"].T).reshape(9, 4, 12)
    latent = x @ p["kv_a"].T
    c, k_pe = latent[:, :10], latent[:, 10:]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + 1e-5) * p["kv_norm"]
    kv = (c @ p["kv_b"].T).reshape(9, 4, 14)
    out = np.zeros((9, 4, 6))
    for head in range(4):
        k = np.concatenate([kv[:, head, :8], k_pe], -1)
        s = q[:, head] @ k.T / np.sqrt(12)
        s = np.where(np.tril(np.ones((9, 9))) > 0, s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        out[:, head] = (e / e.sum(-1, keepdims=True)) @ kv[:, head, 8:]
    np.testing.assert_allclose(got, out.reshape(9, 24) @ p["o"].T,
                               rtol=1e-4, atol=1e-5)


def test_mla_hears_nothing_from_the_future():
    _cfg, _p, block = _mla(4, 8, 4, 6, 10)
    rng = np.random.default_rng(4)
    a = _f(rng, 1, 16, 32)
    b = a.copy()
    b[0, 9] += _f(rng, 32)
    out_a, out_b = (block(nd.array(v)).asnumpy() for v in (a, b))
    np.testing.assert_array_equal(out_a[0, :9], out_b[0, :9])
    assert np.abs(out_a[0, 9:] - out_b[0, 9:]).min(axis=-1).min() > 0


def test_the_latent_widths_are_a_gauge_set_as_a_call_is_traced():
    from mxnet_tpu import telemetry
    _cfg, _p, block = _mla(4, 8, 4, 6, 10)
    block(nd.ones((1, 5, 32)))
    gauge = telemetry.REGISTRY.get("mxnet_mla_latent_channels")
    assert (gauge.value({"part": "kv"}), gauge.value({"part": "rope"})) \
        == (10, 4)


# -- (b) Kimi Delta Attention as Kimi Linear has it ------------------------------------
KDA = {n: (lambda name: lambda b: getattr(b, name))(w) for n, w in (
    ("q", "q_weight"), ("k", "k_weight"), ("v", "v_weight"),
    ("o", "o_weight"), ("q_conv_w", "q_conv_weight"),
    ("k_conv_w", "k_conv_weight"), ("v_conv_w", "v_conv_weight"),
    ("a_down", "a_down_weight"), ("a_up", "a_up_weight"),
    ("beta", "beta_weight"), ("g_down", "g_down_weight"),
    ("g_up", "g_up_weight"), ("A_log", "A_log"), ("dt_bias", "dt_bias"))}
KDA["norm"] = lambda b: b.norm.gamma


def test_kda_without_conv_bias_and_with_a_plain_beta_matches_the_scan():
    p = _layer_params(SMALL, "layers.0.kda.", 5)
    p["A_log"] = np.log(np.array([1.5, 6.0], np.float32))
    block = KimiDeltaAttention(32, 2, 8, 4, 8, 4, neg_eigval=False,
                               conv_bias=False, scope="kimi/kda")
    block.initialize()
    assert not [k for k in block.collect_params() if "conv_bias" in k]
    for name, value in p.items():
        KDA[name](block).set_data(nd.array(value))
    _check_block(block, KDA, p, lambda q, h: REF._kda(q, "", h, SMALL), 32, 6)


def test_causal_conv_without_a_bias_is_the_call_with_a_zero_bias():
    rng = np.random.default_rng(7)
    x, w = nd.array(_f(rng, 2, 9, 6)), nd.array(_f(rng, 6, 4))
    got = nd.contrib.causal_conv1d(x, w, no_bias=True).asnumpy()
    want = nd.contrib.causal_conv1d(x, w, nd.zeros((6,))).asnumpy()
    # the same taps, summed without the zero that starts the other call
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert np.abs(got[:, 3:] - got[:, :-3]).max() > 0.1


# -- (c) the shares add up to the uncut layer -----------------------------------------
def test_expert_shares_add_up_to_the_uncut_layer():
    """8 experts over 2 holders of 4, top-3 over sigmoid + bias,
    renormalised and scaled: the two shares' routed outputs plus the shared
    expert ONCE are the uncut reference's mixture; both holders count the
    same assignments over all 8."""
    cfg = dict(SMALL, num_experts=8, first_routed_expert=0)
    p = _layer_params(cfg, "layers.1.moe.", 8)
    p["bias"] *= 0.05
    h = _f(np.random.default_rng(9), 2, 21, 32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF._moe(p, "", jnp.asarray(h), cfg))
        shared = np.asarray(REF._gated_mlp(jnp.asarray(h), p["shared_in"],
                                           p["shared_out"]))
    total, loads, counts = -shared, [], []
    for first in (0, 4):
        held = slice(first, first + 4)
        block = SparseExperts(32, 16, 8, 4, first, 3, 1, 2.446, tile=4,
                              select_bias=True, scope="kimi/moe")
        _set(block, {"router_weight": p["router"], "w1": p["w1"][held],
                     "w3": p["w3"][held], "w2": p["w2"][held],
                     "select_bias": p["bias"]})
        block.shared.in_weight.set_data(nd.array(p["shared_in"]))
        block.shared.out_weight.set_data(nd.array(p["shared_out"]))
        y, load, _rows, count = block(nd.array(h))
        total = total + y.asnumpy()
        loads.append(load.asnumpy())
        counts.append(count.asnumpy())
    np.testing.assert_allclose(total, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()))
    # all 42 tokens' three choices were computed by one holder or the other
    assert np.concatenate(loads).sum() == 42 * 3 and min(
        load.sum() for load in loads) > 0
    for count in counts:
        np.testing.assert_array_equal(count, np.concatenate(loads))


# -- (d) the layer kinds from the configuration's lists --------------------------------
def test_the_published_lists_count_from_one():
    kinds = layer_kinds(dict(CELL.config, num_hidden_layers=27))
    assert kinds[:5] == ["kda", "kda", "kda", "mla", "kda"]
    assert [i for i, k in enumerate(kinds) if k == "mla"] == \
        [3, 7, 11, 15, 19, 23, 26]
    net = REF.build(CELL.config, "gluon")
    assert [type(layer.mixer).__name__ for layer in net.layers] == \
        ["KimiDeltaAttention"] * 3 + ["MultiHeadLatentAttention",
                                      "KimiDeltaAttention"]
    assert [layer.routed for layer in net.layers] == [False] + [True] * 4


@pytest.mark.parametrize("change,match", [
    ({"linear_attn_config": dict(SMALL["linear_attn_config"],
                                 kda_layers=[1, 2, 3])}, "both"),
    ({"linear_attn_config": dict(SMALL["linear_attn_config"],
                                 kda_layers=[1])}, "neither"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"mla_use_nope": False}, "mla_use_nope"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"num_key_value_heads": 2}, "latent attention")])
def test_kimi_refuses_what_it_does_not_build(change, match):
    with pytest.raises(ValueError, match=match):
        kimi_linear(dict(SMALL, **change))


# -- (e) the model against the plain reference ---------------------------------------
def _model(cfg, seed=0):
    mx.random.seed(seed)
    net = REF.build(cfg, "gluon")
    net.initialize(mx.initializer.Normal(0.1))
    # the vectors that start at 0 or 1 moved, so that a wrong reading shows
    rng = np.random.default_rng(seed + 100)
    for name, p in net.collect_params().items():
        if p.grad_req != "null" and not name.endswith("_weight"):
            p.set_data(p.data() + nd.array(_f(rng, *p.shape, scale=0.1)))
    names = REF.canonical(cfg, "gluon", net)
    params = {names[k]: p.data().asnumpy()
              for k, p in net.collect_params().items()}
    return net, names, params


def _batch(cfg, batch=2, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, cfg["image"][0])).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _step(net, x, y, remat):
    mesh = make_mesh(devices=jax.devices()[:1], dp=1)
    return TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 1.0, "momentum": 0.9}, mesh,
                     example_batch=(nd.array(x), nd.array(y)), remat=remat)


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
                          after={names[n]: np.asarray(a) for n, a in
                                 zip(step.param_names, step.params)},
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
    names = REF.canonical(SMALL, "gluon", net)
    assert {names[k]: tuple(p.shape)
            for k, p in net.collect_params().items()} == \
        {k: tuple(s) for k, s in SHAPES.items()}


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
    assert np.abs(want).max() > 0, "the reference never reads it"
    spacing = float(np.spacing(np.abs(t["params"][name]).max()))
    np.testing.assert_allclose(
        got, want, rtol=2e-3,
        atol=2e-4 * float(np.abs(want).max()) + spacing)


def test_remat_puts_a_boundary_around_every_layer(trained):
    assert trained[True]["boundaries"] == SMALL["num_hidden_layers"] == 3
    assert trained[False]["boundaries"] == 0
    for name, a in trained[True]["after"].items():
        np.testing.assert_allclose(a, trained[False]["after"][name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)


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
    assert load.shape == (2, 4) and rows.shape == (2,)
    np.testing.assert_array_equal(rows, (np.ceil(load / 4) * 4).sum(axis=1))
    assert 0 < load.sum(axis=1).max() <= 2 * 21 * 3


def test_the_reference_notes_its_routing(trained):
    t = trained[True]
    with jax.default_matmul_precision("highest"):
        logits, margin, counts = REF.reference(SMALL, routing=True)(
            t["params"], t["x"])
    np.testing.assert_array_equal(logits, trained["ref_logits"])
    np.testing.assert_array_equal(counts, t["after"]["expert_load"])
    assert margin.shape == (2,) + t["x"].shape and (margin >= 0).all()
