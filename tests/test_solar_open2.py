"""The Solar Open 2 style decoder (gluon.model_zoo.language.solar_open2) at
a small size on the CPU: the chunked delta-rule op ``_contrib_kda_scan``
against the step-by-step recurrence, the routed-expert op
``_contrib_routed_experts`` against a loop over the held experts with a
mask (extreme imbalance included, nothing dropped), the model through
``parallel.spmd.TrainStep`` against the benchmark's plain reference
(logits, loss, every parameter's gradient), the expert load as auxiliary
state of the step, and the share test: the expert shares and the head
shares of a layer add up to the uncut reference."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon.model_zoo.language import (
    GroupedQueryAttention, KimiDeltaAttention, SparseExperts)
from mxnet_tpu.ops._op_linear_attention import kda_scan
from mxnet_tpu.ops._op_moe import routed_experts
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "harness"))
import benchcore  # noqa: E402

CELL = benchcore.Cell("solar-open2-spmd-seq8192-bs1")
REF = CELL.config_module()
# hidden 32; layer 0 softmax (4 query heads over 2 key/value heads of 8),
# layers 1-3 KDA (2 heads of 8, chunk 8); experts 4-7 of 16 held, top-2,
# tiles of 4 rows; 21 positions: a tail in the chunk and in the tiles
SMALL = dict(
    CELL.config, hidden_size=32, head_dim=8, num_attention_heads=4,
    num_key_value_heads=2,
    linear_attn_config=dict(CELL.config["linear_attn_config"], num_heads=2,
                            head_dim=8),
    moe_intermediate_size=16, n_routed_experts=4,
    published={"n_routed_experts": 16}, first_routed_expert=4,
    num_experts_per_tok=2, kda_chunk_size=8, kda_low_rank_dim=8,
    expert_tile_rows=4, vocab_size=64, num_classes=64, image=[22])


# -- (i) the chunked delta rule against the recurrence ---------------------------
def _recurrence(q, k, v, g, beta):
    """S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ,
    o_t = S_tᵀ q_t, one time step at a time."""
    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = s * jnp.exp(g_t)[..., None]
        s = s + (b_t[..., None] * k_t)[..., None] * (
            v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s))[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s0 = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:])
    _, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(x, 1, 0)
                                        for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _scan_inputs(t=37, heads=3, dk=8, dv=6):
    """Unit keys, β up to its bound of 2, decays from none to e^-20 a step."""
    rng = np.random.default_rng(0)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    k = f(2, t, heads, dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = 2.0 / (1.0 + np.exp(-4.0 * f(2, t, heads)))
    beta[:, ::5] = 2.0
    g = -np.exp(1.5 * f(2, t, heads, dk))
    g[:, 3::7] = -20.0
    g[:, 4::7] = 0.0
    return (f(2, t, heads, dk), k, f(2, t, heads, dv),
            g.astype(np.float32), beta.astype(np.float32))


SCAN_INPUTS = ("q", "k", "v", "g", "beta")
CHUNKS = [4, 16, 32, 64]    # 37 steps: tails, sub-blocks of 16, one chunk


@pytest.mark.parametrize("chunk", CHUNKS)
def test_kda_scan_matches_the_recurrence(chunk):
    args = _scan_inputs()
    got = nd.contrib.kda_scan(*map(nd.array, args), chunk_size=chunk)
    np.testing.assert_allclose(got.asnumpy(), _recurrence(*args),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("wrt", range(5), ids=SCAN_INPUTS)
@pytest.mark.parametrize("chunk", CHUNKS)
def test_kda_scan_gradient_matches_the_recurrence(chunk, wrt):
    args = _scan_inputs()
    weight = np.random.default_rng(1).standard_normal(
        args[2].shape).astype(np.float32)
    got = jax.grad(lambda *a: (kda_scan(*a, chunk) * weight).sum(),
                   argnums=wrt)(*args)
    want = jax.grad(lambda *a: (_recurrence(*a) * weight).sum(),
                    argnums=wrt)(*args)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=5e-4,
                               atol=5e-5 * float(np.abs(want).max()))


def test_kda_scan_exponentiates_nothing_positive():
    """Decays of e^-300 a step: a factored exp(−G_s) would overflow, the
    masked differences stay finite and agree with the recurrence."""
    q, k, v, g, beta = _scan_inputs()
    g = np.full_like(g, -300.0)
    got = kda_scan(q, k, v, g, beta, 16)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _recurrence(q, k, v, g, beta),
                               rtol=1e-4, atol=1e-5)


# -- (ii) the routed experts against a loop with a mask ------------------------------
def _masked_loop(h, router, w1, w3, w2, top_k, first):
    """Every token through every held expert, then a mask: the plain way."""
    x = h.reshape(-1, h.shape[-1])
    scores = jax.nn.sigmoid(jnp.matmul(x, router.T, precision="highest"))
    chosen, expert = jax.lax.top_k(scores, top_k)
    chosen = chosen / chosen.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        weight = jnp.sum(jnp.where(expert == first + e, chosen, 0.0), -1)
        y = y + weight[:, None] * (
            (jax.nn.silu(x @ w1[e].T) * (x @ w3[e].T)) @ w2[e].T)
    return y.reshape(h.shape)


def _expert_inputs(case, hidden=16, width=12, total=16, held=4):
    rng = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s).astype(np.float32))
    h, router = f(2, 37, hidden), np.asarray(f(total, hidden)) * 0.5
    if case != "random":      # positive h: a row of ones outscores the rest
        h = jnp.abs(h)
        router[:] = -1.0
        if case == "all_choose_one_held":
            router[5] = 1.0   # held (4..7); the second choice is absent
            router[9] = 0.5
        else:                 # none_held
            router[0] = router[12] = 1.0
    return (h, jnp.asarray(router), f(held, width, hidden) * 0.3,
            f(held, width, hidden) * 0.3, f(held, hidden, width) * 0.3)


CASES = ("random", "all_choose_one_held", "none_held")
EXPERT_INPUTS = ("h", "router", "w1", "w3", "w2")


@pytest.mark.parametrize("tile", [4, 256])
@pytest.mark.parametrize("case", CASES)
def test_routed_experts_match_the_masked_loop_and_drop_nothing(case, tile):
    args = _expert_inputs(case)
    y, load, rows = nd.contrib.routed_experts(
        *map(nd.array, args), experts_total=16, top_k=2, first_expert=4,
        tile=tile)
    np.testing.assert_allclose(y.asnumpy(), _masked_loop(*args, 2, 4),
                               rtol=1e-5, atol=1e-6)
    load, rows = load.asnumpy(), float(rows.asnumpy()[0])
    want = {"random": None, "all_choose_one_held": [0, 74, 0, 0],
            "none_held": [0, 0, 0, 0]}[case]
    if want is not None:      # 74 tokens, every one of them counted
        assert load.tolist() == want
    assert rows == sum(-(-int(c) // tile) * tile for c in load)
    assert load.sum() <= rows < load.sum() + 4 * tile


@pytest.mark.parametrize("wrt", range(5), ids=EXPERT_INPUTS)
@pytest.mark.parametrize("case", CASES)
def test_routed_experts_gradient_matches_the_masked_loop(case, wrt):
    args = _expert_inputs(case)
    weight = jnp.asarray(np.random.default_rng(3).standard_normal(
        args[0].shape).astype(np.float32))
    got = jax.grad(
        lambda *a: (routed_experts(*a, 2, 4, tile=4)[0] * weight).sum(),
        argnums=wrt)(*args)
    want = jax.grad(lambda *a: (_masked_loop(*a, 2, 4) * weight).sum(),
                    argnums=wrt)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()) + 1e-9)


def test_routed_experts_refuse_a_share_outside_the_router():
    args = _expert_inputs("random")
    with pytest.raises(mx.MXNetError, match="experts 14"):
        routed_experts(*args, 2, 14)


# -- (iii) the model through TrainStep against the plain reference ---------------
def _model(cfg, seed=0):
    mx.random.seed(seed)
    net = REF.build(cfg, "gluon")
    net.initialize(mx.initializer.Normal(0.02))
    names = REF.canonical(cfg, "gluon", net)
    params = {names[k]: p.data().asnumpy()
              for k, p in net.collect_params().items()}
    return net, names, params


def _batch(cfg, batch=2, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, cfg["image"][0])).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _step(net, x, y, remat, lr=1.0):
    mesh = make_mesh(devices=jax.devices()[:1], dp=1)
    return TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": lr, "momentum": 0.9}, mesh,
                     example_batch=(nd.array(x), nd.array(y)), remat=remat)


AUX = ("expert_load", "expert_rows")


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
        after = {names[n]: np.asarray(a)
                 for n, a in zip(step.param_names, step.params)}
        out[remat] = dict(params=params, x=x, y=y, logits=logits, loss=loss,
                          after=after, boundaries=step.remat_boundaries,
                          aux=[step.param_names[i] for i in step._aux_idx],
                          names=names)
    with jax.default_matmul_precision("highest"):
        t = out[True]
        out["ref_logits"] = np.asarray(
            REF.reference(SMALL)(t["params"], t["x"]))
        out["ref_loss"], out["ref_grads"] = jax.value_and_grad(
            REF.loss(SMALL))(t["params"], t["x"], t["y"])
    return out


def test_every_size_is_given_so_nothing_waits_for_a_batch():
    net = REF.build(SMALL, "gluon")
    net.initialize(mx.initializer.Normal(0.02))
    assert all(p._data is not None for p in net.collect_params().values())
    shapes = {k: tuple(p.shape) for k, p in net.collect_params().items()}
    names = REF.canonical(SMALL, "gluon", net)
    assert {names[k]: s for k, s in shapes.items()} == \
        {k: tuple(s) for k, s in REF.param_shapes(SMALL).items()}


@pytest.mark.parametrize("remat", [False, True])
def test_logits_and_loss_match_the_reference(trained, remat):
    np.testing.assert_allclose(trained[remat]["logits"],
                               trained["ref_logits"], rtol=1e-4, atol=1e-5)
    assert abs(trained[remat]["loss"] - float(trained["ref_loss"])) < 1e-5


@pytest.mark.parametrize(
    "name", sorted(set(REF.param_shapes(SMALL)) - set(AUX)))
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


def test_remat_holds_a_boundary_per_layer_and_changes_nothing(trained):
    assert trained[True]["boundaries"] == SMALL["num_hidden_layers"] == 4
    assert trained[False]["boundaries"] == 0
    for name, a in trained[True]["after"].items():
        np.testing.assert_allclose(a, trained[False]["after"][name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)
    from mxnet_tpu import telemetry
    # the fixture traced the remat step last
    assert telemetry.REGISTRY.get(
        "mxnet_step_remat_boundaries").value() == 4.0


def test_expert_load_leaves_the_step_as_auxiliary_state(trained):
    """The two arrays are the step's aux parameters (no gradient, no
    momentum), written by the step itself: every layer's count of the
    tokens' 2 choices that fell on experts 4-7, and the rows its tiles of
    4 ran."""
    t = trained[True]
    assert sorted(t["names"][n] for n in t["aux"]) == sorted(AUX)
    load, rows = (t["after"][k] for k in AUX)
    assert load.shape == (4, 4) and rows.shape == (4,)
    assert (t["params"]["expert_load"] == 0).all()
    assert (load == np.round(load)).all() and load.sum() > 0
    assert (load.sum(axis=1) <= 2 * 21 * 2).all()
    np.testing.assert_array_equal(
        rows, (np.ceil(load / 4) * 4).sum(axis=1))
    # the reference routes alike: its own count of the same assignments
    p, x = t["params"], t["x"]
    with jax.default_matmul_precision("highest"):
        hidden = _hidden_states_of_the_reference(p, x)
    for i, h in enumerate(hidden):
        scores = jax.nn.sigmoid(h @ p[f"layers.{i}.moe.router"].T)
        _, expert = jax.lax.top_k(scores, 2)
        want = [(np.asarray(expert) == 4 + e).sum() for e in range(4)]
        assert load[i].tolist() == want


def test_the_reference_notes_its_routing(trained):
    """With ``routing`` the reference returns, beside the same logits, each
    layer's count of assignments to the held experts (the program's own
    auxiliary state) and how far each token's nearest held expert lies
    from the edge of the top k."""
    t = trained[True]
    with jax.default_matmul_precision("highest"):
        logits, margin, counts = REF.reference(SMALL, routing=True)(
            t["params"], t["x"])
    np.testing.assert_array_equal(logits, trained["ref_logits"])
    np.testing.assert_array_equal(counts, t["after"]["expert_load"])
    assert margin.shape == (4,) + t["x"].shape and (margin >= 0).all()
    # top 2 of six, experts 2 and 3 held: 0.7 misses the 2nd (0.8) by 0.1;
    # then 0.9 is chosen 0.4 above the 3rd (0.5), 0.3 misses 0.8 by 0.5
    scores = np.array([[.9, .8, .7, .1, .2, .3], [.1, .2, .9, .3, .8, .5]],
                      np.float32)
    np.testing.assert_allclose(REF._held_margin(scores, 2, 2, 2),
                               [0.1, 0.4], rtol=1e-6)


def test_the_state_sums_over_steps_and_the_gauges_are_means():
    """A second step adds its counts to the first's (at learning rate 0
    the same counts again); ``record_expert_load`` divides the counts by
    the steps it is told and takes the skew from the sums."""
    net, names, _params = _model(SMALL)
    x, y = _batch(SMALL)
    step = _step(net, x, y, remat=True, lr=0.0)
    seen = []
    for _ in range(2):
        step(x, y)
        state = dict(zip(step.param_names, step.params))
        seen.append([np.asarray(state[p.name])
                     for p in (net.expert_load, net.expert_rows)])
    (load, rows), (load2, rows2) = seen
    assert load.sum() > 0
    np.testing.assert_array_equal(load2, 2 * load)
    np.testing.assert_array_equal(rows2, 2 * rows)
    from mxnet_tpu import telemetry
    got = net.record_expert_load(state, steps=2)
    np.testing.assert_array_equal(got[0], load2)
    reg = telemetry.REGISTRY
    assert reg.get("mxnet_moe_assignments_held").value() == load.sum()
    assert reg.get("mxnet_moe_rows_computed").value() == rows.sum()
    assert reg.get("mxnet_moe_expert_load_max_over_mean").value() == \
        pytest.approx((load.max(axis=1) / load.mean(axis=1)).max())


def _hidden_states_of_the_reference(p, ids):
    """What each layer's mixture reads, from the reference's own parts."""
    eps = SMALL["rms_norm_eps"]
    x, out = p["embed"][ids], []
    for i in range(4):
        at = f"layers.{i}."
        h = REF._rms_norm(x, p[at + "norm1"], eps)
        x = x + (REF._attention(p, at + "attn.", h, SMALL) if i == 0
                 else REF._kda(p, at + "kda.", h, SMALL))
        h = REF._rms_norm(x, p[at + "norm2"], eps)
        out.append(h)
        x = x + REF._moe(p, at + "moe.", h, SMALL)
    return out


def test_named_scopes_are_in_the_step_program():
    net, _names, _params = _model(SMALL)
    x, y = _batch(SMALL)
    step = _step(net, x, y, remat=True)
    with step.mesh.jax_mesh:
        text = step._step.lower(
            jax.random.PRNGKey(0), step._train_params, step._aux_params,
            step.opt_state, x, y).as_text(debug_info=True)
    # the op's and the shared blocks' own scopes nest under the model's,
    # the registry's ``op/<name>`` between the model's and the op's own
    for scope in ("solar/kda/proj", "solar/kda/conv", "solar/kda/gates",
                  "solar/kda/scan", "solar/kda/out",
                  "solar/attention/granite/attention",
                  "solar/moe/op/_contrib_routed_experts/routed_experts/router",
                  "solar/moe/op/_contrib_routed_experts/routed_experts/dispatch",
                  "solar/moe/op/_contrib_routed_experts/routed_experts/experts",
                  "solar/moe/shared/granite/mlp",
                  "solar/moe/combine", "solar/head"):
        assert scope in text, scope
    assert text.count("checkpoint") >= 4


# -- (iv) the shares add up to the uncut layer ------------------------------------
def _set(block, values):
    block.initialize()
    for name, value in values.items():
        getattr(block, name).set_data(nd.array(value))


def _uncut(shapes, seed):
    rng = np.random.default_rng(seed)
    return {k: (rng.standard_normal(s) * 0.3).astype(np.float32)
            for k, s in shapes.items()}


def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 holders of 4, top-3: the four shares' outputs,
    with the shared expert (which every holder computes alike) counted
    once, are the uncut reference's mixture."""
    cfg = dict(SMALL, n_routed_experts=16, first_routed_expert=0,
               num_experts_per_tok=3)
    shapes = {k[len("layers.0.moe."):]: s
              for k, s in REF.param_shapes(cfg).items()
              if k.startswith("layers.0.moe.")}
    p = _uncut(shapes, 4)
    h = np.random.default_rng(5).standard_normal(
        (2, 21, 32)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = REF._moe(p, "", jnp.asarray(h), cfg)
        shared = REF._gated_mlp(jnp.asarray(h), p["shared_in"],
                                p["shared_out"])
    total, loads = -3 * np.asarray(shared), []
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        block = SparseExperts(32, 16, 16, 4, first, 3, tile=4)
        _set(block, {"router_weight": p["router"], "w1": p["w1"][held],
                     "w3": p["w3"][held], "w2": p["w2"][held]})
        _set(block.shared, {"in_weight": p["shared_in"],
                            "out_weight": p["shared_out"]})
        y, load, _rows = block(nd.array(h))
        total = total + y.asnumpy()
        loads.append(load.asnumpy())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    # every one of the 42 tokens' 3 choices was computed by some holder
    assert np.concatenate(loads).sum() == 42 * 3


def test_kda_head_shares_add_up_to_the_uncut_layer():
    """4 heads over 2 holders of 2: W_o is linear in the heads, so the
    two shares' outputs add up to the uncut reference's mixer."""
    lin = dict(SMALL["linear_attn_config"], num_heads=4)
    cfg = dict(SMALL, linear_attn_config=lin)
    shapes = {k[len("layers.1.kda."):]: s
              for k, s in REF.param_shapes(cfg).items()
              if k.startswith("layers.1.kda.")}
    p = _uncut(shapes, 6)
    h = np.random.default_rng(7).standard_normal(
        (2, 21, 32)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = REF._kda(p, "", jnp.asarray(h), cfg)
    total = 0.0
    for first in (0, 2):
        rows = slice(first * 8, (first + 2) * 8)    # channels of 2 heads
        block = KimiDeltaAttention(32, 2, 8, low_rank=8, chunk_size=8)
        values = {"a_down_weight": p["a_down"], "g_down_weight": p["g_down"],
                  "a_up_weight": p["a_up"][rows],
                  "g_up_weight": p["g_up"][rows],
                  "A_log": p["A_log"][first:first + 2],
                  "dt_bias": p["dt_bias"][rows],
                  "beta_weight": p["beta"][first:first + 2],
                  "o_weight": p["o"][:, rows]}
        for name in "qkv":
            values[name + "_weight"] = p[name][rows]
            values[name + "_conv_weight"] = p[name + "_conv_w"][rows]
            values[name + "_conv_bias"] = p[name + "_conv_b"][rows]
        _set(block, values)
        _set(block.norm, {"gamma": p["norm"]})
        total = total + block(nd.array(h)).asnumpy()
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_softmax_head_shares_add_up_to_the_uncut_layer():
    """4 query heads over 2 key/value heads, a holder per key/value head
    with its 2 query heads and their rows of the gate."""
    shapes = {k[len("layers.0.attn."):]: s
              for k, s in REF.param_shapes(SMALL).items()
              if k.startswith("layers.0.attn.")}
    p = _uncut(shapes, 8)
    h = np.random.default_rng(9).standard_normal(
        (2, 21, 32)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = REF._attention(p, "", jnp.asarray(h), SMALL)
    total = 0.0
    for kv in (0, 1):
        qs, kvs = slice(kv * 16, (kv + 1) * 16), slice(kv * 8, (kv + 1) * 8)
        block = GroupedQueryAttention(32, 2, 1, 8, 8 ** -0.5, gate=True)
        _set(block, {"q_weight": p["q"][qs], "g_weight": p["g"][qs],
                     "k_weight": p["k"][kvs], "v_weight": p["v"][kvs],
                     "o_weight": p["o"][:, qs]})
        total = total + block(nd.array(h)).asnumpy()
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_the_gate_is_an_option_granite_does_not_take():
    block = GroupedQueryAttention(32, 4, 2, 8, 0.25)
    assert "g_weight" not in {k.split("_", 1)[1]
                              for k in block.collect_params()}
    gated = GroupedQueryAttention(32, 4, 2, 8, 0.25, gate=True)
    assert any(k.endswith("g_weight") for k in gated.collect_params())
