"""The Nemotron-H style decoder (gluon.model_zoo.language.nemotron_h) at a
small size on the CPU: the routed-expert op with relu² experts and a
selection bias against a loop over the held experts with a mask, beside
the gated form (a bias large enough to change the chosen set while the
weights stay the scores'); RMSNorm by group, plain and gated; the model
on a pattern with all three kinds of layer against the benchmark's plain
reference (logits, loss, every parameter's gradient); through
``parallel.spmd.TrainStep`` with AdamW and remat against the reference
stepped with the same rule, the selection bias after two steps included;
``state_dict``/``load_state_dict`` carrying the bias and both optimizer
slots; the bias rule evening a skewed router; and the share test: the
routed parts of all 16 holders plus the shared expert counted once equal
the uncut reference layer."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon.model_zoo.language import SparseExperts
from mxnet_tpu.ops._op_moe import routed_experts
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "harness"))
import benchcore  # noqa: E402

CELL = benchcore.Cell("nemotron3-nano-spmd-seq8192-bs1")
REF = CELL.config_module()
# hidden 32; all three kinds of layer (MEM*EME): Mamba-2 with 4 heads of 8
# in 2 groups (state 16, chunk 8), 4 query heads over 2 key/value heads of
# 8, experts 4-7 of 16 held, top-3, a shared expert of its own width, tiles
# of 4 rows; 21 positions: a tail in the chunk and in the tiles
SMALL = dict(
    CELL.config, hidden_size=32, head_dim=8, num_attention_heads=4,
    num_key_value_heads=2, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, chunk_size=8, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=24, n_routed_experts=4,
    published={"n_routed_experts": 16}, first_routed_expert=4,
    num_experts_per_tok=3, expert_tile_rows=4, vocab_size=64,
    num_classes=64, image=[22], num_hidden_layers=7,
    hybrid_override_pattern="MEM*EME")
ADAMW = {"learning_rate": 1e-2, "beta1": 0.9, "beta2": 0.95,
         "epsilon": 1e-8, "wd": 1e-3}


# -- (a) the routed experts, both forms, with and without a bias -------------------
def _masked_loop(h, router, w1, w3, w2, top_k, first, bias=None):
    """Every token through every held expert, then a mask: the plain way.
    The bias chooses, the scores weigh."""
    x = h.reshape(-1, h.shape[-1])
    scores = jax.nn.sigmoid(jnp.matmul(x, router.T, precision="highest"))
    _, expert = jax.lax.top_k(scores if bias is None else scores + bias,
                              top_k)
    chosen = jnp.take_along_axis(scores, expert, axis=-1)
    chosen = chosen / chosen.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        weight = jnp.sum(jnp.where(expert == first + e, chosen, 0.0), -1)
        mid = jnp.square(jax.nn.relu(x @ w1[e].T)) if w3 is None \
            else jax.nn.silu(x @ w1[e].T) * (x @ w3[e].T)
        y = y + weight[:, None] * (mid @ w2[e].T)
    return y.reshape(h.shape), expert


def _expert_inputs(form, bias, hidden=16, width=12, total=16, held=4):
    """h, router, w1, w3 (None for relu²), w2, bias (or None).  ``far``: a
    bias that lifts experts 5 and 6 (held) over every score, so the chosen
    set is not the top scores' and the weights are still the scores'."""
    rng = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s).astype(np.float32))
    h, router = f(2, 37, hidden), f(total, hidden) * 0.5
    w1, w3, w2 = (f(held, width, hidden) * 0.3, f(held, width, hidden) * 0.3,
                  f(held, hidden, width) * 0.3)
    b = {"none": None, "near": f(total) * 0.05,
         "far": jnp.zeros(total).at[jnp.array([5, 6])].set(2.0)}[bias]
    return h, router, w1, (w3 if form == "gated_silu" else None), w2, b


FORMS = ("gated_silu", "relu2")
BIASES = ("none", "near", "far")


@pytest.mark.parametrize("tile", [4, 256])
@pytest.mark.parametrize("bias", BIASES)
@pytest.mark.parametrize("form", FORMS)
def test_routed_experts_match_the_masked_loop(form, bias, tile):
    h, router, w1, w3, w2, b = _expert_inputs(form, bias)
    inputs = [nd.array(v) for v in (h, router, w1, w3, w2, b)
              if v is not None]
    out = nd.contrib.routed_experts(
        *inputs, experts_total=16, top_k=2, first_expert=4, tile=tile,
        expert_form=form, select_bias=b is not None)
    want, expert = _masked_loop(h, router, w1, w3, w2, 2, 4, b)
    np.testing.assert_allclose(out[0].asnumpy(), want, rtol=1e-5, atol=1e-6)
    load = out[1].asnumpy()
    assert load.tolist() == [(np.asarray(expert) == 4 + e).sum()
                             for e in range(4)]
    assert float(out[2].asnumpy()[0]) == sum(
        -(-int(c) // tile) * tile for c in load)
    if b is None:
        assert len(out) == 3
        return
    # the count over ALL experts: every token's 2 choices, the held
    # experts' part of it is the load
    counts = out[3].asnumpy()
    assert counts.shape == (16,) and counts.sum() == 74 * 2
    np.testing.assert_array_equal(counts[4:8], load)
    if bias == "far":       # every token chose experts 5 and 6
        assert load.tolist() == [0, 74, 74, 0]
        plain = _masked_loop(h, router, w1, w3, w2, 2, 4)[0]
        assert np.abs(np.asarray(plain - want)).max() > 1e-3


EXPERT_INPUTS = ("h", "router", "w1", "w3", "w2")


@pytest.mark.parametrize("form,wrt", [
    (form, wrt) for form in FORMS for wrt in EXPERT_INPUTS
    if (form, wrt) != ("relu2", "w3")])     # relu² experts have two matrices
@pytest.mark.parametrize("bias", BIASES)
def test_routed_experts_gradient_matches_the_masked_loop(form, bias, wrt):
    args = _expert_inputs(form, bias)
    i = EXPERT_INPUTS.index(wrt)
    weight = jnp.asarray(np.random.default_rng(3).standard_normal(
        args[0].shape).astype(np.float32))

    def through(fn):
        def f(v):
            a = args[:i] + (v,) + args[i + 1:]
            return (fn(a) * weight).sum()
        return jax.grad(f)(args[i])

    got = through(lambda a: routed_experts(
        *a[:5], 2, 4, tile=4, select_bias=a[5])[0])
    want = through(lambda a: _masked_loop(*a[:5], 2, 4, a[5])[0])
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()) + 1e-9)


def test_the_bias_never_enters_a_weight():
    """The output's gradient by the bias is zero: it moves the choice,
    which has no derivative, and never a weight."""
    h, router, w1, w3, w2, b = _expert_inputs("relu2", "near")
    got = jax.grad(lambda v: routed_experts(
        h, router, w1, None, w2, 2, 4, tile=4, select_bias=v)[0].sum())(b)
    assert not np.asarray(got).any()


def test_routed_experts_refuse_inputs_that_do_not_fit_the_form():
    h, router, w1, w3, w2, _ = _expert_inputs("gated_silu", "none")
    five = [nd.array(v) for v in (h, router, w1, w3, w2)]
    with pytest.raises(mx.MXNetError, match="inputs"):
        nd.contrib.routed_experts(*five, experts_total=16, top_k=2,
                                  first_expert=4, expert_form="relu2")
    with pytest.raises(mx.MXNetError, match="inputs"):
        nd.contrib.routed_experts(*five, experts_total=16, top_k=2,
                                  first_expert=4, select_bias=True)
    with pytest.raises(mx.MXNetError, match="expert_form"):
        nd.contrib.routed_experts(*five, experts_total=16, top_k=2,
                                  first_expert=4, expert_form="gelu")


# -- (b) the shares add up to the uncut layer ------------------------------------
def _set(block, values):
    block.initialize()
    for name, value in values.items():
        getattr(block, name).set_data(nd.array(value))


def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts over 16 holders of one, top-3 of s + b: the sixteen
    shares' outputs, with the shared expert (which every holder computes
    alike) counted once, are the uncut reference's mixture; every holder
    counts the same assignments over all 16."""
    cfg = dict(SMALL, n_routed_experts=16, first_routed_expert=0)
    at = "layers.1.moe."
    rng = np.random.default_rng(4)
    p = {k[len(at):]: (rng.standard_normal(s) * 0.3).astype(np.float32)
         for k, s in REF.param_shapes(cfg).items() if k.startswith(at)}
    h = rng.standard_normal((2, 21, 32)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = REF._moe(p, "", jnp.asarray(h), cfg)
        shared = REF._relu2_mlp(jnp.asarray(h), p["shared_in"],
                                p["shared_out"])
    shared = np.asarray(shared)
    total, loads, counts = shared.copy(), [], []
    for first in range(16):
        held = slice(first, first + 1)
        block = SparseExperts(32, 16, 16, 1, first, 3, scaling=2.5, tile=4,
                              form="relu2", shared_width=24,
                              select_bias=True)
        _set(block, {"router_weight": p["router"], "w1": p["w1"][held],
                     "w2": p["w2"][held], "select_bias": p["bias"]})
        _set(block.shared, {"in_weight": p["shared_in"],
                            "out_weight": p["shared_out"]})
        y, load, _rows, count = block(nd.array(h))
        total = total + (y.asnumpy() - shared)
        loads.append(load.asnumpy())
        counts.append(count.asnumpy())
    # relu² of sums of a dozen products near 1: float32 spacings of the
    # largest output, not of each one
    np.testing.assert_allclose(total, want, rtol=1e-4,
                               atol=1e-5 * float(np.abs(want).max()))
    # every one of the 42 tokens' 3 choices was computed by some holder
    assert np.concatenate(loads).sum() == 42 * 3
    for count in counts:
        np.testing.assert_array_equal(count, np.concatenate(loads))


def test_the_forms_keep_their_parameters():
    """Solar's mixture builds as before (three matrices, a gated shared
    expert of ``shared_experts × width``, no bias); the relu² form has no
    ``w3`` and a shared expert of its own width."""
    def shapes(block):
        return {k.split("_", 1)[1]: tuple(p.shape)
                for k, p in block.collect_params().items()}
    assert shapes(SparseExperts(32, 16, 16, 4, 4, 2)) == {
        "router_weight": (16, 32), "w1": (4, 16, 32), "w3": (4, 16, 32),
        "w2": (4, 32, 16), "shared_in_weight": (32, 32),
        "shared_out_weight": (32, 16)}
    assert shapes(SparseExperts(32, 16, 16, 4, 4, 2, form="relu2",
                                shared_width=24, select_bias=True)) == {
        "router_weight": (16, 32), "w1": (4, 16, 32), "w2": (4, 32, 16),
        "select_bias": (16,), "shared_in_weight": (24, 32),
        "shared_out_weight": (32, 24)}


# -- (c) RMSNorm by group ----------------------------------------------------------
def _norm_by_group(x, gamma, groups, gate=None, eps=1e-5):
    if gate is not None:
        x = x * jax.nn.silu(gate)
    g = x.reshape(x.shape[:-1] + (groups, -1))
    return (g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
            ).reshape(x.shape) * gamma


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("groups", [1, 2, 8])
def test_rms_norm_by_group_forward_and_gradient(groups, gated):
    rng = np.random.default_rng(5)
    x, gate = (rng.standard_normal((2, 5, 16)).astype(np.float32)
               for _ in range(2))
    gamma = rng.standard_normal(16).astype(np.float32)
    block = gluon.nn.RMSNorm(16, num_groups=groups)
    _set(block, {"gamma": gamma})
    arrays = [nd.array(x)] + ([nd.array(gate)] if gated else [])
    for a in arrays:
        a.attach_grad()
    with autograd.record():
        out = block(*arrays)
        (out * out).sum().backward()
    want = lambda *a: _norm_by_group(  # noqa: E731
        a[0], gamma, groups, a[1] if gated else None)
    given = (x, gate) if gated else (x,)
    np.testing.assert_allclose(out.asnumpy(), want(*given), rtol=1e-5,
                               atol=1e-6)
    grads = jax.grad(lambda *a: (want(*a) ** 2).sum(),
                     argnums=tuple(range(len(given))))(*given)
    for a, g in zip(arrays, grads):
        np.testing.assert_allclose(a.grad.asnumpy(), g, rtol=1e-4,
                                   atol=1e-5)
    if groups > 1:      # not the norm over the whole width
        whole = _norm_by_group(*given[:1], gamma, 1,
                               gate if gated else None)
        assert np.abs(np.asarray(whole) - out.asnumpy()).max() > 1e-2


def test_rms_norm_refuses_groups_that_do_not_divide():
    with pytest.raises(mx.MXNetError, match="groups"):
        nd.RMSNorm(nd.ones((2, 10)), nd.ones((10,)), num_groups=4)


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
    assert [layer.kind for layer in net.layers] == list("MEM*EME")
    with pytest.raises(ValueError, match="pattern"):
        REF.build(dict(SMALL, hybrid_override_pattern="ME-*EME"), "gluon")
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        REF.build(dict(SMALL, mlp_hidden_act="silu"), "gluon")


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


def test_remat_holds_a_boundary_per_layer_and_changes_nothing(trained):
    assert trained[True]["boundaries"] == SMALL["num_hidden_layers"] == 7
    assert trained[False]["boundaries"] == 0
    for name, a in trained[True]["after"].items():
        np.testing.assert_allclose(a, trained[False]["after"][name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)
    from mxnet_tpu import telemetry
    # the fixture traced the remat step last
    assert telemetry.REGISTRY.get(
        "mxnet_step_remat_boundaries").value() == 7.0


def test_the_bias_and_the_load_leave_the_step_as_auxiliary_state(trained):
    """The three biases and the two counts are the step's aux parameters
    (no gradient, no optimizer slot).  The layers read the bias inside
    their remat boundaries; the step writes the rule's result after them:
    u up or down by the sign of mean(c) − c over all 16 experts."""
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


def test_the_reference_notes_its_routing(trained):
    t = trained[True]
    with jax.default_matmul_precision("highest"):
        logits, margin, counts = REF.reference(SMALL, routing=True)(
            t["params"], t["x"])
    np.testing.assert_array_equal(logits, trained["ref_logits"])
    np.testing.assert_array_equal(counts, t["after"]["expert_load"])
    assert margin.shape == (3,) + t["x"].shape and (margin >= 0).all()
    # a token's margin is the least among it and the 8 tokens it follows
    # (routing_margin_window): a flip reaches the tokens after it
    assert SMALL["routing_margin_window"] == 8
    with jax.default_matmul_precision("highest"):
        own = np.asarray(REF.reference(
            dict(SMALL, routing_margin_window=0), routing=True)(
                t["params"], t["x"])[1])
    assert (own >= margin).all() and (own > margin).any()
    for pos in (0, 5, 20):
        np.testing.assert_array_equal(
            margin[..., pos], own[..., max(0, pos - 8):pos + 1].min(axis=-1))


# -- (e) AdamW through TrainStep, save and load ------------------------------------
def _adamw_reference(params, batches, cfg, hyper):
    """The reference stepped with TrainStep's adamw rule (the op
    ``adamw_update``: no bias correction, wd not multiplied by the
    learning rate), the selection bias by its own rule."""
    lr, b1, b2, eps, wd = (hyper[k] for k in (
        "learning_rate", "beta1", "beta2", "epsilon", "wd"))
    names = REF.trained(REF.param_shapes(cfg))
    p = {k: jnp.asarray(v) for k, v in params.items()}
    m = {k: jnp.zeros_like(p[k]) for k in names}
    v = {k: jnp.zeros_like(p[k]) for k in names}
    losses = []
    with jax.default_matmul_precision("highest"):
        for x, y in batches:
            loss, g = jax.value_and_grad(REF.loss(cfg))(p, x, y)
            bias = REF.updated_bias(cfg)(p, x)
            losses.append(float(loss))
            for k in names:
                m[k] = b1 * m[k] + (1 - b1) * g[k]
                v[k] = b2 * v[k] + (1 - b2) * g[k] ** 2
                p[k] = p[k] - (lr * m[k] / (jnp.sqrt(v[k]) + eps)
                               + wd * p[k])
            p.update(bias)
    return p, m, v, losses


@pytest.fixture(scope="module")
def adamw():
    out = {}
    for remat in (False, True):
        net, names, params = _model(SMALL)
        batches = [_batch(SMALL, seed=s) for s in (0, 1)]
        step = _step(net, *batches[0], remat, "adamw", ADAMW)
        losses = [float(step(x, y)) for x, y in batches]
        out[remat] = dict(step=step, names=names, losses=losses,
                          after=_state(step, names))
    out["ref"] = _adamw_reference(params, batches, SMALL, ADAMW)
    out["third"] = _batch(SMALL, seed=2)
    return out


@pytest.mark.parametrize("name", TRAINED + BIAS)
def test_two_adamw_steps_match_the_reference_stepped_alike(adamw, name):
    """Two steps on two batches: the second step's routers read the bias
    the first step wrote.  Adam's first steps are lr · sign-like, so a
    parameter is held to a hundredth of the learning rate."""
    got, want = adamw[True]["after"][name], np.asarray(adamw["ref"][0][name])
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-2 * ADAMW["learning_rate"])
    np.testing.assert_allclose(adamw[True]["losses"], adamw["ref"][3],
                               rtol=1e-5)


def test_adamw_holds_two_slots_a_trained_parameter_and_none_for_the_bias(
        adamw):
    step, names = adamw[True]["step"], adamw[True]["names"]
    state = step.state_dict()
    owned = {names[step.param_names[i]] for i in step._train_idx}
    assert owned == set(TRAINED)
    for n in step.param_names:
        slots = [k for k in state if k.startswith(f"opt:{n}:")]
        assert len(slots) == (2 if names[n] in owned else 0), names[n]
    _p, m, v, _l = adamw["ref"]
    for n in step.param_names:
        if names[n] in owned:
            for slot, want in ((0, m), (1, v)):
                w = np.asarray(want[names[n]])
                np.testing.assert_allclose(
                    np.asarray(state[f"opt:{n}:{slot}"]), w, rtol=5e-3,
                    atol=2e-4 * float(np.abs(w).max()), err_msg=names[n])


def test_remat_changes_nothing_under_adamw(adamw):
    for name, a in adamw[True]["after"].items():
        np.testing.assert_allclose(
            a, adamw[False]["after"][name], rtol=0,
            atol=1e-2 * ADAMW["learning_rate"], err_msg=name)
    np.testing.assert_allclose(adamw[True]["losses"],
                               adamw[False]["losses"], rtol=1e-6)


def test_save_and_load_restore_the_bias_and_both_slots(adamw):
    """A fresh step (other seed: other weights, other bias, zero slots)
    that loads the saved state makes the third step the saved one makes."""
    step, names = adamw[True]["step"], adamw[True]["names"]
    saved = {k: np.asarray(v) for k, v in step.state_dict().items()}
    bias = [n for n in step.param_names if names[n] in BIAS]
    assert len(bias) == 3 and all(f"param:{n}" in saved for n in bias)
    net, _names, _params = _model(SMALL, seed=7)
    x, y = adamw["third"]
    fresh = _step(net, x, y, True, "adamw", ADAMW)
    # the two nets' parameters differ by their blocks' prefixes only
    rename = dict(zip(fresh.param_names, step.param_names))
    before = np.asarray(fresh.state_dict()[f"param:{fresh.param_names[0]}"])
    assert np.abs(before - saved[f"param:{step.param_names[0]}"]).max() > 0
    def theirs(key):
        kind, name, *slot = key.split(":")
        return saved[":".join([kind, rename[name]] + slot)]

    fresh.load_state_dict({k: theirs(k) for k in fresh.state_dict()})
    for k, v in fresh.state_dict().items():
        np.testing.assert_array_equal(np.asarray(v), theirs(k))
    assert float(fresh(x, y)) == float(step(x, y))
    for a, b in zip(fresh.params, step.params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_checkpoint_carries_the_bias_and_both_slots(adamw, tmp_path):
    """Through ``save_checkpoint`` / ``restore_checkpoint``: a second step
    over the same block (its initial parameters, zero slots) adopts the
    trained one's whole state, the three biases among it."""
    from mxnet_tpu.checkpoint import CheckpointManager
    step, names = adamw[True]["step"], adamw[True]["names"]
    x, y = adamw["third"]
    other = _step(step.block, x, y, True, "adamw", ADAMW)
    saved = {k: np.asarray(v) for k, v in step.state_dict().items()}
    bias = [f"param:{n}" for n in step.param_names if names[n] in BIAS]
    assert any(np.abs(np.asarray(other.state_dict()[k]) - saved[k]).max() > 0
               for k in bias)
    with CheckpointManager(tmp_path) as mgr:
        step.save_checkpoint(mgr, 2, block=True)
        assert other.restore_checkpoint(mgr).step == 2
    restored = other.state_dict()
    assert set(restored) == set(saved)
    assert sum(k.startswith("opt:") for k in saved) == 2 * len(TRAINED)
    for k in saved:
        np.testing.assert_array_equal(np.asarray(restored[k]), saved[k],
                                      err_msg=k)


# -- (f) the bias rule evens a skewed router ---------------------------------------
def test_the_bias_rule_lowers_the_load_skew_of_a_skewed_router():
    """A router whose rows 0-2 all tokens score highest (positive hidden
    states against rows of ones): top-3 of 16 sends everything to three
    experts.  Fifty training forwards of the rule, the weights untouched
    (no optimizer), at u = 0.02: the busiest expert's load over the mean
    falls from 16/3 toward 1."""
    block = SparseExperts(16, 8, 16, 16, 0, 3, form="relu2",
                          shared_width=8, select_bias=True)
    block.initialize(mx.initializer.Normal(0.02))
    router = np.random.default_rng(6).standard_normal(
        (16, 16)).astype(np.float32) * 0.05
    router[:3] += 0.3
    block.router_weight.set_data(nd.array(router))
    block.select_bias.set_data(nd.zeros((16,)))
    h = nd.array(np.abs(np.random.default_rng(7).standard_normal(
        (4, 64, 16))).astype(np.float32))
    from mxnet_tpu.gluon.model_zoo.language import balanced_bias
    skew = []
    for _ in range(50):
        _y, _load, _rows, count = block(h)
        c = count.asnumpy()
        skew.append(c.max() / c.mean())
        block.select_bias.set_data(balanced_bias(
            nd, block.select_bias.data(), count, 0.02))
    assert skew[0] == pytest.approx(16 / 3)
    assert skew[-1] < 0.5 * skew[0]
    assert min(skew) == pytest.approx(min(skew[-10:]))


def test_the_model_applies_the_rule_in_training_mode_only():
    net, _names, _params = _model(SMALL)
    x, _y = _batch(SMALL)
    bias = [layer.mixer.select_bias for layer in net.expert_layers]
    before = [b.data().asnumpy().copy() for b in bias]
    net(nd.array(x))
    for b, was in zip(bias, before):
        np.testing.assert_array_equal(b.data().asnumpy(), was)
    with autograd.record():
        net(nd.array(x))
    for b, was in zip(bias, before):
        moved = b.data().asnumpy() - was
        assert np.abs(moved).max() == pytest.approx(1e-3, rel=1e-3)
    from mxnet_tpu import telemetry
    net.record_expert_load(steps=2)
    assert telemetry.REGISTRY.get(
        "mxnet_moe_router_bias_abs_mean").value() == pytest.approx(
            np.abs(np.stack([b.data().asnumpy() for b in bias])).mean())


# -- tracing -----------------------------------------------------------------------
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
    for scope in ("nemotron/mamba/granite/mamba/in_proj",
                  "nemotron/mamba/granite/mamba/conv",
                  "nemotron/mamba/granite/mamba/ssd",
                  "nemotron/mamba/granite/mamba/gated_norm",
                  "nemotron/mamba/granite/mamba/out_proj",
                  "nemotron/attention/granite/attention",
                  "nemotron/moe/op/_contrib_routed_experts/routed_experts/router",
                  "nemotron/moe/op/_contrib_routed_experts/routed_experts/dispatch",
                  "nemotron/moe/op/_contrib_routed_experts/routed_experts/experts",
                  "nemotron/moe/shared/relu2_mlp",
                  "nemotron/moe/combine", "nemotron/head"):
        assert scope in text, scope
    assert text.count("checkpoint") >= 7
