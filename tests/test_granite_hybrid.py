"""The hybrid state-space language model (gluon.model_zoo.language) at a
small size on the CPU: the chunked scan op ``_contrib_ssd_scan`` against
the step-by-step recurrence, the model through ``parallel.spmd.TrainStep``
against the benchmark's plain reference (logits, loss, every parameter's
gradient), the per-layer remat boundary, the vocabulary slice, grouped
heads and the Pallas backward of flash attention, and the published
configuration's counts from its shapes alone."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.ops import pallas_attention
from mxnet_tpu.ops._op_ssm import ssd_scan
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "harness"))
import benchcore  # noqa: E402

CELL = benchcore.Cell("granite4h-spmd-seq4096-bs1")
REF = CELL.config_module()
# hidden 64, 4 Mamba heads of 16, state 16, chunk 8, both kinds of layer
SMALL = dict(CELL.config, hidden_size=64, shared_intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=2,
             attention_multiplier=0.25, mamba_n_heads=4, mamba_d_head=16,
             mamba_d_state=16, mamba_chunk_size=8, num_hidden_layers=5,
             layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
             vocab_size=64, num_classes=64, image=[22])


# -- (i) the chunked scan against the recurrence -------------------------------
def _recurrence(x, dt, a, b, c, d):
    """S_t = exp(Δ_t a) S_{t-1} + Δ_t x_t B_tᵀ, y_t = S_t C_t + D x_t, one
    time step at a time."""
    heads = x.shape[2]
    b, c = (jnp.repeat(v, heads // v.shape[2], axis=2) for v in (b, c))

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = s * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t) + d[:, None] * x_t

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    _, ys = jax.lax.scan(step, s0, tuple(jnp.moveaxis(v, 1, 0)
                                         for v in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1)


def _scan_inputs(groups, t=21, heads=4, p=16, n=16):
    rng = np.random.default_rng(0)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return (f(2, t, heads, p), np.log1p(np.exp(f(2, t, heads))),
            -np.exp(rng.uniform(0, 2, heads)).astype(np.float32),
            f(2, t, groups, n), f(2, t, groups, n), f(heads))


SCAN_INPUTS = ("x", "dt", "A", "B", "C", "D")


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [4, 8, 32])     # 21 steps: a tail, one chunk
def test_ssd_scan_matches_the_recurrence(chunk, groups):
    args = _scan_inputs(groups)
    got = nd.contrib.ssd_scan(*map(nd.array, args), chunk_size=chunk)
    np.testing.assert_allclose(got.asnumpy(), _recurrence(*args),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("wrt", range(6), ids=SCAN_INPUTS)
@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_ssd_scan_gradient_matches_the_recurrence(chunk, wrt):
    args = _scan_inputs(groups=2)
    weight = np.random.default_rng(1).standard_normal(
        args[0].shape).astype(np.float32)
    got = jax.grad(lambda *v: (ssd_scan(*v, chunk) * weight).sum(),
                   argnums=wrt)(*args)
    want = jax.grad(lambda *v: (_recurrence(*v) * weight).sum(),
                    argnums=wrt)(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(np.abs(want).max()))


# -- (ii) the model through TrainStep against the plain reference ----------------
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
        lowered = _flash_bwd_lowered("pallas")
        loss = float(step(x, y))
        after = {names[n]: np.asarray(a)
                 for n, a in zip(step.param_names, step.params)}
        out[remat] = dict(params=params, x=x, y=y, logits=logits, loss=loss,
                          after=after, boundaries=step.remat_boundaries,
                          flash_bwd=_flash_bwd_lowered("pallas") - lowered)
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


@pytest.mark.parametrize("name", sorted(REF.param_shapes(SMALL)))
def test_gradient_of_every_parameter_matches_the_reference(trained, name):
    """learning rate 1, momentum from zero: before − after = the gradient,
    to within the float32 spacing of the parameter it was taken from."""
    t = trained[True]
    got = t["params"][name] - t["after"][name]
    want = np.asarray(trained["ref_grads"][name])
    spacing = float(np.spacing(np.abs(t["params"][name]).max()))
    np.testing.assert_allclose(
        got, want, rtol=2e-3,
        atol=2e-4 * float(np.abs(want).max()) + spacing)


def test_remat_holds_a_boundary_per_layer_and_changes_nothing(trained):
    assert trained[True]["boundaries"] == SMALL["num_hidden_layers"]
    assert trained[False]["boundaries"] == 0
    for name, a in trained[True]["after"].items():
        np.testing.assert_allclose(a, trained[False]["after"][name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)
    from mxnet_tpu import telemetry
    # the fixture traced the remat step last
    assert telemetry.REGISTRY.get(
        "mxnet_step_remat_boundaries").value() == 5.0


@pytest.mark.parametrize("remat", [False, True])
def test_every_attention_layer_lowers_the_pallas_backward_once(trained, remat):
    """Tracing the step counts one kernel backward per attention layer,
    under remat too, and the zoo's shapes never take another."""
    assert trained[remat]["flash_bwd"] == \
        SMALL["layer_types"].count("attention")
    assert _flash_bwd_lowered("xla") == 0


def test_whole_forward_remat_is_one_boundary_for_a_block_without_layers():
    net = gluon.nn.Dense(4, in_units=3)
    net.initialize()
    x = np.ones((2, 3), np.float32)
    y = np.zeros((2,), np.float32)
    step = _step(net, x, y, remat=True, lr=0.1)
    float(step(x, y))
    assert step.remat_boundaries == 1


def test_named_scopes_are_in_the_step_program():
    net, _names, _params = _model(SMALL)
    x, y = _batch(SMALL)
    step = _step(net, x, y, remat=True)
    with step.mesh.jax_mesh:
        text = step._step.lower(
            jax.random.PRNGKey(0), step._train_params, step._aux_params,
            step.opt_state, x, y).as_text(debug_info=True)
    for scope in ("granite/mamba/in_proj", "granite/mamba/conv",
                  "granite/mamba/ssd", "granite/mamba/gated_norm",
                  "granite/attention", "granite/mlp", "granite/head"):
        assert scope in text, scope
    assert text.count("checkpoint") >= SMALL["num_hidden_layers"]


def test_a_layer_that_updates_statistics_cannot_be_a_boundary():
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.bn = gluon.nn.BatchNorm(in_channels=3)
            self.remat_layers = [self.bn]

        def hybrid_forward(self, F, x):
            return self.bn(x)

    net = Net()
    net.initialize()
    x = np.ones((2, 3), np.float32)
    with pytest.raises(mx.MXNetError, match="rematerialisation boundary"):
        step = _step(net, x, np.zeros((2,), np.float32), remat=True)
        step(x, np.zeros((2,), np.float32))


# -- (iii) the vocabulary slice ------------------------------------------------------
def test_logits_over_a_vocabulary_slice_are_the_uncut_columns():
    """A model that holds the first 16 rows of the table gives, for ids
    inside the slice, the same logits as the uncut reference's first 16
    columns."""
    cut = dict(SMALL, vocab_size=16, num_classes=16)
    _net, _names, params = _model(SMALL)
    net, names, _ = _model(cut)
    for k, p in net.collect_params().items():
        p.set_data(nd.array(params[names[k]][:16] if names[k] == "embed"
                            else params[names[k]]))
    x, _y = _batch(cut)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(REF.reference(SMALL)(params, x))
    np.testing.assert_allclose(net(nd.array(x)).asnumpy(), whole[..., :16],
                               rtol=1e-4, atol=1e-5)


# -- (iv) flash attention: grouped heads, the Pallas backward --------------------------
def _flash_bwd_lowered(impl):
    from mxnet_tpu import telemetry
    return telemetry.REGISTRY.get(
        "mxnet_flash_attention_bwd_lowered_total").value({"impl": impl})


def _qkvw(heads, kv_heads, s, d, dtype=np.float32):
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.standard_normal((1, n, s, d)), dtype)
                 for n in (heads, kv_heads, kv_heads, heads))


def _plain(q, k, v, causal):
    """softmax(q kᵀ · 0.2) v over repeated heads."""
    k, v = (jnp.repeat(a, q.shape[1] // a.shape[1], axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.2
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


# (causal, key/value heads, query heads a key/value head, positions, head
# size, block_q, block_k); the first two were the row-blocked backward's
# cases: 8 key/value heads under 32 query heads, 200 positions (a tail)
@pytest.mark.parametrize("causal,kv_heads,group,s,d,block_q,block_k", [
    (True, 8, 4, 200, 16, 128, 128), (False, 8, 4, 200, 16, 128, 128),
    (True, 2, 1, 200, 64, 128, 128), (True, 1, 16, 200, 16, 128, 128),
    (True, 1, 16, 256, 128, 128, 128), (False, 1, 16, 200, 64, 64, 128),
    (True, 2, 4, 256, 64, 128, 64), (True, 2, 4, 256, 16, 64, 128),
    (True, 2, 1, 384, 16, 128, 256), (False, 2, 1, 256, 128, 128, 256),
    (True, 2, 4, 512, 128, 256, 128), (False, 2, 4, 300, 64, 256, 128)])
def test_pallas_backward_equals_the_plain_gradient(causal, kv_heads, group,
                                                   s, d, block_q, block_k):
    """Forward and every gradient equal plain attention over repeated
    heads, and each traced backward counts once as the kernels'."""
    q, k, v, w = _qkvw(group * kv_heads, kv_heads, s, d)

    def flash(q, k, v):
        return pallas_attention.flash_attention(q, k, v, causal, 0.2,
                                                block_q, block_k)

    np.testing.assert_allclose(flash(q, k, v), _plain(q, k, v, causal),
                               rtol=1e-4, atol=1e-5)
    before = _flash_bwd_lowered("pallas")
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    assert _flash_bwd_lowered("pallas") == before + 1
    assert _flash_bwd_lowered("xla") == 0
    for g, want in zip(got, jax.grad(
            lambda *a: (_plain(*a, causal) * w).sum(), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, want, rtol=1e-4,
                                   atol=1e-5 * float(np.abs(want).max()))


def test_bfloat16_operands_give_bfloat16_gradients_near_the_float32_ones():
    args = _qkvw(4, 1, 200, 64)

    def grads(*a):
        return jax.grad(lambda q, k, v, w: (pallas_attention.flash_attention(
            q, k, v, True, 0.2) * w).sum().astype(jnp.float32),
            (0, 1, 2))(*a)

    for g, want in zip(grads(*(a.astype(jnp.bfloat16) for a in args)),
                       grads(*args)):
        assert g.dtype == jnp.bfloat16
        g = np.asarray(g, np.float32)
        assert np.isfinite(g).all()
        assert np.abs(g - want).max() <= 2e-2 * float(np.abs(want).max())


def test_rows_past_the_sequence_get_exactly_zero_gradients():
    """200 positions in tiles of 128 are padded to 256: the padded keys
    are masked, the padded queries carry no cotangent, and what the
    kernels write there is zero, not small."""
    q, k, v, do = _qkvw(4, 2, 200, 16)
    out, m, l = pallas_attention._flash_fwd(
        q, k, v, causal=True, sm_scale=0.2, block_q=128, block_k=128)
    grads = pallas_attention._flash_bwd(
        q, k, v, out, m + jnp.log(l), do, causal=True, sm_scale=0.2,
        block_q=128, block_k=128)
    for g in grads:
        assert g.shape[2] == 256
        assert np.isfinite(g).all() and np.abs(g[:, :, :200]).max() > 0
        assert not np.asarray(g[:, :, 200:]).any()


def test_the_forward_under_differentiation_is_the_forward_bit_for_bit():
    """The rule that keeps (out, lse) for the backward returns the very
    output of the forward kernel, whose (out, m, l) ring attention reads."""
    q, k, v, _ = _qkvw(4, 2, 200, 16)
    out, m, l = pallas_attention._flash_fwd(
        q, k, v, causal=True, sm_scale=0.2, block_q=128, block_k=128)
    primal, (_, _, _, kept, lse) = pallas_attention._flash_fwd_rule(
        q, k, v, True, 0.2, 128, 128)
    plain = pallas_attention.flash_attention(q, k, v, True, 0.2, 128, 128)
    for a in (primal, kept, plain):
        np.testing.assert_array_equal(a, out)
    assert lse.shape == (1, 4, 200) and lse.dtype == jnp.float32
    np.testing.assert_array_equal(lse, m + jnp.log(l))


def test_flash_attention_refuses_heads_that_do_not_divide():
    q = np.zeros((1, 6, 8, 4), np.float32)
    kv = np.zeros((1, 4, 8, 4), np.float32)
    with pytest.raises(ValueError, match="must divide"):
        pallas_attention.flash_attention(q, kv, kv)


# -- (v) the published configuration, from shapes alone --------------------------------
def test_published_widths_give_the_issue_counts():
    cfg = CELL.config
    shapes = REF.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 772_160_448
    per_kind = {}
    for k, s in shapes.items():
        if k.startswith("layers.0.") or k.startswith("layers.5."):
            per_kind[k[7]] = per_kind.get(k[7], 0) + int(np.prod(s))
    assert per_kind == {"0": 76_182_976, "5": 60_821_504}
    macs = REF.macs_per_image(cfg)
    assert abs(macs / 3.275e12 - 1) < 0.01
    assert macs // 4096 == 799_599_616     # per token, as the docstring adds
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["layer_types"][:10].count("attention") == 1
