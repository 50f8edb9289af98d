"""mxnet_tpu/kernels — gated Pallas kernels + measured autotuner.

Acceptance surface (ISSUE 17):

* every registered kernel passes its interpreter-mode fwd+bwd
  correctness gate vs its pure-XLA reference, across shapes (including
  non-divisor row counts) and dtypes (f32 + bf16);
* a spec that produces wrong numbers NEVER dispatches: the gate fails,
  ``kernels.get`` serves the reference, and the fallback counter the
  ``kernel_fallback`` alert watches increments;
* tuner ladder: tuned winners persist into the versioned namespace next
  to the PR 7 compile-cache ladders, reload as ``persisted`` (zero
  re-tunes, asserted cross-process), and a salt flip invalidates
  cleanly down to the heuristic default;
* mode matrix: ``MXNET_KERNELS=off|reference|tuned`` plus per-kernel
  ``MXNET_KERNELS_OVERRIDES``; bad values raise MXNetError;
* integration: ``MXNET_KERNELS=reference`` fits are bitwise identical
  to kernels-off under ScanTrainStep and the dp×tp mesh window, with
  dispatch counts pinned; tuned mode engages real Pallas configs inside
  the scanned body without changing the dispatch budget;
* the serving engine's prefill can ride the attention kernel;
* telemetry: the ``mxnet_kernel_*`` families exist and the ``kernels``
  collector reports into REGISTRY.snapshot().
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import io as mxio
from mxnet_tpu import profiler as prof
from mxnet_tpu import kernels
from mxnet_tpu.kernels import autotune, registry

_ENV_KEYS = ("MXNET_KERNELS", "MXNET_KERNELS_OVERRIDES",
             "MXNET_KERNELS_TUNE_REPEATS", "MXNET_KERNELS_TUNE_BUDGET",
             "MXNET_FUSED_LAYERNORM", "MXNET_FUSED_SOFTMAX_CE",
             "MXNET_FUSED_STEP", "MXNET_SCAN_STEPS", "MXNET_SCAN_ACCUM",
             "MXNET_MESH_FUSED_STEP", "MXNET_COMPILE_CACHE_DIR",
             "MXNET_COMPILE_CACHE_SALT")


@pytest.fixture(autouse=True)
def _kernels_clean():
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    kernels.reset_for_tests()
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    kernels.reset_for_tests()


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")


# -- correctness gates --------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name,shape", [
    ("layernorm", (32, 16)),
    ("layernorm", (33, 16)),      # non-divisor rows: heuristic re-tile
    ("softmax_ce", (32, 8)),
    ("softmax_ce", (40, 12)),
    ("attention", (1, 2, 16, 8)),
])
def test_gate_fwd_bwd_parity(name, shape, dtype):
    """The default config passes its interpreter-mode gate — forward
    AND backward through the kernel's custom_vjp — for every kernel,
    across shapes (incl. rows the tuned tile cannot divide) and dtypes."""
    spec = registry.get_spec(name)
    cfg = spec.default_config(shape, dtype)
    assert registry.gate(name, cfg, shape, dtype), \
        f"{name} default config failed its gate on {shape} {jnp.dtype(dtype).name}"


@pytest.mark.parametrize("op_name,spec_name,shape,dtype", [
    ("LayerNorm", "layernorm", (4096, 1024), jnp.bfloat16),
    ("LayerNorm", "layernorm", (100, 1024), jnp.float32),
    ("softmax_cross_entropy", "softmax_ce", (128, 1000), jnp.float32),
    ("softmax_cross_entropy", "softmax_ce", (32, 1000), jnp.float32),
    ("_contrib_flash_attention", "attention", (2, 8, 1024, 128),
     jnp.bfloat16),
])
def test_ops_lower_to_mosaic_for_tpu_from_the_cpu(op_name, spec_name, shape,
                                                  dtype):
    """Lower each kernel, as its op, for the TPU platform from this CPU
    process (no interpreter: the tpu branch of per_platform) at the
    shapes chip_smoke.py compiles on the chip.  Lowering is the first
    gate Mosaic applies — a gather, a 1-D statistic block or a row tile
    under the sublane packing is refused HERE, without a chip — and the
    same trace lowered for the CPU must hold no Mosaic call."""
    from mxnet_tpu.ops import registry as op_registry
    spec = registry.get_spec(spec_name)
    args, kwargs = spec.example_inputs(shape, dtype,
                                       np.random.RandomState(0))
    fn = op_registry.get(op_name).fcompute
    traced = jax.jit(lambda *a: fn(dict(kwargs), *a)).trace(
        *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args])
    assert "tpu_custom_call" in traced.lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in traced.lower(
        lowering_platforms=("cpu",)).as_text()


def test_gate_report_full_grid():
    """Every config in each spec's (small-shape) search space is
    classifiable, and all of them pass on these shapes."""
    shapes = {"layernorm": (64, 32), "softmax_ce": (64, 16),
              "attention": (2, 2, 32, 8)}
    for name, shape in shapes.items():
        report = registry.gate_report(name, shape, np.float32)
        assert report, name
        bad = [k for k, ok in report.items() if not ok]
        assert not bad, f"{name}: gate failed for {bad}"


def test_wrong_kernel_never_dispatches(monkeypatch):
    """A spec whose implementation produces wrong numbers fails its
    gate; kernels.get serves the reference and counts the fallback."""
    from mxnet_tpu.telemetry import REGISTRY

    def _ref(x):
        return x * 2.0

    spec = registry.KernelSpec(
        name="_test_broken", doc="intentionally wrong",
        reference=_ref,
        make=lambda cfg: (lambda x: x * 3.0),   # wrong on purpose
        config_space=lambda shape, dtype: [{}],
        default_config=lambda shape, dtype: {},
        example_inputs=lambda shape, dtype, rng: (
            (jnp.asarray(rng.randn(*shape).astype(np.float32)),), {}),
        grad_argnums=(0,), tolerance=lambda dtype: (1e-5, 1e-5))
    registry.register_kernel(spec)
    try:
        monkeypatch.setenv("MXNET_KERNELS", "tuned")
        kernels.reset_for_tests()
        assert registry.gate("_test_broken", {}, (4, 4), np.float32) is False
        kb = kernels.get("_test_broken", (4, 4), np.float32)
        assert kb is not None and kb.source == "fallback-reference"
        x = jnp.ones((4, 4))
        np.testing.assert_array_equal(np.asarray(kb(x)), np.asarray(_ref(x)))
        dump = REGISTRY.prometheus_dump()
        assert 'mxnet_kernel_fallback_total{kernel="_test_broken"' in dump \
            or ('mxnet_kernel_fallback_total' in dump and "_test_broken" in dump)
        assert 'result="fail"' in dump
    finally:
        registry._SPECS.pop("_test_broken", None)
        kernels.reset_for_tests()


# -- the tuner ladder ---------------------------------------------------------
def test_tune_persist_reload_and_salt_invalidation(tmp_path, monkeypatch):
    """tuned -> persisted -> (salt flip) default, with the stale
    namespace visible to stale_namespaces() and removable by
    prune_stale()."""
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("MXNET_COMPILE_CACHE_SALT", raising=False)
    kernels.reset_for_tests()

    shape = (64, 32)
    cfg, source = kernels.tune("layernorm", shape, np.float32,
                               configs=[{"block_rows": 64},
                                        {"block_rows": 16}], repeats=1)
    assert source == "tuned" and cfg["block_rows"] in (64, 16)
    assert autotune.tunes_performed() == 1
    path = autotune.winners_path()
    assert os.path.exists(path)
    payload = json.load(open(path))
    assert payload["version"] in path  # namespace == version_key

    # a fresh "process" (full reset) reloads the winner: persisted rung
    kernels.reset_for_tests()
    cfg2, source2 = autotune.lookup("layernorm", shape, np.float32)
    assert source2 == "persisted" and cfg2 == cfg
    assert autotune.tunes_performed() == 0

    # a salt flip renames the namespace: the old file is stale, lookup
    # falls through to the heuristic default — no crash, no reload
    monkeypatch.setenv("MXNET_COMPILE_CACHE_SALT", "kernels-test-stale")
    kernels.reset_for_tests()
    cfg3, source3 = autotune.lookup("layernorm", shape, np.float32)
    assert source3 == "default"
    stale = autotune.stale_namespaces()
    assert os.path.basename(path) in stale
    removed = autotune.prune_stale()
    assert os.path.basename(path) in removed
    assert not os.path.exists(path)


def test_second_process_zero_retunes(tmp_path, monkeypatch):
    """Winners tuned here reload in a NEW interpreter with zero
    re-tunes (the child asserts from its own counters)."""
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("MXNET_COMPILE_CACHE_SALT", raising=False)
    kernels.reset_for_tests()
    shape = (64, 32)
    _, source = kernels.tune("layernorm", shape, np.float32,
                             configs=[{"block_rows": 32}], repeats=1)
    assert source == "tuned"

    child = ("import json, numpy as np\n"
             "from mxnet_tpu import kernels\n"
             "from mxnet_tpu.kernels import autotune\n"
             "cfg, src = autotune.lookup('layernorm', (64, 32), np.float32)\n"
             "print(json.dumps({'tunes': autotune.tunes_performed(),"
             " 'source': src, 'config': cfg}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["tunes"] == 0
    assert got["source"] == "persisted"
    assert got["config"] == {"block_rows": 32}


def test_corrupt_winners_quarantined_once(tmp_path, monkeypatch, caplog):
    """A torn winners file is renamed .corrupt with ONE warning and the
    ladder falls through to the default — planner.load_ladder doctrine."""
    import logging

    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    kernels.reset_for_tests()
    path = autotune.winners_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write('{"version": "torn')
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.kernels"):
        cfg, source = autotune.lookup("layernorm", (64, 32), np.float32)
        autotune.lookup("softmax_ce", (64, 16), np.float32)
    assert source == "default"
    assert os.path.exists(path + ".corrupt")
    assert not os.path.exists(path)
    warns = [r for r in caplog.records
             if "corrupt persisted kernel tunings" in r.getMessage()]
    assert len(warns) == 1


# -- mode matrix --------------------------------------------------------------
def test_mode_matrix(monkeypatch):
    shape, dt = (32, 16), np.float32
    monkeypatch.setenv("MXNET_KERNELS", "off")
    kernels.reset_for_tests()
    assert kernels.mode() == "off"
    assert kernels.get("layernorm", shape, dt) is None

    monkeypatch.setenv("MXNET_KERNELS", "reference")
    kernels.reset_for_tests()
    kb = kernels.get("layernorm", shape, dt)
    assert kb is not None and kb.source == "reference"

    monkeypatch.setenv("MXNET_KERNELS", "tuned")
    kernels.reset_for_tests()
    kb = kernels.get("layernorm", shape, dt)
    assert kb is not None and kb.source in ("default", "tuned", "persisted")


def test_per_kernel_overrides(monkeypatch):
    monkeypatch.setenv("MXNET_KERNELS", "reference")
    monkeypatch.setenv("MXNET_KERNELS_OVERRIDES", "layernorm=off")
    kernels.reset_for_tests()
    assert kernels.mode("layernorm") == "off"
    assert kernels.mode("softmax_ce") == "reference"
    assert kernels.get("layernorm", (32, 16), np.float32) is None
    kb = kernels.get("softmax_ce", (32, 8), np.float32)
    assert kb is not None and kb.source == "reference"


def test_invalid_modes_raise(monkeypatch):
    from mxnet_tpu.base import MXNetError
    monkeypatch.setenv("MXNET_KERNELS", "turbo")
    kernels.reset_for_tests()
    with pytest.raises(MXNetError, match="MXNET_KERNELS"):
        kernels.mode()
    monkeypatch.setenv("MXNET_KERNELS", "reference")
    monkeypatch.setenv("MXNET_KERNELS_OVERRIDES", "layernorm=warp9")
    kernels.reset_for_tests()
    with pytest.raises(MXNetError, match="OVERRIDES"):
        kernels.mode("layernorm")


# -- fit integration: ScanTrainStep ------------------------------------------
def _ln_mlp():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=32, name="fc1")
    h = mx.sym.LayerNorm(h, name="ln1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _ln_init(seed=5):
    rng = np.random.RandomState(seed)
    return {"fc1_weight": mx.nd.array(rng.randn(32, 20) * 0.1),
            "fc1_bias": mx.nd.zeros((32,)),
            "ln1_gamma": mx.nd.ones((32,)),
            "ln1_beta": mx.nd.zeros((32,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 32) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}


def _scan_fit(monkeypatch, mode):
    """One scanned epoch (K=4, 8 batches) of the LayerNorm MLP under a
    kernels mode, with the legacy fused-op gates pinned OFF so the off
    baseline is the plain-XLA path."""
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_SCAN_STEPS", "4")
    monkeypatch.setenv("MXNET_FUSED_LAYERNORM", "0")
    monkeypatch.setenv("MXNET_FUSED_SOFTMAX_CE", "0")
    monkeypatch.setenv("MXNET_KERNELS", mode)
    kernels.reset_for_tests()
    mx.random.seed(0)
    rng = np.random.RandomState(3)
    x = rng.randn(128, 20).astype(np.float32)
    y = rng.randint(0, 10, 128).astype(np.float32)
    it = mxio.NDArrayIter(mx.nd.array(x), mx.nd.array(y), batch_size=16,
                          label_name="softmax_label")
    mod = mx.mod.Module(_ln_mlp(), context=mx.cpu())
    prof.reset_dispatch_counts()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05},
            arg_params={k: v.copy() for k, v in _ln_init().items()})
    counts = prof.dispatch_counts()
    params, _ = mod.get_params()
    sel = {k[0]: v["source"] for k, v in kernels._SELECTED.items()}
    return {k: v.asnumpy() for k, v in params.items()}, counts, sel


def test_scan_fit_reference_bitwise_and_dispatch_pinned(monkeypatch):
    """MXNET_KERNELS=reference == off bit for bit (both lower the same
    plain_layer_norm / plain_softmax_ce jaxpr), and the dispatch budget
    is pinned: 2 scan windows, 2 total dispatches, in BOTH modes."""
    p_off, c_off, _ = _scan_fit(monkeypatch, "off")
    p_ref, c_ref, sel = _scan_fit(monkeypatch, "reference")
    assert c_off == {"scan_window": 2, "total": 2}
    assert c_ref == {"scan_window": 2, "total": 2}
    assert sel.get("layernorm") == "reference"
    for k in p_off:
        np.testing.assert_array_equal(p_off[k], p_ref[k], err_msg=k)


def test_scan_fit_tuned_engages_pallas(monkeypatch):
    """Tuned mode resolves a real (gated) Pallas config inside the
    scanned body — not the fallback — with the dispatch budget
    unchanged and numerics within fp tolerance of the off baseline."""
    p_off, _c, _s = _scan_fit(monkeypatch, "off")
    p_tun, c_tun, sel = _scan_fit(monkeypatch, "tuned")
    assert c_tun == {"scan_window": 2, "total": 2}
    assert sel.get("layernorm") in ("default", "tuned", "persisted"), sel
    for k in p_off:
        np.testing.assert_allclose(p_off[k], p_tun[k], rtol=1e-3,
                                   atol=1e-4, err_msg=k)


# -- fit integration: dp×tp mesh window --------------------------------------
def _mesh_ln_models():
    def build():
        d = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(d, num_hidden=64, name="fc1")
        h = mx.sym.LayerNorm(h, name="ln1")
        h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    rng = np.random.RandomState(0)
    init = {"fc1_weight": mx.nd.array(rng.randn(64, 50) * 0.1),
            "fc1_bias": mx.nd.zeros((64,)),
            "ln1_gamma": mx.nd.ones((64,)),
            "ln1_beta": mx.nd.zeros((64,)),
            "fc2_weight": mx.nd.array(rng.randn(10, 64) * 0.1),
            "fc2_bias": mx.nd.zeros((10,))}
    return build, init


def test_mesh_fit_reference_bitwise_and_counts(monkeypatch):
    """Under the dp=2×tp=2 mesh window, reference mode == off bit for
    bit (weights), with the mesh dispatch budget pinned."""
    _need_devices(4)
    from mxnet_tpu.parallel import fused as F

    monkeypatch.setenv("MXNET_FUSED_LAYERNORM", "0")
    monkeypatch.setenv("MXNET_FUSED_SOFTMAX_CE", "0")
    build, init = _mesh_ln_models()
    K, NB, BS = 4, 8, 16
    rng = np.random.RandomState(0)
    x = rng.randn(NB * BS, 50).astype(np.float32)
    y = rng.randint(0, 10, NB * BS).astype(np.float32)

    runs = {}
    for m in ("off", "reference"):
        monkeypatch.setenv("MXNET_KERNELS", m)
        kernels.reset_for_tests()
        params, _s, counts, _mod = F._run_mesh_fit(
            K, NB, BS, "sgd", {"learning_rate": 0.1},
            build, {k: v.copy() for k, v in init.items()}, x, y)
        assert counts.get("mesh_window", 0) == NB // K, (m, counts)
        runs[m] = params
    for k in runs["off"]:
        np.testing.assert_array_equal(runs["off"][k], runs["reference"][k],
                                      err_msg=k)


# -- serving integration ------------------------------------------------------
def test_generation_prefill_rides_attention_kernel(monkeypatch):
    """The engine resolves the attention kernel at model build; greedy
    generations match the kernels-off engine token for token."""
    from mxnet_tpu.serving.generation import GenerationEngine, tiny_lm

    def _tokens(mode):
        monkeypatch.setenv("MXNET_KERNELS", mode)
        kernels.reset_for_tests()
        model = tiny_lm(vocab=24, d_model=8, max_len=64, seed=2, jit=True)
        eng = GenerationEngine(model, name=f"lm-{mode}", slots=4,
                               page_tokens=8, kv_budget_mb=8, max_len=64)
        eng.warm()
        try:
            prompts = [np.arange(1, 1 + n, dtype=np.int32) % 23 + 1
                       for n in (5, 9, 13)]
            return [eng.generate(p, max_new_tokens=8, greedy=True)
                    for p in prompts]
        finally:
            eng.close()

    t_off = _tokens("off")
    t_ref = _tokens("reference")
    assert [list(t) for t in t_off] == [list(t) for t in t_ref]
    # the kernel really was resolved for the prefill shape
    assert any(k[0] == "attention" for k in kernels._SELECTED), \
        kernels._SELECTED.keys()


# -- telemetry ----------------------------------------------------------------
def test_telemetry_families_and_collector(monkeypatch, tmp_path):
    from mxnet_tpu import telemetry as T

    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_KERNELS", "tuned")
    kernels.reset_for_tests()
    kernels.tune("layernorm", (64, 32), np.float32,
                 configs=[{"block_rows": 64}], repeats=1)
    kernels.get("layernorm", (64, 32), np.float32)
    dump = T.prometheus_dump()
    assert "mxnet_kernel_gate_total" in dump
    assert "mxnet_kernel_tune_seconds" in dump
    assert "mxnet_kernel_selected_config" in dump
    snap = T.REGISTRY.snapshot()
    assert "kernels" in snap
    assert snap["kernels"]["tunes_performed"] == 1
    assert snap["kernels"]["registered"] == ["attention", "layernorm",
                                             "softmax_ce"]
    assert any(v["source"] == "tuned"
               for v in snap["kernels"]["selected"].values())


def test_kernel_fallback_alert_in_default_pack():
    from mxnet_tpu.telemetry import alerts
    rules = {r.name: r for r in alerts.default_rules()}
    assert "kernel_fallback" in rules
    rule = rules["kernel_fallback"]
    assert rule.family == "mxnet_kernel_fallback_total"
    assert rule.severity == "warn"


# -- flash attention with a value head size of its own ------------------------
@pytest.mark.parametrize("h,h_kv,s,mask", [
    (2, 2, 200, "causal"),                          # a tail in the tiles
    (2, 2, 272, ("block_diffusion", 4, 136)),
    (4, 2, 200, "causal"),                          # grouped queries
], ids=["causal", "block_diffusion", "grouped"])
def test_flash_takes_a_value_head_of_its_own(h, h_kv, s, mask):
    """Keys of 192 over values of 128, as latent attention has them: the
    three kernels in the interpreter against ``reference_attention``, the
    output and the three gradients; dV and the output take v's size."""
    from mxnet_tpu.ops.pallas_attention import (Mask, flash_attention,
                                                reference_attention)
    mask = Mask(*mask) if isinstance(mask, tuple) else Mask(mask)
    rng = np.random.default_rng(44)
    q, k, v, do = (jnp.asarray(rng.standard_normal((1, n, s, d)), jnp.float32)
                   for n, d in ((h, 192), (h_kv, 192), (h_kv, 128), (h, 128)))
    scale = 192 ** -0.5
    got, vjp = jax.vjp(lambda *a: flash_attention(*a, mask, scale, 128, 128),
                       q, k, v)
    with jax.default_matmul_precision("highest"):
        want, ref_vjp = jax.vjp(
            lambda *a: reference_attention(*a, mask, scale), q, k, v)
        grads = ref_vjp(do)
    assert got.shape == (1, h, s, 128)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip("qkv", vjp(do), grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5, err_msg=name)


def test_flash_refuses_a_value_that_is_not_the_keys():
    from mxnet_tpu.ops.pallas_attention import flash_attention
    q = np.zeros((1, 4, 8, 24), np.float32)
    with pytest.raises(ValueError, match="head count"):
        flash_attention(q, q[:, :2], np.zeros((1, 4, 8, 16), np.float32))
    with pytest.raises(ValueError, match="head size"):
        flash_attention(q, q[:, :2, :, :16], np.zeros((1, 2, 8, 16),
                                                      np.float32))
