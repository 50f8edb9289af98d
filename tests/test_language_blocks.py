"""The language zoo's seams at a small size on the CPU: every model file
imports the package's shared blocks from ``blocks`` alone, and what the
benchmark and the checkpoints bind to stays as it was recorded before the
blocks two or more models share moved there.

For each of the five models, at the size its own test file builds: the
sorted names of its parameters (less the model's own prefix), and the
``jax.named_scope`` paths of its train step (``TrainStep``, ``remat=True``)
as ``profiler.parse_op_name`` reads them, cut where an operator's own
scopes begin (``op/<name>``).  Zeros initialise the weights: a trace
reads their shapes and nothing else."""
import importlib
import os
import re

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, profiler
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.spmd import TrainStep

LANGUAGE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "mxnet_tpu", "gluon", "model_zoo", "language")
MODELS = ("granite_hybrid", "solar_open2", "nemotron_h", "sdar_moe", "zaya")

PARAMS = {
    "granite_hybrid": """
        embed_weight final_norm_gamma
        layers_hybriddecoderlayer0_input_norm_gamma
        layers_hybriddecoderlayer0_mixer_A_log
        layers_hybriddecoderlayer0_mixer_D
        layers_hybriddecoderlayer0_mixer_conv_bias
        layers_hybriddecoderlayer0_mixer_conv_weight
        layers_hybriddecoderlayer0_mixer_dt_bias
        layers_hybriddecoderlayer0_mixer_in_proj_weight
        layers_hybriddecoderlayer0_mixer_norm_gamma
        layers_hybriddecoderlayer0_mixer_out_proj_weight
        layers_hybriddecoderlayer0_mlp_in_weight
        layers_hybriddecoderlayer0_mlp_out_weight
        layers_hybriddecoderlayer0_post_norm_gamma
        layers_hybriddecoderlayer1_input_norm_gamma
        layers_hybriddecoderlayer1_mixer_A_log
        layers_hybriddecoderlayer1_mixer_D
        layers_hybriddecoderlayer1_mixer_conv_bias
        layers_hybriddecoderlayer1_mixer_conv_weight
        layers_hybriddecoderlayer1_mixer_dt_bias
        layers_hybriddecoderlayer1_mixer_in_proj_weight
        layers_hybriddecoderlayer1_mixer_norm_gamma
        layers_hybriddecoderlayer1_mixer_out_proj_weight
        layers_hybriddecoderlayer1_mlp_in_weight
        layers_hybriddecoderlayer1_mlp_out_weight
        layers_hybriddecoderlayer1_post_norm_gamma
        layers_hybriddecoderlayer2_input_norm_gamma
        layers_hybriddecoderlayer2_mixer_k_weight
        layers_hybriddecoderlayer2_mixer_o_weight
        layers_hybriddecoderlayer2_mixer_q_weight
        layers_hybriddecoderlayer2_mixer_v_weight
        layers_hybriddecoderlayer2_mlp_in_weight
        layers_hybriddecoderlayer2_mlp_out_weight
        layers_hybriddecoderlayer2_post_norm_gamma
        layers_hybriddecoderlayer3_input_norm_gamma
        layers_hybriddecoderlayer3_mixer_A_log
        layers_hybriddecoderlayer3_mixer_D
        layers_hybriddecoderlayer3_mixer_conv_bias
        layers_hybriddecoderlayer3_mixer_conv_weight
        layers_hybriddecoderlayer3_mixer_dt_bias
        layers_hybriddecoderlayer3_mixer_in_proj_weight
        layers_hybriddecoderlayer3_mixer_norm_gamma
        layers_hybriddecoderlayer3_mixer_out_proj_weight
        layers_hybriddecoderlayer3_mlp_in_weight
        layers_hybriddecoderlayer3_mlp_out_weight
        layers_hybriddecoderlayer3_post_norm_gamma
        layers_hybriddecoderlayer4_input_norm_gamma
        layers_hybriddecoderlayer4_mixer_A_log
        layers_hybriddecoderlayer4_mixer_D
        layers_hybriddecoderlayer4_mixer_conv_bias
        layers_hybriddecoderlayer4_mixer_conv_weight
        layers_hybriddecoderlayer4_mixer_dt_bias
        layers_hybriddecoderlayer4_mixer_in_proj_weight
        layers_hybriddecoderlayer4_mixer_norm_gamma
        layers_hybriddecoderlayer4_mixer_out_proj_weight
        layers_hybriddecoderlayer4_mlp_in_weight
        layers_hybriddecoderlayer4_mlp_out_weight
        layers_hybriddecoderlayer4_post_norm_gamma""",
    "solar_open2": """
        embed_weight expert_load expert_rows final_norm_gamma
        head_weight layers_solardecoderlayer0_input_norm_gamma
        layers_solardecoderlayer0_mixer_g_weight
        layers_solardecoderlayer0_mixer_k_weight
        layers_solardecoderlayer0_mixer_o_weight
        layers_solardecoderlayer0_mixer_q_weight
        layers_solardecoderlayer0_mixer_v_weight
        layers_solardecoderlayer0_moe_router_weight
        layers_solardecoderlayer0_moe_shared_in_weight
        layers_solardecoderlayer0_moe_shared_out_weight
        layers_solardecoderlayer0_moe_w1
        layers_solardecoderlayer0_moe_w2
        layers_solardecoderlayer0_moe_w3
        layers_solardecoderlayer0_post_norm_gamma
        layers_solardecoderlayer1_input_norm_gamma
        layers_solardecoderlayer1_mixer_A_log
        layers_solardecoderlayer1_mixer_a_down_weight
        layers_solardecoderlayer1_mixer_a_up_weight
        layers_solardecoderlayer1_mixer_beta_weight
        layers_solardecoderlayer1_mixer_dt_bias
        layers_solardecoderlayer1_mixer_g_down_weight
        layers_solardecoderlayer1_mixer_g_up_weight
        layers_solardecoderlayer1_mixer_k_conv_bias
        layers_solardecoderlayer1_mixer_k_conv_weight
        layers_solardecoderlayer1_mixer_k_weight
        layers_solardecoderlayer1_mixer_norm_gamma
        layers_solardecoderlayer1_mixer_o_weight
        layers_solardecoderlayer1_mixer_q_conv_bias
        layers_solardecoderlayer1_mixer_q_conv_weight
        layers_solardecoderlayer1_mixer_q_weight
        layers_solardecoderlayer1_mixer_v_conv_bias
        layers_solardecoderlayer1_mixer_v_conv_weight
        layers_solardecoderlayer1_mixer_v_weight
        layers_solardecoderlayer1_moe_router_weight
        layers_solardecoderlayer1_moe_shared_in_weight
        layers_solardecoderlayer1_moe_shared_out_weight
        layers_solardecoderlayer1_moe_w1
        layers_solardecoderlayer1_moe_w2
        layers_solardecoderlayer1_moe_w3
        layers_solardecoderlayer1_post_norm_gamma
        layers_solardecoderlayer2_input_norm_gamma
        layers_solardecoderlayer2_mixer_A_log
        layers_solardecoderlayer2_mixer_a_down_weight
        layers_solardecoderlayer2_mixer_a_up_weight
        layers_solardecoderlayer2_mixer_beta_weight
        layers_solardecoderlayer2_mixer_dt_bias
        layers_solardecoderlayer2_mixer_g_down_weight
        layers_solardecoderlayer2_mixer_g_up_weight
        layers_solardecoderlayer2_mixer_k_conv_bias
        layers_solardecoderlayer2_mixer_k_conv_weight
        layers_solardecoderlayer2_mixer_k_weight
        layers_solardecoderlayer2_mixer_norm_gamma
        layers_solardecoderlayer2_mixer_o_weight
        layers_solardecoderlayer2_mixer_q_conv_bias
        layers_solardecoderlayer2_mixer_q_conv_weight
        layers_solardecoderlayer2_mixer_q_weight
        layers_solardecoderlayer2_mixer_v_conv_bias
        layers_solardecoderlayer2_mixer_v_conv_weight
        layers_solardecoderlayer2_mixer_v_weight
        layers_solardecoderlayer2_moe_router_weight
        layers_solardecoderlayer2_moe_shared_in_weight
        layers_solardecoderlayer2_moe_shared_out_weight
        layers_solardecoderlayer2_moe_w1
        layers_solardecoderlayer2_moe_w2
        layers_solardecoderlayer2_moe_w3
        layers_solardecoderlayer2_post_norm_gamma
        layers_solardecoderlayer3_input_norm_gamma
        layers_solardecoderlayer3_mixer_A_log
        layers_solardecoderlayer3_mixer_a_down_weight
        layers_solardecoderlayer3_mixer_a_up_weight
        layers_solardecoderlayer3_mixer_beta_weight
        layers_solardecoderlayer3_mixer_dt_bias
        layers_solardecoderlayer3_mixer_g_down_weight
        layers_solardecoderlayer3_mixer_g_up_weight
        layers_solardecoderlayer3_mixer_k_conv_bias
        layers_solardecoderlayer3_mixer_k_conv_weight
        layers_solardecoderlayer3_mixer_k_weight
        layers_solardecoderlayer3_mixer_norm_gamma
        layers_solardecoderlayer3_mixer_o_weight
        layers_solardecoderlayer3_mixer_q_conv_bias
        layers_solardecoderlayer3_mixer_q_conv_weight
        layers_solardecoderlayer3_mixer_q_weight
        layers_solardecoderlayer3_mixer_v_conv_bias
        layers_solardecoderlayer3_mixer_v_conv_weight
        layers_solardecoderlayer3_mixer_v_weight
        layers_solardecoderlayer3_moe_router_weight
        layers_solardecoderlayer3_moe_shared_in_weight
        layers_solardecoderlayer3_moe_shared_out_weight
        layers_solardecoderlayer3_moe_w1
        layers_solardecoderlayer3_moe_w2
        layers_solardecoderlayer3_moe_w3
        layers_solardecoderlayer3_post_norm_gamma""",
    "nemotron_h": """
        embed_weight expert_load expert_rows final_norm_gamma
        head_weight layers_nemotronlayer0_mixer_A_log
        layers_nemotronlayer0_mixer_D
        layers_nemotronlayer0_mixer_conv_bias
        layers_nemotronlayer0_mixer_conv_weight
        layers_nemotronlayer0_mixer_dt_bias
        layers_nemotronlayer0_mixer_in_proj_weight
        layers_nemotronlayer0_mixer_norm_gamma
        layers_nemotronlayer0_mixer_out_proj_weight
        layers_nemotronlayer0_norm_gamma
        layers_nemotronlayer1_mixer_router_weight
        layers_nemotronlayer1_mixer_select_bias
        layers_nemotronlayer1_mixer_shared_in_weight
        layers_nemotronlayer1_mixer_shared_out_weight
        layers_nemotronlayer1_mixer_w1 layers_nemotronlayer1_mixer_w2
        layers_nemotronlayer1_norm_gamma
        layers_nemotronlayer2_mixer_A_log
        layers_nemotronlayer2_mixer_D
        layers_nemotronlayer2_mixer_conv_bias
        layers_nemotronlayer2_mixer_conv_weight
        layers_nemotronlayer2_mixer_dt_bias
        layers_nemotronlayer2_mixer_in_proj_weight
        layers_nemotronlayer2_mixer_norm_gamma
        layers_nemotronlayer2_mixer_out_proj_weight
        layers_nemotronlayer2_norm_gamma
        layers_nemotronlayer3_mixer_k_weight
        layers_nemotronlayer3_mixer_o_weight
        layers_nemotronlayer3_mixer_q_weight
        layers_nemotronlayer3_mixer_v_weight
        layers_nemotronlayer3_norm_gamma
        layers_nemotronlayer4_mixer_router_weight
        layers_nemotronlayer4_mixer_select_bias
        layers_nemotronlayer4_mixer_shared_in_weight
        layers_nemotronlayer4_mixer_shared_out_weight
        layers_nemotronlayer4_mixer_w1 layers_nemotronlayer4_mixer_w2
        layers_nemotronlayer4_norm_gamma
        layers_nemotronlayer5_mixer_A_log
        layers_nemotronlayer5_mixer_D
        layers_nemotronlayer5_mixer_conv_bias
        layers_nemotronlayer5_mixer_conv_weight
        layers_nemotronlayer5_mixer_dt_bias
        layers_nemotronlayer5_mixer_in_proj_weight
        layers_nemotronlayer5_mixer_norm_gamma
        layers_nemotronlayer5_mixer_out_proj_weight
        layers_nemotronlayer5_norm_gamma
        layers_nemotronlayer6_mixer_router_weight
        layers_nemotronlayer6_mixer_select_bias
        layers_nemotronlayer6_mixer_shared_in_weight
        layers_nemotronlayer6_mixer_shared_out_weight
        layers_nemotronlayer6_mixer_w1 layers_nemotronlayer6_mixer_w2
        layers_nemotronlayer6_norm_gamma""",
    "sdar_moe": """
        embed_weight expert_load expert_rows final_norm_gamma
        head_weight layers_sdardecoderlayer0_attention_k_norm_gamma
        layers_sdardecoderlayer0_attention_k_weight
        layers_sdardecoderlayer0_attention_o_weight
        layers_sdardecoderlayer0_attention_q_norm_gamma
        layers_sdardecoderlayer0_attention_q_weight
        layers_sdardecoderlayer0_attention_v_weight
        layers_sdardecoderlayer0_input_norm_gamma
        layers_sdardecoderlayer0_moe_router_weight
        layers_sdardecoderlayer0_moe_w1
        layers_sdardecoderlayer0_moe_w2
        layers_sdardecoderlayer0_moe_w3
        layers_sdardecoderlayer0_post_norm_gamma
        layers_sdardecoderlayer1_attention_k_norm_gamma
        layers_sdardecoderlayer1_attention_k_weight
        layers_sdardecoderlayer1_attention_o_weight
        layers_sdardecoderlayer1_attention_q_norm_gamma
        layers_sdardecoderlayer1_attention_q_weight
        layers_sdardecoderlayer1_attention_v_weight
        layers_sdardecoderlayer1_input_norm_gamma
        layers_sdardecoderlayer1_moe_router_weight
        layers_sdardecoderlayer1_moe_w1
        layers_sdardecoderlayer1_moe_w2
        layers_sdardecoderlayer1_moe_w3
        layers_sdardecoderlayer1_post_norm_gamma
        layers_sdardecoderlayer2_attention_k_norm_gamma
        layers_sdardecoderlayer2_attention_k_weight
        layers_sdardecoderlayer2_attention_o_weight
        layers_sdardecoderlayer2_attention_q_norm_gamma
        layers_sdardecoderlayer2_attention_q_weight
        layers_sdardecoderlayer2_attention_v_weight
        layers_sdardecoderlayer2_input_norm_gamma
        layers_sdardecoderlayer2_moe_router_weight
        layers_sdardecoderlayer2_moe_w1
        layers_sdardecoderlayer2_moe_w2
        layers_sdardecoderlayer2_moe_w3
        layers_sdardecoderlayer2_post_norm_gamma""",
    "zaya": """
        embed_weight expert_load expert_rows final_norm_gamma
        layers_zayadecoderlayer0_attention_conv0_bias
        layers_zayadecoderlayer0_attention_conv0_weight
        layers_zayadecoderlayer0_attention_conv1_bias
        layers_zayadecoderlayer0_attention_conv1_weight
        layers_zayadecoderlayer0_attention_k_weight
        layers_zayadecoderlayer0_attention_o_weight
        layers_zayadecoderlayer0_attention_q_weight
        layers_zayadecoderlayer0_attention_residual_out_bias
        layers_zayadecoderlayer0_attention_residual_out_scale
        layers_zayadecoderlayer0_attention_residual_skip_bias
        layers_zayadecoderlayer0_attention_residual_skip_scale
        layers_zayadecoderlayer0_attention_temperature
        layers_zayadecoderlayer0_attention_v1_weight
        layers_zayadecoderlayer0_attention_v2_weight
        layers_zayadecoderlayer0_input_norm_gamma
        layers_zayadecoderlayer0_moe_residual_out_bias
        layers_zayadecoderlayer0_moe_residual_out_scale
        layers_zayadecoderlayer0_moe_residual_skip_bias
        layers_zayadecoderlayer0_moe_residual_skip_scale
        layers_zayadecoderlayer0_moe_router_down_bias
        layers_zayadecoderlayer0_moe_router_down_weight
        layers_zayadecoderlayer0_moe_router_fc1_bias
        layers_zayadecoderlayer0_moe_router_fc1_weight
        layers_zayadecoderlayer0_moe_router_fc2_bias
        layers_zayadecoderlayer0_moe_router_fc2_weight
        layers_zayadecoderlayer0_moe_router_gamma
        layers_zayadecoderlayer0_moe_router_norm_gamma
        layers_zayadecoderlayer0_moe_router_out_bias
        layers_zayadecoderlayer0_moe_router_out_weight
        layers_zayadecoderlayer0_moe_select_bias
        layers_zayadecoderlayer0_moe_w1
        layers_zayadecoderlayer0_moe_w2
        layers_zayadecoderlayer0_moe_w3
        layers_zayadecoderlayer0_post_norm_gamma
        layers_zayadecoderlayer1_attention_conv0_bias
        layers_zayadecoderlayer1_attention_conv0_weight
        layers_zayadecoderlayer1_attention_conv1_bias
        layers_zayadecoderlayer1_attention_conv1_weight
        layers_zayadecoderlayer1_attention_k_weight
        layers_zayadecoderlayer1_attention_o_weight
        layers_zayadecoderlayer1_attention_q_weight
        layers_zayadecoderlayer1_attention_residual_out_bias
        layers_zayadecoderlayer1_attention_residual_out_scale
        layers_zayadecoderlayer1_attention_residual_skip_bias
        layers_zayadecoderlayer1_attention_residual_skip_scale
        layers_zayadecoderlayer1_attention_temperature
        layers_zayadecoderlayer1_attention_v1_weight
        layers_zayadecoderlayer1_attention_v2_weight
        layers_zayadecoderlayer1_input_norm_gamma
        layers_zayadecoderlayer1_moe_residual_out_bias
        layers_zayadecoderlayer1_moe_residual_out_scale
        layers_zayadecoderlayer1_moe_residual_skip_bias
        layers_zayadecoderlayer1_moe_residual_skip_scale
        layers_zayadecoderlayer1_moe_router_down_bias
        layers_zayadecoderlayer1_moe_router_down_weight
        layers_zayadecoderlayer1_moe_router_fc1_bias
        layers_zayadecoderlayer1_moe_router_fc1_weight
        layers_zayadecoderlayer1_moe_router_fc2_bias
        layers_zayadecoderlayer1_moe_router_fc2_weight
        layers_zayadecoderlayer1_moe_router_gamma
        layers_zayadecoderlayer1_moe_router_norm_gamma
        layers_zayadecoderlayer1_moe_router_out_bias
        layers_zayadecoderlayer1_moe_router_out_weight
        layers_zayadecoderlayer1_moe_select_bias
        layers_zayadecoderlayer1_moe_w1
        layers_zayadecoderlayer1_moe_w2
        layers_zayadecoderlayer1_moe_w3
        layers_zayadecoderlayer1_post_norm_gamma
        layers_zayadecoderlayer2_attention_conv0_bias
        layers_zayadecoderlayer2_attention_conv0_weight
        layers_zayadecoderlayer2_attention_conv1_bias
        layers_zayadecoderlayer2_attention_conv1_weight
        layers_zayadecoderlayer2_attention_k_weight
        layers_zayadecoderlayer2_attention_o_weight
        layers_zayadecoderlayer2_attention_q_weight
        layers_zayadecoderlayer2_attention_residual_out_bias
        layers_zayadecoderlayer2_attention_residual_out_scale
        layers_zayadecoderlayer2_attention_residual_skip_bias
        layers_zayadecoderlayer2_attention_residual_skip_scale
        layers_zayadecoderlayer2_attention_temperature
        layers_zayadecoderlayer2_attention_v1_weight
        layers_zayadecoderlayer2_attention_v2_weight
        layers_zayadecoderlayer2_input_norm_gamma
        layers_zayadecoderlayer2_moe_residual_out_bias
        layers_zayadecoderlayer2_moe_residual_out_scale
        layers_zayadecoderlayer2_moe_residual_skip_bias
        layers_zayadecoderlayer2_moe_residual_skip_scale
        layers_zayadecoderlayer2_moe_router_down_bias
        layers_zayadecoderlayer2_moe_router_down_weight
        layers_zayadecoderlayer2_moe_router_fc1_bias
        layers_zayadecoderlayer2_moe_router_fc1_weight
        layers_zayadecoderlayer2_moe_router_fc2_bias
        layers_zayadecoderlayer2_moe_router_fc2_weight
        layers_zayadecoderlayer2_moe_router_gamma
        layers_zayadecoderlayer2_moe_router_norm_gamma
        layers_zayadecoderlayer2_moe_router_out_bias
        layers_zayadecoderlayer2_moe_router_out_weight
        layers_zayadecoderlayer2_moe_select_bias
        layers_zayadecoderlayer2_moe_w1
        layers_zayadecoderlayer2_moe_w2
        layers_zayadecoderlayer2_moe_w3
        layers_zayadecoderlayer2_post_norm_gamma""",
}
SCOPES = {
    "granite_hybrid": """
        granite/attention granite/head granite/mamba/conv
        granite/mamba/gated_norm granite/mamba/in_proj
        granite/mamba/out_proj granite/mamba/ssd granite/mlp
        mx_flash_attention_bwd_dkv mx_flash_attention_bwd_dq
        mx_flash_attention_fwd step/loss step/optimizer""",
    "solar_open2": """
        bhqk,bhkv->bhqv bhqk,bhqv->bhkv mx_flash_attention_bwd_dkv
        mx_flash_attention_bwd_dq mx_flash_attention_fwd
        solar/attention solar/attention/granite/attention solar/head
        solar/kda/conv solar/kda/gates solar/kda/out solar/kda/proj
        solar/kda/scan solar/moe solar/moe/combine
        solar/moe/shared/granite/mlp step/aux_state step/loss
        step/optimizer""",
    "nemotron_h": """
        mx_flash_attention_bwd_dkv mx_flash_attention_bwd_dq
        mx_flash_attention_fwd nemotron/attention
        nemotron/attention/granite/attention nemotron/head
        nemotron/mamba nemotron/mamba/granite/mamba/conv
        nemotron/mamba/granite/mamba/gated_norm
        nemotron/mamba/granite/mamba/in_proj
        nemotron/mamba/granite/mamba/out_proj
        nemotron/mamba/granite/mamba/ssd nemotron/moe
        nemotron/moe/combine nemotron/moe/shared/relu2_mlp
        step/aux_state step/loss step/optimizer""",
    "sdar_moe": """
        mx_flash_attention_bwd_dkv mx_flash_attention_bwd_dq
        mx_flash_attention_fwd sdar/attention
        sdar/attention/granite/attention
        sdar/attention/granite/attention/qk_norm
        sdar/attention/granite/attention/rope sdar/head sdar/moe
        step/aux_state step/loss step/optimizer""",
    "zaya": """
        mx_flash_attention_bwd_dkv mx_flash_attention_bwd_dq
        mx_flash_attention_fwd step/aux_state step/loss step/optimizer
        zaya/attention zaya/attention/mix zaya/attention/out
        zaya/attention/proj zaya/attention/rope zaya/head zaya/moe
        zaya/moe/router zaya/residual_scale""",
}


def _scopes(jaxpr, out):
    """The scope path of every equation, nested programs included."""
    for eqn in jaxpr.eqns:
        (path,), _ = profiler.parse_op_name(
            f"{eqn.source_info.name_stack}/{eqn.primitive}")
        parts = path.split("/")
        out.add("/".join(parts[:parts.index("op")] if "op" in parts
                         else parts))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _scopes(sub, out)
    return out


@pytest.mark.parametrize("model", MODELS)
def test_parameter_names_and_step_scopes_are_as_recorded(model):
    cell = importlib.import_module("test_" + model)
    net = cell.REF.build(cell.SMALL, "gluon")
    net.initialize(mx.initializer.Zero())
    assert sorted(k[len(net.prefix):] for k in net.collect_params()) == \
        PARAMS[model].split()
    x, y, *weights = cell._batch(cell.SMALL)
    batch = (x, y, *(w[..., None] for w in weights))
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 1.0, "momentum": 0.9},
                     make_mesh(devices=jax.devices()[:1], dp=1),
                     example_batch=tuple(nd.array(a) for a in batch),
                     remat=True)
    with step.mesh.jax_mesh:
        traced = step._step.trace(
            jax.random.PRNGKey(0), step._train_params, step._aux_params,
            step.opt_state, *batch)
    assert sorted(_scopes(traced.jaxpr.jaxpr, set()) - {""}) == \
        SCOPES[model].split()


def test_model_files_import_no_other_model_file():
    """A model file imports the package's blocks from ``blocks`` alone;
    ``__init__`` is the one module that imports the model files."""
    model_file = re.compile(
        rf"^from \.({'|'.join(MODELS)}) import", re.MULTILINE)
    for name in sorted(os.listdir(LANGUAGE)):
        if name.endswith(".py") and name != "__init__.py":
            with open(os.path.join(LANGUAGE, name)) as f:
                assert not model_file.findall(f.read()), name
