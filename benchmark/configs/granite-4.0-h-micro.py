"""granite-4.0-h-micro (``model_type`` ``granitemoehybrid``, IBM, config.json
named in the JSON beside this file): how the program builds it, the shapes
of its layers, its multiply-accumulates, and its plain reference.

The equations, from the published ``config``; ``h`` is a (T, 2048) sequence,
every matrix ``W`` is stored (out, in) and applied as ``h Wᵀ``, no bias
anywhere but the convolution:

    x = E[ids] · embedding_multiplier (12)
    each layer i:  x = x + residual_multiplier (0.22) · mixer_i(RMSNorm(x))
                   x = x + 0.22 · MLP(RMSNorm(x))
    logits = RMSNorm(x) Eᵀ / logits_scaling (8)           (tied weights)
    RMSNorm(v) = v · rsqrt(mean(v²) + 1e-5) · weight
    MLP(h) = W_out (silu(g) ⊙ u),  [g, u] = W_in h,  width 8192
        (num_local_experts 0: the shared MLP is the only one)

    attention mixer (layer_types[i] == "attention"): 32 query heads and 8
        key/value heads of 64, no positional encoding ("nope"), causal,
        softmax(q kᵀ · attention_multiplier (0.015625)) v, then W_o; query
        head j reads key/value head j // 4
    mamba mixer: [z, xBC, dt] = W_in h, sizes 4096, 4352, 64;
        xBC = silu(conv1d_causal(xBC, width 4, depthwise) + b);
        xBC = [x (64 heads × P=64), B (N=128), C (N=128)], one group;
        Δ = softplus(dt + dt_bias);  a = −exp(A_log), per head;
        per head:  S_t = exp(Δ_t a) S_{t−1} + Δ_t x_t B_tᵀ   (S is P × N)
                   y_t = S_t C_t + D x_t
        y = RMSNorm(y ⊙ silu(z)) over all 4096 channels;  W_out y

Departures from the published model, all in the JSON's ``reduced`` and
``assumed``: layers 0–9 of 40; rows 0–12543 of the 100,352-row vocabulary
(ids, logits and loss are over the slice); no ``time_step_limit`` clamp on
Δ; a sequence is one document (no segment mask).

The reference is straight ``jax.numpy`` in float32: the recurrence is a
``lax.scan`` over single time steps (it shares nothing with the program's
chunked op), attention is a full masked softmax taken ``ROWS`` query rows
at a time so that 4096 positions fit.  Nothing is imported from
``mxnet_tpu`` outside ``build``.  Parameters reach it under canonical names:

    embed  final_norm
    layers.<i>.norm1  layers.<i>.norm2  layers.<i>.mlp.{in,out}
    layers.<i>.mamba.{in_proj,conv_w,conv_b,A_log,D,dt_bias,norm,out_proj}
    layers.<i>.attn.{q,k,v,o}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 512      # query rows of attention scored at once


def _layer_types(cfg):
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _sizes(cfg):
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return inner, bc


# -- the program's build -------------------------------------------------------
def build(cfg, which):
    if which != "gluon":
        raise ValueError(f"granite-4.0-h-micro has no build {which!r}")
    from mxnet_tpu.gluon.model_zoo.language import granite_hybrid
    return granite_hybrid(cfg)


def canonical(cfg, which, net):
    """{the program's parameter name: canonical name}."""
    names = {net.embed_weight.name: "embed",
             net.final_norm.gamma.name: "final_norm"}
    for i, layer in enumerate(net.layers):
        at = f"layers.{i}."
        names[layer.input_norm.gamma.name] = at + "norm1"
        names[layer.post_norm.gamma.name] = at + "norm2"
        names[layer.mlp.in_weight.name] = at + "mlp.in"
        names[layer.mlp.out_weight.name] = at + "mlp.out"
        m = layer.mixer
        if _layer_types(cfg)[i] == "mamba":
            for ours, theirs in (
                    ("in_proj", m.in_proj_weight), ("conv_w", m.conv_weight),
                    ("conv_b", m.conv_bias), ("A_log", m.A_log), ("D", m.D),
                    ("dt_bias", m.dt_bias), ("norm", m.norm.gamma),
                    ("out_proj", m.out_proj_weight)):
                names[theirs.name] = at + "mamba." + ours
        else:
            for ours in "qkvo":
                names[getattr(m, ours + "_weight").name] = at + "attn." + ours
    return names


# -- shapes --------------------------------------------------------------------
def param_shapes(cfg, which="gluon"):
    """{canonical name: shape}."""
    hid, mlp = cfg["hidden_size"], cfg["shared_intermediate_size"]
    inner, bc = _sizes(cfg)
    heads = cfg["mamba_n_heads"]
    dh = hid // cfg["num_attention_heads"]
    shapes = {"embed": (cfg["vocab_size"], hid), "final_norm": (hid,)}
    for i, kind in enumerate(_layer_types(cfg)):
        at = f"layers.{i}."
        shapes[at + "norm1"] = shapes[at + "norm2"] = (hid,)
        shapes[at + "mlp.in"] = (2 * mlp, hid)
        shapes[at + "mlp.out"] = (hid, mlp)
        if kind == "mamba":
            at += "mamba."
            shapes[at + "in_proj"] = (2 * inner + 2 * bc + heads, hid)
            shapes[at + "conv_w"] = (inner + 2 * bc, cfg["mamba_d_conv"])
            shapes[at + "conv_b"] = (inner + 2 * bc,)
            shapes[at + "A_log"] = shapes[at + "D"] = (heads,)
            shapes[at + "dt_bias"] = (heads,)
            shapes[at + "norm"] = (inner,)
            shapes[at + "out_proj"] = (hid, inner)
        else:
            at += "attn."
            shapes[at + "q"] = shapes[at + "o"] = (hid, hid)
            shapes[at + "k"] = shapes[at + "v"] = (
                cfg["num_key_value_heads"] * dh, hid)
    return shapes


def macs_per_image(cfg, which="gluon"):
    """Multiply-accumulates of one forward pass over one sequence (the
    harness's "image") of ``cfg["image"][0] - 1`` tokens, per token:

    * matrices: every 2-D parameter once, the tied table once as the head
      (its use as the embedding is a gather);
    * convolution: ``mamba_d_conv`` taps on each of the 4352 channels;
    * scan, as the chunked algorithm computes it with chunk Q, P×N state
      and H heads: C·Bᵀ inside the chunk Q·N, (L ⊙ C Bᵀ) X Q·H·P, the
      chunk's state N·H·P, the entering state's output N·H·P;
    * causal attention at T positions: scores and values, (T+1)/2 keys a
      query on average: heads · d · (T + 1).
    """
    t = int(cfg["image"][0]) - 1
    shapes = param_shapes(cfg, which)
    matrices = sum(s[0] * s[1] for k, s in shapes.items()
                   if len(s) == 2 and not k.endswith("conv_w"))
    inner, bc = _sizes(cfg)
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    kinds = _layer_types(cfg)
    conv = kinds.count("mamba") * cfg["mamba_d_conv"] * (inner + 2 * bc)
    scan = kinds.count("mamba") * (q * n * cfg["mamba_n_groups"]
                                   + q * inner + 2 * n * inner)
    attn = kinds.count("attention") * cfg["hidden_size"] * (t + 1)
    return t * (matrices + conv + scan + attn)


# -- the plain reference -------------------------------------------------------
def _rms_norm(v, weight, eps):
    return v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps) \
        * weight


def _mamba(p, at, h, cfg):
    inner, bc = _sizes(cfg)
    heads, dh, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    groups, k = cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    bsz, t, _ = h.shape
    zxbcdt = h @ p[at + "in_proj"].T
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], axis=-1)
    # depthwise, causal: tap k-1 multiplies the current step
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    xbc = p[at + "conv_b"] + sum(
        padded[:, j:j + t] * p[at + "conv_w"][:, j] for j in range(k))
    xbc = jax.nn.silu(xbc)
    x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
    x = x.reshape(bsz, t, heads, dh)
    # a head reads its group's B and C
    b = jnp.repeat(b.reshape(bsz, t, groups, n), heads // groups, axis=2)
    c = jnp.repeat(c.reshape(bsz, t, groups, n), heads // groups, axis=2)
    dt = jax.nn.softplus(dt + p[at + "dt_bias"])
    a = -jnp.exp(p[at + "A_log"])

    def step(state, inp):                       # one time step, all heads
        x_t, dt_t, b_t, c_t = inp
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, y = lax.scan(step, jnp.zeros((bsz, heads, dh, n), jnp.float32),
                    tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1) + p[at + "D"][:, None] * x
    y = _rms_norm(y.reshape(bsz, t, inner) * jax.nn.silu(z),
                  p[at + "norm"], cfg["rms_norm_eps"])
    return y @ p[at + "out_proj"].T


def _attention(p, at, h, cfg):
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    bsz, t, hid = h.shape
    dh = hid // nq

    def heads(w, n):
        return (h @ w.T).reshape(bsz, t, n, dh).transpose(0, 2, 1, 3)

    q = heads(p[at + "q"], nq)
    k = jnp.repeat(heads(p[at + "k"], nkv), nq // nkv, axis=1)
    v = jnp.repeat(heads(p[at + "v"], nkv), nq // nkv, axis=1)
    keys = jnp.arange(t)
    out = []
    for start in range(0, t, ROWS):             # full softmax, a block of rows
        rows = slice(start, min(start + ROWS, t))
        s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, rows], k) \
            * cfg["attention_multiplier"]
        s = jnp.where(keys[None, :] <= keys[rows, None], s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(s, axis=-1), v))
    out = jnp.concatenate(out, axis=2).transpose(0, 2, 1, 3)
    return out.reshape(bsz, t, hid) @ p[at + "o"].T


def reference(cfg, which="gluon"):
    """``forward(params, ids, train=False) -> logits`` (batch, T, vocab);
    the model has no mode, ``train`` is the harness's signature."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mlp = cfg["shared_intermediate_size"]
    kinds = _layer_types(cfg)

    def forward(p, ids, train=False):
        x = p["embed"][ids] * cfg["embedding_multiplier"]
        for i, kind in enumerate(kinds):
            at = f"layers.{i}."
            h = _rms_norm(x, p[at + "norm1"], eps)
            x = x + res * (_mamba(p, at + "mamba.", h, cfg) if kind == "mamba"
                           else _attention(p, at + "attn.", h, cfg))
            g, u = jnp.split(_rms_norm(x, p[at + "norm2"], eps)
                             @ p[at + "mlp.in"].T, [mlp], axis=-1)
            x = x + res * ((jax.nn.silu(g) * u) @ p[at + "mlp.out"].T)
        return _rms_norm(x, p["final_norm"], eps) @ p["embed"].T \
            / cfg["logits_scaling"]

    return forward


def cross_entropy(logits, labels):
    """Mean over all tokens of −log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1))


def loss(cfg, which="gluon"):
    """``(params, ids, labels) -> cross_entropy(forward(params, ids),
    labels)``; its ``jax.grad`` is the reference's gradient."""
    forward = reference(cfg, which)
    return lambda p, ids, labels: cross_entropy(forward(p, ids), labels)
