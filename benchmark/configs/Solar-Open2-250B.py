"""Solar-Open2-250B (``model_type`` ``solar_open2``, Upstage, config.json
named in the JSON beside this file), ONE CHIP'S SHARE of one period of it:
how the program builds it, the shapes of its layers, its
multiply-accumulates, and its plain reference.

The equations; ``h`` is a (T, 4096) sequence, every matrix ``W`` is stored
(out, in) and applied as ``h Wᵀ``, no bias but the convolutions':

    x = E[ids]
    layer i:  x = x + mixer_i(RMSNorm(x));   x = x + MoE(RMSNorm(x))     (eps 1e-5)
    logits = RMSNorm(x) W_headᵀ                                           (untied)
    RMSNorm(v) = v · rsqrt(mean(v²) + eps) · weight

    softmax mixer (i in gqa_layers): H_q query heads, H_kv key/value heads
        of 128, no positions, causal; query head j reads key/value head
        j // (H_q / H_kv);
        a = softmax(q kᵀ / sqrt(128)) v;   out = W_o (sigmoid(W_g h) ⊙ a)
        (W_g: H_q·128 × 4096, an elementwise gate)

    KDA mixer, per head, d = 128; conv is causal, depthwise, 4 taps, biased:
        q = l2norm(silu(conv(W_q h))) / sqrt(d);  k = l2norm(silu(conv(W_k h)))
        v = silu(conv(W_v h));      l2norm(x) = x · rsqrt(Σ x² + 1e-6)
        g_t = −exp(A_log) · softplus(W_a↑ W_a↓ h_t + dt_bias)   in R^d, ≤ 0
            (A_log per head; W_a↓ 128 × 4096, W_a↑ H·d × 128)
        β_t = 2 · sigmoid(w_β · h_t)                   in (0, 2)  (kda_allow_neg_eigval)
        S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ   (S is d × d, S_0 = 0)
        o_t = S_tᵀ q_t
        out = W_o (RMSNorm_head(o_t) ⊙ sigmoid(W_o↑ W_o↓ h_t))   (kda_use_full_proj false)

    MoE: s = sigmoid(W_r h) in R^320 (W_r h in float32 at the highest precision);
        top-8 of s;  w_e = s_e / Σ_top8 s (norm_topk_prob) · routed_scaling_factor (1)
        y = Σ_{e in top-8, e held here} w_e · W2_e (silu(W1_e h) ⊙ W3_e h)
            + the shared expert, of the same form, width 1280

From Kimi Linear (arXiv:2510.26692, the KDA section, and its public ``kda``
layer in flash-linear-attention) for the KDA layer, and the config's own
keys for the rest.  The share: layers 0–3 of 48; experts
``first_routed_expert .. + n_routed_experts − 1`` (8) of the router's 320;
8 of 64 KDA heads; 8 of 64 query heads with 1 of 8 key/value heads; rows
0–24575 of the 196,608 of both vocabulary tables.  What the absent experts
and heads would add is left out here exactly as in the program.  Every
departure and assumed size is in the JSON's ``reduced`` and ``assumed``.

The reference is straight ``jax.numpy`` in float32: the recurrence is a
``lax.scan`` over single time steps (it shares nothing with the program's
chunked op), attention is a full masked softmax taken ``ROWS`` query rows
at a time, the experts a loop over the held experts that computes every
token for each and masks (the plain way, the one the program may not
use).  Nothing is imported from ``mxnet_tpu`` outside ``build``.
Parameters reach it under canonical names:

    embed  head  final_norm  expert_load  expert_rows  (the last two: the
        program's auxiliary state, which the reference does not read)
    layers.<i>.norm1  layers.<i>.norm2
    layers.<i>.attn.{q,k,v,o,g}
    layers.<i>.kda.{q,k,v}  layers.<i>.kda.{q,k,v}_conv_{w,b}
    layers.<i>.kda.{a_down,a_up,A_log,dt_bias,beta,g_down,g_up,norm,o}
    layers.<i>.moe.{router,w1,w3,w2,shared_in,shared_out}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 512      # query rows of attention scored at once


def _kinds(cfg):
    softmax = set(cfg["gqa_layers"])
    return ["attention" if i in softmax else "kda"
            for i in range(cfg["num_hidden_layers"])]


def _experts_total(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


# -- the program's build -------------------------------------------------------
def build(cfg, which):
    if which != "gluon":
        raise ValueError(f"Solar-Open2-250B has no build {which!r}")
    from mxnet_tpu.gluon.model_zoo.language import solar_open2
    return solar_open2(cfg)


def canonical(cfg, which, net):
    """{the program's parameter name: canonical name}."""
    names = {net.embed_weight.name: "embed", net.head_weight.name: "head",
             net.final_norm.gamma.name: "final_norm",
             net.expert_load.name: "expert_load",
             net.expert_rows.name: "expert_rows"}
    for i, layer in enumerate(net.layers):
        at = f"layers.{i}."
        names[layer.input_norm.gamma.name] = at + "norm1"
        names[layer.post_norm.gamma.name] = at + "norm2"
        moe = layer.moe
        for ours, theirs in (
                ("router", moe.router_weight), ("w1", moe.w1),
                ("w3", moe.w3), ("w2", moe.w2),
                ("shared_in", moe.shared.in_weight),
                ("shared_out", moe.shared.out_weight)):
            names[theirs.name] = at + "moe." + ours
        m = layer.mixer
        if _kinds(cfg)[i] == "attention":
            for ours in "qkvog":
                names[getattr(m, ours + "_weight").name] = at + "attn." + ours
            continue
        for ours in "qkv":
            names[getattr(m, ours + "_weight").name] = at + "kda." + ours
            names[getattr(m, ours + "_conv_weight").name] = \
                at + f"kda.{ours}_conv_w"
            names[getattr(m, ours + "_conv_bias").name] = \
                at + f"kda.{ours}_conv_b"
        for ours, theirs in (
                ("a_down", m.a_down_weight), ("a_up", m.a_up_weight),
                ("A_log", m.A_log), ("dt_bias", m.dt_bias),
                ("beta", m.beta_weight), ("g_down", m.g_down_weight),
                ("g_up", m.g_up_weight), ("norm", m.norm.gamma),
                ("o", m.o_weight)):
            names[theirs.name] = at + "kda." + ours
    return names


# -- shapes --------------------------------------------------------------------
def param_shapes(cfg, which="gluon"):
    """{canonical name: shape}, the two arrays of auxiliary state among
    them (``expert_load``, ``expert_rows``: no gradient, no optimizer)."""
    hid, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    lin = cfg["linear_attn_config"]
    inner, rank = lin["num_heads"] * lin["head_dim"], \
        cfg.get("kda_low_rank_dim") or lin["head_dim"]
    dh = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * dh, \
        cfg["num_key_value_heads"] * dh
    kinds = _kinds(cfg)
    shapes = {"embed": (cfg["vocab_size"], hid),
              "head": (cfg["vocab_size"], hid), "final_norm": (hid,),
              "expert_load": (len(kinds), held),
              "expert_rows": (len(kinds),)}
    for i, kind in enumerate(kinds):
        at = f"layers.{i}."
        shapes[at + "norm1"] = shapes[at + "norm2"] = (hid,)
        shapes[at + "moe.router"] = (_experts_total(cfg), hid)
        shapes[at + "moe.w1"] = shapes[at + "moe.w3"] = (held, width, hid)
        shapes[at + "moe.w2"] = (held, hid, width)
        shared = cfg["n_shared_experts"] * width
        shapes[at + "moe.shared_in"] = (2 * shared, hid)
        shapes[at + "moe.shared_out"] = (hid, shared)
        if kind == "attention":
            at += "attn."
            shapes[at + "q"] = shapes[at + "g"] = (nq, hid)
            shapes[at + "k"] = shapes[at + "v"] = (nkv, hid)
            shapes[at + "o"] = (hid, nq)
            continue
        at += "kda."
        for name in "qkv":
            shapes[at + name] = (inner, hid)
            shapes[at + name + "_conv_w"] = (
                inner, lin["short_conv_kernel_size"])
            shapes[at + name + "_conv_b"] = (inner,)
        shapes[at + "a_down"] = shapes[at + "g_down"] = (rank, hid)
        shapes[at + "a_up"] = shapes[at + "g_up"] = (inner, rank)
        shapes[at + "A_log"] = (lin["num_heads"],)
        shapes[at + "dt_bias"] = (inner,)
        shapes[at + "beta"] = (lin["num_heads"], hid)
        shapes[at + "norm"] = (lin["head_dim"],)
        shapes[at + "o"] = (hid, inner)
    return shapes


def macs_per_image(cfg, which="gluon"):
    """Multiply-accumulates of one forward pass over one sequence (the
    harness's "image") of ``cfg["image"][0] - 1`` tokens, per token:

    * matrices: every 2-D parameter once (the embedding is a gather and
      does not count; the head does; the router's 320 outputs do);
    * routed experts: the three matrices of ONE expert times the EXPECTED
      assignments a token sends to the experts held here under a uniform
      router, ``top_k · E_here / E`` (0.2): what the deployment computes,
      not what a mask over every held expert would, and not the padding
      of each expert's last tile;
    * convolutions: 4 taps on each of the 3 × H·d channels of a KDA layer;
    * the scan, as the chunked algorithm computes it with chunk Q and H
      heads of d: the two Q × Q matrices of decayed products (Q·d each a
      row), the triangular solve of the d + d right-hand sides (Q·d a
      row), the intra-chunk output (Q·d), and three d × d products a row
      with the carried state: H · (4 Q d + 3 d²);
    * causal attention at T positions: scores and values, (T+1)/2 keys a
      query on average: heads · d · (T + 1).
    """
    t = int(cfg["image"][0]) - 1
    shapes = param_shapes(cfg, which)
    matrices = sum(s[0] * s[1] for k, s in shapes.items()
                   if len(s) == 2 and k not in ("embed", "expert_load")
                   and not k.endswith("_conv_w"))
    kinds = _kinds(cfg)
    share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / _experts_total(cfg)
    routed = len(kinds) * share * 3 * cfg["moe_intermediate_size"] \
        * cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    h, d, q = lin["num_heads"], lin["head_dim"], cfg.get("kda_chunk_size", 64)
    conv = kinds.count("kda") * 3 * h * d * lin["short_conv_kernel_size"]
    scan = kinds.count("kda") * h * (4 * q * d + 3 * d * d)
    attn = kinds.count("attention") * cfg["num_attention_heads"] \
        * cfg["head_dim"] * (t + 1)
    return int(t * (matrices + routed + conv + scan + attn))


# -- the plain reference -------------------------------------------------------
def _rms_norm(v, weight, eps):
    return v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps) \
        * weight


def _conv(x, w, b):
    """Depthwise, causal: tap K−1 multiplies the current step."""
    k, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    return b + sum(padded[:, j:j + t] * w[:, j] for j in range(k))


def _kda(p, at, h, cfg):
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    bsz, t, _ = h.shape

    def branch(name):
        x = jax.nn.silu(_conv(h @ p[at + name].T, p[at + name + "_conv_w"],
                              p[at + name + "_conv_b"]))
        return x.reshape(bsz, t, heads, d)

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q, k, v = unit(branch("q")) / d ** 0.5, unit(branch("k")), branch("v")
    g = -jnp.exp(p[at + "A_log"])[:, None] * jax.nn.softplus(
        h @ p[at + "a_down"].T @ p[at + "a_up"].T + p[at + "dt_bias"]
    ).reshape(bsz, t, heads, d)
    beta = (2.0 if cfg["kda_allow_neg_eigval"] else 1.0) \
        * jax.nn.sigmoid(h @ p[at + "beta"].T)

    def step(state, inp):                       # one time step, all heads
        q_t, k_t, v_t, g_t, b_t = inp
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + (b_t[..., None] * k_t)[..., None] \
            * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, o = lax.scan(step, jnp.zeros((bsz, heads, d, d), jnp.float32),
                    tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    gate = jax.nn.sigmoid(h @ p[at + "g_down"].T @ p[at + "g_up"].T)
    o = _rms_norm(jnp.moveaxis(o, 0, 1), p[at + "norm"], cfg["rms_norm_eps"])
    return (o.reshape(bsz, t, heads * d) * gate) @ p[at + "o"].T


def _attention(p, at, h, cfg):
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    bsz, t, _ = h.shape

    def heads(w, n):
        return (h @ w.T).reshape(bsz, t, n, dh).transpose(0, 2, 1, 3)

    q = heads(p[at + "q"], nq)
    k = jnp.repeat(heads(p[at + "k"], nkv), nq // nkv, axis=1)
    v = jnp.repeat(heads(p[at + "v"], nkv), nq // nkv, axis=1)
    keys = jnp.arange(t)
    out = []
    for start in range(0, t, ROWS):             # full softmax, a block of rows
        rows = slice(start, min(start + ROWS, t))
        s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, rows], k) / dh ** 0.5
        s = jnp.where(keys[None, :] <= keys[rows, None], s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(s, axis=-1), v))
    out = jnp.concatenate(out, axis=2).transpose(0, 2, 1, 3)
    out = out.reshape(bsz, t, nq * dh)
    if cfg["use_gqa_gate"]:
        out = out * jax.nn.sigmoid(h @ p[at + "g"].T)
    return out @ p[at + "o"].T


def _gated_mlp(h, w_in, w_out):
    g, u = jnp.split(h @ w_in.T, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out.T


def _moe(p, at, h, cfg, notes=None):
    first, held = cfg.get("first_routed_expert", 0), cfg["n_routed_experts"]
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(
        h, p[at + "router"].T, precision=lax.Precision.HIGHEST))
    chosen, expert = lax.top_k(scores, k)
    if notes is not None:
        notes.append((_held_margin(scores, k, first, held), jnp.sum(
            expert.reshape(-1, 1) == first + jnp.arange(held), axis=0)))
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    chosen = chosen * cfg["routed_scaling_factor"]
    y = _gated_mlp(h, p[at + "shared_in"], p[at + "shared_out"])
    for e in range(held):                       # every token, then a mask
        weight = jnp.sum(jnp.where(expert == first + e, chosen, 0.0), axis=-1)
        y = y + weight[..., None] * (
            (jax.nn.silu(h @ p[at + "w1"][e].T) * (h @ p[at + "w3"][e].T))
            @ p[at + "w2"][e].T)
    return y


def _held_margin(scores, k, first, held):
    """Per token, how far the nearest held expert's score lies from the
    edge of the top k: a chosen one above the (k+1)-th score, another one
    below the k-th.  A token with a small margin gains or loses a whole
    expert's output here when its hidden state is rounded otherwise."""
    top = lax.top_k(scores, k + 1)[0]
    kth, after = top[..., k - 1:k], top[..., k:]
    mine = scores[..., first:first + held]
    return jnp.min(jnp.where(mine >= kth, mine - after, kth - mine), axis=-1)


def reference(cfg, which="gluon", routing=False):
    """``forward(params, ids, train=False) -> logits`` (batch, T, vocab);
    the model has no mode, ``train`` is the harness's signature.  With
    ``routing`` it returns ``(logits, margin, counts)``: each layer's
    ``_held_margin`` (layers, batch, T) and its assignments to each held
    expert (layers, held), both of the reference's own scores."""
    eps = cfg["rms_norm_eps"]
    kinds = _kinds(cfg)

    def forward(p, ids, train=False):
        x = p["embed"][ids]
        notes = [] if routing else None
        for i, kind in enumerate(kinds):
            at = f"layers.{i}."
            h = _rms_norm(x, p[at + "norm1"], eps)
            x = x + (_kda(p, at + "kda.", h, cfg) if kind == "kda"
                     else _attention(p, at + "attn.", h, cfg))
            x = x + _moe(p, at + "moe.", _rms_norm(x, p[at + "norm2"], eps),
                         cfg, notes)
        logits = _rms_norm(x, p["final_norm"], eps) @ p["head"].T
        if not routing:
            return logits
        margin, counts = zip(*notes)
        return logits, jnp.stack(margin), jnp.stack(counts)

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
