"""Kimi-Linear-48B-A3B-Instruct (``model_type`` ``kimi_linear``, Moonshot
AI, config.json named in the JSON beside this file), ONE CHIP'S SHARE of
its first five layers: how the program builds it, the shapes of its
layers, its multiply-accumulates, the work its latent attention's kernels
need, and its plain reference.

The equations; ``h`` is a (T, 2304) sequence, every matrix ``W`` is stored
(out, in) and applied as ``h Wᵀ``, no bias anywhere:

    x = E[ids]
    layer i:  x = x + mixer_i(RMSNorm(x));   x = x + ffn_i(RMSNorm(x))  (eps 1e-5)
    logits = RMSNorm(x) W_headᵀ                                         (untied)
    RMSNorm(v) = v · rsqrt(mean(v²) + eps) · weight

    mixer i: MLA where i + 1 is in linear_attn_config.full_attn_layers,
        KDA where it is in kda_layers (both lists count from 1): layers
        0-2 and 4 KDA, layer 3 MLA

    KDA mixer, 32 heads of d = 128; conv causal, depthwise, 4 taps, NO bias:
        q = l2norm(silu(conv(W_q h))) / sqrt(d);  k = l2norm(silu(conv(W_k h)))
        v = silu(conv(W_v h));      l2norm(x) = x · rsqrt(Σ x² + 1e-6)
        g_t = −exp(A_log) · softplus(W_a↑ W_a↓ h_t + dt_bias)   in R^d, ≤ 0
            (A_log per head; W_a↓ 128 × 2304, W_a↑ 4096 × 128)
        β_t = sigmoid(w_β · h_t)                        in (0, 1)
        S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ   (S_0 = 0)
        o_t = S_tᵀ q_t
        out = W_o (RMSNorm_head(o_t) ⊙ sigmoid(W_g↑ W_g↓ h_t))

    MLA mixer (mla_use_nope: no positions), H = 32, c = 512:
        [q_nope ; q_pe] = W_q h        a head: 128 + 64   (q_lora_rank null)
        [c_kv ; k_pe]   = W_kva h      512 + 64, k_pe ONE for all heads
        [k_nope ; v]    = W_kvb RMSNorm(c_kv)    a head: 128 + 128
        q = [q_nope ; q_pe];  k = [k_nope ; k_pe]
        out = W_o softmax(q kᵀ / sqrt(192) + causal) v        W_o 2304 × 4096

    ffn 0 (first_k_dense_replace 1): W_out (silu(g) ⊙ u), [g, u] = W_in h,
        width 9216
    ffn i > 0: s = sigmoid(W_r h) in R^256 (W_r h in float32 at the highest
        precision);  the chosen = top-8 of s + b  (one group: no group
        limit; b the selection bias, which chooses and never weighs);
        w_e = 2.446 · s_e / Σ_chosen s  (moe_renormalize, routed_scaling_factor)
        y = Σ_{e chosen, e held here} w_e · W2_e (silu(W1_e h) ⊙ W3_e h)
            + the shared expert, of the same form, width 1024

    the bias rule (training mode only, after the layers): with c_e the
        step's assignments to expert e over ALL 256,
        b_e ← b_e + u · sign(mean(c) − c_e),  u = router_bias_update_rate.
        The forward below READS b; ``updated_bias`` is the rule.

From the Kimi Linear paper (arXiv:2510.26692) and the public ``kda`` layer
of flash-linear-attention for KDA, DeepSeek-V2's MLA section
(arXiv:2405.04434) for MLA, and the config's own keys for the rest.  The
share: layers 0–4 of 27; experts ``first_routed_expert .. + num_experts −
1`` (8) of the router's 256; every head of both kinds; rows 0–20479 of the
163,840 of both vocabulary tables.  What the absent experts would add is
left out here exactly as in the program.  Every departure and assumed size
is in the JSON's ``reduced`` and ``assumed``.

The reference is straight ``jax.numpy`` in float32: the recurrence is a
``lax.scan`` over single time steps (it shares nothing with the program's
chunked op), attention a full masked softmax taken ``ROWS`` query rows at
a time over keys and values expanded for every head, the experts a loop
over the held experts that computes every token for each and masks (the
plain way, the one the program may not use).  Nothing is imported from
``mxnet_tpu`` outside ``build``.  Parameters reach it under canonical
names:

    embed  head  final_norm  expert_load  expert_rows  (the last two: the
        program's counts, which the reference does not read)
    layers.<i>.{norm1,norm2}
    layers.<i>.kda.{q,k,v,q_conv_w,k_conv_w,v_conv_w,a_down,a_up,A_log,
        dt_bias,beta,g_down,g_up,norm,o}
    layers.<i>.mla.{q,kv_a,kv_norm,kv_b,o}
    layers.<i>.mlp.{in,out}
    layers.<i>.moe.{router,bias,w1,w3,w2,shared_in,shared_out}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 512      # query rows of attention scored at once
AUX = ("expert_load", "expert_rows")


def _kinds(cfg):
    """Each layer's (mixer, feed-forward): ("kda" | "mla", "mlp" | "moe")."""
    full = set(cfg["linear_attn_config"]["full_attn_layers"])
    return [("mla" if i + 1 in full else "kda",
             "mlp" if i < cfg["first_k_dense_replace"] else "moe")
            for i in range(cfg["num_hidden_layers"])]


def _experts_total(cfg):
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def _kda_rank(cfg):
    return cfg.get("kda_low_rank_dim") or cfg["linear_attn_config"]["head_dim"]


# -- the program's build -------------------------------------------------------
def build(cfg, which):
    if which != "gluon":
        raise ValueError(f"Kimi-Linear-48B-A3B-Instruct has no build {which!r}")
    from mxnet_tpu.gluon.model_zoo.language import kimi_linear
    return kimi_linear(cfg)


def canonical(cfg, which, net):
    """{the program's parameter name: canonical name}."""
    names = {net.embed_weight.name: "embed", net.head_weight.name: "head",
             net.final_norm.gamma.name: "final_norm",
             net.expert_load.name: "expert_load",
             net.expert_rows.name: "expert_rows"}
    for i, ((mixer, ffn), layer) in enumerate(zip(_kinds(cfg), net.layers)):
        at = f"layers.{i}."
        pairs = [("norm1", layer.input_norm.gamma),
                 ("norm2", layer.post_norm.gamma)]
        m, f = layer.mixer, layer.ffn
        if mixer == "kda":
            pairs += [(f"kda.{n}", getattr(m, n + "_weight")) for n in "qkvo"]
            pairs += [(f"kda.{n}_conv_w", getattr(m, n + "_conv_weight"))
                      for n in "qkv"]
            pairs += [(f"kda.{n}", getattr(m, n + "_weight"))
                      for n in ("a_down", "a_up", "beta", "g_down", "g_up")]
            pairs += [("kda.A_log", m.A_log), ("kda.dt_bias", m.dt_bias),
                      ("kda.norm", m.norm.gamma)]
        else:
            pairs += [(f"mla.{n}", getattr(m, n + "_weight"))
                      for n in ("q", "kv_a", "kv_b", "o")]
            pairs += [("mla.kv_norm", m.latent_norm.gamma)]
        if ffn == "mlp":
            pairs += [("mlp.in", f.in_weight), ("mlp.out", f.out_weight)]
        else:
            pairs += [("moe.router", f.router_weight),
                      ("moe.bias", f.select_bias), ("moe.w1", f.w1),
                      ("moe.w3", f.w3), ("moe.w2", f.w2),
                      ("moe.shared_in", f.shared.in_weight),
                      ("moe.shared_out", f.shared.out_weight)]
        for ours, theirs in pairs:
            names[theirs.name] = at + ours
    return names


# -- shapes --------------------------------------------------------------------
def param_shapes(cfg, which="gluon"):
    """{canonical name: shape}, the auxiliary state among them
    (``expert_load``, ``expert_rows`` and each mixture's ``bias``: no
    gradient, no optimizer)."""
    hid, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, total = cfg["num_experts"], _experts_total(cfg)
    lin = cfg["linear_attn_config"]
    inner, rank = lin["num_heads"] * lin["head_dim"], _kda_rank(cfg)
    heads, rope = cfg["num_attention_heads"], cfg["qk_rope_head_dim"]
    nope, dv, c = (cfg["qk_nope_head_dim"], cfg["v_head_dim"],
                   cfg["kv_lora_rank"])
    kinds = _kinds(cfg)
    routed = sum(1 for _, ffn in kinds if ffn == "moe")
    shapes = {"embed": (cfg["vocab_size"], hid),
              "head": (cfg["vocab_size"], hid), "final_norm": (hid,),
              "expert_load": (routed, held), "expert_rows": (routed,)}
    for i, (mixer, ffn) in enumerate(kinds):
        at = f"layers.{i}."
        shapes[at + "norm1"] = shapes[at + "norm2"] = (hid,)
        if mixer == "kda":
            for name in "qkv":
                shapes[at + "kda." + name] = (inner, hid)
                shapes[at + f"kda.{name}_conv_w"] = (
                    inner, lin["short_conv_kernel_size"])
            shapes[at + "kda.a_down"] = shapes[at + "kda.g_down"] = (rank, hid)
            shapes[at + "kda.a_up"] = shapes[at + "kda.g_up"] = (inner, rank)
            shapes[at + "kda.A_log"] = (lin["num_heads"],)
            shapes[at + "kda.dt_bias"] = (inner,)
            shapes[at + "kda.beta"] = (lin["num_heads"], hid)
            shapes[at + "kda.norm"] = (lin["head_dim"],)
            shapes[at + "kda.o"] = (hid, inner)
        else:
            shapes[at + "mla.q"] = (heads * (nope + rope), hid)
            shapes[at + "mla.kv_a"] = (c + rope, hid)
            shapes[at + "mla.kv_norm"] = (c,)
            shapes[at + "mla.kv_b"] = (heads * (nope + dv), c)
            shapes[at + "mla.o"] = (hid, heads * dv)
        if ffn == "mlp":
            shapes[at + "mlp.in"] = (2 * cfg["intermediate_size"], hid)
            shapes[at + "mlp.out"] = (hid, cfg["intermediate_size"])
            continue
        shared = cfg["num_shared_experts"] * width
        shapes[at + "moe.router"] = (total, hid)
        shapes[at + "moe.bias"] = (total,)
        shapes[at + "moe.w1"] = shapes[at + "moe.w3"] = (held, width, hid)
        shapes[at + "moe.w2"] = (held, hid, width)
        shapes[at + "moe.shared_in"] = (2 * shared, hid)
        shapes[at + "moe.shared_out"] = (hid, shared)
    return shapes


def trained(shapes):
    """The names the optimizer owns: all but the auxiliary state."""
    return [k for k in shapes if k not in AUX and not k.endswith("moe.bias")]


def macs_per_image(cfg, which="gluon"):
    """Multiply-accumulates of one forward pass over one sequence (the
    harness's "image") of ``cfg["image"][0] - 1`` tokens, per token:

    * matrices: every 2-D parameter once (the embedding is a gather and
      does not count; the head does; the router's 256 outputs do);
    * routed experts: the three matrices of ONE expert times the EXPECTED
      assignments a token sends to the experts held here under an even
      router, ``top_k · E_here / E`` (0.25): not the padding of an
      expert's last tile;
    * convolutions: 4 taps on each of the 3 × H·d channels of a KDA layer;
    * the scan, as the chunked algorithm computes it with chunk Q and H
      heads of d: H · (4 Q d + 3 d²) (as Solar's configuration counts it);
    * causal latent attention at T positions: scores over d_qk and values
      over d_v, (T+1)/2 keys a query on average: H · (d_qk + d_v) ·
      (T + 1) / 2.
    """
    t = int(cfg["image"][0]) - 1
    shapes = param_shapes(cfg, which)
    matrices = sum(s[0] * s[1] for k, s in shapes.items()
                   if len(s) == 2 and k not in ("embed", "expert_load")
                   and not k.endswith("_conv_w"))
    kinds = [mixer for mixer, _ in _kinds(cfg)]
    routed = sum(1 for _, ffn in _kinds(cfg) if ffn == "moe")
    share = cfg["num_experts_per_token"] * cfg["num_experts"] \
        / _experts_total(cfg)
    experts = routed * share * 3 * cfg["moe_intermediate_size"] \
        * cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    h, d, q = lin["num_heads"], lin["head_dim"], cfg.get("kda_chunk_size", 64)
    conv = kinds.count("kda") * 3 * h * d * lin["short_conv_kernel_size"]
    scan = kinds.count("kda") * h * (4 * q * d + 3 * d * d)
    attn = kinds.count("mla") * cfg["num_attention_heads"] \
        * (_d_qk(cfg) + cfg["v_head_dim"]) * (t + 1) / 2
    return int(t * (matrices + experts + conv + scan + attn))


# -- the latent attention kernels' work, from the shapes alone -------------------
def _d_qk(cfg):
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def attention_kernel_flops(cfg):
    """FLOPs a training step's latent attention NEEDS, whatever computes
    it: 2 a multiply-accumulate over the causal pairs × heads × layers ×
    the six products: three over the query/key head size (forward q kᵀ,
    backward dQ = dS k and dK = dSᵀ q) and three over the value's (forward
    p v, backward dP = dO vᵀ and dV = pᵀ dO).  Scores recomputed in the
    backward are not counted."""
    t = int(cfg["image"][0]) - 1
    layers = sum(1 for mixer, _ in _kinds(cfg) if mixer == "mla")
    return 2 * (t * (t + 1) // 2) * cfg["num_attention_heads"] * layers \
        * (3 * _d_qk(cfg) + 3 * cfg["v_head_dim"])


def attention_kernel_bytes(cfg):
    """Bytes a training step's latent attention has to move between HBM
    and the chip once, in float32, a head: the forward reads q, k, v and
    writes o; the backward reads q, k, v, o, dO and writes dQ, dK, dV;
    q, k, dQ, dK of the query/key head size, v, o, dO, dV of the value's;
    over the T positions, the heads and the layers."""
    layers = sum(1 for mixer, _ in _kinds(cfg) if mixer == "mla")
    a_head = (2 + 4) * _d_qk(cfg) + (2 + 4) * cfg["v_head_dim"]
    return 4 * a_head * cfg["num_attention_heads"] \
        * (int(cfg["image"][0]) - 1) * layers


# -- the plain reference -------------------------------------------------------
def _rms_norm(v, weight, eps):
    return v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps) \
        * weight


def _conv(x, w):
    """Depthwise, causal, no bias: tap K−1 multiplies the current step."""
    k, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    return sum(padded[:, j:j + t] * w[:, j] for j in range(k))


def _kda(p, at, h, cfg):
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    bsz, t, _ = h.shape

    def branch(name):
        x = jax.nn.silu(_conv(h @ p[at + name].T, p[at + name + "_conv_w"]))
        return x.reshape(bsz, t, heads, d)

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q, k, v = unit(branch("q")) / d ** 0.5, unit(branch("k")), branch("v")
    g = -jnp.exp(p[at + "A_log"])[:, None] * jax.nn.softplus(
        h @ p[at + "a_down"].T @ p[at + "a_up"].T + p[at + "dt_bias"]
    ).reshape(bsz, t, heads, d)
    beta = jax.nn.sigmoid(h @ p[at + "beta"].T)

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


def _mla(p, at, h, cfg):
    heads, nope, rope, dv, c = (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    bsz, t, _ = h.shape
    q = (h @ p[at + "q"].T).reshape(bsz, t, heads, nope + rope)
    c_kv, k_pe = jnp.split(h @ p[at + "kv_a"].T, [c], axis=-1)
    kv = (_rms_norm(c_kv, p[at + "kv_norm"], cfg["rms_norm_eps"])
          @ p[at + "kv_b"].T).reshape(bsz, t, heads, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None, :], (bsz, t, heads, rope))], -1)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, kv[..., nope:]))
    keys = jnp.arange(t)
    out = []
    for start in range(0, t, ROWS):             # full softmax, a block of rows
        rows = slice(start, min(start + ROWS, t))
        s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, rows], k) \
            / (nope + rope) ** 0.5
        s = jnp.where(keys[None, :] <= keys[rows, None], s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(s, axis=-1), v))
    out = jnp.concatenate(out, axis=2).transpose(0, 2, 1, 3)
    return out.reshape(bsz, t, heads * dv) @ p[at + "o"].T


def _gated_mlp(h, w_in, w_out):
    g, u = jnp.split(h @ w_in.T, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out.T


def _route(p, at, h, cfg):
    """(scores, the chosen experts): the bias chooses, the scores weigh."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h, p[at + "router"].T, precision=lax.Precision.HIGHEST))
    _, expert = lax.top_k(scores + p[at + "bias"],
                          cfg["num_experts_per_token"])
    return scores, expert


def _moe(p, at, h, cfg, note=None):
    """The mixture of one layer; ``note(at, scores, expert)`` is shown the
    routing it was computed from."""
    first, held = cfg.get("first_routed_expert", 0), cfg["num_experts"]
    scores, expert = _route(p, at, h, cfg)
    if note is not None:
        note(at, scores, expert)
    chosen = jnp.take_along_axis(scores, expert, axis=-1)
    if cfg["moe_renormalize"]:
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
    below the k-th.  ``scores`` are what the choice is made over (the
    biased ones).  A token with a small margin gains or loses a whole
    expert's output here when its hidden state is rounded otherwise."""
    top = lax.top_k(scores, k + 1)[0]
    kth, after = top[..., k - 1:k], top[..., k:]
    mine = scores[..., first:first + held]
    return jnp.min(jnp.where(mine >= kth, mine - after, kth - mine), axis=-1)


def _over_window(margin, window):
    """(batch, T): the least margin among a token and the ``window`` tokens
    before it.  A token whose routing flips by rounding gains or loses a
    whole expert's output, and the next layers' convolutions and states
    hand that change to the tokens that follow: a token is as far from a
    flip as the nearest of the tokens it still hears."""
    t = margin.shape[1]
    padded = jnp.pad(margin, [(0, 0), (window, 0)], constant_values=jnp.inf)
    return jnp.min(jnp.stack([padded[:, j:j + t]
                              for j in range(window + 1)]), axis=0)


_MIXERS = {"kda": _kda, "mla": _mla}


def _layers(p, ids, cfg, note=None):
    """The hidden state after the last layer; ``note`` is every
    mixture's (``_moe``)."""
    eps = cfg["rms_norm_eps"]
    x = p["embed"][ids]
    for i, (mixer, ffn) in enumerate(_kinds(cfg)):
        at = f"layers.{i}."
        x = x + _MIXERS[mixer](p, f"{at}{mixer}.",
                               _rms_norm(x, p[at + "norm1"], eps), cfg)
        h = _rms_norm(x, p[at + "norm2"], eps)
        x = x + (_moe(p, at + "moe.", h, cfg, note) if ffn == "moe"
                 else _gated_mlp(h, p[at + "mlp.in"], p[at + "mlp.out"]))
    return x


def reference(cfg, which="gluon", routing=False):
    """``forward(params, ids, train=False) -> logits`` (batch, T, vocab);
    the forward has no mode (the bias rule is ``updated_bias``), ``train``
    is the harness's signature.  With ``routing`` it returns ``(logits,
    margin, counts)``: each expert layer's ``_held_margin`` over s + b,
    the least over a token and the ``routing_margin_window`` tokens before
    it (expert layers, batch, T), and its assignments to each held expert
    (expert layers, held), both of the reference's own scores."""
    first, held = cfg.get("first_routed_expert", 0), cfg["num_experts"]
    k, window = cfg["num_experts_per_token"], \
        cfg.get("routing_margin_window", 0)

    def forward(p, ids, train=False):
        notes = []

        def note(at, scores, expert):
            notes.append((
                _over_window(_held_margin(scores + p[at + "bias"], k, first,
                                          held), window),
                jnp.sum(expert.reshape(-1, 1) == first + jnp.arange(held),
                        axis=0)))

        x = _layers(p, ids, cfg, note if routing else None)
        logits = _rms_norm(x, p["final_norm"], cfg["rms_norm_eps"]) \
            @ p["head"].T
        if not routing:
            return logits
        margin, counts = zip(*notes)
        return logits, jnp.stack(margin), jnp.stack(counts)

    return forward


def updated_bias(cfg, which="gluon"):
    """``(params, ids) -> {canonical name of a bias: its value after one
    training step's rule}``: b + u · sign(mean(c) − c), c the forward's
    assignments to each of ALL experts."""
    rate, total = cfg.get("router_bias_update_rate", 1e-3), \
        _experts_total(cfg)

    def rule(p, ids):
        out = {}

        def note(at, scores, expert):
            c = jnp.sum(expert.reshape(-1, 1) == jnp.arange(total), axis=0
                        ).astype(jnp.float32)
            out[at + "bias"] = p[at + "bias"] + rate * jnp.sign(
                jnp.mean(c) - c)

        _layers(p, ids, cfg, note)
        return out

    return rule


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
