"""ZAYA1-8B (``model_type`` ``zaya``, config.json named in the JSON beside
this file), ONE CHIP'S SHARE of its first five layers: how the program
builds it, the shapes of its layers, its multiply-accumulates, the work its
attention kernels need, and its plain reference.

The equations; ``x`` is a (T, 2048) sequence, every matrix ``W`` is stored
(out, in) and applied as ``h Wᵀ``, ``E`` the tied table, H = 8 query heads
over G = 2 key/value heads of d = 128, g = H/G = 4:

    x = E[ids];  r = 0  (T, 256)
    layer i, the CCA sublayer (arXiv:2510.04476):
      a = RMSNorm(x; 1e-5);  q̃ = W_q a  (T, 1024);  k̃ = W_k a  (T, 256)
      u = [q̃ ; k̃]  (T, 1280);  c = conv1(conv0(u)):
        conv0[t, ch] = b0[ch] + Σ_j w0[ch, j] · u[t − (K0−1) + j, ch]
        conv1[t, ch] = b1[ch] + Σ_j Σ_i w1[ch, i, j]
                                · conv0[t − (K1−1) + j, 128·head(ch) + i]
        (K0 = cca_time0 = 2, K1 = cca_time1 = 2; zeros before the sequence,
        no nonlinearity between them; the 10 groups are the 8 query and 2
        key heads);  [c_q ; c_k] = c
      q' = c_q + ½ (q̃ + k̃↑);  k' = c_k + ½ (q̃↓ + k̃)    in heads: k̃↑ a key
        head repeated over the g query heads of its group, q̃↓ their mean
      q̂ = √d · q' / ‖q'‖₂;  k̂ = τ_j · √d · k' / ‖k'‖₂   a head, τ a key head
        (‖·‖₂ = sqrt(Σ² + 1e-6))
      q̂, k̂ = rope(·, t) over the FIRST 64 channels of every head
        (partial_rotary_factor 0.5, rotate-half within those 64, θ = 5e6)
      v_t = [W_v1 a_t ; W_v2 a_{t−1}]  a key head: its first 64 channels
        from this token, its last 64 from the one before (a_{−1} = 0)
      y = W_o softmax(q̂ k̂ᵀ / √d + causal) v
      x = s₁ ⊙ (x + b₁) + s₂ ⊙ (y + b₂)
    the expert sublayer (arXiv:2511.17127):
      m = RMSNorm(x; 1e-5)
      r = W_down m + b_down + γ · r          (the r of the layer before)
      ℓ = W₃ gelu(W₂ gelu(W₁ RMSNorm(r) + b₁') + b₂') + b₃'  (exact-erf gelu)
      s = softmax₁₆(ℓ);  e* = argmax(s + β)  (β chooses and never weighs)
      y = s_{e*} · W2_{e*} (silu(W1_{e*} m) ⊙ W3_{e*} m)  if e* is held here
      x = s₃ ⊙ (x + b₃) + s₄ ⊙ (y + b₄)
    logits = RMSNorm(x; 1e-5) Eᵀ

    the bias rule (training mode only, after the layers): with c_e the
        step's assignments to expert e over ALL 16,
        β_e ← β_e + u · sign(mean(c) − c_e),  u = router_bias_update_rate.
        The forward below READS β; ``updated_bias`` is the rule.

The share: layers 0–4 of 40; experts ``first_routed_expert .. + num_experts
− 1`` (8) of the router's 16; rows 0–32783 of the 262,272 of the tied
table; every head and width as published.  What the absent experts would
add is left out here exactly as in the program.  Every departure and
assumed size is in the JSON's ``reduced`` and ``assumed``.

The reference is straight ``jax.numpy`` in float32: the convolutions are
explicit sums over shifted copies, attention a full masked softmax taken
``ROWS`` query rows at a time, the experts a loop over the held experts
that computes every token for each and masks (the plain way, the one the
program may not use).  Nothing is imported from ``mxnet_tpu`` outside
``build``.  Parameters reach it under canonical names:

    embed  final_norm  expert_load  expert_rows  (the last two: the
        program's counts, which the reference does not read)
    layers.<i>.{input_norm,post_norm}
    layers.<i>.attn.{q,k,v1,v2,o,conv0_w,conv0_b,conv1_w,conv1_b,temp}
    layers.<i>.{attn_res,moe_res}.{skip_scale,skip_bias,out_scale,out_bias}
    layers.<i>.router.{down_w,down_b,gamma,norm,w1,b1,w2,b2,w3,b3}
    layers.<i>.moe.{bias,w1,w3,w2}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 512      # query rows of attention scored at once
AUX = ("expert_load", "expert_rows")
NORM_EPS = 1e-6     # under the root of a head's squared length


def _experts_total(cfg):
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def _rope_of(cfg):
    """(channels of a head that are turned, θ)."""
    rope = cfg["rope_parameters"]["hybrid"]
    return (int(cfg["head_dim"] * rope["partial_rotary_factor"]),
            float(rope["rope_theta"]))


def _widths(cfg):
    """(the query latent, the key/value latent)."""
    return (cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


# -- the program's build -------------------------------------------------------
def build(cfg, which):
    if which != "gluon":
        raise ValueError(f"ZAYA1-8B has no build {which!r}")
    from mxnet_tpu.gluon.model_zoo.language import zaya
    return zaya(cfg)


def canonical(cfg, which, net):
    """{the program's parameter name: canonical name}."""
    names = {net.embed_weight.name: "embed",
             net.final_norm.gamma.name: "final_norm",
             net.expert_load.name: "expert_load",
             net.expert_rows.name: "expert_rows"}
    for i, layer in enumerate(net.layers):
        at = f"layers.{i}."
        a, router = layer.attention, layer.moe.router
        pairs = [("input_norm", layer.input_norm.gamma),
                 ("post_norm", layer.post_norm.gamma),
                 ("attn.temp", a.temperature),
                 ("router.gamma", router.gamma),
                 ("router.norm", router.norm.gamma),
                 ("router.down_w", router.down_weight),
                 ("router.down_b", router.down_bias),
                 ("moe.bias", layer.moe.select_bias)]
        pairs += [(f"attn.{n}", getattr(a, n + "_weight"))
                  for n in ("q", "k", "v1", "v2", "o")]
        for n in ("conv0", "conv1"):
            pairs += [(f"attn.{n}_w", getattr(a, n + "_weight")),
                      (f"attn.{n}_b", getattr(a, n + "_bias"))]
        for ours, theirs in (("1", "fc1"), ("2", "fc2"), ("3", "out")):
            pairs += [("router.w" + ours, getattr(router, theirs + "_weight")),
                      ("router.b" + ours, getattr(router, theirs + "_bias"))]
        pairs += [(f"moe.{n}", getattr(layer.moe, n))
                  for n in ("w1", "w3", "w2")]
        for ours, block in (("attn_res", layer.attention_residual),
                            ("moe_res", layer.moe_residual)):
            pairs += [(f"{ours}.{n}", getattr(block, n))
                      for n in ("skip_scale", "skip_bias", "out_scale",
                                "out_bias")]
        for ours, theirs in pairs:
            names[theirs.name] = at + ours
    return names


# -- shapes --------------------------------------------------------------------
def param_shapes(cfg, which="gluon"):
    """{canonical name: shape}, the auxiliary state among them
    (``expert_load``, ``expert_rows`` and each layer's ``moe.bias``: no
    gradient, no optimizer)."""
    hid, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, total = cfg["num_experts"], _experts_total(cfg)
    q, kv = _widths(cfg)
    d, rw = cfg["head_dim"], cfg["router_hidden_size"]
    layers = cfg["num_hidden_layers"]
    shapes = {"embed": (cfg["vocab_size"], hid), "final_norm": (hid,),
              "expert_load": (layers, held), "expert_rows": (layers,)}
    for i in range(layers):
        at = f"layers.{i}."
        shapes[at + "input_norm"] = shapes[at + "post_norm"] = (hid,)
        shapes[at + "attn.q"] = (q, hid)
        shapes[at + "attn.k"] = (kv, hid)
        shapes[at + "attn.v1"] = shapes[at + "attn.v2"] = (kv // 2, hid)
        shapes[at + "attn.o"] = (hid, q)
        shapes[at + "attn.conv0_w"] = (q + kv, cfg["cca_time0"])
        shapes[at + "attn.conv1_w"] = (q + kv, d, cfg["cca_time1"])
        shapes[at + "attn.conv0_b"] = shapes[at + "attn.conv1_b"] = (q + kv,)
        shapes[at + "attn.temp"] = (cfg["num_key_value_heads"],)
        for res in ("attn_res.", "moe_res."):
            for n in ("skip_scale", "skip_bias", "out_scale", "out_bias"):
                shapes[at + res + n] = (hid,)
        shapes[at + "router.down_w"] = (rw, hid)
        shapes[at + "router.down_b"] = shapes[at + "router.norm"] = (rw,)
        shapes[at + "router.gamma"] = (1,)
        shapes[at + "router.w1"] = shapes[at + "router.w2"] = (rw, rw)
        shapes[at + "router.b1"] = shapes[at + "router.b2"] = (rw,)
        shapes[at + "router.w3"] = (total, rw)
        shapes[at + "router.b3"] = shapes[at + "moe.bias"] = (total,)
        shapes[at + "moe.w1"] = shapes[at + "moe.w3"] = (held, width, hid)
        shapes[at + "moe.w2"] = (held, hid, width)
    return shapes


def trained(shapes):
    """The names the optimizer owns: all but the auxiliary state."""
    return [k for k in shapes if k not in AUX and not k.endswith("moe.bias")]


def macs_per_image(cfg, which="gluon"):
    """Multiply-accumulates of one forward pass over one sequence (the
    harness's "image") of ``cfg["image"][0] - 1`` tokens, per token:

    * matrices: every 2-D parameter once (CCA's five projections, the
      router's four matrices); the tied table once, for the head (the
      embedding is a gather and does not count);
    * the two convolutions: ``cca_time0`` taps a channel, and ``cca_time1``
      taps of ``head_dim`` channels a channel;
    * routed experts: the three matrices of ONE expert times the EXPECTED
      assignments a token sends to the experts held here under an even
      router, ``top_k · E_here / E`` (0.5): half the tokens get nothing
      from the expert sublayer here; not the padding of an expert's last
      tile;
    * causal attention at T positions: scores and values, (T+1)/2 keys a
      query on average: heads · d · (T + 1).
    """
    t = int(cfg["image"][0]) - 1
    shapes = param_shapes(cfg, which)
    matrices = sum(s[0] * s[1] for k, s in shapes.items()
                   if len(s) == 2 and k != "expert_load"
                   and not k.endswith("conv0_w"))
    q, kv = _widths(cfg)
    layers = cfg["num_hidden_layers"]
    convs = layers * (q + kv) * (cfg["cca_time0"]
                                 + cfg["cca_time1"] * cfg["head_dim"])
    share = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / _experts_total(cfg)
    routed = layers * share * 3 * cfg["moe_intermediate_size"] \
        * cfg["hidden_size"]
    attn = layers * q * (t + 1)
    return int(t * (matrices + convs + routed + attn))


# -- the attention kernels' work, from the shapes alone --------------------------
def attention_kernel_flops(cfg):
    """FLOPs a training step's attention NEEDS, whatever computes it: 2 a
    multiply-accumulate × 6 products over the causal pairs (forward q kᵀ
    and p v; backward dP = dO vᵀ, dV = pᵀ dO, dQ = dS k, dK = dSᵀ q) ×
    head_dim × query heads × layers.  Scores recomputed in the backward
    and a forward run again under a remat boundary are not counted."""
    t = int(cfg["image"][0]) - 1
    return 2 * 6 * (t * (t + 1) // 2) * cfg["head_dim"] \
        * cfg["num_attention_heads"] * cfg["num_hidden_layers"]


def attention_kernel_bytes(cfg):
    """Bytes a training step's attention has to move between HBM and the
    chip once, in float32: the forward reads q, k, v and writes o; the
    backward reads q, k, v, o, dO and writes dQ, dK, dV; over the T
    positions and the layers."""
    q, kv = _widths(cfg)
    a_position = (2 * q + 2 * kv) + (4 * q + 4 * kv)
    return 4 * a_position * (int(cfg["image"][0]) - 1) \
        * cfg["num_hidden_layers"]


# -- the plain reference -------------------------------------------------------
def _rms_norm(v, weight, eps):
    return v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps) \
        * weight


def _shift(v, steps):
    """``out[:, t] = v[:, t − steps]``, zeros before the sequence."""
    if not steps:
        return v
    pad = [(0, 0), (steps, 0)] + [(0, 0)] * (v.ndim - 2)
    return jnp.pad(v, pad)[:, :v.shape[1]]


def _depthwise_conv(u, w, b):
    """w (channels, K): tap K−1 multiplies the current step."""
    k = w.shape[1]
    return b + sum(_shift(u, k - 1 - j) * w[:, j] for j in range(k))


def _grouped_conv(u, w, b):
    """w (channels, channels a group, K): an output channel reads the
    channels of its own group at every tap."""
    channels, per, k = w.shape
    bsz, t, _ = u.shape
    groups = channels // per
    w = w.reshape(groups, per, per, k)              # group, out, in, tap
    return b + sum(
        jnp.einsum("btgi,goi->btgo",
                   _shift(u, k - 1 - j).reshape(bsz, t, groups, per),
                   w[..., j]).reshape(bsz, t, channels)
        for j in range(k))


def _rope(x, rotary, theta):
    """x (batch, heads, T, d): the first ``rotary`` channels of a head are
    turned by the position, the rest pass."""
    t = x.shape[2]
    freq = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = (x[..., :rotary // 2], x[..., rotary // 2:rotary],
                    x[..., rotary:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _unit(v, d):
    """Each head's vector to length √d."""
    return v * (d ** 0.5) / jnp.sqrt(
        jnp.sum(v * v, axis=-1, keepdims=True) + NORM_EPS)


def _cca(p, at, a, cfg):
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    g = nq // nkv
    bsz, t, _ = a.shape
    q, k = a @ p[at + "q"].T, a @ p[at + "k"].T
    c = _grouped_conv(
        _depthwise_conv(jnp.concatenate([q, k], -1), p[at + "conv0_w"],
                        p[at + "conv0_b"]),
        p[at + "conv1_w"], p[at + "conv1_b"])
    # in heads; query head h = G-index · g + index within its group
    q = q.reshape(bsz, t, nkv, g, d)
    k = k.reshape(bsz, t, nkv, 1, d)
    q_new = c[..., :nq * d].reshape(q.shape) + 0.5 * (q + k)
    k_new = c[..., nq * d:].reshape(k.shape) \
        + 0.5 * (jnp.mean(q, axis=3, keepdims=True) + k)
    q = _unit(q_new, d).reshape(bsz, t, nq, d).transpose(0, 2, 1, 3)
    k = (_unit(k_new, d) * p[at + "temp"][:, None, None]
         ).reshape(bsz, t, nkv, d).transpose(0, 2, 1, 3)
    rotary, theta = _rope_of(cfg)
    q, k = _rope(q, rotary, theta), _rope(k, rotary, theta)
    v = jnp.concatenate(
        [(a @ p[at + "v1"].T).reshape(bsz, t, nkv, d // 2),
         (_shift(a, 1) @ p[at + "v2"].T).reshape(bsz, t, nkv, d // 2)],
        -1).transpose(0, 2, 1, 3)
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    keys = jnp.arange(t)
    out = []
    for start in range(0, t, ROWS):             # full softmax, a block of rows
        rows = slice(start, min(start + ROWS, t))
        s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, rows], k) / d ** 0.5
        s = jnp.where(keys[None, :] <= keys[rows, None], s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(s, axis=-1), v))
    out = jnp.concatenate(out, axis=2).transpose(0, 2, 1, 3)
    return out.reshape(bsz, t, nq * d) @ p[at + "o"].T


def _residual(p, at, x, y):
    return p[at + "skip_scale"] * (x + p[at + "skip_bias"]) \
        + p[at + "out_scale"] * (y + p[at + "out_bias"])


def _router(p, at, m, r, cfg):
    """(scores over all experts, the router's state after this layer)."""
    hi = lax.Precision.HIGHEST

    def dense(x, w, b):
        return jnp.matmul(x, p[at + w].T, precision=hi) + p[at + b]

    r = dense(m, "down_w", "down_b") + p[at + "gamma"] * r
    z = _rms_norm(r, p[at + "norm"], cfg["rms_norm_eps"])
    z = jax.nn.gelu(dense(z, "w1", "b1"), approximate=False)
    z = jax.nn.gelu(dense(z, "w2", "b2"), approximate=False)
    return jax.nn.softmax(dense(z, "w3", "b3"), axis=-1), r


def _gated_mlp(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1.T) * (h @ w3.T)) @ w2.T


def _moe(p, at, m, scores, cfg, note=None):
    """The held experts' part of one layer's mixture; ``note(at, scores,
    expert)`` is shown the routing it was computed from."""
    first, held = cfg.get("first_routed_expert", 0), cfg["num_experts"]
    _, expert = lax.top_k(scores + p[at + "bias"],
                          cfg["num_experts_per_tok"])
    if note is not None:
        note(at, scores, expert)
    chosen = jnp.take_along_axis(scores, expert, axis=-1)   # no renorming
    y = jnp.zeros_like(m)
    for e in range(held):                       # every token, then a mask
        weight = jnp.sum(jnp.where(expert == first + e, chosen, 0.0), axis=-1)
        y = y + weight[..., None] * _gated_mlp(
            m, p[at + "w1"][e], p[at + "w3"][e], p[at + "w2"][e])
    return y


def _held_margin(scores, k, first, held):
    """Per token, how far the nearest held expert's score lies from the
    edge of the top k: a chosen one above the (k+1)-th score, another one
    below the k-th.  ``scores`` are what the choice is made over (the
    biased ones).  With k = 1: a held argmax's lead over the runner-up, or
    how far the best held expert lies under an absent argmax.  A token with
    a small margin gains or loses its whole expert output here when its
    hidden state is rounded otherwise."""
    top = lax.top_k(scores, k + 1)[0]
    kth, after = top[..., k - 1:k], top[..., k:]
    mine = scores[..., first:first + held]
    return jnp.min(jnp.where(mine >= kth, mine - after, kth - mine), axis=-1)


def _over_window(margin, window):
    """(batch, T): the least margin among a token and the ``window`` tokens
    before it: the convolutions and the value's shift hand a flipped
    token's change to the tokens next after it."""
    t = margin.shape[1]
    padded = jnp.pad(margin, [(0, 0), (window, 0)], constant_values=jnp.inf)
    return jnp.min(jnp.stack([padded[:, j:j + t]
                              for j in range(window + 1)]), axis=0)


def _layers(p, ids, cfg, note=None):
    """The hidden state after the last layer; ``note`` is every
    mixture's (``_moe``)."""
    eps = cfg["rms_norm_eps"]
    x = p["embed"][ids]
    r = jnp.zeros(ids.shape + (cfg["router_hidden_size"],), jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        at = f"layers.{i}."
        a = _rms_norm(x, p[at + "input_norm"], eps)
        x = _residual(p, at + "attn_res.", x, _cca(p, at + "attn.", a, cfg))
        m = _rms_norm(x, p[at + "post_norm"], eps)
        scores, r = _router(p, at + "router.", m, r, cfg)
        x = _residual(p, at + "moe_res.", x,
                      _moe(p, at + "moe.", m, scores, cfg, note))
    return x


def reference(cfg, which="gluon", routing=False):
    """``forward(params, ids, train=False) -> logits`` (batch, T, vocab);
    the forward has no mode (the bias rule is ``updated_bias``), ``train``
    is the harness's signature.  With ``routing`` it returns ``(logits,
    margin, counts)``: each layer's ``_held_margin`` over s + β, the least
    over a token and the ``routing_margin_window`` tokens before it
    (layers, batch, T), and its assignments to each held expert (layers,
    held), both of the reference's own scores."""
    first, held = cfg.get("first_routed_expert", 0), cfg["num_experts"]
    k, window = cfg["num_experts_per_tok"], \
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
            @ p["embed"].T
        if not routing:
            return logits
        margin, counts = zip(*notes)
        return logits, jnp.stack(margin), jnp.stack(counts)

    return forward


def updated_bias(cfg, which="gluon"):
    """``(params, ids) -> {canonical name of a bias: its value after one
    training step's rule}``: β + u · sign(mean(c) − c), c the forward's
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
