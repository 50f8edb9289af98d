"""NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (``model_type`` ``nemotron_h``,
config.json named in the JSON beside this file), ONE CHIP'S SHARE of its
first nine layers: how the program builds it, the shapes of its layers,
its multiply-accumulates, and its plain reference.

The equations; ``h`` is a (T, 2688) sequence, every matrix ``W`` is stored
(out, in) and applied as ``h Wᵀ``, no bias but the convolution's:

    x = E[ids]                                               (no embedding scale)
    layer i:  x = x + mixer_i(RMSNorm(x))                    (eps 1e-5)
        one mixer a layer, by character i of hybrid_override_pattern
    logits = RMSNorm(x) W_headᵀ                              (untied)
    RMSNorm(v) = v · rsqrt(mean(v²) + eps) · weight

    M, Mamba-2: 64 heads of 64, state 128, 8 groups (heads 8g..8g+7 read
        B_g, C_g), causal depthwise conv of 4 taps with bias, then SiLU:
        [z, xBC, dt] = W_in h   (4096 + 6144 + 64);  xBC = silu(conv(xBC))
        Δ = softplus(dt + dt_bias);  a = −exp(A_log)
        S_t = exp(Δ_t a) S_{t−1} + Δ_t x_t B_tᵀ;   y_t = S_t C_t + D x_t
        u = y ⊙ silu(z);  out_c = γ_c · u_c · rsqrt(mean over c's group of
        512 channels of u² + eps);  then W_out

    E, the mixture: s = sigmoid(W_r h) in R^128 (W_r h in float32 at the
        highest precision);  the chosen = top-6 of s + b  (n_group 1,
        topk_group 1: no group limit; b the selection bias, which chooses
        and never weighs);  w_e = 2.5 · s_e / Σ_chosen s  (norm_topk_prob,
        routed_scaling_factor)
        y = Σ_{e chosen, e held here} w_e · W2_e relu(W1_e h)²
            + W2_sh relu(W1_sh h)²            (relu2, no gate; shared width 3712)

    *, attention: 32 query heads over 2 key/value heads of 128, causal,
        scale 128^−½, no bias, no rotary;  a = softmax(q kᵀ / sqrt(128)) v;
        out = W_o a

    the bias rule (training mode only, after the layers): with c_e the
        step's assignments to expert e over ALL 128,
        b_e ← b_e + u · sign(mean(c) − c_e),  u = router_bias_update_rate
        (Wang et al. arXiv:2408.15664).  The forward below READS b;
        ``updated_bias`` is the rule.

The share: layers 0–8 of 52 (``MEMEM*EME``); experts
``first_routed_expert .. + n_routed_experts − 1`` (8) of the router's 128;
rows 0–16383 of the 131,072 of both vocabulary tables; every head, group
and width as published.  What the absent experts would add is left out
here exactly as in the program.  Every departure and assumed size is in
the JSON's ``reduced`` and ``assumed``.

The reference is straight ``jax.numpy`` in float32: Mamba's recurrence is
a ``lax.scan`` over single time steps (it shares nothing with the
program's chunked op), attention is a full masked softmax taken ``ROWS``
query rows at a time, the experts a loop over the held experts that
computes every token for each and masks (the plain way, the one the
program may not use).  Nothing is imported from ``mxnet_tpu`` outside
``build``.  Parameters reach it under canonical names:

    embed  head  final_norm  expert_load  expert_rows  (the last two: the
        program's counts, which the reference does not read)
    layers.<i>.norm
    layers.<i>.mamba.{in_proj,conv_w,conv_b,A_log,D,dt_bias,norm,out_proj}
    layers.<i>.attn.{q,k,v,o}
    layers.<i>.moe.{router,bias,w1,w2,shared_in,shared_out}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 512      # query rows of attention scored at once
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
AUX = ("expert_load", "expert_rows")


def _kinds(cfg):
    return [KINDS[c] for c in
            cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]]


def _experts_total(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def _sizes(cfg):
    """(Mamba's inner width, the width of its B or C, its heads)."""
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"],
            cfg["n_groups"] * cfg["ssm_state_size"], cfg["mamba_num_heads"])


# -- the program's build -------------------------------------------------------
def build(cfg, which):
    if which != "gluon":
        raise ValueError(
            f"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 has no build {which!r}")
    from mxnet_tpu.gluon.model_zoo.language import nemotron_h
    return nemotron_h(cfg)


def canonical(cfg, which, net):
    """{the program's parameter name: canonical name}."""
    names = {net.embed_weight.name: "embed", net.head_weight.name: "head",
             net.final_norm.gamma.name: "final_norm",
             net.expert_load.name: "expert_load",
             net.expert_rows.name: "expert_rows"}
    for i, (kind, layer) in enumerate(zip(_kinds(cfg), net.layers)):
        at = f"layers.{i}."
        names[layer.norm.gamma.name] = at + "norm"
        m = layer.mixer
        if kind == "mamba":
            pairs = (("in_proj", m.in_proj_weight), ("conv_w", m.conv_weight),
                     ("conv_b", m.conv_bias), ("A_log", m.A_log), ("D", m.D),
                     ("dt_bias", m.dt_bias), ("norm", m.norm.gamma),
                     ("out_proj", m.out_proj_weight))
        elif kind == "attn":
            pairs = tuple((ours, getattr(m, ours + "_weight"))
                          for ours in "qkvo")
        else:
            pairs = (("router", m.router_weight), ("bias", m.select_bias),
                     ("w1", m.w1), ("w2", m.w2),
                     ("shared_in", m.shared.in_weight),
                     ("shared_out", m.shared.out_weight))
        for ours, theirs in pairs:
            names[theirs.name] = f"{at}{kind}.{ours}"
    return names


# -- shapes --------------------------------------------------------------------
def param_shapes(cfg, which="gluon"):
    """{canonical name: shape}, the auxiliary state among them
    (``expert_load``, ``expert_rows`` and each mixture's ``bias``: no
    gradient, no optimizer)."""
    hid, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] \
        * cfg["moe_shared_expert_intermediate_size"]
    held, total = cfg["n_routed_experts"], _experts_total(cfg)
    inner, bc, heads = _sizes(cfg)
    dh = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * dh, \
        cfg["num_key_value_heads"] * dh
    kinds = _kinds(cfg)
    shapes = {"embed": (cfg["vocab_size"], hid),
              "head": (cfg["vocab_size"], hid), "final_norm": (hid,),
              "expert_load": (kinds.count("moe"), held),
              "expert_rows": (kinds.count("moe"),)}
    for i, kind in enumerate(kinds):
        shapes[f"layers.{i}.norm"] = (hid,)
        at = f"layers.{i}.{kind}."
        if kind == "mamba":
            shapes[at + "in_proj"] = (2 * inner + 2 * bc + heads, hid)
            shapes[at + "conv_w"] = (inner + 2 * bc, cfg["conv_kernel"])
            shapes[at + "conv_b"] = (inner + 2 * bc,)
            shapes[at + "A_log"] = shapes[at + "D"] = (heads,)
            shapes[at + "dt_bias"] = (heads,)
            shapes[at + "norm"] = (inner,)
            shapes[at + "out_proj"] = (hid, inner)
        elif kind == "attn":
            shapes[at + "q"] = (nq, hid)
            shapes[at + "k"] = shapes[at + "v"] = (nkv, hid)
            shapes[at + "o"] = (hid, nq)
        else:
            shapes[at + "router"] = (total, hid)
            shapes[at + "bias"] = (total,)
            shapes[at + "w1"] = (held, width, hid)
            shapes[at + "w2"] = (held, hid, width)
            shapes[at + "shared_in"] = (shared, hid)
            shapes[at + "shared_out"] = (hid, shared)
    return shapes


def trained(shapes):
    """The names the optimizer owns: all but the auxiliary state."""
    return [k for k in shapes if k not in AUX and not k.endswith("moe.bias")]


def macs_per_image(cfg, which="gluon"):
    """Multiply-accumulates of one forward pass over one sequence (the
    harness's "image") of ``cfg["image"][0] - 1`` tokens, per token:

    * matrices: every 2-D parameter once (the embedding is a gather and
      does not count; the head does; the router's 128 outputs do);
    * routed experts: the two matrices of ONE expert times the EXPECTED
      assignments a token sends to the experts held here under an even
      router, ``top_k · E_here / E`` (0.375): what the deployment
      computes, not what a mask over every held expert would, and not the
      padding of each expert's last tile;
    * convolution: ``conv_kernel`` taps on each of the 6144 channels;
    * Mamba's scan, as the chunked algorithm computes it with chunk Q, G
      groups, P×N state and H heads: C·Bᵀ inside the chunk Q·N·G,
      (L ⊙ C Bᵀ) X  Q·H·P, the chunk's state N·H·P, the entering
      state's output N·H·P;
    * causal attention at T positions: scores and values, (T+1)/2 keys a
      query on average: heads · d · (T + 1).
    """
    t = int(cfg["image"][0]) - 1
    shapes = param_shapes(cfg, which)
    matrices = sum(s[0] * s[1] for k, s in shapes.items()
                   if len(s) == 2 and k not in ("embed", "expert_load")
                   and not k.endswith("conv_w"))
    kinds = _kinds(cfg)
    share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / _experts_total(cfg)
    routed = kinds.count("moe") * share * 2 * cfg["moe_intermediate_size"] \
        * cfg["hidden_size"]
    inner, bc, _heads = _sizes(cfg)
    q, n = cfg["chunk_size"], cfg["ssm_state_size"]
    conv = kinds.count("mamba") * cfg["conv_kernel"] * (inner + 2 * bc)
    scan = kinds.count("mamba") * (q * n * cfg["n_groups"] + q * inner
                                   + 2 * n * inner)
    attn = kinds.count("attn") * cfg["num_attention_heads"] \
        * cfg["head_dim"] * (t + 1)
    return int(t * (matrices + routed + conv + scan + attn))


# -- the plain reference -------------------------------------------------------
def _rms_norm(v, weight, eps, groups=1):
    by_group = v.reshape(v.shape[:-1] + (groups, -1))
    return (by_group * lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
            ).reshape(v.shape) * weight


def _mamba(p, at, h, cfg):
    inner, bc, heads = _sizes(cfg)
    dh, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    groups, k = cfg["n_groups"], cfg["conv_kernel"]
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
                  p[at + "norm"], cfg["layer_norm_epsilon"], groups)
    return y @ p[at + "out_proj"].T


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
    return out.reshape(bsz, t, nq * dh) @ p[at + "o"].T


def _relu2_mlp(h, w_in, w_out):
    return jnp.square(jax.nn.relu(h @ w_in.T)) @ w_out.T


def _route(p, at, h, cfg):
    """(scores, the chosen experts): the bias chooses, the scores weigh."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h, p[at + "router"].T, precision=lax.Precision.HIGHEST))
    _, expert = lax.top_k(scores + p[at + "bias"],
                          cfg["num_experts_per_tok"])
    return scores, expert


def _moe(p, at, h, cfg, note=None):
    """The mixture of one layer; ``note(at, scores, expert)`` is shown the
    routing it was computed from."""
    first, held = cfg.get("first_routed_expert", 0), cfg["n_routed_experts"]
    scores, expert = _route(p, at, h, cfg)
    if note is not None:
        note(at, scores, expert)
    chosen = jnp.take_along_axis(scores, expert, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    chosen = chosen * cfg["routed_scaling_factor"]
    y = _relu2_mlp(h, p[at + "shared_in"], p[at + "shared_out"])
    for e in range(held):                       # every token, then a mask
        weight = jnp.sum(jnp.where(expert == first + e, chosen, 0.0), axis=-1)
        y = y + weight[..., None] * _relu2_mlp(h, p[at + "w1"][e],
                                               p[at + "w2"][e])
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
    whole expert's output, and Mamba's convolution and state hand that
    change to the tokens that follow: a token is as far from a flip as the
    nearest of the tokens it still hears."""
    t = margin.shape[1]
    padded = jnp.pad(margin, [(0, 0), (window, 0)], constant_values=jnp.inf)
    return jnp.min(jnp.stack([padded[:, j:j + t]
                              for j in range(window + 1)]), axis=0)


_MIXERS = {"mamba": _mamba, "attn": _attention}


def _layers(p, ids, cfg, note=None):
    """The hidden state after the last layer; ``note`` is every
    mixture's (``_moe``)."""
    x = p["embed"][ids]
    for i, kind in enumerate(_kinds(cfg)):
        h = _rms_norm(x, p[f"layers.{i}.norm"], cfg["layer_norm_epsilon"])
        at = f"layers.{i}.{kind}."
        x = x + (_moe(p, at, h, cfg, note) if kind == "moe"
                 else _MIXERS[kind](p, at, h, cfg))
    return x


def reference(cfg, which="gluon", routing=False):
    """``forward(params, ids, train=False) -> logits`` (batch, T, vocab);
    the forward has no mode (the bias rule is ``updated_bias``), ``train``
    is the harness's signature.  With ``routing`` it returns ``(logits,
    margin, counts)``: each expert layer's ``_held_margin`` over s + b,
    the least over a token and the ``routing_margin_window`` tokens before
    it (expert layers, batch, T), and its assignments to each held expert
    (expert layers, held), both of the reference's own scores."""
    first, held = cfg.get("first_routed_expert", 0), cfg["n_routed_experts"]
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
        logits = _rms_norm(x, p["final_norm"], cfg["layer_norm_epsilon"]) \
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
