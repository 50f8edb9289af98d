"""SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``, config.json named in the
JSON beside this file), ONE CHIP'S SHARE of its first six layers, trained
by diffusion over blocks: how the program builds it, the shapes of its
layers, its multiply-accumulates, the operations and bytes of its attention
kernels, and its plain reference.

The equations; ``h`` is a sequence of width 2048, every matrix ``W`` is
stored (out, in) and applied as ``h Wᵀ``, no bias anywhere:

    input: clean ids x0 (T) and noisy ids xt (T), xt_i = MASK where
        position i is masked, else x0_i;  ids = [x0 ; xt]  (2T),
        positions p = [0..T−1 ; 0..T−1],  block of a position b(i) = p_i // B
    h = E[ids]                                               (no embedding scale)
    layer:  a = RMSNorm(h)                                   (eps 1e-6)
        q, k, v = W_q a, W_k a, W_v a  in 32 / 4 / 4 heads of 128
        q, k = RMSNorm_128(q), RMSNorm_128(k)    per head, a learned weight of 128
        q, k = rope(q, p), rope(k, p)    the whole head, rotate-half form:
            θ_i = 1e6^(−2i/128), [x1, x2] the head's halves,
            rope(x, p) = [x1 cos pθ − x2 sin pθ, x2 cos pθ + x1 sin pθ]
        o = softmax(q kᵀ / sqrt(128) + M) v      keys shared by 8 query heads
        h = h + W_o o
        M[i, j] = 0 where allowed, −inf elsewhere; allowed:
            clean → clean  b(j) <= b(i);   noisy → noisy  b(j) = b(i);
            noisy → clean  b(j) <  b(i);   clean → noisy  never
        m = RMSNorm(h);  s = softmax_128(W_r m)   (W_r m in float32, highest)
        the chosen = top-8 of s;  w_e = s_e / Σ_chosen s      (norm_topk_prob)
        h = h + Σ_{e chosen, e held here} w_e · W2_e (silu(W1_e m) ⊙ W3_e m)
    logits = RMSNorm(h[T:]) W_headᵀ     over the noisy half alone, (T, vocab)
    loss = (1/T) Σ_i w_i · CE(logits_i, x0_i),  w_i = 1 / t_{b(i)} where i
        is masked, 0 elsewhere

The noise is DATA: the level ``t`` of each block, which tokens it masks and
the weights ``w`` are drawn on the host by the driver, so program and
reference see the same ``xt`` and ``w``.  What the absent experts would add
is left out here exactly as in the program; there is no shared expert.
Every departure and assumed size is in the JSON's ``reduced`` and
``assumed``.

The reference is straight ``jax.numpy`` in float32: the mask is built
explicitly from ``b(i)`` for ``ROWS`` query rows at a time (16,384² scores
of 32 heads are 34 GB whole), the experts a loop over the held experts that
computes every position for each and masks (the plain way, the one the
program may not use).  Nothing is imported from ``mxnet_tpu`` outside
``build``.  ``denoise`` is the second forward: one noisy block behind its
clean prefix, under the block-causal mask, which the training layout's
logits of that block must equal.  Parameters reach both under canonical
names:

    embed  head  final_norm  expert_load  expert_rows  (the last two: the
        program's counts, which the reference does not read)
    layers.<i>.{input_norm,post_norm}
    layers.<i>.attn.{q,k,v,o,q_norm,k_norm}
    layers.<i>.moe.{router,w1,w3,w2}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ROWS = 512      # query rows of attention scored at once
AUX = ("expert_load", "expert_rows")


def _experts_total(cfg):
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def _length(cfg):
    """T: the tokens of one sequence (the harness's "image")."""
    return int(cfg["image"][0])


# -- the program's build -------------------------------------------------------
def build(cfg, which):
    if which != "gluon":
        raise ValueError(f"SDAR-30B-A3B-Chat has no build {which!r}")
    from mxnet_tpu.gluon.model_zoo.language import sdar_moe
    return sdar_moe(cfg)


def canonical(cfg, which, net):
    """{the program's parameter name: canonical name}."""
    names = {net.embed_weight.name: "embed", net.head_weight.name: "head",
             net.final_norm.gamma.name: "final_norm",
             net.expert_load.name: "expert_load",
             net.expert_rows.name: "expert_rows"}
    for i, layer in enumerate(net.layers):
        at = f"layers.{i}."
        a, m = layer.attention, layer.moe
        for ours, theirs in (
                ("input_norm", layer.input_norm.gamma),
                ("post_norm", layer.post_norm.gamma),
                ("attn.q", a.q_weight), ("attn.k", a.k_weight),
                ("attn.v", a.v_weight), ("attn.o", a.o_weight),
                ("attn.q_norm", a.q_norm.gamma),
                ("attn.k_norm", a.k_norm.gamma),
                ("moe.router", m.router_weight), ("moe.w1", m.w1),
                ("moe.w3", m.w3), ("moe.w2", m.w2)):
            names[theirs.name] = at + ours
    return names


# -- shapes --------------------------------------------------------------------
def param_shapes(cfg, which="gluon"):
    """{canonical name: shape}, the auxiliary state among them
    (``expert_load`` and ``expert_rows``: no gradient, no optimizer)."""
    hid, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, total = cfg["num_experts"], _experts_total(cfg)
    dh, n = cfg["head_dim"], cfg["num_hidden_layers"]
    nq, nkv = cfg["num_attention_heads"] * dh, \
        cfg["num_key_value_heads"] * dh
    shapes = {"embed": (cfg["vocab_size"], hid),
              "head": (cfg["vocab_size"], hid), "final_norm": (hid,),
              "expert_load": (n, held), "expert_rows": (n,)}
    for i in range(n):
        at = f"layers.{i}."
        shapes[at + "input_norm"] = shapes[at + "post_norm"] = (hid,)
        shapes[at + "attn.q"] = (nq, hid)
        shapes[at + "attn.k"] = shapes[at + "attn.v"] = (nkv, hid)
        shapes[at + "attn.o"] = (hid, nq)
        shapes[at + "attn.q_norm"] = shapes[at + "attn.k_norm"] = (dh,)
        shapes[at + "moe.router"] = (total, hid)
        shapes[at + "moe.w1"] = shapes[at + "moe.w3"] = (held, width, hid)
        shapes[at + "moe.w2"] = (held, hid, width)
    return shapes


def trained(shapes):
    """The names the optimizer owns: all but the auxiliary state."""
    return [k for k in shapes if k not in AUX]


def allowed_pairs(cfg):
    """(query, key) pairs the block-diffusion mask allows over the 2T
    positions of one sequence: clean → clean (T² + T·B) / 2, noisy → clean
    (T² − T·B) / 2, noisy → noisy T·B: T² + T·B, where all (2T)² would be
    four times T²."""
    t, b = _length(cfg), cfg["block_length"]
    return t * t + t * b


def macs_per_image(cfg, which="gluon"):
    """Multiply-accumulates of one forward pass over one sequence (the
    harness's "image") of T tokens, which the training layout lays out as
    2T positions:

    * matrices of a layer (the four projections and the router's 128
      outputs), at each of the 2T positions; the head over the T rows of
      the noisy half; the embedding is a gather and does not count;
    * routed experts: the three matrices of ONE expert times the EXPECTED
      assignments a position sends to the experts held here under an even
      router, ``top_k · E_here / E`` (1.0), at each of the 2T positions:
      what the deployment computes, not the padding of a last tile;
    * attention: scores and values over the pairs the mask ALLOWS
      (``allowed_pairs``), heads · head_dim each.  A count over all (2T)²
      pairs would read four times the attention's work.
    """
    t = _length(cfg)
    shapes = param_shapes(cfg, which)
    layer = sum(s[0] * s[1] for k, s in shapes.items()
                if k.startswith("layers.0.") and len(s) == 2)
    share = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / _experts_total(cfg)
    routed = share * 3 * cfg["moe_intermediate_size"] * cfg["hidden_size"]
    attn = 2 * allowed_pairs(cfg) * cfg["num_attention_heads"] \
        * cfg["head_dim"]
    head = cfg["vocab_size"] * cfg["hidden_size"]
    return int(cfg["num_hidden_layers"] * (2 * t * (layer + routed) + attn)
               + t * head)


# -- the attention kernels' work, from the shapes alone --------------------------
def attention_kernel_flops(cfg):
    """FLOPs a training step's attention NEEDS, whatever computes it: 2 a
    multiply-accumulate × 6 products over the allowed pairs (forward q kᵀ
    and p v; backward dP = dO vᵀ, dV = pᵀ dO, dQ = dS k, dK = dSᵀ q) ×
    head_dim × query heads × layers.  Scores recomputed in the backward
    and a forward run again under a remat boundary are not counted."""
    return 2 * 6 * allowed_pairs(cfg) * cfg["head_dim"] \
        * cfg["num_attention_heads"] * cfg["num_hidden_layers"]


def attention_kernel_bytes(cfg):
    """Bytes a training step's attention has to move between HBM and the
    chip once, in float32: the forward reads q, k, v and writes o; the
    backward reads q, k, v, o, dO and writes dQ, dK, dV; over the 2T
    positions and the layers."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    a_position = (2 * q + 2 * kv) + (4 * q + 4 * kv)
    return 4 * a_position * 2 * _length(cfg) * cfg["num_hidden_layers"]


# -- the plain reference -------------------------------------------------------
def _rms_norm(v, weight, eps):
    return v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps) \
        * weight


def _rope(x, positions, theta):
    """x (batch, heads, L, d), positions (L,)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def training_mask(rows, length, half, block):
    """(len(rows), length) bool: which keys the query rows may see in the
    training layout ``[x0 ; xt]`` of ``half`` positions each."""
    q, k = rows[:, None], jnp.arange(length)[None, :]
    q_noisy, k_noisy = q >= half, k >= half
    qb = jnp.where(q_noisy, q - half, q) // block
    kb = jnp.where(k_noisy, k - half, k) // block
    return jnp.where(
        q_noisy, jnp.where(k_noisy, kb == qb, kb < qb),
        ~k_noisy & (kb <= qb))


def block_causal_mask(rows, length, block):
    return (jnp.arange(length)[None, :] // block) <= (rows[:, None] // block)


def _attention(p, at, h, positions, mask_of, cfg):
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    bsz, length, _ = h.shape

    def heads(w, n):
        return (h @ w.T).reshape(bsz, length, n, dh)

    def turned(x, weight):      # per-head norm, then the rotation
        return _rope(_rms_norm(x, weight, eps).transpose(0, 2, 1, 3),
                     positions, float(cfg["rope_theta"]))

    q = turned(heads(p[at + "q"], nq), p[at + "q_norm"])
    k = jnp.repeat(turned(heads(p[at + "k"], nkv), p[at + "k_norm"]),
                   nq // nkv, axis=1)
    v = jnp.repeat(heads(p[at + "v"], nkv).transpose(0, 2, 1, 3),
                   nq // nkv, axis=1)
    out = []
    for start in range(0, length, ROWS):        # full softmax, a block of rows
        rows = jnp.arange(start, min(start + ROWS, length))
        s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, rows], k) / dh ** 0.5
        s = jnp.where(mask_of(rows, length), s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(s, axis=-1), v))
    out = jnp.concatenate(out, axis=2).transpose(0, 2, 1, 3)
    return out.reshape(bsz, length, nq * dh) @ p[at + "o"].T


def _route(p, at, m, cfg):
    """(scores over all experts, the chosen experts)."""
    scores = jax.nn.softmax(jnp.matmul(
        m, p[at + "router"].T, precision=lax.Precision.HIGHEST), axis=-1)
    return scores, lax.top_k(scores, cfg["num_experts_per_tok"])[1]


def _moe(p, at, m, cfg, note=None, held=None):
    """The mixture of one layer over the experts ``held`` = (first, count)
    (by default the configuration's share); ``note(scores, expert)`` is
    shown the routing it was computed from."""
    first, count = held or (cfg.get("first_routed_expert", 0),
                            cfg["num_experts"])
    scores, expert = _route(p, at, m, cfg)
    if note is not None:
        note(scores, expert)
    chosen = jnp.take_along_axis(scores, expert, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    y = jnp.zeros_like(m)
    for e in range(count):                      # every position, then a mask
        weight = jnp.sum(jnp.where(expert == first + e, chosen, 0.0), axis=-1)
        mid = jax.nn.silu(m @ p[at + "w1"][e].T) * (m @ p[at + "w3"][e].T)
        y = y + weight[..., None] * (mid @ p[at + "w2"][e].T)
    return y


def _held_margin(scores, k, first, held):
    """Per position, how far the nearest held expert's score lies from the
    edge of the top k: a chosen one above the (k+1)-th score, another one
    below the k-th.  A position with a small margin gains or loses a whole
    expert's output here when its hidden state is rounded otherwise."""
    top = lax.top_k(scores, k + 1)[0]
    kth, after = top[..., k - 1:k], top[..., k:]
    mine = scores[..., first:first + held]
    return jnp.min(jnp.where(mine >= kth, mine - after, kth - mine), axis=-1)


def _layers(p, ids, positions, mask_of, cfg, note=None):
    x = p["embed"][ids]
    eps = cfg["rms_norm_eps"]
    for i in range(cfg["num_hidden_layers"]):
        at = f"layers.{i}."
        x = x + _attention(p, at + "attn.",
                           _rms_norm(x, p[at + "input_norm"], eps),
                           positions, mask_of, cfg)
        x = x + _moe(p, at + "moe.", _rms_norm(x, p[at + "post_norm"], eps),
                     cfg, note)
    return x


def _head(p, x, cfg):
    return _rms_norm(x, p["final_norm"], cfg["rms_norm_eps"]) @ p["head"].T


def reference(cfg, which="gluon", routing=False):
    """``forward(params, ids, train=False) -> logits``: ids (batch, 2T) in
    the training layout ``[x0 ; xt]``, logits (batch, T, vocab) of the
    noisy half; the forward has no mode, ``train`` is the harness's
    signature.  With ``routing`` it returns ``(logits, margin, counts)``:
    each layer's ``_held_margin`` of every one of the 2T positions
    (layers, batch, 2T) and its assignments to each held expert (layers,
    held), both of the reference's own scores."""
    first, held = cfg.get("first_routed_expert", 0), cfg["num_experts"]
    k, block = cfg["num_experts_per_tok"], cfg["block_length"]

    def forward(p, ids, train=False):
        half = ids.shape[1] // 2
        positions = jnp.concatenate([jnp.arange(half)] * 2)
        notes = []

        def note(scores, expert):
            notes.append((
                _held_margin(scores, k, first, held),
                jnp.sum(expert.reshape(-1, 1) == first + jnp.arange(held),
                        axis=0)))

        x = _layers(p, ids, positions,
                    lambda rows, n: training_mask(rows, n, half, block),
                    cfg, note if routing else None)
        logits = _head(p, x[:, half:], cfg)
        if not routing:
            return logits
        margin, counts = zip(*notes)
        return logits, jnp.stack(margin), jnp.stack(counts)

    return forward


def denoise(cfg, which="gluon"):
    """``forward(params, ids) -> logits`` (batch, L, vocab): the denoising
    forward of ``ids = [clean prefix ; one noisy block]`` at positions
    ``0..L−1`` under the block-causal mask; the last block's logits are
    what a generation step reads."""
    block = cfg["block_length"]

    def forward(p, ids):
        length = ids.shape[1]
        x = _layers(p, ids, jnp.arange(length),
                    lambda rows, n: block_causal_mask(rows, n, block), cfg)
        return _head(p, x, cfg)

    return forward


def cross_entropy(logits, labels, weights):
    """``(1/T) Σ_i w_i · (−log softmax(logits_i)[label_i])``, a mean over
    the batch: ``weights`` (batch, T)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return -jnp.mean(weights * picked)


def loss(cfg, which="gluon"):
    """``(params, ids, labels, weights) -> cross_entropy(forward(params,
    ids), labels, weights)``; its ``jax.grad`` is the reference's
    gradient."""
    forward = reference(cfg, which)
    return lambda p, ids, labels, weights: cross_entropy(
        forward(p, ids), labels, weights)
