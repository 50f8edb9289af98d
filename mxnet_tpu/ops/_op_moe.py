"""Routed experts for one holder of a share of a mixture of experts: scores
over ALL experts, top-k, and the MLPs of the experts held here for exactly
the rows routed to them.

No reference analog: MXNet 1.x has no routed layer.  ``parallel/moe.py``
is a top-1 router with a capacity; this op is the layer a deployment with
experts over several chips runs on each of them, without the exchange:

    s = sigmoid(W_r h)  over all ``experts_total`` outputs (float32, highest),
        or softmax(W_r h) over them (``score_function``); or of logits a
        router OUTSIDE the op computed (``router`` ``logits``: a network
        with state of its own, not one matrix)
    the chosen: top-k of s, or of s + b with a selection bias b (which
        chooses and never weighs: Wang et al. arXiv:2408.15664)
    w_e = s_e / Σ_chosen s · scaling   (``norm_topk``)
    y = Σ_{e chosen, first ≤ e < first + E_here} w_e · expert_e(h)
    expert_e(h) = W2_e (silu(W1_e h) ⊙ W3_e h)     (form ``gated_silu``)
                = W2_e relu(W1_e h)²                (form ``relu2``)

What an absent expert would add is left out.  NOTHING IS DROPPED: the held
assignments are sorted by expert and walked in tiles of ``tile`` rows, one
expert a tile, as many tiles as the routing of this batch needs.  The only
static bounds are on index arrays: a token chooses an expert at most once,
so no routing gives more than ``N · min(top_k, E_here)`` held assignments
or more than that over ``tile`` plus ``E_here`` tiles; the rows themselves
are gathered a tile at a time inside the loop and never laid out at that
bound.  The products therefore cost what the routing asks plus each
expert's last, partly filled tile; a batch in which every token chooses one
held expert just walks more tiles.  The backward walks the same tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register
from ..base import MXNetError


def _plan(expert, held, n_held, tile):
    """The tiles of one batch.  ``expert`` (A,) the local expert of every
    assignment, ``held`` (A,) whether it lies here.  Returns ``order`` (A,)
    assignments sorted by expert with the absent ones last, ``counts``,
    ``first_row`` and ``first_tile`` (E_here,) of each expert's group in
    that order, and the number of tiles."""
    key = jnp.where(held, expert, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0,
                     dtype=jnp.int32)
    tiles = (counts + tile - 1) // tile
    return (order, counts, jnp.cumsum(counts) - counts,
            jnp.cumsum(tiles) - tiles, jnp.sum(tiles))


def _tile(j, plan, top_k, tile, n_tokens):
    """Tile ``j``: its expert, the flat assignment, token and validity of
    each of its rows.  Rows past the end of the expert's group are not
    valid: their token index is ``n_tokens`` (out of range: scatters
    drop it, gathers are told to clip) and their weight must be taken as 0."""
    order, counts, first_row, first_tile, _ = plan
    # a group with no rows shares its first tile with the next group: of
    # the groups that start at or before j the last is the one with rows
    e = jnp.sum(first_tile <= j) - 1
    within = (j - first_tile[e]) * tile + jnp.arange(tile, dtype=jnp.int32)
    valid = within < counts[e]
    flat = order[jnp.clip(first_row[e] + within, 0, order.shape[0] - 1)]
    token = jnp.where(valid, flat // top_k, n_tokens)
    return e, flat, token, valid


# An expert's form: what stands between its up-projections and its
# down-projection, as (the value, the derivative by each up-projection).
# An expert of form F with up matrices U_1.. and the down matrix W2 is
# ``W2 F(U_1 h, ..)``; the walk and its pullback are written over F.
def _gated_silu(a, b):
    s, sig = jax.nn.silu(a), jax.nn.sigmoid(a)
    return s * b, (b * (sig + s * (1.0 - sig)), s)


def _relu2(a):
    r = jnp.maximum(a, 0.0)
    return r * r, (2.0 * r,)


FORMS = {"gated_silu": _gated_silu, "relu2": _relu2}


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _walk(h, weight, ups, down, plan, form, top_k, tile):
    """``y[n] = Σ weight[a] · expert_e(a)(h[n])`` over the held assignments
    ``a`` of token ``n``: h (N, hidden), weight (A,) per flat assignment,
    ``ups`` the up matrices of ``FORMS[form]`` (each (E_here, width,
    hidden)), ``down`` (E_here, hidden, width)."""
    n = h.shape[0]

    def body(state):
        j, y = state
        e, flat, token, valid = _tile(j, plan, top_k, tile, n)
        x = h.at[token].get(mode="clip")
        mid = FORMS[form](*(x @ w[e].T for w in ups))[0]
        out = (mid @ down[e].T) * jnp.where(valid, weight[flat], 0.0)[:, None]
        return j + 1, y.at[token].add(out, mode="drop")

    return lax.while_loop(lambda s: s[0] < plan[-1], body,
                          (jnp.int32(0), jnp.zeros_like(h)))[1]


def _walk_fwd(h, weight, ups, down, plan, form, top_k, tile):
    return (_walk(h, weight, ups, down, plan, form, top_k, tile),
            (h, weight, ups, down, plan))


def _walk_bwd(form, top_k, tile, saved, dy):
    h, weight, ups, down, plan = saved
    n = h.shape[0]

    def body(state):
        j, dh, dweight, dups, ddown = state
        e, flat, token, valid = _tile(j, plan, top_k, tile, n)
        x = h.at[token].get(mode="clip")
        mid, slopes = FORMS[form](*(x @ w[e].T for w in ups))
        # rows that are not valid gather dy's last row: mask it
        dout = jnp.where(valid[:, None], dy.at[token].get(mode="clip"),
                         0.0)
        dmid = dout @ down[e]                                 # unweighted
        dweight = dweight.at[jnp.where(valid, flat, weight.shape[0])].add(
            jnp.sum(dmid * mid, axis=-1), mode="drop")
        wt = jnp.where(valid, weight[flat], 0.0)[:, None]
        dmid = dmid * wt
        dpre = tuple(dmid * slope for slope in slopes)
        dups = tuple(dw.at[e].add(d.T @ x) for dw, d in zip(dups, dpre))
        ddown = ddown.at[e].add(dout.T @ (mid * wt))
        dh = dh.at[token].add(sum(d @ w[e] for d, w in zip(dpre, ups)),
                              mode="drop")
        return j + 1, dh, dweight, dups, ddown

    out = lax.while_loop(
        lambda s: s[0] < plan[-1], body,
        (jnp.int32(0),) + jax.tree_util.tree_map(
            jnp.zeros_like, (h, weight, ups, down)))
    return out[1:] + (None,)


_walk.defvjp(_walk_fwd, _walk_bwd)


SCORES = {"sigmoid": jax.nn.sigmoid,
          "softmax": functools.partial(jax.nn.softmax, axis=-1)}


def routed_experts(h, router_w, w1, w3, w2, top_k, first_expert,
                   scaling=1.0, norm_topk=True, tile=256, select_bias=None,
                   score_function="sigmoid", router="weight"):
    """The held experts' part of a routed layer.  h (..., hidden);
    router_w (experts_total, hidden); w1 (E_here, width, hidden); w2
    (E_here, hidden, width); w3 like w1 for gated SiLU experts, None for
    relu² experts of two matrices; the experts held are ``first_expert ..
    first_expert + E_here − 1`` of ``experts_total``.  Returns ``(y, load,
    rows)``: y like h; load (E_here,) float32, the assignments each held
    expert received; rows (1,) float32, the rows the grouped products ran,
    every expert's last tile counted whole.  With ``select_bias``
    (experts_total,) the chosen are the top k of scores + bias, weighed by
    their scores alone, and a fourth output counts the assignments to each
    of ALL ``experts_total`` experts (the rule that balances the bias
    needs the absent experts' load too).  ``score_function`` is what
    turns the router's outputs into scores: ``sigmoid``, each expert
    alone, or ``softmax`` over all of them.  With ``router`` ``logits``
    the second argument is not the router's matrix but its outputs,
    (..., experts_total), computed by whoever calls: no product is made
    here, everything after the scores is the same.  No assignment is
    dropped whatever the imbalance (see the module's head)."""
    hidden = h.shape[-1]
    total = router_w.shape[-1 if router == "logits" else 0]
    n_held = w1.shape[0]
    if not (0 <= first_expert and first_expert + n_held <= total
            and 1 <= top_k <= total and tile >= 1
            and score_function in SCORES and router in ("weight", "logits")
            and (router == "weight"
                 or router_w.shape[:-1] == h.shape[:-1])):
        raise MXNetError(
            f"routed_experts: experts {first_expert}..{first_expert + n_held}"
            f" of {total}, top {top_k}, tile {tile}, scores "
            f"{score_function!r}, router {router!r} {router_w.shape} for "
            f"data {h.shape}")
    form, ups = ("relu2", (w1,)) if w3 is None else ("gated_silu", (w1, w3))
    x = h.reshape(-1, hidden)
    with jax.named_scope("routed_experts/router"):
        # in float32 at the highest precision: near-ties among 320 scores
        # must fall the same way wherever the product is computed
        scores = SCORES[score_function](
            router_w.astype(jnp.float32).reshape(-1, total)
            if router == "logits" else jnp.matmul(
                x.astype(jnp.float32), router_w.astype(jnp.float32).T,
                precision=lax.Precision.HIGHEST))
        if select_bias is None:     # the values top_k returns ARE the weights
            chosen, expert = lax.top_k(scores, top_k)
        else:
            _, expert = lax.top_k(
                scores + select_bias.astype(jnp.float32), top_k)
            chosen = jnp.take_along_axis(scores, expert, axis=-1)
        if norm_topk:
            chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        weight = (chosen * scaling).astype(h.dtype).reshape(-1)
    with jax.named_scope("routed_experts/dispatch"):
        local = expert.reshape(-1) - first_expert
        plan = _plan(local, (local >= 0) & (local < n_held), n_held, tile)
    with jax.named_scope("routed_experts/experts"):
        y = _walk(x, weight, ups, w2, plan, form, top_k, tile)
    load = plan[1].astype(jnp.float32)
    rows = (plan[-1] * tile).astype(jnp.float32).reshape(1)
    if select_bias is None:
        return y.reshape(h.shape), load, rows
    counts = jnp.sum(expert.reshape(-1, 1) == jnp.arange(total), axis=0,
                     dtype=jnp.float32)
    return y.reshape(h.shape), load, rows, counts


def _input_names(attrs):
    """``router`` ``logits`` takes the router's outputs where its weight
    stood; ``expert_form`` ``relu2`` has no ``w3``; ``select_bias`` adds
    the bias as the last input (and the count over all experts as the last
    output)."""
    form = attrs.get("expert_form", "gated_silu")
    if form not in FORMS:
        raise MXNetError(f"routed_experts: expert_form {form!r} is not one "
                         f"of {sorted(FORMS)}")
    logits = str(attrs.get("router", "weight")) == "logits"
    return (("data", "router_logits" if logits else "router_weight", "w1")
            + (("w3",) if form == "gated_silu" else ()) + ("w2",)
            + (("select_bias",) if attrs.get("select_bias", False) else ()))


@register("_contrib_routed_experts", alias=("routed_experts",),
          num_outputs=lambda a: 4 if a.get("select_bias", False) else 3,
          input_names=_input_names)
def _routed_experts(attrs, h, router_w, *rest):
    names = _input_names(attrs)[2:]
    if len(rest) != len(names):
        raise MXNetError(f"routed_experts: inputs {names} expected after "
                         f"the router's, {len(rest)} given")
    given = dict(zip(names, rest))
    router = str(attrs.get("router", "weight"))
    width = router_w.shape[-1 if router == "logits" else 0]
    total = int(attrs.get("experts_total", width))
    if total != width:
        raise MXNetError(f"routed_experts: the router has {width} outputs, "
                         f"experts_total {total}")
    return routed_experts(
        h, router_w, given["w1"], given.get("w3"), given["w2"],
        int(attrs["top_k"]), int(attrs.get("first_expert", 0)),
        float(attrs.get("routed_scaling_factor", 1.0)),
        bool(attrs.get("norm_topk_prob", True)),
        int(attrs.get("tile", 256)), given.get("select_bias"),
        str(attrs.get("score_function", "sigmoid")), router)
