"""Plain reference for Keye-VL-2.0-30B-A3B's language model: one
full-sequence pass, no cache, no windows, no gather, no bisection, no
sorting of rows by expert, no kernel.

A decoder-only model without biases, untied embedding and head, every layer
alike.  With ``RMSNorm(x; g) = x / sqrt(mean(x^2) + EPS) g``, a query at
sequence index ``t`` and the keys ``s <= t`` of its sequence:

    x  = RMSNorm(h; g_a)
    q  = x Wq (H heads of d), k = x Wk, v = x Wv (K heads of d); q and k
         RMS-normed over d (gains g_q, g_k), then rotated by split halves:
         element j with j + d/2 by the angle p_c(j) THETA^(-2j/d), p =
         (temporal, height, width), c(j) the component that SECTIONS
         (pairs a component, contiguous) gives pair j
    qI = x WqI (J heads of e);  kI = LN(x WkI; g_I) (mean taken out, no
         bias);  both rotated over their e numbers by the temporal
         position; w = x Ww / sqrt(J e)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
    S_t = the min(INDEX_TOPK, t + 1) keys s <= t with the largest I[t, s],
          equal scores to the lower s (``chosen``: the INDEX_TOPK-th largest
          by one sort, then a running count of the scores equal to it)
    a  = [softmax_{s in S_t}(q_i . k_{i // (H/K), s} / sqrt(d)) v]_i Wo
    h <- h + a;  y = RMSNorm(h; g_f)
    g  = softmax(y Wr) over ALL experts, T its TOP_K largest, w_e = g_e /
         sum_T g;  h <- h + sum_{e in T} w_e (silu(y G_e) * (y U_e)) D_e

then ``RMSNorm(h; g)`` and the head.  The widths are read off the weights;
what they do not say is ``SIZES``.  ``select=False`` attends over every
``s <= t`` instead (what a program that ignored its indexer would compute).

The weights stay in the dtype and in the arrays they were served in and are
raised to float32 one product at a time (the experts one at a time, under a
loop); attention runs a block of queries at a time over all keys.  Both
only bound memory: every product is float32 at precision ``highest``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

THETA, EPS, TOP_K, INDEX_TOPK, SECTIONS = 1e7, 1e-6, 8, 2048, (16, 24, 24)
QUERY_BLOCK = 128
# What ``logits`` and its kin take as keywords, with the published values.
SIZES = dict(theta=THETA, eps=EPS, top_k=TOP_K, index_topk=INDEX_TOPK,
             sections=SECTIONS, select=True)


def from_served_tree(flat: Dict[str, jax.Array], n_layers: int) -> Dict:
    """The reference reads the served leaves where they lie, by their
    paths; numbers and dtypes as served.  ``n_layers`` is checked."""
    if f"layer_{n_layers - 1}/attn/q_proj/kernel" not in flat \
            or f"layer_{n_layers}/attn/q_proj/kernel" in flat:
        raise ValueError(f"the tree does not hold {n_layers} layers")
    return dict(flat)


def n_layers_of(params: Dict) -> int:
    return sum(k.endswith("/attn/q_proj/kernel") for k in params)


def rms_norm(x, g, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * g.astype(jnp.float32)


def rotary(x, pos, theta, sections=None):
    """x [t, ..., d], pos [3, t]: element ``j`` with ``j + d/2``, pair ``j``
    by the component of ``pos`` that ``sections`` gives it (the temporal
    one for every pair where None)."""
    half = x.shape[-1] // 2
    which = np.zeros(half, int) if sections is None else np.repeat(
        np.arange(len(sections)), sections)
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32).T[:, which] * freq        # [t, half]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([
        a * jnp.cos(angle) - b * jnp.sin(angle),
        b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def chosen(index, rows, topk):
    """index [q, n]: the index scores of the queries at sequence indices
    ``rows`` [q] against the keys 0 .. n-1 -> bool [q, n]: the ``min(topk,
    rows + 1)`` keys ``s <= rows`` with the largest scores, equal scores to
    the lower ``s``."""
    n = index.shape[1]
    seen = jnp.arange(n)[None, :] <= rows[:, None]
    index = jnp.where(seen, jnp.where(index == 0, 0.0, index), -jnp.inf)
    if topk >= n:
        return seen
    kth = jnp.sort(index, axis=-1)[:, n - topk][:, None]
    above, equal = index > kth, index == kth
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return seen & (above | (equal & (jnp.cumsum(equal, axis=-1) <= room)))


def attention(p: Dict, at: str, x, pos, mode: str, s: Dict):
    """x [t, d_model], pos [3, t] -> [t, d_model]."""
    w = lambda name: p[f"{at}/attn/{name}/kernel"]
    gain = lambda name: p[f"{at}/attn/{name}/scale"]
    times = lambda a, b: C.weight_product("ti,io->to", a, b, mode, (1,), (0,))
    t, d = x.shape[0], gain("q_norm").shape[0]
    e = w("index_k").shape[1]
    q = rms_norm(times(x, w("q_proj")).reshape(t, -1, d), gain("q_norm"),
                 s["eps"])
    k = rms_norm(times(x, w("k_proj")).reshape(t, -1, d), gain("k_norm"),
                 s["eps"])
    v = times(x, w("v_proj")).reshape(t, -1, d)
    q = rotary(q, pos, s["theta"], s["sections"])
    k = rotary(k, pos, s["theta"], s["sections"])
    n_kv = k.shape[1]
    q = q.reshape(t, n_kv, -1, d)             # head i = (i // g, i % g)

    qi = rotary(times(x, w("index_q")).reshape(t, -1, e), pos, s["theta"])
    ki = times(x, w("index_k"))
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki / jnp.sqrt(jnp.mean(jnp.square(ki), axis=-1, keepdims=True)
                       + s["eps"]) * gain("index_norm").astype(jnp.float32)
    ki = rotary(ki, pos, s["theta"])
    wi = times(x, w("index_w")) * (qi.shape[1] * e) ** -0.5

    block = next(n for n in range(min(t, QUERY_BLOCK), 0, -1) if t % n == 0)

    def one_block(start):
        rows = start + jnp.arange(block)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, 0)
        if s["select"]:
            dots = C.product("qjd,sd->qjs", cut(qi), ki)
            index = jnp.sum(jax.nn.relu(dots) * cut(wi)[:, :, None], axis=1)
            ok = chosen(index, rows, s["index_topk"])
        else:
            ok = jnp.arange(t)[None, :] <= rows[:, None]
        score = C.product("qkgd,ukd->kgqu", cut(q), k) * d ** -0.5
        prob = jax.nn.softmax(jnp.where(ok, score, C.NEG_INF), -1)
        return C.product("kgqu,ukd->qkgd", prob, v)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))
    return times(out.reshape(t, -1), w("o_proj"))


def gated(x, gate, up, down, mode: str):
    times = lambda a, b: C.weight_product("ti,io->to", a, b, mode, (1,), (0,))
    return times(jax.nn.silu(times(x, gate)) * times(x, up), down)


def routing(p: Dict, at: str, x, mode: str, top_k):
    """-> ``[t, n_experts]``: the weight each token gives each expert, 0
    for the experts it did not choose."""
    g = jax.nn.softmax(C.weight_product(
        "ti,ie->te", x, p[at + "/router"], mode, (1,), (0,)), -1)
    kth = jnp.sort(g, axis=-1)[:, -top_k][:, None]
    chose = jnp.where(g >= kth, g, 0.0)
    return chose / jnp.sum(chose, -1, keepdims=True)


def experts(p: Dict, at: str, x, mode: str, top_k):
    """Every expert over every token, times the token's weight for it."""
    weight = routing(p, at, x, mode, top_k)

    def add(e, y):
        # one expert after the other: unrolled, the compiler keeps every
        # expert's output alive at once
        w = lambda name: jax.lax.dynamic_index_in_dim(
            p[f"{at}/experts_{name}"], e, 0, keepdims=False)
        out = gated(x, w("gate"), w("up"), w("down"), mode)
        return y + jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1) * out

    return jax.lax.fori_loop(
        0, p[at + "/experts_gate"].shape[0], add, jnp.zeros_like(x))


def block(p: Dict, at: str, h, pos, mode: str, s: Dict):
    x = rms_norm(h, p[f"{at}/attn_norm/scale"], s["eps"])
    h = h + attention(p, at, x, pos, mode, s)
    y = rms_norm(h, p[f"{at}/ffn_norm/scale"], s["eps"])
    return h + experts(p, at + "/ffn", y, mode, s["top_k"])


def hidden(params: Dict, tokens, mode: str = "f32", positions=None, **shape):
    """tokens [t] -> the residual stream after the last block,
    [t, d_model].  ``positions`` [3, t] (temporal, height, width); a
    text's, ``0 .. t-1`` in all three, where None."""
    s = {**SIZES, **shape}
    t = tokens.shape[0]
    pos = jnp.broadcast_to(jnp.arange(t), (3, t)) if positions is None \
        else jnp.asarray(positions)
    h = params["embed/embedding"][tokens].astype(jnp.float32)
    for i in range(n_layers_of(params)):
        h = block(params, f"layer_{i}", h, pos, mode, s)
    return h


def head_logits(params: Dict, h, mode: str = "f32", *, eps=EPS):
    """h [n, d_model] -> [n, vocab]."""
    return C.weight_product(
        "ti,io->to", rms_norm(h, params["final_norm/scale"], eps),
        params["head"], mode, (1,), (0,))


def logits(params: Dict, inputs, input_mask, targets, mode: str = "f32",
           positions=None, **shape):
    """inputs, input_mask [b, le]; targets [b, ld] -> logits [b, ld,
    vocab]: row ``j`` is the distribution ``targets[j]`` was drawn from.
    The sequence is the prompt's valid tokens (from the left) with the
    targets straight after the last of them; ``positions`` [3, b, le + ld]
    are that sequence's, a text's where None."""
    le, ld = inputs.shape[1], targets.shape[1]
    total = le + ld
    eps = shape.get("eps", EPS)

    def row(inp, mask, tgt, pos):
        n = jnp.sum(mask > 0)
        at = jnp.arange(total)
        tokens = jnp.where(
            at < n, inp[jnp.minimum(at, le - 1)],
            jnp.where(at < n + ld, tgt[jnp.clip(at - n, 0, ld - 1)], 0))
        h = hidden(params, tokens, mode, pos, **shape)
        read = jax.lax.dynamic_slice_in_dim(h, n - 1, ld, axis=0)
        return head_logits(params, read, mode, eps=eps)

    return jnp.stack([
        row(inputs[i], input_mask[i], targets[i],
            None if positions is None else positions[:, i])
        for i in range(inputs.shape[0])])


def token_gaps(ref_logits, tokens):
    """By how much each token's logit lies below the best of its
    position, in units of that position's standard deviation of logits.
    ref_logits [l, V], tokens [l] -> [l]."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(ref_logits, axis=-1)
