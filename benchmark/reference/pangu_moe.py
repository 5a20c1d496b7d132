"""Plain reference for openPangu-Ultra-MoE: one full-sequence pass, no
cache, no windows, no absorbed form, no sorting.

A decoder-only model without biases, untied embedding and head.  With ``h``
the residual stream and ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) g``,
every block has sandwich norms:

    h <- h + RMSNorm(Attn(RMSNorm(h; g1)); g2)
    h <- h + RMSNorm(FFN(RMSNorm(h; g3)); g4)

Latent attention, ``x`` the normed input at position ``t``:
``c_q = RMSNorm(W_dq x; g_q)``, ``q_i = W_uq,i c_q = [q_i^n ; q_i^r]``,
``[c ; k^r] = W_dkv x``, ``c <- RMSNorm(c; g_kv)``, the rotary code (theta,
absolute position, element ``j`` paired with ``j + d/2``) on ``q_i^r`` and
on the ONE ``k^r`` all heads share; ``k_i^n = W_uk,i c``, ``v_i = W_uv,i c``;
``score_i(t, u) = s (q_i^n . k_i^n(u) + q_i^r . k^r(u))`` for ``u <= t``
with ``s = (nope + rope) ** -0.5``, softmax over ``u``, ``o_i = sum p
v_i(u)``, output ``W_o [o_1 .. o_H]``.

FFN: the leading layers ``W_down(silu(W_gate x) * W_up x)``; the others,
with ``E`` the same block at the expert width, ``sigma = sigmoid(W_r x)``
over ALL experts, ``T`` the ``TOP_K`` largest, ``w_e = SCALING sigma_e /
sum_{j in T} sigma_j``, and ``y = E_shared(x) + sum_{e in T, e held} w_e
E_e(x)``: the experts held are ``[offset, offset + held)`` with ``held``
read off the weights; what an absent expert would add is left out here as
in the program.  A final RMSNorm, then the head over the ids held.

The prediction module (where the weights have one): ``h'_t = W_p
[RMSNorm(h_t; g_h) ; RMSNorm(Emb(x_{t+1}); g_e)]``, one expert block, the
same final norm and head: row ``t`` predicts token ``t + 2``.

The weights stay in the dtype and in the arrays they were served in (a
second copy would not fit beside them) and are raised to float32 one
product at a time; attention runs in blocks of queries.  Both only bound
memory: every product is float32 at precision ``highest``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as C

THETA, EPS, TOP_K, SCALING, EXPERT_OFFSET = 25600000.0, 1e-5, 8, 2.5, 0
QUERY_BLOCK = 256


def from_served_tree(flat: Dict[str, jax.Array], n_layers: int) -> Dict:
    """The reference reads the served leaves where they lie, by their
    paths; numbers and dtypes as served.  ``n_layers`` is checked."""
    if f"layer_{n_layers - 1}/attn/q_up" not in flat \
            or f"layer_{n_layers}/attn/q_up" in flat:
        raise ValueError(f"the tree does not hold {n_layers} layers")
    return dict(flat)


def n_layers_of(params: Dict) -> int:
    return sum(k.endswith("/attn/q_up") and k.startswith("layer_")
               for k in params)


def rms_norm(x, g, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * g.astype(jnp.float32)


def rotary(x, theta):
    """x [t, ..., d] at positions 0 .. t-1."""
    t, d = x.shape[0], x.shape[-1]
    half = d // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32).reshape(
        (t,) + (1,) * (x.ndim - 1)) * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([
        a * jnp.cos(angle) - b * jnp.sin(angle),
        b * jnp.cos(angle) + a * jnp.sin(angle),
    ], axis=-1)


def attention(p: Dict, at: str, x, mode: str, theta, eps):
    """x [t, d_model] -> [t, d_model]: the expanded form under a causal
    mask."""
    w = lambda name: p[f"{at}/attn/{name}"]
    times = lambda eq, a, b: C.weight_product(eq, a, b, mode, (1,), (0,))
    rank = w("kv_norm/scale").shape[0]
    nope = w("k_up").shape[2]
    c_q = rms_norm(times("ti,io->to", x, w("q_down/kernel")),
                   w("q_norm/scale"), eps)
    q = times("tr,rhd->thd", c_q, w("q_up"))
    q_n, q_r = q[..., :nope], rotary(q[..., nope:], theta)
    down = times("ti,io->to", x, w("kv_down/kernel"))
    c = rms_norm(down[:, :rank], w("kv_norm/scale"), eps)
    k_r = rotary(down[:, rank:], theta)
    k_n = times("ur,rhd->uhd", c, w("k_up"))
    v = times("ur,rhd->uhd", c, w("v_up"))
    t = x.shape[0]
    s = q.shape[-1] ** -0.5
    block = next(n for n in range(min(t, QUERY_BLOCK), 0, -1) if t % n == 0)

    def one_block(start):
        rows = start + jnp.arange(block)
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, 0)
        score = (C.product("qhd,uhd->hqu", take(q_n), k_n)
                 + C.product("qhd,ud->hqu", take(q_r), k_r)) * s
        ok = jnp.arange(t)[None, :] <= rows[:, None]
        prob = jax.nn.softmax(jnp.where(ok[None], score, C.NEG_INF), -1)
        return C.product("hqu,uhd->qhd", prob, v)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))
    return times("ti,io->to", out.reshape(t, -1), w("o_proj/kernel"))


def gated(p: Dict, at: str, x, mode: str):
    times = lambda a, b: C.weight_product(
        "ti,io->to", a, b, mode, (1,), (0,))
    return times(
        jax.nn.silu(times(x, p[at + "/gate/kernel"]))
        * times(x, p[at + "/up/kernel"]), p[at + "/down/kernel"])


def routing(p: Dict, at: str, x, mode: str, top_k, scaling):
    """-> ``[t, n_experts]``: the weight each token gives each expert,
    0 for the experts it did not choose."""
    sigma = jax.nn.sigmoid(C.weight_product(
        "ti,ie->te", x, p[at + "/router"], mode, (1,), (0,)))
    kth = jnp.sort(sigma, axis=-1)[:, -top_k][:, None]
    chosen = jnp.where(sigma >= kth, sigma, 0.0)
    return scaling * chosen / jnp.sum(chosen, -1, keepdims=True)


def experts(p: Dict, at: str, x, mode: str, top_k, scaling, offset):
    """The shared expert and the held experts' part of the routed sum:
    every held expert over every token, times the token's weight for it."""
    weight = routing(p, at, x, mode, top_k, scaling)
    times = lambda a, b: C.weight_product(
        "ti,io->to", a, b, mode, (1,), (0,))
    y = gated(p, at + "/shared", x, mode)
    for e in range(p[at + "/experts_gate"].shape[0]):
        out = times(
            jax.nn.silu(times(x, p[at + "/experts_gate"][e]))
            * times(x, p[at + "/experts_up"][e]), p[at + "/experts_down"][e])
        y = y + weight[:, offset + e, None] * out
    return y


def block(p: Dict, at: str, h, mode: str, theta, eps, top_k, scaling,
          offset):
    norm = lambda x, name: rms_norm(x, p[f"{at}/{name}/scale"], eps)
    a = attention(p, at, norm(h, "attn_norm"), mode, theta, eps)
    h = h + norm(a, "attn_post_norm")
    x = norm(h, "ffn_norm")
    if at + "/ffn/router" in p:
        y = experts(p, at + "/ffn", x, mode, top_k, scaling, offset)
    else:
        y = gated(p, at + "/ffn", x, mode)
    return h + norm(y, "ffn_post_norm")


def hidden(params: Dict, tokens, mode: str = "f32", *, theta=THETA, eps=EPS,
           top_k=TOP_K, scaling=SCALING, offset=EXPERT_OFFSET):
    """tokens [t] -> the residual stream after the last block,
    [t, d_model]."""
    h = params["embed/embedding"][tokens].astype(jnp.float32)
    for i in range(n_layers_of(params)):
        h = block(params, f"layer_{i}", h, mode, theta, eps, top_k, scaling,
                  offset)
    return h


def head_logits(params: Dict, h, mode: str = "f32", *, eps=EPS):
    """h [n, d_model] -> [n, vocab held]."""
    return C.weight_product(
        "ti,io->to", rms_norm(h, params["final_norm/scale"], eps),
        params["head"], mode, (1,), (0,))


def mtp_logits(params: Dict, tokens, mode: str = "f32", *, theta=THETA,
               eps=EPS, top_k=TOP_K, scaling=SCALING, offset=EXPERT_OFFSET):
    """tokens [t] -> [t - 1, vocab]: row ``t`` predicts token ``t + 2``."""
    h = hidden(params, tokens, mode, theta=theta, eps=eps, top_k=top_k,
               scaling=scaling, offset=offset)
    emb = params["embed/embedding"][tokens[1:]].astype(jnp.float32)
    both = jnp.concatenate([
        rms_norm(h[:-1], params["mtp_h_norm/scale"], eps),
        rms_norm(emb, params["mtp_e_norm/scale"], eps)], -1)
    h2 = C.weight_product(
        "ti,io->to", both, params["mtp_proj/kernel"], mode, (1,), (0,))
    h2 = block(params, "mtp_block", h2, mode, theta, eps, top_k, scaling,
               offset)
    return head_logits(params, h2, mode, eps=eps)


def logits(params: Dict, inputs, input_mask, targets, mode: str = "f32",
           **shape):
    """inputs, input_mask [b, le]; targets [b, ld] -> logits [b, ld,
    vocab]: row ``j`` is the distribution ``targets[j]`` was drawn from.
    The sequence is the prompt's valid tokens (from the left) with the
    targets straight after the last of them."""
    le, ld = inputs.shape[1], targets.shape[1]
    total = le + ld
    eps = shape.get("eps", EPS)

    def row(inp, mask, tgt):
        n = jnp.sum(mask > 0)
        at = jnp.arange(total)
        tokens = jnp.where(
            at < n, inp[jnp.minimum(at, le - 1)],
            jnp.where(at < n + ld, tgt[jnp.clip(at - n, 0, ld - 1)], 0))
        h = hidden(params, tokens, mode, **shape)
        read = jax.lax.dynamic_slice_in_dim(h, n - 1, ld, axis=0)
        return head_logits(params, read, mode, eps=eps)

    return jnp.stack([
        row(inputs[i], input_mask[i], targets[i])
        for i in range(inputs.shape[0])])


def token_gaps(ref_logits, tokens):
    """By how much each token's logit lies below the best of its
    position, in units of that position's standard deviation of logits.
    ref_logits [l, V], tokens [l] -> [l]."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(ref_logits, axis=-1)
