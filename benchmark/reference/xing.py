"""Plain reference for Xing4.0-29B-A4B: one full-sequence pass, no cache,
no windows, no absorbed form, no sorting.

A decoder-only model without biases, untied embedding and head, whose
residual path is ``n`` streams a token, ``X [n, C]`` float32 (``n`` read
off the weights): ``X_0`` is the token's embedding in every row.  With
``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) g``, every sub-layer ``F``
(latent attention, then the dense MLP or the expert layer) has its own
``phi [n C, 2n + n^2]`` (columns: pre, post, res row by row), ``b_pre,
b_post [n]``, ``b_res [n, n]``, three scalars and a gain ``g``:

    x^     = flatten(X) / sqrt(mean(flatten(X)^2) + HC_EPS)
    H_pre  = sigmoid(a_pre x^ phi_pre + b_pre)
    H_post = 2 sigmoid(a_post x^ phi_post + b_post)
    M      = exp(clip(a_res mat(x^ phi_res) + b_res, CLAMP))
    HC_ITERS times: M <- M / (sum_j M_ij + HC_EPS), then
                    M <- M / (sum_i M_ij + HC_EPS);  H_res = M
    u      = sum_i H_pre[i] X[i]
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] F(RMSNorm(u; g))

After the last block ``h = sum_i X[i]``, a final RMSNorm, the head.  No
norm behind a sub-layer.

Latent attention as in reference/pangu_moe.py (normed ``c_q`` and ``c``,
one rotary key for all heads, element ``j`` paired with ``j + d/2``), with
YaRN: ``f_i = theta^(-2i/d)``, ``lo, hi = floor, ceil of d ln(L / (2 pi
beta)) / (2 ln theta)`` at ``beta_fast`` and ``beta_slow`` (held inside
``[0, d - 1]``), ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)``, ``inv_i =
f_i / s ramp_i + f_i (1 - ramp_i)``; cos and sin times ``m(s, mscale) /
m(s, mscale_all_dim)`` with ``m(s, k) = 0.1 k ln s + 1``; the softmax scale
is ``(nope + rope)^(-1/2) m(s, mscale_all_dim)^2``.

Experts: ``sigma = sigmoid(W_r x)`` over ALL experts, ``T`` the ``TOP_K``
largest of ``sigma + e_score_correction_bias``, ``w_e = SCALING sigma_e /
sum_{j in T} sigma_j`` (the bias chooses and does not weigh), ``y =
E_shared(x) + sum_{e in T, e held} w_e E_e(x)``; the experts held are
``[offset, offset + held)`` with ``held`` read off the weights.

The prediction module (where the weights have one): ``h'_t = W_m
[RMSNorm(h_t; g_h) ; RMSNorm(Emb(x_{t+1}); g_e)]`` with ``h`` the merged
stream before the final norm, copied into ``n`` streams, one expert block,
merged, the same final norm and head: row ``t`` predicts token ``t + 2``.

The weights stay in the dtype and in the arrays they were served in and
are raised to float32 one product at a time (the experts one at a time,
under a loop); attention runs in blocks of queries.  Both only bound
memory: every product is float32 at precision ``highest``.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as C

THETA, EPS, TOP_K, SCALING, EXPERT_OFFSET = 10000.0, 1e-6, 4, 2.0, 0
YARN = dict(factor=64.0, original=4096, beta_fast=32.0, beta_slow=1.0,
            mscale=1.0, mscale_all_dim=1.0)
HC_ITERS, HC_EPS, CLAMP = 20, 1e-6, (-30.0, 30.0)
QUERY_BLOCK = 256
# What ``logits`` and its kin take as keywords, with the published values.
SHAPE = dict(theta=THETA, eps=EPS, top_k=TOP_K, scaling=SCALING,
             offset=EXPERT_OFFSET, yarn=YARN, hc_iters=HC_ITERS,
             hc_eps=HC_EPS, clamp=CLAMP)


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
    y = x / jnp.sqrt(ms + eps)
    return y if g is None else y * g.astype(jnp.float32)


def mscale(s, k):
    return 0.1 * k * math.log(s) + 1.0 if s > 1 else 1.0


def inverse_frequencies(d, theta, yarn):
    """[d/2]: plain where ``yarn`` is None."""
    f = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    if yarn is None:
        return f
    at = lambda beta: d * math.log(
        yarn["original"] / (2 * math.pi * beta)) / (2 * math.log(theta))
    lo = max(math.floor(at(yarn["beta_fast"])), 0)
    hi = min(math.ceil(at(yarn["beta_slow"])), d - 1)
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - lo)
        / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    return f / yarn["factor"] * ramp + f * (1.0 - ramp)


def rotary(x, theta, yarn):
    """x [t, ..., d] at positions 0 .. t-1."""
    t, d = x.shape[0], x.shape[-1]
    half = d // 2
    angle = jnp.arange(t, dtype=jnp.float32).reshape(
        (t,) + (1,) * (x.ndim - 1)) * inverse_frequencies(d, theta, yarn)
    gain = 1.0 if yarn is None else mscale(
        yarn["factor"], yarn["mscale"]) / mscale(
        yarn["factor"], yarn["mscale_all_dim"])
    a, b = x[..., :half], x[..., half:]
    return gain * jnp.concatenate([
        a * jnp.cos(angle) - b * jnp.sin(angle),
        b * jnp.cos(angle) + a * jnp.sin(angle),
    ], axis=-1)


def attention(p: Dict, at: str, x, mode: str, s: Dict):
    """x [t, d_model] -> [t, d_model]: the expanded form under a causal
    mask."""
    w = lambda name: p[f"{at}/attn/{name}"]
    times = lambda eq, a, b: C.weight_product(eq, a, b, mode, (1,), (0,))
    theta, eps, yarn = s["theta"], s["eps"], s["yarn"]
    rank = w("kv_norm/scale").shape[0]
    nope = w("k_up").shape[2]
    c_q = rms_norm(times("ti,io->to", x, w("q_down/kernel")),
                   w("q_norm/scale"), eps)
    q = times("tr,rhd->thd", c_q, w("q_up"))
    q_n, q_r = q[..., :nope], rotary(q[..., nope:], theta, yarn)
    down = times("ti,io->to", x, w("kv_down/kernel"))
    c = rms_norm(down[:, :rank], w("kv_norm/scale"), eps)
    k_r = rotary(down[:, rank:], theta, yarn)
    k_n = times("ur,rhd->uhd", c, w("k_up"))
    v = times("ur,rhd->uhd", c, w("v_up"))
    t = x.shape[0]
    scale = q.shape[-1] ** -0.5
    if yarn is not None:
        scale = scale * mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    block = next(n for n in range(min(t, QUERY_BLOCK), 0, -1) if t % n == 0)

    def one_block(start):
        rows = start + jnp.arange(block)
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, 0)
        score = (C.product("qhd,uhd->hqu", take(q_n), k_n)
                 + C.product("qhd,ud->hqu", take(q_r), k_r)) * scale
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
    choose = sigma + p[at + "/e_score_correction_bias"].astype(jnp.float32)
    kth = jnp.sort(choose, axis=-1)[:, -top_k][:, None]
    chosen = jnp.where(choose >= kth, sigma, 0.0)
    return scaling * chosen / jnp.sum(chosen, -1, keepdims=True)


def experts(p: Dict, at: str, x, mode: str, top_k, scaling, offset):
    """The shared expert and the held experts' part of the routed sum:
    every held expert over every token, times the token's weight for it."""
    weight = routing(p, at, x, mode, top_k, scaling)
    times = lambda a, b: C.weight_product(
        "ti,io->to", a, b, mode, (1,), (0,))
    kernels = tuple(
        p[f"{at}/experts_{name}"] for name in ("gate", "up", "down"))

    def add(y, e):
        gate, up, down = (k[e] for k in kernels)
        out = times(jax.nn.silu(times(x, gate)) * times(x, up), down)
        mine = jax.lax.dynamic_slice_in_dim(weight, offset + e, 1, axis=1)
        return y + mine * out, None

    return jax.lax.scan(
        add, gated(p, at + "/shared", x, mode),
        jnp.arange(kernels[0].shape[0]))[0]


def mixing(p: Dict, at: str, x, s: Dict):
    """x [t, n, C] -> ``H_pre`` [t, n], ``H_post`` [t, n], ``H_res``
    [t, n, n]."""
    n = x.shape[1]
    leaf = lambda name: p[f"{at}/{name}"].astype(jnp.float32)
    z = C.product(
        "tk,kj->tj", rms_norm(x.reshape(x.shape[0], -1), None, s["hc_eps"]),
        leaf("phi"))
    pre = jax.nn.sigmoid(leaf("pre_alpha") * z[:, :n] + leaf("b_pre"))
    post = 2.0 * jax.nn.sigmoid(
        leaf("post_alpha") * z[:, n:2 * n] + leaf("b_post"))
    m = jnp.exp(jnp.clip(
        leaf("res_alpha") * z[:, 2 * n:].reshape(-1, n, n) + leaf("b_res"),
        *s["clamp"]))
    for _ in range(s["hc_iters"]):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + s["hc_eps"])
        m = m / (jnp.sum(m, axis=1, keepdims=True) + s["hc_eps"])
    return pre, post, m


def sub_layer(p: Dict, at: str, which: str, x, s: Dict, f):
    """One sub-layer around ``f``: x [t, n, C] -> [t, n, C]."""
    pre, post, res = mixing(p, f"{at}/{which}_mix", x, s)
    u = jnp.sum(pre[:, :, None] * x, axis=1)
    y = f(rms_norm(u, p[f"{at}/{which}_norm/scale"], s["eps"]))
    return jnp.sum(res[:, :, :, None] * x[:, None, :, :], axis=2) \
        + post[:, :, None] * y[:, None, :]


def block(p: Dict, at: str, x, mode: str, s: Dict):
    x = sub_layer(p, at, "attn", x, s,
                  lambda u: attention(p, at, u, mode, s))
    if at + "/ffn/router" in p:
        ffn = lambda u: experts(
            p, at + "/ffn", u, mode, s["top_k"], s["scaling"], s["offset"])
    else:
        ffn = lambda u: gated(p, at + "/ffn", u, mode)
    return sub_layer(p, at, "ffn", x, s, ffn)


def streams(p: Dict, h):
    """h [t, C] -> ``X_0`` [t, n, C]."""
    n = p["layer_0/attn_mix/b_pre"].shape[0]
    return jnp.broadcast_to(h[:, None, :], (h.shape[0], n, h.shape[1]))


def hidden(params: Dict, tokens, mode: str = "f32", **shape):
    """tokens [t] -> the streams' sum after the last block, [t, d_model]."""
    s = {**SHAPE, **shape}
    x = streams(
        params, params["embed/embedding"][tokens].astype(jnp.float32))
    for i in range(n_layers_of(params)):
        x = block(params, f"layer_{i}", x, mode, s)
    return jnp.sum(x, axis=1)


def head_logits(params: Dict, h, mode: str = "f32", *, eps=EPS):
    """h [n, d_model] -> [n, vocab held]."""
    return C.weight_product(
        "ti,io->to", rms_norm(h, params["final_norm/scale"], eps),
        params["head"], mode, (1,), (0,))


def mtp_logits(params: Dict, tokens, mode: str = "f32", **shape):
    """tokens [t] -> [t - 1, vocab]: row ``t`` predicts token ``t + 2``."""
    s = {**SHAPE, **shape}
    h = hidden(params, tokens, mode, **shape)
    emb = params["embed/embedding"][tokens[1:]].astype(jnp.float32)
    both = jnp.concatenate([
        rms_norm(h[:-1], params["mtp_h_norm/scale"], s["eps"]),
        rms_norm(emb, params["mtp_e_norm/scale"], s["eps"])], -1)
    h2 = C.weight_product(
        "ti,io->to", both, params["mtp_proj/kernel"], mode, (1,), (0,))
    x2 = block(params, "mtp_block", streams(params, h2), mode, s)
    return head_logits(params, jnp.sum(x2, axis=1), mode, eps=s["eps"])


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
