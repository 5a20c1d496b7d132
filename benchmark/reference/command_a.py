"""Plain reference for Command A+ (``cohere2_moe``): one full-sequence
pass, no cache, no ring, no windows of prefill, no sorting, no kernel.

A decoder-only model without biases whose head is its embedding.  With
``h`` the residual stream and ``LN(x; g) = (x - mean) / sqrt(var + eps) g``
(no bias), layer ``l`` is a PARALLEL block: one norm, two branches from the
same ``x = LN(h; g_l)``, both added:

    h <- h + Attn_l(x) + Experts_l(x)

Attention: ``q = x Wq`` (``H`` heads of ``d``), ``k = x Wk``, ``v = x Wv``
(``K`` heads of ``d``), no biases, no norms on q or k; query head ``i``
reads key/value head ``i // (H / K)``; ``score_i(t, u) = q_i(t) . k(u) /
sqrt(d)``, softmax over the ``u`` that ``t`` sees, ``o_i = sum p v(u)``,
output ``[o_1 .. o_H] Wo``.
``l % FULL_EVERY < FULL_EVERY - 1`` (sliding attention): q and k are
rotated by INTERLEAVED pairs, ``(x_2j, x_2j+1)`` by the angle ``t theta ** (-2j
/ d)``, and ``t`` sees ``u`` in ``(t - WINDOW, t]``.
``l % FULL_EVERY == FULL_EVERY - 1`` (full attention): no rotation, no
positions at all, and ``t`` sees every ``u <= t``.

Experts, with ``E(x; G, U, D) = (silu(x G) * (x U)) D``: ``s = sigmoid(x
Wr)`` over ALL experts, ``T`` the ``TOP_K`` largest, ``w_e = s_e / sum_{j in
T} s_j`` (no further factor), and

    y = sum_{e in T, e held} w_e E_e(x) + (1 / N_SHARED) sum_j E'_j(x)

the experts held are ``[offset, offset + held)`` with ``held`` read off the
weights; what an absent expert would add is left out here as in the
program.  The ``N_SHARED`` shared experts are served as one matrix of their
joint width: expert ``j`` is columns ``[j f, (j + 1) f)`` of its gate and up
matrices and the same rows of its down matrix, and they are AVERAGED.

After the last layer ``LN(h; g)`` and ``logits = h E^T * LOGIT_SCALE`` over
the rows of the embedding held (tied).

The weights stay in the dtype and in the arrays they were served in (a
second copy would not fit beside them) and are raised to float32 one
product at a time; attention runs a block of queries at a time, and a
sliding layer's block over the stretch of keys it can see.  Both only
bound memory and time: every product is float32 at precision ``highest``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as C

THETA, EPS, TOP_K, EXPERT_OFFSET = 50000.0, 1e-5, 8, 0
WINDOW, FULL_EVERY, HEAD_DIM, N_SHARED, LOGIT_SCALE = 4096, 4, 128, 4, 1.0
QUERY_BLOCK = 64
SIZES = dict(
    theta=THETA, eps=EPS, top_k=TOP_K, offset=EXPERT_OFFSET, window=WINDOW,
    full_every=FULL_EVERY, head_dim=HEAD_DIM, n_shared=N_SHARED,
    logit_scale=LOGIT_SCALE)


def from_served_tree(flat: Dict[str, jax.Array], n_layers: int) -> Dict:
    """The reference reads the served leaves where they lie, by their
    paths; numbers and dtypes as served.  ``n_layers`` is checked."""
    if f"layer_{n_layers - 1}/attn/q_proj/kernel" not in flat \
            or f"layer_{n_layers}/attn/q_proj/kernel" in flat:
        raise ValueError(f"the tree does not hold {n_layers} layers")
    return dict(flat)


def n_layers_of(params: Dict) -> int:
    return sum(k.endswith("/attn/q_proj/kernel") for k in params)


def layer_norm(x, g, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return centred / jnp.sqrt(var + eps) * g.astype(jnp.float32)


def rotary_interleaved(x, theta):
    """x [t, heads, d] at positions 0 .. t-1: the pair ``(x_2j, x_2j+1)``
    turned by ``t theta ** (-2j / d)``."""
    t, d = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * jnp.cos(angle) - odd * jnp.sin(angle))
    return out.at[..., 1::2].set(odd * jnp.cos(angle) + even * jnp.sin(angle))


def attention(p: Dict, at: str, x, mode: str, full: bool, s: Dict):
    """x [t, d_model] -> [t, d_model] under the layer's own mask."""
    w = lambda name: p[f"{at}/attn/{name}/kernel"]
    times = lambda a, b: C.weight_product("ti,io->to", a, b, mode, (1,), (0,))
    t, d = x.shape[0], s["head_dim"]
    q = times(x, w("q_proj")).reshape(t, -1, d)
    k = times(x, w("k_proj")).reshape(t, -1, d)
    v = times(x, w("v_proj")).reshape(t, -1, d)
    if not full:
        q, k = rotary_interleaved(q, s["theta"]), rotary_interleaved(
            k, s["theta"])
    n_kv = k.shape[1]
    q = q.reshape(t, n_kv, -1, d)             # head i = (i // g, i % g)
    block = next(n for n in range(min(t, QUERY_BLOCK), 0, -1) if t % n == 0)
    # The keys a block of queries can see lie in one stretch: all of them
    # under full attention, the window before the block's last otherwise.
    stretch = t if full else min(t, s["window"] + block)

    def one_block(start):
        rows = start + jnp.arange(block)
        first = 0 if full else jnp.clip(
            start + block - stretch, 0, t - stretch)
        keys = first + jnp.arange(stretch)
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, first, stretch, 0)
        score = C.product(
            "qkgd,ukd->kgqu",
            jax.lax.dynamic_slice_in_dim(q, start, block, 0),
            take(k)) * d ** -0.5
        ok = keys[None, :] <= rows[:, None]
        if not full:
            ok = ok & (keys[None, :] > rows[:, None] - s["window"])
        prob = jax.nn.softmax(jnp.where(ok, score, C.NEG_INF), -1)
        return C.product("kgqu,ukd->qkgd", prob, take(v))

    out = jax.lax.map(one_block, jnp.arange(0, t, block))
    return times(out.reshape(t, -1), w("o_proj"))


def gated(x, gate, up, down, mode: str):
    times = lambda a, b: C.weight_product("ti,io->to", a, b, mode, (1,), (0,))
    return times(jax.nn.silu(times(x, gate)) * times(x, up), down)


def routing(p: Dict, at: str, x, mode: str, top_k):
    """-> ``[t, n_experts]``: the weight each token gives each expert,
    0 for the experts it did not choose."""
    sigma = jax.nn.sigmoid(C.weight_product(
        "ti,ie->te", x, p[at + "/router"], mode, (1,), (0,)))
    kth = jnp.sort(sigma, axis=-1)[:, -top_k][:, None]
    chosen = jnp.where(sigma >= kth, sigma, 0.0)
    return chosen / jnp.sum(chosen, -1, keepdims=True)


def shared_experts(p: Dict, at: str, x, mode: str, n_shared):
    """The mean of the shared experts, each a slice of the served
    matrices."""
    gate, up, down = (p[f"{at}/shared/{n}/kernel"]
                      for n in ("gate", "up", "down"))
    f = down.shape[0] // n_shared
    y = 0.0
    for j in range(n_shared):
        cut = slice(j * f, (j + 1) * f)
        y = y + gated(x, gate[:, cut], up[:, cut], down[cut], mode)
    return y / n_shared


def experts(p: Dict, at: str, x, mode: str, s: Dict):
    """The shared experts' mean and the held experts' part of the routed
    sum: every held expert over every token, times the token's weight
    for it."""
    weight = routing(p, at, x, mode, s["top_k"])
    held = p[at + "/experts_gate"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(weight, s["offset"], held, axis=1)

    def add(e, y):
        # one expert after the other: unrolled, the compiler keeps every
        # expert's output alive at once (18 GB at 18,432 positions)
        w = lambda name: jax.lax.dynamic_index_in_dim(
            p[f"{at}/experts_{name}"], e, 0, keepdims=False)
        out = gated(x, w("gate"), w("up"), w("down"), mode)
        return y + jax.lax.dynamic_slice_in_dim(mine, e, 1, axis=1) * out

    return jax.lax.fori_loop(
        0, held, add, shared_experts(p, at, x, mode, s["n_shared"]))


def block(p: Dict, at: str, h, mode: str, full: bool, s: Dict):
    x = layer_norm(h, p[f"{at}/norm/scale"], s["eps"])
    return h + attention(p, at, x, mode, full, s) \
        + experts(p, at + "/ffn", x, mode, s)


def hidden(params: Dict, tokens, mode: str = "f32", **shape):
    """tokens [t] -> the residual stream after the last block,
    [t, d_model]."""
    s = {**SIZES, **shape}
    h = params["embed/embedding"][tokens].astype(jnp.float32)
    for i in range(n_layers_of(params)):
        full = i % s["full_every"] == s["full_every"] - 1
        h = block(params, f"layer_{i}", h, mode, full, s)
    return h


def head_logits(params: Dict, h, mode: str = "f32", **shape):
    """h [n, d_model] -> [n, vocab held]: the embedding's own rows."""
    s = {**SIZES, **shape}
    return s["logit_scale"] * C.weight_product(
        "ti,oi->to", layer_norm(h, params["final_norm/scale"], s["eps"]),
        params["embed/embedding"], mode, (1,), (1,))


def logits(params: Dict, inputs, input_mask, targets, mode: str = "f32",
           **shape):
    """inputs, input_mask [b, le]; targets [b, ld] -> logits [b, ld,
    vocab]: row ``j`` is the distribution ``targets[j]`` was drawn from.
    The sequence is the prompt's valid tokens (from the left) with the
    targets straight after the last of them."""
    le, ld = inputs.shape[1], targets.shape[1]
    total = le + ld

    def row(inp, mask, tgt):
        n = jnp.sum(mask > 0)
        at = jnp.arange(total)
        tokens = jnp.where(
            at < n, inp[jnp.minimum(at, le - 1)],
            jnp.where(at < n + ld, tgt[jnp.clip(at - n, 0, ld - 1)], 0))
        h = hidden(params, tokens, mode, **shape)
        read = jax.lax.dynamic_slice_in_dim(h, n - 1, ld, axis=0)
        return head_logits(params, read, mode, **shape)

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
