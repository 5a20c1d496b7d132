"""Plain reference for EvaByte: one full-sequence pass, no cache.

A byte-level decoder-only model of pre-norm residual blocks without
biases.  With ``h`` the residual stream, ``s = head_dim ** -0.5``, chunks
``C_j = {c j .. c j + c - 1}`` and windows ``W_m = {w m .. w m + w - 1}``:

    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)
    h <- h + Attn(RMSNorm(h));  h <- h + W_down(silu(W_gate x) * W_up x)

Attention per head: q, k, v from three projections, the rotary code
(theta, absolute position, element ``i`` paired with ``i + d/2``) on q and
k.  Every chunk has a summary ``a_i = softmax_{i in C_j}(s phi.k_i)``,
``k~_j = sum a_i k_i + mu``, ``v~_j = sum a_i v_i`` (keys after the rotary
code).  Query ``t`` in ``W_m`` attends, under one softmax over
``s q_t.key``, to the exact pairs ``{i in W_m, i <= t}`` and to the
summaries ``{j : C_j in W_m', m' < m}``: ``visible`` below is that
sentence and nothing else, applied to ALL keys and ALL summaries of the
sequence.  A final RMSNorm, then ``num_pred_heads`` heads of ``vocab``
columns each, head ``p`` in columns ``[p vocab, (p + 1) vocab)``
predicting byte ``t + 1 + p``.

The weights stay in the dtype they were served in and are raised to
float32 one layer at a time inside the scan over layers; attention runs in
blocks of queries.  Both only bound memory: every product is float32 at
precision ``highest``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmark.reference import common as C

WINDOW, CHUNK, THETA, EPS = 2048, 16, 100000.0, 1e-5
QUERY_BLOCK = 256

_LAYER_LEAVES = {
    "attn/q_proj/kernel": "q_w", "attn/k_proj/kernel": "k_w",
    "attn/v_proj/kernel": "v_w", "attn/o_proj/kernel": "o_w",
    "attn/phi": "phi", "attn/mu": "mu",
    "attn_norm/scale": "attn_norm", "mlp_norm/scale": "mlp_norm",
    "mlp/gate/kernel": "gate_w", "mlp/up/kernel": "up_w",
    "mlp/down/kernel": "down_w",
}


def from_served_tree(flat: Dict[str, jax.Array], n_layers: int) -> Dict:
    """The reference's layout from ``{leaf path: array}`` of the weights
    the benchmark made for the program; numbers and dtypes as served."""
    out = {
        "embedding": flat["embed/embedding"],
        "final_norm": flat["final_norm/scale"],
        "head": flat["head/kernel"],
    }
    # One program per stack: stacked eagerly, every layer's array would be
    # copied once more on its way in, and 13 GB of weights is all a chip
    # has room for beside the originals.
    stack = jax.jit(lambda *layers: jnp.stack(layers))
    for path, name in _LAYER_LEAVES.items():
        out["layers." + name] = stack(
            *(flat[f"layer_{i}/{path}"] for i in range(n_layers)))
    return out


def visible(t, n_keys: int, window: int, chunk: int):
    """Who query position(s) ``t`` [n] see(s): exact keys [n, n_keys] and
    chunk summaries [n, n_keys // chunk]."""
    t = jnp.asarray(t)[:, None]
    i = jnp.arange(n_keys)[None, :]
    exact = (i // window == t // window) & (i <= t)
    j = jnp.arange(n_keys // chunk)[None, :]
    summary = (j * chunk) // window < t // window
    return exact, summary


def rms_norm(x, g, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * (1.0 + g.astype(jnp.float32))


def rotary(x, theta):
    """x [t, h, d] at positions 0 .. t-1."""
    t, _, d = x.shape
    half = d // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([
        a * jnp.cos(angle) - b * jnp.sin(angle),
        b * jnp.cos(angle) + a * jnp.sin(angle),
    ], axis=-1)


def _attention(q, k, v, phi, mu, window, chunk):
    """q, k, v [t, h, d] -> [t, h, d]."""
    t, h, d = q.shape
    s = d ** -0.5
    kc = k.reshape(t // chunk, chunk, h, d)
    vc = v.reshape(t // chunk, chunk, h, d)
    a = jax.nn.softmax(C.product("jchd,hd->jch", kc, phi) * s, axis=1)
    k_sum = jnp.sum(a[..., None] * kc, axis=1) + mu.astype(jnp.float32)
    v_sum = jnp.sum(a[..., None] * vc, axis=1)
    block = next(
        n for n in range(min(t, QUERY_BLOCK), 0, -1) if t % n == 0)

    def one_block(start):
        rows = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        exact_ok, summary_ok = visible(rows, t, window, chunk)
        scores = jnp.concatenate([
            jnp.where(exact_ok[None], C.product("qhd,khd->hqk", qb, k) * s,
                      C.NEG_INF),
            jnp.where(summary_ok[None],
                      C.product("qhd,jhd->hqj", qb, k_sum) * s, C.NEG_INF),
        ], axis=-1)
        p = jax.nn.softmax(scores, axis=-1)
        return C.product("hqk,khd->qhd", p[..., :t], v) \
            + C.product("hqj,jhd->qhd", p[..., t:], v_sum)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))
    return out.reshape(t, h, d)


def hidden(params: Dict, tokens, mode: str = "f32", *, window=WINDOW,
           chunk=CHUNK, theta=THETA, eps=EPS):
    """tokens [t] (t a multiple of ``chunk``) -> the residual stream after
    the last block, [t, d_model]."""
    h = params["embedding"][tokens].astype(jnp.float32)
    heads, head_dim = params["layers.phi"].shape[1:]
    split = lambda x: x.reshape(x.shape[0], heads, head_dim)
    times = lambda x, w: C.weight_product(
        "ti,io->to", x, w, mode, (1,), (0,))

    def layer(h, p):
        x = rms_norm(h, p["layers.attn_norm"], eps)
        q = rotary(split(times(x, p["layers.q_w"])), theta)
        k = rotary(split(times(x, p["layers.k_w"])), theta)
        v = split(times(x, p["layers.v_w"]))
        a = _attention(q, k, v, p["layers.phi"].astype(jnp.float32),
                       p["layers.mu"], window, chunk)
        h = h + times(a.reshape(a.shape[0], -1), p["layers.o_w"])
        x = rms_norm(h, p["layers.mlp_norm"], eps)
        gated = jax.nn.silu(times(x, p["layers.gate_w"])) \
            * times(x, p["layers.up_w"])
        return h + times(gated, p["layers.down_w"]), None

    layers = {k: v for k, v in params.items() if k.startswith("layers.")}
    h, _ = jax.lax.scan(layer, h, layers)
    return h


def head_logits(params: Dict, h, mode: str = "f32", *, eps=EPS):
    """h [n, d_model] -> logits of every head, [n, heads * vocab]."""
    return C.weight_product(
        "ti,io->to", rms_norm(h, params["final_norm"], eps),
        params["head"], mode, (1,), (0,))


def logits(params: Dict, inputs, input_mask, targets, mode: str = "f32",
           **shape):
    """inputs, input_mask [b, le]; targets [b, ld] -> head 0's logits
    [b, ld, vocab]: row ``j`` is the distribution ``targets[j]`` was drawn
    from.  The sequence is the prompt's valid bytes (from the left) with
    the targets straight after the last of them."""
    le, ld = inputs.shape[1], targets.shape[1]
    chunk = shape.get("chunk", CHUNK)
    total = -(-(le + ld) // chunk) * chunk
    vocab = params["embedding"].shape[0]
    eps = shape.get("eps", EPS)

    def row(inp, mask, tgt):
        n = jnp.sum(mask > 0)
        at = jnp.arange(total)
        tokens = jnp.where(
            at < n, inp[jnp.minimum(at, le - 1)],
            jnp.where(at < n + ld, tgt[jnp.clip(at - n, 0, ld - 1)], 0))
        h = hidden(params, tokens, mode, **shape)
        read = jax.lax.dynamic_slice_in_dim(h, n - 1, ld, axis=0)
        return head_logits(params, read, mode, eps=eps)[:, :vocab]

    return jax.vmap(row)(inputs, input_mask, targets)


def token_gaps(ref_logits, tokens):
    """By how much each token's logit lies below the best of its
    position, in units of that position's standard deviation of logits.
    ref_logits [l, V], tokens [l] -> [l]."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(ref_logits, axis=-1)
