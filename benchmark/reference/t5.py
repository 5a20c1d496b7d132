"""Plain reference for the T5 encoder-decoder: one teacher-forced pass.

Follows Raffel et al. 2020 (pre-norm RMSNorm blocks, bucketed relative
position bias shared by the self-attention layers of each stack, tied
embedding scaled by ``d_model ** -0.5`` at the logits).  Departures, each
shared with the program so that the two compute the same function: the MLP
applies GELU (tanh form) where T5 v1.0 has ReLU, every projection carries a
bias, attention scores are scaled by ``head_dim ** -0.5``.

The check on a served model: for a prompt and the tokens the engine served,
``token_gaps`` gives, at every position, by how much the served token's
logit lies below this pass's best.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C

_ATTN = {"query": "q", "key": "k", "value": "v", "out": "o"}


def _stack_names(stack: str, cross: bool) -> Dict[str, str]:
    names = {}
    for block in ("attn", "cross") if cross else ("attn",):
        for proj, short in _ATTN.items():
            names[f"{block}/{proj}/kernel"] = f"layers.{stack}.{block}_{short}_w"
            names[f"{block}/{proj}/bias"] = f"layers.{stack}.{block}_{short}_b"
        names[f"{block}_norm/scale"] = f"layers.{stack}.{block}_norm"
    names["mlp/wi/kernel"] = f"layers.{stack}.wi_w"
    names["mlp/wi/bias"] = f"layers.{stack}.wi_b"
    names["mlp/wo/kernel"] = f"layers.{stack}.wo_w"
    names["mlp/wo/bias"] = f"layers.{stack}.wo_b"
    names["mlp_norm/scale"] = f"layers.{stack}.mlp_norm"
    return names


def from_served_tree(flat: Dict[str, jax.Array], n_layers: int) -> Dict:
    """The reference's layout from ``{leaf path: array}`` of the weights
    the benchmark made for the program."""
    out = {
        "embedding": flat["shared/embedding"],
        "enc.rel": flat["encoder/rel_pos/rel_embedding"],
        "dec.rel": flat["decoder/rel_pos/rel_embedding"],
        "enc.final_norm": flat["encoder/final_norm/scale"],
        "dec.final_norm": flat["decoder/final_norm/scale"],
    }
    for stack, long in (("enc", "encoder"), ("dec", "decoder")):
        for path, name in _stack_names(stack, stack == "dec").items():
            out[name] = jnp.stack([
                flat[f"{long}/layer_{i}/{path}"] for i in range(n_layers)
            ])
    return {k: v.astype(jnp.float32) for k, v in out.items()}


def relative_buckets(qlen: int, klen: int, *, bidirectional: bool,
                     num_buckets: int = 32, max_distance: int = 128):
    """T5's log-spaced buckets of ``key position - query position``."""
    rel = np.arange(klen)[None, :] - np.arange(qlen)[:, None]
    n = num_buckets
    buckets = np.zeros_like(rel)
    if bidirectional:
        n //= 2
        buckets += (rel > 0).astype(np.int64) * n
        rel = np.abs(rel)
    else:
        rel = -np.minimum(rel, 0)
    max_exact = n // 2
    large = max_exact + (
        np.log(np.maximum(rel, 1) / max_exact)
        / np.log(max_distance / max_exact) * (n - max_exact)
    ).astype(np.int64)
    large = np.minimum(large, n - 1)
    return buckets + np.where(rel < max_exact, rel, large)


def _bias(table, qlen, klen, bidirectional):
    b = relative_buckets(qlen, klen, bidirectional=bidirectional,
                         num_buckets=table.shape[0])
    return jnp.transpose(table[b], (2, 0, 1))[None]       # [1, h, q, k]


def _attend(p, stack, block, x_q, x_kv, mode, **kw):
    proj = lambda x, s: C.weight_product(
        "bld,dhk->blhk", x, p[f"layers.{stack}.{block}_{s}_w"], mode,
        (2,), (0,)) + p[f"layers.{stack}.{block}_{s}_b"]
    a = C.attention(proj(x_q, "q"), proj(x_kv, "k"), proj(x_kv, "v"), **kw)
    return C.weight_product(
        "blhk,hkd->bld", a, p[f"layers.{stack}.{block}_o_w"], mode,
        (2, 3), (0, 1)) + p[f"layers.{stack}.{block}_o_b"]


def _mlp(p, stack, x, mode):
    h = C.gelu_tanh(C.weight_product(
        "bld,df->blf", x, p[f"layers.{stack}.wi_w"], mode, (2,), (0,)
    ) + p[f"layers.{stack}.wi_b"])
    return C.weight_product(
        "blf,fd->bld", h, p[f"layers.{stack}.wo_w"], mode, (2,), (0,)
    ) + p[f"layers.{stack}.wo_b"]


def _layers(params, stack):
    prefix = f"layers.{stack}."
    return {k: v for k, v in params.items() if k.startswith(prefix)}


def logits(params: Dict, inputs, input_mask, targets, mode: str = "f32"):
    """inputs, input_mask [b, le]; targets [b, ld] -> logits [b, ld, V].
    The decoder reads ``targets`` shifted right behind a BOS of 0."""
    emb = params["embedding"]
    x = emb[inputs]
    enc_bias = _bias(params["enc.rel"], x.shape[1], x.shape[1], True)

    def enc_layer(x, p):
        h = C.rms_norm(x, p["layers.enc.attn_norm"])
        x = x + _attend(p, "enc", "attn", h, h, mode,
                        key_mask=input_mask, bias=enc_bias)
        h = C.rms_norm(x, p["layers.enc.mlp_norm"])
        return x + _mlp(p, "enc", h, mode), None

    x, _ = jax.lax.scan(enc_layer, x, _layers(params, "enc"))
    encoded = C.rms_norm(x, params["enc.final_norm"])

    dec_in = jnp.pad(targets, ((0, 0), (1, 0)))[:, :-1]
    y = emb[dec_in]
    dec_bias = _bias(params["dec.rel"], y.shape[1], y.shape[1], False)

    def dec_layer(y, p):
        h = C.rms_norm(y, p["layers.dec.attn_norm"])
        y = y + _attend(p, "dec", "attn", h, h, mode,
                        bias=dec_bias, causal=True)
        h = C.rms_norm(y, p["layers.dec.cross_norm"])
        y = y + _attend(p, "dec", "cross", h, encoded, mode,
                        key_mask=input_mask)
        h = C.rms_norm(y, p["layers.dec.mlp_norm"])
        return y + _mlp(p, "dec", h, mode), None

    y, _ = jax.lax.scan(dec_layer, y, _layers(params, "dec"))
    y = C.rms_norm(y, params["dec.final_norm"]) * (emb.shape[1] ** -0.5)
    return C.product("bld,vd->blv", y, emb)


def token_gaps(ref_logits, tokens):
    """By how much each token's logit lies below the best of its
    position, in units of that position's standard deviation of logits.
    ref_logits [l, V], tokens [l] -> [l]."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(ref_logits, axis=-1)
