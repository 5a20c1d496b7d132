"""Keye-VL-2.0-30B-A3B's language model (``KeyeVL2``): grouped-query
attention over the positions a learned indexer selects, and softmax-routed
experts without a shared one.

Every layer is alike.  For a stream ``h`` (float32), a query at sequence
index ``t`` and the keys ``s <= t`` of its row, with ``RMSNorm(x; g) = x /
sqrt(mean(x^2) + eps) g``:

    x  = RMSNorm(h)
    q  = x Wq -> n_heads x head_dim;  k = x Wk, v = x Wv -> n_kv_heads x
         head_dim; q and k each RMS-normed over head_dim with a gain, then
         rotated by split halves: element j with j + head_dim / 2 by the
         angle p_c(j) * theta^(-2j / head_dim), where the position is
         p = (temporal, height, width) and c(j) is 0, 1, 2 over
         ``mrope_section`` contiguous pairs each (text: p_0 = p_1 = p_2 = t)
    qI = x WqI -> index_heads x index_dim;  kI = LayerNorm(x WkI) (a gain,
         no bias) -> index_dim;  qI and kI rotated over their index_dim
         numbers by the temporal position with the same theta;
         w = x Ww * index_heads^-0.5 * index_dim^-0.5 -> index_heads
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])              float32
    S_t = the min(index_topk, t + 1) positions s <= t with the largest
          I[t, s]; equal scores go to the lower position
    a  = concat_i softmax_{s in S_t}(q_i . k_{i // g, s} / sqrt(head_dim))
         v_{i // g, s}  Wo,  g = n_heads / n_kv_heads
    h <- h + a;  y = RMSNorm(h)
    g  = softmax(y Wr) over ALL experts;  T = its experts_per_token
         largest, weights g_e / sum_T g;  expert e: (silu(y G_e) * (y U_e))
         D_e;  no shared expert
    h <- h + sum_{e in T} weight_e expert_e(y)

After the last layer an RMSNorm and an untied head.  A row no deeper than
``index_topk`` attends over everything; past that over ``index_topk`` of
its positions, chosen anew at every layer and every token.  The stream, the
norms, both kinds of scores, the softmaxes, the router and the logits are
float32, the matrix products bfloat16 with float32 accumulation, and what
is cached is bfloat16.  The expert layer is ``RoutedExperts`` of
models/pangu_moe.py (``scoring_func`` softmax, ``n_shared_experts`` 0).

Two kinds of cache that live at the same positions, both by position,
written by every step where they lie: ``kv``, keys (normed, rotated) and
values ``[slots, positions, n_kv_heads * head_dim]``, and ``index``, the
index keys ``[slots, positions, index_dim]``.  A position's key/value heads
lie side by side so that a selected position is ONE contiguous fetch; with
the heads before the positions (models/command_a.py, whose step reads whole
blocks of one head) the chip's compiler copies the whole array
heads-innermost before every gather and back behind it.

The selection is exact everywhere (no approximate top-k).
``decode_step`` scores a row's index keys, takes the ``index_topk``
largest (``jax.lax.top_k``: equal scores by the lower position), FETCHES
ONLY THOSE entries of ``kv`` (a gather by position) and attends over the
gathered entries through ``ops/flash_attention.py
grouped_decode_attention``, to the depth ``min(t + 1, index_topk)``: what
it reads of ``kv`` does not grow with the row.  ``prefill_window`` and
``__call__`` give every query its own set as a threshold: the score of the
``min(index_topk, t + 1)``-th largest of its row is found by bisection over
the scores' bit patterns (``kth_largest``: 32 counts, no sort), and scores
equal to it are taken in order of position while there is room.  The mask,
that count and the attention are ONE kernel (``ops/flash_attention.py
selected_attention``) over the row's keys and values where they lie: it
builds the mask of a block of 512 x 512 from the order keys and the
threshold, carries the count of equal keys from block to block (and counts
only where a query has more of them than room), keeps scores and
statistics on the chip, and visits no block past the window's last
position.  ``selected`` states the same rule over a whole row at once.  A
window's index scores and the threshold's counts still run over the whole
row whatever its place in it, in plain XLA.

Device operations carry ``dsa.index`` (the indexer's projections inside
``attention_proj``, its scores inside ``attention_core``), ``dsa.select``
(the top-k, or the threshold; the window's mask is the kernel's) and
``dsa.gather`` (the fetch by position), both inside ``attention_core``; the
index key's write lies in ``cache_write``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpu_pipelines.models.command_a import LayerNorm
from tpu_pipelines.models.decode_contract import CacheKind, DecodeContract
from tpu_pipelines.models.pangu_moe import (
    RMSNorm, RoutedExperts, config_from, tally_account)
from tpu_pipelines.ops.flash_attention import (
    grouped_decode_attention, grouped_decode_block, selected_attention,
    selected_block, selected_blocks, selected_last_block)


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    """The widths and counts of one model, as every module reads them.
    The defaults are Keye-VL-2.0-30B-A3B's language model as published,
    with every expert held."""

    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    d_expert: int = 768
    n_experts: int = 128
    experts_held: int = 128
    expert_offset: int = 0
    experts_per_token: int = 8
    n_shared_experts: int = 0
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    rope_theta: float = 10000000.0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16


# Keys a block of a window's index scores holds: the heads' float32
# products are window x heads x this (34 MB at 512 x 16 x 1,024).
KEY_BLOCK = 1024


def rope_sections(x, pos, theta: float, sections=None):
    """Rotary position code in float32 by split halves, element ``j`` with
    ``j + d / 2``.  x [b, l, ..., d]; pos [3, b, l] (temporal, height,
    width): pair ``j`` turns by the component that ``sections`` (pairs a
    component, contiguous) gives it; every pair by the temporal one where
    ``sections`` is None."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    which = np.repeat(np.arange(len(sections or (half,))), sections or (half,))
    ang = jnp.moveaxis(pos.astype(jnp.float32), 0, -1)[..., which] * inv
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def three(pos):
    """Positions as the rotary code takes them, ``[3, b, l]``: a text's one
    number a token stands for all three components."""
    pos = jnp.asarray(pos)
    return pos if pos.ndim == 3 else jnp.broadcast_to(pos, (3,) + pos.shape)


def sortable(x):
    """float32 -> uint32 whose unsigned order is the numbers' own, both
    zeros one key and 0 below every number's."""
    x = jnp.where(x == 0, 0.0, x.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(keys, k):
    """keys [rows, n] uint32, k [rows] in [1, n] -> [rows]: each row's
    ``k``-th largest key, exactly: the largest ``T`` that at least ``k``
    keys reach, found bit by bit from the top (32 counts over the row)."""
    def bit(i, t):
        cand = t | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        reach = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, t)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:1], jnp.uint32))


def threshold(scores, t, topk: int):
    """A window's sets as ``selected_block`` takes them.  scores [lq, n]
    float32, entry ``[i, s]`` the index score of the key at sequence index
    ``s``; t [lq] the queries' own indices -> the scores' order keys
    [lq, n] uint32 (0 where ``s > t``), each query's threshold [lq], the
    ``min(topk, t + 1)``-th largest of its keys (``kth_largest``: exact),
    and the room [lq] int32 that the keys over it leave for those equal
    to it."""
    at = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    keys = jnp.where(at <= t[:, None], sortable(scores), jnp.uint32(0))
    room = jnp.minimum(topk, t + 1).astype(jnp.int32)
    kth = kth_largest(keys, room)
    above = jnp.sum(keys > kth[:, None], axis=1, dtype=jnp.int32)
    return keys, kth, room - above


def selected(scores, t, topk: int):
    """Which keys each query attends over.  scores [lq, n] float32, entry
    ``[i, s]`` the index score of the key at sequence index ``s``; t [lq]
    the queries' own indices -> bool [lq, n]: the ``min(topk, t + 1)`` keys
    ``s <= t`` with the largest scores, equal scores to the lower index.
    Exact: ``threshold``, then the rule that the window's kernel applies
    block by block (ops/flash_attention.py ``selected_block``), here over
    the whole row as one block."""
    keys, kth, room = threshold(scores, t, topk)
    at = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    return selected_block(
        keys, at, t[:, None], kth[:, None], room[:, None])[0]


class SparseAttention(nn.Module):
    """Grouped-query attention of one layer over the positions its indexer
    selects."""

    cfg: KeyeConfig

    def setup(self):
        c = self.cfg
        dense = lambda n, name: nn.Dense(
            n, use_bias=False, dtype=c.dtype, param_dtype=c.param_dtype,
            name=name)
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.param_dtype, name=name)
        self.q_proj = dense(c.n_heads * c.head_dim, "q_proj")
        self.k_proj = dense(c.n_kv_heads * c.head_dim, "k_proj")
        self.v_proj = dense(c.n_kv_heads * c.head_dim, "v_proj")
        self.o_proj = dense(c.d_model, "o_proj")
        self.q_norm, self.k_norm = norm("q_norm"), norm("k_norm")
        self.index_q = dense(c.index_heads * c.index_dim, "index_q")
        self.index_k = dense(c.index_dim, "index_k")
        self.index_w = dense(c.index_heads, "index_w")
        self.index_norm = LayerNorm(
            c.rms_norm_eps, c.param_dtype, name="index_norm")

    def project(self, x, pos):
        """x [b, l, d_model], pos [3, b, l] -> q [b, kv, g, l, d] (normed,
        rotated, scaled), k and v [b, l, kv, d] (k normed and rotated):
        query head ``i`` is ``(i // g, i % g)``."""
        c = self.cfg
        with jax.named_scope("attention_proj"):
            x = x.astype(c.dtype)
            b, l = x.shape[:2]
            # The barrier keeps the products as they are written (see
            # models/command_a.py ``GroupedAttention.project``).
            q, k, v = jax.lax.optimization_barrier(
                (self.q_proj(x), self.k_proj(x), self.v_proj(x)))
            q = q.reshape(b, l, c.n_heads, c.head_dim)
            k = k.reshape(b, l, c.n_kv_heads, c.head_dim)
            v = v.reshape(b, l, c.n_kv_heads, c.head_dim)
        q, k = self.q_norm(q), self.k_norm(k)
        with jax.named_scope("attention_proj"):
            q = rope_sections(q, pos, c.rope_theta, c.mrope_section)
            k = rope_sections(k, pos, c.rope_theta, c.mrope_section)
            q = (q * c.head_dim ** -0.5).astype(c.dtype)
            q = q.reshape(b, l, c.n_kv_heads, -1, c.head_dim)
            return (jnp.transpose(q, (0, 2, 3, 1, 4)), k.astype(c.dtype),
                    v.astype(c.dtype))

    def index(self, x, pos):
        """The indexer's three products of x [b, l, d_model]: queries
        [b, l, index_heads, index_dim] and keys [b, l, index_dim], both
        rotated by the temporal position, and the heads' weights
        [b, l, index_heads] float32, scaled."""
        c = self.cfg
        with jax.named_scope("attention_proj"), jax.named_scope("dsa.index"):
            x = x.astype(c.dtype)
            b, l = x.shape[:2]
            qi = self.index_q(x).reshape(b, l, c.index_heads, c.index_dim)
            ki = self.index_norm(self.index_k(x))
            qi = rope_sections(qi, pos, c.rope_theta).astype(c.dtype)
            ki = rope_sections(ki, pos, c.rope_theta).astype(c.dtype)
            w = self.index_w(x).astype(jnp.float32) * (
                c.index_heads ** -0.5 * c.index_dim ** -0.5)
            return qi, ki, w

    def scores(self, qi, ki, w):
        """``I``: qi [..., lq, heads, dim], ki [..., n, dim], w [..., lq,
        heads] -> [..., lq, n] float32."""
        dots = jnp.einsum("...qhd,...kd->...qhk", qi, ki,
                          preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots) * w[..., None], axis=-2)

    def over(self, q, k, v, qi, ki, w, start):
        """One row's queries at sequence indices ``start + [0, lq)`` over
        the row's keys from index 0 on (an array by position, whose tail
        may not be written yet): each query over its own set, in ONE
        kernel (``selected_attention``).  q [kv, g, lq, d]; k, v
        [n, kv * d], a position's heads side by side; qi [lq, heads, dim];
        ki [n, dim]; w [lq, heads].  -> [lq, n_heads * d]."""
        c = self.cfg
        lq, n = q.shape[2], k.shape[0]
        with jax.named_scope("attention_core"), jax.named_scope("dsa.index"):
            block = min(KEY_BLOCK, n)
            pad = -n % block
            blocks = jnp.pad(ki, ((0, pad), (0, 0))).reshape(
                -1, block, ki.shape[-1])
            # a block of keys at a time: the heads' products of all of
            # them at once would be heads x window x keys
            index = jax.lax.map(lambda kb: self.scores(qi, kb, w), blocks)
            index = jnp.moveaxis(index, 0, 1).reshape(lq, -1)[:, :n]
        with jax.named_scope("attention_core"), \
                jax.named_scope("dsa.select"):
            t = start + jnp.arange(lq, dtype=jnp.int32)
            keys, kth, room = threshold(index, t, c.index_topk)
        with jax.named_scope("attention_core"):
            return selected_attention(q, k, v, keys, kth, room, start)

    def whole(self, x, pos):
        """A whole sequence of one row, no cache.  x [1, l, d_model], pos
        [3, 1, l]."""
        q, k, v = self.project(x, pos)
        qi, ki, w = self.index(x, pos)
        flat = lambda a: a[0].reshape(a.shape[1], -1)
        out = self.over(q[0], flat(k), flat(v), qi[0], ki[0], w[0], 0)
        with jax.named_scope("attention_proj"):
            return self.o_proj(out.astype(self.cfg.dtype))[None]

    def window(self, x, index, cache):
        """One window of one row.  x [1, P, d_model] at positions ``index *
        P + [0, P)``; ``cache`` the layer's three arrays, one row: the
        window's keys, values and index keys are written at their
        positions, and its queries work over the row.  What is written
        past the prompt's end is masked or rewritten by the decode steps
        that follow."""
        p = x.shape[1]
        with jax.named_scope("attention_proj"):
            start = index * p
            pos = three(start + jnp.arange(p)[None])
        q, k, v = self.project(x, pos)
        qi, ki, w = self.index(x, pos)
        with jax.named_scope("cache_write"):
            put = lambda a, new: jax.lax.dynamic_update_slice_in_dim(
                a, new.reshape(1, p, -1), start, axis=1)
            cache = {"k": put(cache["k"], k), "v": put(cache["v"], v),
                     "index": put(cache["index"], ki)}
        out = self.over(
            q[0], cache["k"][0], cache["v"][0], qi[0], cache["index"][0],
            w[0], start)
        with jax.named_scope("attention_proj"):
            return self.o_proj(out.astype(self.cfg.dtype))[None], cache

    def choose(self, qi, ci, w, pos, klen: int):
        """The entries a step's rows fetch.  qi [b, heads, dim], w
        [b, heads]; ``ci`` the index keys ``[slots, positions, dim]``;
        pos [b] -> [b, K] int32, ``K = min(index_topk, klen)``: each row's
        ``K`` best positions ``s <= pos`` by index score, the best first
        and of equal scores the lower position first; where a row holds
        fewer, positions past its own follow, which nothing reads."""
        b = qi.shape[0]
        with jax.named_scope("attention_core"), jax.named_scope("dsa.index"):
            index = self.scores(qi[:, None], ci[:b, :klen], w[:, None])[:, 0]
        with jax.named_scope("attention_core"), \
                jax.named_scope("dsa.select"):
            valid = jnp.arange(klen)[None, :] <= pos[:, None]
            _, chosen = jax.lax.top_k(
                jnp.where(valid, index, -jnp.inf),
                min(self.cfg.index_topk, klen))
            return chosen

    def step(self, x, pos, cache, klen: int):
        """One token per row.  x [b, d_model], pos [b]; cache leaves
        [slots, ...] with ``slots >= b``: rows ``[0, b)`` are written at
        their own positions where they lie, then each row scores its index
        keys among the first ``klen`` positions, takes its ``index_topk``
        best and fetches those entries of ``k`` and ``v`` alone; ONE
        kernel (``grouped_decode_attention``) attends over the fetched
        entries, to the depth ``min(pos + 1, index_topk)``."""
        b = x.shape[0]
        with jax.named_scope("attention_proj"):
            one = x[:, None], three(pos[:, None])
        q, k, v = self.project(*one)
        qi, ki, w = self.index(*one)
        ck, cv, ci = cache["k"], cache["v"], cache["index"]
        # One write per row, not a scatter over rows (see
        # models/command_a.py ``GroupedAttention.step``).
        with jax.named_scope("cache_write"):
            for r in range(b):
                at = (r, pos[r], 0)
                ck = jax.lax.dynamic_update_slice(
                    ck, k[r].reshape(1, 1, -1), at)
                cv = jax.lax.dynamic_update_slice(
                    cv, v[r].reshape(1, 1, -1), at)
                ci = jax.lax.dynamic_update_slice(ci, ki[r][None], at)
        chosen = self.choose(qi[:, 0], ci, w[:, 0], pos, klen)
        with jax.named_scope("attention_core"), \
                jax.named_scope("dsa.gather"):
            # a position's heads come as one fetch; the kernel takes a
            # row's entries head by head
            fetch = lambda a: jnp.swapaxes(jnp.take_along_axis(
                a[:b], chosen[:, :, None], axis=1,
            ).reshape(chosen.shape + k.shape[2:]), 1, 2)
            gk, gv = fetch(ck), fetch(cv)
        with jax.named_scope("attention_core"):
            entries = chosen.shape[1]
            out = grouped_decode_attention(
                q[:, :, :, 0], gk, gv, jnp.minimum(pos, entries - 1),
                entries)
        with jax.named_scope("attention_proj"):
            out = self.o_proj(out.reshape(b, -1))
        return out, {"k": ck, "v": cv, "index": ci}


class KeyeBlock(nn.Module):
    cfg: KeyeConfig

    def setup(self):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.param_dtype, name=name)
        self.attn_norm, self.ffn_norm = norm("attn_norm"), norm("ffn_norm")
        self.attn = SparseAttention(c, name="attn")
        self.ffn = RoutedExperts(c, name="ffn")

    def _rest(self, h, a):
        """-> the stream after both sub-layers, and which experts each
        token chose."""
        # The add that takes a sub-layer into the stream is booked with
        # the part that closes the sub-layer.
        with jax.named_scope("attention_proj"):
            h = h + a.astype(jnp.float32)
        y = self.ffn_norm(h)
        with jax.named_scope("mlp"):
            rows = y.reshape(-1, y.shape[-1])
        m, picked = self.ffn(rows)
        with jax.named_scope("mlp"):
            return (h + m.reshape(y.shape),
                    picked.reshape(y.shape[:-1] + (-1,)))

    def whole(self, h, pos):
        return self._rest(h, self.attn.whole(self.attn_norm(h), pos))[0]

    def window(self, h, index, cache):
        a, cache = self.attn.window(self.attn_norm(h), index, cache)
        return self._rest(h, a)[0], cache

    def step(self, h, pos, cache, klen: int):
        a, cache = self.attn.step(self.attn_norm(h), pos, cache, klen)
        h, picked = self._rest(h, a)
        return h, cache, picked


class Keye(nn.Module):
    """batch {inputs [b, l]; optionally positions [3, b, l]} -> logits
    [b, l, vocab]."""

    cfg: KeyeConfig

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(
            c.vocab_size, c.d_model, param_dtype=c.param_dtype, name="embed")
        self.blocks = [
            KeyeBlock(c, name=f"layer_{i}") for i in range(c.n_layers)]
        self.final_norm = RMSNorm(
            c.rms_norm_eps, c.param_dtype, name="final_norm")
        self.head = self.param(
            "head", nn.initializers.lecun_normal(),
            (c.d_model, c.vocab_size), c.param_dtype)

    def blank_cache(self, batch: int, positions: int):
        """Per layer the keys and the values of every position, ``[batch,
        positions, n_kv_heads * head_dim]``, and its index key, ``[batch,
        positions, index_dim]``."""
        c = self.cfg
        array = lambda: jnp.zeros(
            (batch, positions, c.n_kv_heads * c.head_dim), c.dtype)
        return {
            f"layer_{i}": {
                "k": array(), "v": array(),
                "index": jnp.zeros((batch, positions, c.index_dim), c.dtype)}
            for i in range(c.n_layers)
        }

    def head_logits(self, h):
        """Float32 logits: the product in the compute dtype, accumulated
        and handed out in float32."""
        with jax.named_scope("embed_head"):
            return jnp.dot(
                self.final_norm(h).astype(self.cfg.dtype),
                self.head.astype(self.cfg.dtype),
                preferred_element_type=jnp.float32)

    def prefill_window(self, tokens, n_valid, index, cache):
        """One window of a prompt: ``n_valid`` of the ``P`` tokens count.
        -> the row's cache and the logits [1, vocab] at the last valid
        position (the prompt's first new token when this is its last
        window)."""
        with jax.named_scope("embed_head"):
            h = self.embed(tokens).astype(jnp.float32)
        new = {}
        for i, block in enumerate(self.blocks):
            h, new[f"layer_{i}"] = block.window(
                h, index, cache[f"layer_{i}"])
        with jax.named_scope("embed_head"):
            last = jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
            return new, self.head_logits(last[:, 0])

    def decode_step(self, tok, pos, cache, klen: int):
        """tok, pos [b] -> cache, logits [b, vocab], and which experts each
        row chose, [b, n_layers * experts_held], layer by layer."""
        with jax.named_scope("embed_head"):
            h = self.embed(tok).astype(jnp.float32)
        new, picked = {}, []
        for i, block in enumerate(self.blocks):
            h, new[f"layer_{i}"], chose = block.step(
                h, pos, cache[f"layer_{i}"], klen)
            picked.append(chose)
        logits = self.head_logits(h)
        with jax.named_scope("mlp"):
            return new, logits, jnp.concatenate(picked, -1)

    def __call__(self, batch: Dict[str, Any], *, deterministic: bool = True):
        inputs = jnp.asarray(batch["inputs"], jnp.int32)
        b, n = inputs.shape
        with jax.named_scope("attention_proj"):
            pos = three(batch.get(
                "positions", jnp.broadcast_to(jnp.arange(n), (b, n))))
        rows = []
        for r in range(b):
            with jax.named_scope("embed_head"):
                h = self.embed(inputs[r:r + 1]).astype(jnp.float32)
            for block in self.blocks:
                h = block.whole(h, pos[:, r:r + 1])
            rows.append(h)
        with jax.named_scope("embed_head"):
            h = jnp.concatenate(rows, 0)
        return self.head_logits(h)


def build_keye_model(hparams: Dict, mesh=None) -> Keye:
    """``hparams``: fields of ``KeyeConfig`` (the published model where
    left out), ``mrope_section`` as the source's list, ``compute_dtype``
    and ``param_dtype``; other keys (the names a driver reads, such as
    ``d_ff``) are passed over."""
    hp = dict(hparams or {})
    sections = tuple(
        int(n) for n in hp.pop("mrope_section", KeyeConfig.mrope_section))
    cfg = dataclasses.replace(
        config_from(KeyeConfig, hp), mrope_section=sections)
    if not 0 <= cfg.expert_offset <= cfg.n_experts - cfg.experts_held:
        raise ValueError(
            "the experts held must lie inside the router's outputs")
    if cfg.n_heads % cfg.n_kv_heads:
        raise ValueError("n_heads must be a multiple of n_kv_heads")
    if 2 * sum(sections) != cfg.head_dim:
        raise ValueError("mrope_section must cover head_dim / 2 pairs")
    return Keye(cfg)


def make_continuous_decode_fns(
    model: Keye,
    *,
    max_decode_len: int = 32,
    eos_id: int = 1,
    pad_id: int = 0,
    max_input_len: int = 64,
    prefill_window_len: int = 512,
):
    """Keye's ``DecodeContract`` (models/decode_contract.py), of the
    decoder-only family (``DecodeContract.decoder_only``): TWO kinds of
    cache at the same positions, one read only where the other says.
    Its own:

      - ``kv``, ``CacheKind(by_position=True)``: a layer's keys and values
        at every position from the prompt's first token on, ``[slots,
        positions, n_kv_heads * head_dim]``;
      - ``index``, ``CacheKind(by_position=True)``: the layer's index key
        at the same positions, ``[slots, positions, index_dim]``;
      - both written by every step and worked on in place: the engine only
        ever indexes the slot axis of an array it hands over whole;
      - ``cache_positions`` is a whole number of windows;
      - the engine's traffic is text: a step's ``pos`` and a window's
        positions are one number a token, and stand for all three
        components of the rotary code;
      - ``step_tally_len``: the held experts of every layer;
      - ``step_account``: per kind the entries and bytes that are valid
        for the live rows and the bytes that the step fetches for them:
        of ``kv`` the ``min(t + 1, index_topk)`` entries a row that were
        selected (whole blocks of the gathered entries, as
        ``grouped_decode_attention`` fetches them), of ``index`` every
        position of the step's bucket, which the scores' product reads
        whatever the row's depth; and ``selected_entries``, how many
        entries the rows' selections hold, layers together;
      - ``window_account``: the key blocks that window ``index`` of a
        prompt visits (``selected_attention``'s, up to the window's last
        position) and those the row holds, layers together.
    """
    c = model.cfg
    p = int(prefill_window_len)
    positions = -(-(int(max_input_len) + int(max_decode_len)) // p) * p
    itemsize = jnp.dtype(c.dtype).itemsize
    entry_bytes = {
        "kv": 2 * c.n_kv_heads * c.head_dim * itemsize,
        "index": c.index_dim * itemsize}
    held = c.experts_held

    def prefill_window(params, cache, tokens, n_valid, index):
        return model.apply(
            {"params": params}, tokens, n_valid, index, cache,
            method=Keye.prefill_window)

    def step(params, cache, tok, pos, encoded, enc_mask, klen: int):
        return model.apply(
            {"params": params}, tok, pos, cache, klen,
            method=Keye.decode_step)

    def blank_cache(batch: int):
        return model.blank_cache(batch, positions)

    def cache_kind_of(path) -> str:
        leaf = str(getattr(path[-1], "key", path[-1]))
        return "index" if leaf == "index" else "kv"

    def step_account(at, tally, bucket=None):
        """``at``: the live rows' positions; ``tally``: assignments to each
        expert, layer by layer; ``bucket``: the step's rows and positions
        (the whole cache where it is not given)."""
        klen = positions if bucket is None else int(bucket[1])
        most = min(c.index_topk, klen)
        block = grouped_decode_block(most)
        chosen = [min(t + 1, most) for t in at]
        entries = c.n_layers * sum(t + 1 for t in at)
        return {
            "cache_entries": {"kv": entries, "index": entries},
            "cache_bytes": {
                k: entries * n for k, n in entry_bytes.items()},
            "cache_span_bytes": {
                "kv": c.n_layers * entry_bytes["kv"] * sum(
                    min(-(-n // block) * block, most) for n in chosen),
                "index": c.n_layers * entry_bytes["index"] * klen * len(at)},
            "selected_entries": c.n_layers * sum(chosen),
            **tally_account(tally, held)}

    def window_account(index):
        block_q, block_k = selected_blocks(p, positions)
        each = c.n_layers * -(-p // block_q)     # layers x query blocks
        return {"key_blocks": {
            "visited": each * (
                1 + selected_last_block(index * p, p, block_k)),
            "held": each * -(-positions // block_k)}}

    return DecodeContract.decoder_only(
        step=step,
        step_tally_len=c.n_layers * held,
        prefill_window=prefill_window,
        prefill_window_len=p,
        blank_cache=blank_cache,
        cache_positions=positions,
        cache_kinds={
            "kv": CacheKind(True, written=True, in_place=True),
            "index": CacheKind(True, written=True, in_place=True)},
        cache_kind_of=cache_kind_of,
        step_account=step_account,
        window_account=window_account,
        max_decode_len=max_decode_len,
        eos_id=eos_id,
        pad_id=pad_id,
        max_input_len=max_input_len,
    )
