"""Command A+ (``cohere2_moe``): a decoder-only language model whose layers
are of two kinds in a fixed pattern, as one chip's share of an
expert-parallel deployment.

For layer ``l`` with stream ``h`` (float32), ONE bias-free LayerNorm a
layer, ``x = (h - mean) / sqrt(var + eps) * g``, feeds attention and the
expert layer side by side (the parallel block):

    q = x Wq -> n_heads x head_dim;  k = x Wk, v = x Wv -> n_kv_heads x
    head_dim; no biases, no q/k norm.  Query head i reads key/value head
    i // (n_heads / n_kv_heads).  Scores q.k / sqrt(head_dim), softmax in
    float32.
    l % full_every < full_every - 1 (a WINDOW layer): q and k rotated by
    interleaved pairs (2j, 2j + 1), angle t * theta ** (-2j / head_dim);
    the query at t sees keys t - window_size + 1 .. t.
    l % full_every == full_every - 1 (a FULL layer): no rotation, no
    positions at all; the query at t sees keys 0 .. t.
    a = concat(heads) Wo.
    s = sigmoid(x Wr) over ALL n_experts (float32), the top
    experts_per_token, weights s_i / sum_top s; expert e:
    (silu(x G_e) * (x U_e)) D_e.  m = sum_top w_e expert_e(x) + the MEAN
    of the n_shared_experts shared experts (one gated MLP of their joint
    width whose output is divided by their number).
    h <- h + a + m.

After the last layer a LayerNorm, and logits = h E^T * logit_scale with
``E`` the embedding (tied).  The residual stream, the norms, the scores,
their softmax, the router and the logits are float32, the matrix
products bfloat16 with float32 accumulation.

The expert layer is ``RoutedExperts`` of models/pangu_moe.py, told which
experts it holds: it routes over all, computes its own part of the sum
and the shared experts; what the absent experts would add is the other
chips' to compute.

Two kinds of cache, by layer.  A window layer keeps a RING of
``window_size`` entries, ``[slots, n_kv_heads, window_size, head_dim]``
for keys (rotated) and for values: position ``t`` lies at ``t %
window_size``, and an entry is valid by mask (a roll-over clears
nothing).  A full layer keeps every position, ``[slots, n_kv_heads,
positions, head_dim]``.  The key/value heads lie BEFORE the entries so
that a step's products are batched over (row, key/value head) with the
entries and the head's numbers as the matrix, as the arrays lie; with
the heads behind the entries the chip pads 8 heads to 16.

Entry points: ``prefill_window`` (one window of a prompt against a row's
cache: the full layers write the window's keys and attend over the array
by position; the window layers over the window's own keys plus the ring
as it was, and then write the window's VALID keys into the ring, which
wraps while a long prompt is prefilled), ``decode_step`` (one token per
live row, each at its own position) and ``__call__`` (a whole sequence,
no cache: what the tests compare with the plain reference).

A window's and a whole sequence's attention is ONE Pallas kernel a layer
(``ops/flash_attention.py grouped_attention``; interpreted off the chip):
the 16 query heads of a key/value head share each block of keys it
fetches, a block's scores and the running softmax stay in the chip's fast
memory, the mask is ``GroupedAttention.sees`` over the position each entry
holds (a ring as it lies, an array whose tail is not yet written), and a
block of entries that no query of a block sees is neither fetched nor
computed.  A decode step's is one more (``grouped_decode_attention``):
each row's 16 query heads a key/value head over the row's own entries, a
ring's or an array's as they lie, fetched once and only to the row's
depth.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpu_pipelines.models.decode_contract import (
    CacheKind, DecodeContract, window_positions)
from tpu_pipelines.models.pangu_moe import (
    RoutedExperts, config_from, tally_account)
from tpu_pipelines.ops.flash_attention import (
    grouped_attention, grouped_decode_attention, grouped_decode_block)


@dataclasses.dataclass(frozen=True)
class CommandAConfig:
    """The widths and counts of one model, as every module reads them.
    The defaults are command-a-plus-05-2026 as published, with every
    expert held."""

    vocab_size: int = 262144
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    window_size: int = 4096
    full_every: int = 4
    d_expert: int = 4096
    n_experts: int = 128
    experts_held: int = 128
    expert_offset: int = 0
    experts_per_token: int = 8
    n_shared_experts: int = 4
    shared_average: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def is_full(self, layer: int) -> bool:
        return layer % self.full_every == self.full_every - 1


def rope_interleaved(x, pos, theta: float):
    """Rotary position code in float32 by interleaved pairs: element
    ``2j`` with ``2j + 1``.  x [b, l, ..., d], pos [b, l].  The partner of
    an element is fetched by a roll along ``d``, not by a reshape to
    pairs: the chip's compiler moves such a reshape onto the projection's
    weights and re-lays them out at every call."""
    d = x.shape[-1]
    inv = theta ** (-(jnp.arange(d) // 2 * 2).astype(jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv             # [b, l, d]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d,))
    x = x.astype(jnp.float32)
    even = jnp.arange(d) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x, -1, -1), jnp.roll(x, 1, -1))
    return x * jnp.cos(ang) + partner * jnp.sin(ang)


class LayerNorm(nn.Module):
    """``(x - mean) / sqrt(var + eps) * g`` in float32, no bias."""

    eps: float
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype)
        with jax.named_scope("norm"):
            x = x.astype(jnp.float32)
            x = x - jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            return x * jax.lax.rsqrt(var + self.eps) * g.astype(jnp.float32)


class GroupedAttention(nn.Module):
    """Grouped-query attention of one layer, of the kind ``full`` says."""

    cfg: CommandAConfig
    full: bool

    def setup(self):
        c = self.cfg
        dense = lambda n, name: nn.Dense(
            n, use_bias=False, dtype=c.dtype, param_dtype=c.param_dtype,
            name=name)
        self.q_proj = dense(c.n_heads * c.head_dim, "q_proj")
        self.k_proj = dense(c.n_kv_heads * c.head_dim, "k_proj")
        self.v_proj = dense(c.n_kv_heads * c.head_dim, "v_proj")
        self.o_proj = dense(c.d_model, "o_proj")
        self.span = "attn.full" if self.full else "attn.window"

    def project(self, x, pos):
        """x [b, l, d_model], pos [b, l] -> q [b, kv, g, l, d] (scaled),
        k and v [b, kv, l, d]: query head ``i`` is ``(i // g, i % g)``;
        the rotary code is on q and k of a window layer."""
        c = self.cfg
        with jax.named_scope("attention_proj"):
            x = x.astype(c.dtype)
            b, l = x.shape[:2]
            # The barrier keeps the products as they are written.  Without
            # it the chip's compiler lays q, k and v out for the attention
            # products, pushes that layout back through the projections
            # and copies Wq, Wk and Wv into another layout at every call
            # (1.6 ms of a 22.8 ms step, PERF.md section 6, PR 35).
            q, k, v = jax.lax.optimization_barrier(
                (self.q_proj(x), self.k_proj(x), self.v_proj(x)))
            q = q.reshape(b, l, c.n_heads, c.head_dim)
            k = k.reshape(b, l, c.n_kv_heads, c.head_dim)
            v = v.reshape(b, l, c.n_kv_heads, c.head_dim)
            if not self.full:
                q = rope_interleaved(q, pos, c.rope_theta)
                k = rope_interleaved(k, pos, c.rope_theta)
            q = (q.astype(jnp.float32) * c.head_dim ** -0.5).astype(c.dtype)
            q = q.reshape(b, l, c.n_kv_heads, -1, c.head_dim)
            heads_first = lambda y: jnp.swapaxes(y.astype(c.dtype), 1, 2)
            return (jnp.transpose(q, (0, 2, 3, 1, 4)), heads_first(k),
                    heads_first(v))

    def merge(self, out):
        """out [kv, g, l, d] -> the layer's output [l, d_model]."""
        with jax.named_scope("attention_proj"):
            out = jnp.transpose(out, (2, 0, 1, 3)).reshape(out.shape[2], -1)
            return self.o_proj(out.astype(self.cfg.dtype))

    def sees(self, t, u):
        """Whether the query at ``t`` sees the key at position ``u``
        (arrays that broadcast); ``u < 0``: no key there."""
        ok = (u <= t) & (u >= 0)
        return ok if self.full else ok & (u > t - self.cfg.window_size)

    def blocks(self, q, k, v, start, held):
        """One row's queries over one row's entries, as ONE kernel
        (ops/flash_attention.py ``grouped_attention``): the scores of a
        block never leave the chip's fast memory, a block of keys is
        fetched once for the ``g`` heads that share it, and one that no
        query of a block sees is neither fetched nor computed.  q
        [kv, g, l, d] at positions ``start + [0, l)``; k and v [kv, s, d],
        of which entry ``j`` holds position ``held[j]`` (negative: none).
        Every query sees a key.  -> [l, d_model]."""
        with jax.named_scope("attention_core"), jax.named_scope(self.span):
            out = grouped_attention(q, k, v, held, start, self.sees)
        return self.merge(out)

    def whole(self, x, pos):
        """A whole sequence of one row under the layer's mask, no cache.
        x [1, l, d_model], pos [1, l] from 0."""
        q, k, v = self.project(x, pos)
        return self.blocks(q[0], k[0], v[0], 0, pos[0])[None]

    def window(self, x, n_valid, index, cache):
        """One window of one row.  x [1, P, d_model] at positions
        ``index * P + [0, P)``, of which the first ``n_valid`` are the
        prompt's; ``cache`` the layer's two arrays, one row.  What a full
        layer writes past the prompt's end is masked or rewritten by the
        decode steps that follow; a ring takes the valid keys only,
        because a position past the end would lie over one that the
        steps still see."""
        p = x.shape[1]
        with jax.named_scope("attention_proj"):
            start = index * p
            positions = start + jnp.arange(p)[None]
        q, k, v = self.project(x, positions)
        if self.full:
            put = lambda a, new: jax.lax.dynamic_update_slice_in_dim(
                a, new, start, axis=2)
            with jax.named_scope("cache_write"):
                cache = {"full_k": put(cache["full_k"], k),
                         "full_v": put(cache["full_v"], v)}
            with jax.named_scope("attention_core"):
                entries = (
                    q[0], cache["full_k"][0], cache["full_v"][0], start,
                    jnp.arange(cache["full_k"].shape[2]))
            out = self.blocks(*entries)
            with jax.named_scope("attention_proj"):
                return out[None], cache
        w = self.cfg.window_size
        # The window's own keys, then the ring as it was: entry j holds
        # the last position before ``start`` that lies at j, a negative
        # one (no key) until the ring has wrapped that far.
        with jax.named_scope("attention_core"):
            at = jnp.arange(w)
            held = jnp.concatenate([
                start + jnp.arange(p), start - 1 - (start - 1 - at) % w])
            keys = jnp.concatenate([k[0], cache["ring_k"][0]], 1)
            values = jnp.concatenate([v[0], cache["ring_v"][0]], 1)
            entries = q[0], keys, values, start, held
        out = self.blocks(*entries)

        def put(ring, new):
            old = jax.lax.dynamic_slice_in_dim(ring, start % w, p, axis=2)
            return jax.lax.dynamic_update_slice_in_dim(
                ring, jnp.where(valid, new, old), start % w, axis=2)

        with jax.named_scope("cache_write"):
            valid = (jnp.arange(p) < n_valid)[None, None, :, None]
        with jax.named_scope("attention_proj"):
            out = out[None]
        with jax.named_scope("cache_write"):
            return out, {"ring_k": put(cache["ring_k"], k),
                         "ring_v": put(cache["ring_v"], v)}

    def step(self, x, pos, cache, klen: int):
        """One token per row.  x [b, d_model], pos [b]; cache leaves
        [slots, kv, entries, d] with ``slots >= b``: rows ``[0, b)`` are
        written at their own positions where they lie; then ONE kernel
        (ops/flash_attention.py ``grouped_decode_attention``) reads each
        row's entries to the row's depth, a full layer's within its first
        ``klen`` positions, a window layer's within the ring."""
        c = self.cfg
        b = x.shape[0]
        with jax.named_scope("attention_proj"):
            one = x[:, None], pos[:, None]
        q, k, v = self.project(*one)
        names = ("full_k", "full_v") if self.full else ("ring_k", "ring_v")
        ck, cv = cache[names[0]], cache[names[1]]
        entries = klen if self.full else c.window_size
        with jax.named_scope("cache_write"):
            at = pos if self.full else pos % c.window_size
        # One write per row, not a scatter over rows: for a scatter the
        # chip's compiler copies the whole array into another layout.
        with jax.named_scope("cache_write"):
            for r in range(b):
                ck = jax.lax.dynamic_update_slice(
                    ck, k[r][None], (r, 0, at[r], 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, v[r][None], (r, 0, at[r], 0))
        # How deep a row's valid entries go once this step's is written: a
        # ring holds ``[0, pos]`` until it has wrapped and every entry
        # after, each inside the window by construction, so the kernel
        # needs no mask by position.
        with jax.named_scope("attention_core"), jax.named_scope(self.span):
            depth = pos if self.full else jnp.minimum(pos, entries - 1)
            out = grouped_decode_attention(
                q[:, :, :, 0], ck, cv, depth, entries)
        with jax.named_scope("attention_proj"):
            out = self.o_proj(out.reshape(b, -1))
        return out, {names[0]: ck, names[1]: cv}


class CommandABlock(nn.Module):
    cfg: CommandAConfig
    full: bool

    def setup(self):
        c = self.cfg
        self.norm = LayerNorm(c.layer_norm_eps, c.param_dtype, name="norm")
        self.attn = GroupedAttention(c, self.full, name="attn")
        self.ffn = RoutedExperts(c, name="ffn")

    def _both(self, h, x, a):
        """-> the stream with both branches added, and which held experts
        each token chose."""
        # The add that takes a branch into the stream is booked with the
        # part that closes the branch.
        with jax.named_scope("mlp"):
            rows = x.reshape(-1, x.shape[-1])
        m, picked = self.ffn(rows)
        with jax.named_scope("attention_proj"):
            h = h + a.astype(jnp.float32)
        with jax.named_scope("mlp"):
            h = h + m.reshape(x.shape)
            return h, picked.reshape(x.shape[:-1] + (-1,))

    def whole(self, h, pos):
        x = self.norm(h)
        return self._both(h, x, self.attn.whole(x, pos))[0]

    def window(self, h, n_valid, index, cache):
        x = self.norm(h)
        a, cache = self.attn.window(x, n_valid, index, cache)
        return self._both(h, x, a)[0], cache

    def step(self, h, pos, cache, klen: int):
        x = self.norm(h)
        a, cache = self.attn.step(x, pos, cache, klen)
        h, picked = self._both(h, x, a)
        return h, cache, picked


class CommandA(nn.Module):
    """batch {inputs [b, l]} -> logits [b, l, vocab]."""

    cfg: CommandAConfig

    def setup(self):
        c = self.cfg
        self.embed = nn.Embed(
            c.vocab_size, c.d_model, param_dtype=c.param_dtype, name="embed")
        self.blocks = [
            CommandABlock(c, c.is_full(i), name=f"layer_{i}")
            for i in range(c.n_layers)
        ]
        self.final_norm = LayerNorm(
            c.layer_norm_eps, c.param_dtype, name="final_norm")

    def blank_cache(self, batch: int, positions: int):
        """Per window layer the ring's keys and values, per full layer
        every position's: ``[batch, n_kv_heads, entries, head_dim]``."""
        c = self.cfg
        array = lambda n: jnp.zeros(
            (batch, c.n_kv_heads, n, c.head_dim), c.dtype)
        return {
            f"layer_{i}": (
                {"full_k": array(positions), "full_v": array(positions)}
                if c.is_full(i) else
                {"ring_k": array(c.window_size),
                 "ring_v": array(c.window_size)})
            for i in range(c.n_layers)
        }

    def head_logits(self, h):
        """Float32 logits over the embedding's own rows (tied): the
        product in the compute dtype, accumulated and handed out in
        float32."""
        c = self.cfg
        with jax.named_scope("embed_head"):
            return c.logit_scale * jnp.einsum(
                "...d,vd->...v", self.final_norm(h).astype(c.dtype),
                self.embed.embedding.astype(c.dtype),
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
                h, n_valid, index, cache[f"layer_{i}"])
        with jax.named_scope("embed_head"):
            last = jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
            return new, self.head_logits(last[:, 0])

    def decode_step(self, tok, pos, cache, klen: int):
        """tok, pos [b] -> cache, logits [b, vocab], and which held
        experts each row chose, [b, n_layers * experts_held], layer by
        layer."""
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
        pos = jnp.arange(n)[None]
        rows = []
        for r in range(b):
            with jax.named_scope("embed_head"):
                h = self.embed(inputs[r:r + 1]).astype(jnp.float32)
            for block in self.blocks:
                h = block.whole(h, pos)
            rows.append(h)
        return self.head_logits(jnp.concatenate(rows, 0))


def build_command_a_model(hparams: Dict, mesh=None) -> CommandA:
    """``hparams``: fields of ``CommandAConfig`` (the published model
    where left out), ``compute_dtype`` and ``param_dtype``; other keys
    (the names a driver reads, such as ``d_ff``) are passed over."""
    cfg = config_from(CommandAConfig, hparams)
    if not 0 <= cfg.expert_offset <= cfg.n_experts - cfg.experts_held:
        raise ValueError(
            "the experts held must lie inside the router's outputs")
    if cfg.n_heads % cfg.n_kv_heads:
        raise ValueError("n_heads must be a multiple of n_kv_heads")
    return CommandA(cfg)


def make_continuous_decode_fns(
    model: CommandA,
    *,
    max_decode_len: int = 32,
    eos_id: int = 1,
    pad_id: int = 0,
    max_input_len: int = 64,
    prefill_window_len: int = 512,
):
    """Command A's ``DecodeContract`` (models/decode_contract.py), of the
    decoder-only family (``DecodeContract.decoder_only``): layers that
    keep caches of TWO kinds in one arena.  Its own:

      - ``window``, ``CacheKind(by_position=False)``: a window layer's
        ring of ``window_size`` entries, written at ``pos %
        window_size``, valid by mask.  It wraps while a prompt longer
        than the window is prefilled and again while decoding.
      - ``full``, ``CacheKind(by_position=True)``: a full layer's keys
        and values at every position from the prompt's first token on
        (``cache_positions``); ``step`` attends over the first ``klen``
        of them.
      - both worked on in place, both ``[slots, n_kv_heads, entries,
        head_dim]``: the engine only ever indexes the slot axis of an
        array it hands over whole.
      - ``prefill_window_len`` divides ``window_size``: a window's keys
        lie in the ring without a wrap inside them.
      - ``step_tally_len``: the held experts of every layer.
      - ``step_account``: per kind the entries and bytes that are valid
        for the live rows, and the bytes that the step's attention
        kernel fetches for them: whole key blocks up to the one that
        holds a row's depth.
    """
    c = model.cfg
    p, w = int(prefill_window_len), c.window_size
    if w % p:
        raise ValueError("prefill_window_len must divide window_size")
    _, positions = window_positions(max_input_len, max_decode_len, p)
    n_full = sum(c.is_full(i) for i in range(c.n_layers))
    n_ring = c.n_layers - n_full
    entry_bytes = (
        2 * c.n_kv_heads * c.head_dim * jnp.dtype(c.dtype).itemsize)
    held = c.experts_held
    # per kind: its layers, the entries an array holds, and of them a
    # key block of the step's kernel
    layers = {"window": n_ring, "full": n_full}
    size = {"window": w, "full": positions}
    block = {k: grouped_decode_block(n) for k, n in size.items()}

    def prefill_window(params, cache, tokens, n_valid, index):
        return model.apply(
            {"params": params}, tokens, n_valid, index, cache,
            method=CommandA.prefill_window)

    def step(params, cache, tok, pos, encoded, enc_mask, klen: int):
        return model.apply(
            {"params": params}, tok, pos, cache, klen,
            method=CommandA.decode_step)

    def blank_cache(batch: int):
        return model.blank_cache(batch, positions)

    def cache_kind_of(path) -> str:
        leaf = str(getattr(path[-1], "key", path[-1]))
        return "window" if leaf.startswith("ring") else "full"

    def step_account(at, tally, bucket=None):
        """``at``: the live rows' positions; ``tally``: assignments to
        each held expert, layer by layer (``bucket``, the step's rows
        and positions, is not read: the kernel stops at a row's depth,
        not at the bucket's end).  The span is what
        ``grouped_decode_attention`` fetches for the live rows: whole key
        blocks up to the one that holds a row's depth, cut at the array's
        end."""
        depth = {"window": [min(t, w - 1) for t in at], "full": list(at)}
        entries = {k: layers[k] * sum(t + 1 for t in depth[k]) for k in depth}
        return {
            "cache_entries": entries,
            "cache_bytes": {k: n * entry_bytes for k, n in entries.items()},
            "cache_span_bytes": {
                k: layers[k] * entry_bytes * sum(
                    min((t // block[k] + 1) * block[k], size[k])
                    for t in depth[k])
                for k in depth},
            "window_rollovers": sum(t % w == 0 for t in at),
            **tally_account(tally, held)}

    return DecodeContract.decoder_only(
        step=step,
        step_tally_len=c.n_layers * held,
        prefill_window=prefill_window,
        prefill_window_len=p,
        blank_cache=blank_cache,
        cache_positions=positions,
        cache_kinds={
            "window": CacheKind(False, written=True, in_place=True),
            "full": CacheKind(True, written=True, in_place=True)},
        cache_kind_of=cache_kind_of,
        step_account=step_account,
        max_decode_len=max_decode_len,
        eos_id=eos_id,
        pad_id=pad_id,
        max_input_len=max_input_len,
    )
