"""EvaByte: a byte-level decoder-only language model on EVA attention.

Pre-norm residual blocks without biases: unit-offset RMSNorm, rotary
position code on queries and keys, a gated (SwiGLU) MLP, and a head that
predicts the next ``num_pred_heads`` bytes at once.  The residual stream
and the logits are float32, the matrix products bfloat16 with float32
accumulation, attention scores and their softmax float32.

EVA attention (chunked linearised attention) keeps two kinds of state per
layer.  Positions are grouped in chunks of ``chunk_size`` and windows of
``window_size``:

  * the current window's keys and values, exact;
  * of every earlier window, one pooled ``(key, value)`` pair per chunk:
    ``a_i = softmax_{i in chunk}(s * phi . k_i)``, ``k~ = sum a_i k_i + mu``,
    ``v~ = sum a_i v_i``, with ``phi`` and ``mu`` learned per head and
    ``s = head_dim ** -0.5``.

A query attends, under ONE softmax, to the exact pairs of its own window
up to itself and to the summaries of the chunks of completed windows; a
chunk's summary is never visible before its window is complete.  So the
cache of a sequence is a ring of ``window_size`` exact positions (written
at ``t % window_size``, valid by mask: a roll-over clears nothing) beside
a table of one entry per chunk, 16 times fewer than positions.

Three entry points share the layer code: ``prefill_window`` (one window of
a prompt against a row's cache), ``decode_step`` (one token per live row,
each at its own position) and ``__call__`` (a whole sequence, window by
window: what the tests compare with the plain reference).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpu_pipelines.models.decode_contract import CacheKind, DecodeContract
from tpu_pipelines.ops.flash_attention import (
    ring_table_blocks, ring_table_decode_attention)

NEG_INF = -1e30


def rope(x, pos, theta: float, inv=None):
    """Rotary position code in float32.  x [b, l, h, d], pos [b, l]
    (absolute positions).  Halves are paired: element ``i`` with
    ``i + d/2``, the convention of the source's family.  ``inv`` [d/2]:
    the inverse frequencies where they are not ``theta``'s own (scaled
    positions: ``yarn_inv_freq``)."""
    d = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv           # [b, l, d/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@dataclasses.dataclass(frozen=True)
class Yarn:
    """A source's ``rope_scaling`` group of type ``yarn``, under its own
    keys."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @classmethod
    def of(cls, group):
        """``group``: the source's dict (other keys passed over), a
        ``Yarn``, or None."""
        if group is None or isinstance(group, cls):
            return group
        return cls(**{f.name: group[f.name] for f in dataclasses.fields(cls)
                      if f.name in group})


def yarn_mscale(factor: float, k: float) -> float:
    """``0.1 k ln(factor) + 1``: what a scaling by ``factor`` multiplies
    magnitudes with."""
    return 0.1 * k * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(d: int, theta: float, y: Yarn):
    """YaRN's inverse frequencies [d/2], float32: pair ``i`` keeps
    ``theta ** (-2i/d)`` below the ramp (it turns more than ``beta_fast``
    times over the original positions), is divided by ``factor`` above it
    (under ``beta_slow`` turns), and is blended linearly between."""
    turn = lambda beta: d * math.log(
        y.original_max_position_embeddings / (2 * math.pi * beta)) \
        / (2 * math.log(theta))
    lo = max(math.floor(turn(y.beta_fast)), 0)
    hi = min(math.ceil(turn(y.beta_slow)), d - 1)
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - lo)
        / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    return f / y.factor * ramp + f * (1.0 - ramp)


class UnitOffsetRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + g)`` in float32."""

    eps: float
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       self.param_dtype)
        with jax.named_scope("norm"):
            x = x.astype(jnp.float32)
            ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            return x * jax.lax.rsqrt(ms + self.eps) * (
                1.0 + g.astype(jnp.float32))


class EvaAttention(nn.Module):
    d_model: int
    n_heads: int
    head_dim: int
    window_size: int
    chunk_size: int
    rope_theta: float
    init_std: float
    dtype: Any
    param_dtype: Any

    def setup(self):
        inner = self.n_heads * self.head_dim
        dense = lambda n, name: nn.Dense(
            n, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        self.q_proj = dense(inner, "q_proj")
        self.k_proj = dense(inner, "k_proj")
        self.v_proj = dense(inner, "v_proj")
        self.o_proj = dense(self.d_model, "o_proj")
        init = nn.initializers.normal(self.init_std)
        shape = (self.n_heads, self.head_dim)
        self.phi = self.param("phi", init, shape, self.param_dtype)
        self.mu = self.param("mu", init, shape, self.param_dtype)

    def qkv(self, x, pos):
        """x [b, l, d_model], pos [b, l] -> q, k, v [b, l, h, d]; the
        rotary code is on q and k."""
        split = lambda y: y.reshape(
            y.shape[:2] + (self.n_heads, self.head_dim))
        with jax.named_scope("attention_proj"):
            x = x.astype(self.dtype)
            q = rope(split(self.q_proj(x)), pos, self.rope_theta)
            k = rope(split(self.k_proj(x)), pos, self.rope_theta)
            return (q.astype(self.dtype), k.astype(self.dtype),
                    split(self.v_proj(x)))

    def summarize(self, k, v):
        """One pooled pair per chunk.  k, v [..., chunk, h, d] (keys as
        cached: after the rotary code) -> [..., h, d] each."""
        with jax.named_scope("attention_core"), \
                jax.named_scope("eva.summarize"):
            kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
            score = jnp.einsum(
                "...chd,hd->...ch", kf, self.phi.astype(jnp.float32)
            ) * (self.head_dim ** -0.5)
            a = jax.nn.softmax(score, axis=-2)
            ks = jnp.einsum("...ch,...chd->...hd", a, kf) \
                + self.mu.astype(jnp.float32)
            vs = jnp.einsum("...ch,...chd->...hd", a, vf)
            return ks.astype(self.dtype), vs.astype(self.dtype)

    def attend(self, q, k, v, k_ok, ck, cv, c_ok):
        """One softmax over exact pairs and chunk summaries.  q
        [b, lq, h, d]; k, v [b, lk, h, d] with k_ok [b, lq, lk]; ck, cv
        [b, lc, h, d] with c_ok [b, lq, lc].  Every query sees at least
        its own position among the exact pairs.  -> [b, lq, h * d]."""
        with jax.named_scope("attention_core"), \
                jax.named_scope("eva.attend"):
            s = self.head_dim ** -0.5
            f32 = dict(preferred_element_type=jnp.float32)
            sx = jnp.einsum("bqhd,bkhd->bhqk", q, k, **f32) * s
            sc = jnp.einsum("bqhd,bjhd->bhqj", q, ck, **f32) * s
            sx = jnp.where(k_ok[:, None], sx, NEG_INF)
            sc = jnp.where(c_ok[:, None], sc, NEG_INF)
            top = jnp.maximum(sx.max(-1), sc.max(-1))[..., None]
            ex, ec = jnp.exp(sx - top), jnp.exp(sc - top)
            total = ex.sum(-1) + ec.sum(-1)                      # [b, h, q]
            out = jnp.einsum(
                "bhqk,bkhd->bqhd", ex.astype(self.dtype), v, **f32
            ) + jnp.einsum(
                "bhqj,bjhd->bqhd", ec.astype(self.dtype), cv, **f32)
            out = out / jnp.swapaxes(total, 1, 2)[..., None]
            return out.reshape(out.shape[:2] + (-1,)).astype(self.dtype)

    def window(self, x, index, cache):
        """One window of one row.  x [1, W, d_model]; ``index`` the
        window's number; ``cache`` the row's four arrays.  The ring is
        overwritten whole and the window's chunk entries are written;
        what lies past the prompt's end there is masked or rewritten by
        the decode steps that follow."""
        w, c = self.window_size, self.chunk_size
        per = w // c
        with jax.named_scope("attention_proj"):
            pos = index * w + jnp.arange(w)[None]
        q, k, v = self.qkv(x, pos)
        n_chunks = cache["chunk_k"].shape[1]
        qb = next(n for n in range(min(w, 512), 0, -1) if w % n == 0)
        chunks = lambda y: y.reshape((1, per, c) + y.shape[2:])
        with jax.named_scope("attention_core"):
            c_ok = (jnp.arange(n_chunks) < index * per)[None, None]

            def block(i):
                rows = i * qb + jnp.arange(qb)
                k_ok = (jnp.arange(w)[None, :] <= rows[:, None])[None]
                qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
                return self.attend(
                    qs, k, v, k_ok, cache["chunk_k"], cache["chunk_v"],
                    c_ok)[0]

            out = jax.lax.map(block, jnp.arange(w // qb)).reshape(1, w, -1)
            ks, vs = self.summarize(chunks(k), chunks(v))
        put = lambda table, rows: jax.lax.dynamic_update_slice_in_dim(
            table, rows, index * per, axis=1)
        with jax.named_scope("cache_write"):
            cache = {
                # ``set`` and not the bare ``k``: the ring that came in is
                # then an operand, and its buffer is used again.
                "window_k": cache["window_k"].at[:].set(k),
                "window_v": cache["window_v"].at[:].set(v),
                "chunk_k": put(cache["chunk_k"], ks),
                "chunk_v": put(cache["chunk_v"], vs),
            }
        with jax.named_scope("attention_proj"):
            return self.o_proj(out), cache

    def step(self, x, pos, cache):
        """One token per row.  x [b, d_model], pos [b] (each row's own
        position); cache leaves [slots, ...] with ``slots >= b``: rows
        ``[0, b)`` are read and written where they lie."""
        w, c = self.window_size, self.chunk_size
        b = x.shape[0]
        with jax.named_scope("cache_write"):
            rows = jnp.arange(b)
        with jax.named_scope("attention_proj"):
            one = x[:, None], pos[:, None]
        q, k, v = self.qkv(*one)
        with jax.named_scope("cache_write"):
            at = pos % w
            ring_k = cache["window_k"].at[rows, at].set(k[:, 0])
            ring_v = cache["window_v"].at[rows, at].set(v[:, 0])
        n_chunks = cache["chunk_k"].shape[1]
        with jax.named_scope("attention_core"):
            # One kernel over the row's ring to ``at`` and its table to
            # the last completed window, each array where it lies; what
            # lies past either depth is neither fetched nor used.
            with jax.named_scope("eva.attend"):
                out = ring_table_decode_attention(
                    q[:, 0], ring_k, ring_v, cache["chunk_k"],
                    cache["chunk_v"], at + 1,
                    jnp.minimum((pos // w) * (w // c), n_chunks),
                    scale=self.head_dim ** -0.5).reshape(b, -1)
            # The chunk this position lies in, as the ring holds it; its
            # summary is stored only by the step that closes the chunk (an
            # index past the table's end is dropped by the scatter).
            start = (at // c) * c
            # One slice per row, not a gather over rows: for a gather the
            # chip's compiler copies the whole ring into another layout.
            take = lambda ring: jnp.concatenate([
                jax.lax.dynamic_slice(
                    ring, (r, start[r], 0, 0), (1, c) + ring.shape[2:])
                for r in range(b)])
            ks, vs = self.summarize(take(ring_k), take(ring_v))
        with jax.named_scope("cache_write"):
            entry = jnp.where((pos + 1) % c == 0, pos // c, n_chunks)
            cache = {
                "window_k": ring_k, "window_v": ring_v,
                "chunk_k": cache["chunk_k"].at[rows, entry].set(
                    ks, mode="drop"),
                "chunk_v": cache["chunk_v"].at[rows, entry].set(
                    vs, mode="drop"),
            }
        with jax.named_scope("attention_proj"):
            return self.o_proj(out), cache


class GatedMlp(nn.Module):
    d_model: int
    d_ff: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(
            n, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        with jax.named_scope("mlp"):
            x = x.astype(self.dtype)
            gate = dense(self.d_ff, "gate")(x).astype(jnp.float32)
            up = dense(self.d_ff, "up")(x).astype(jnp.float32)
            return dense(self.d_model, "down")(
                (jax.nn.silu(gate) * up).astype(self.dtype))


class OutputHeads(nn.Module):
    """The output matrix of every prediction head, read by column: its
    first ``columns`` (all when None) give float32 logits."""

    features: int
    param_dtype: Any

    @nn.compact
    def __call__(self, x, columns: Optional[int] = None):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), self.param_dtype)
        with jax.named_scope("embed_head"):
            return jnp.dot(
                x, kernel[:, :columns].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)


class EvaBlock(nn.Module):
    d_model: int
    n_heads: int
    head_dim: int
    d_ff: int
    window_size: int
    chunk_size: int
    rope_theta: float
    rms_norm_eps: float
    init_std: float
    dtype: Any
    param_dtype: Any

    def setup(self):
        norm = lambda name: UnitOffsetRMSNorm(
            self.rms_norm_eps, self.param_dtype, name=name)
        self.attn_norm = norm("attn_norm")
        self.mlp_norm = norm("mlp_norm")
        self.attn = EvaAttention(
            self.d_model, self.n_heads, self.head_dim, self.window_size,
            self.chunk_size, self.rope_theta, self.init_std, self.dtype,
            self.param_dtype, name="attn")
        self.mlp = GatedMlp(
            self.d_model, self.d_ff, self.dtype, self.param_dtype,
            name="mlp")

    def _rest(self, h, a):
        # The add that takes a sub-layer into the stream is booked with
        # the part that closes the sub-layer.
        with jax.named_scope("attention_proj"):
            h = h + a.astype(jnp.float32)
        m = self.mlp(self.mlp_norm(h))
        with jax.named_scope("mlp"):
            return h + m.astype(jnp.float32)

    def window(self, h, index, cache):
        a, cache = self.attn.window(self.attn_norm(h), index, cache)
        return self._rest(h, a), cache

    def step(self, h, pos, cache):
        a, cache = self.attn.step(self.attn_norm(h), pos, cache)
        return self._rest(h, a), cache


class EvaByte(nn.Module):
    """batch {inputs [b, l]} -> logits [b, l, num_pred_heads, vocab]:
    head ``p`` at position ``t`` predicts byte ``t + 1 + p``."""

    vocab_size: int = 320
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    head_dim: int = 128
    d_ff: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    init_std: float = 0.01275
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def setup(self):
        if self.window_size % self.chunk_size:
            raise ValueError("window_size must be a multiple of chunk_size")
        self.embed = nn.Embed(
            self.vocab_size, self.d_model, param_dtype=self.param_dtype,
            name="embed")
        self.blocks = [
            EvaBlock(
                self.d_model, self.n_heads, self.head_dim, self.d_ff,
                self.window_size, self.chunk_size, self.rope_theta,
                self.rms_norm_eps, self.init_std, self.dtype,
                self.param_dtype, name=f"layer_{i}")
            for i in range(self.n_layers)
        ]
        self.final_norm = UnitOffsetRMSNorm(
            self.rms_norm_eps, self.param_dtype, name="final_norm")
        self.head = OutputHeads(
            self.num_pred_heads * self.vocab_size, self.param_dtype,
            name="head")

    def blank_cache(self, batch: int, context_len: int):
        """A cache for ``batch`` rows and ``context_len`` positions
        (rounded up to whole windows): per layer the ring of one window
        and the table of every chunk."""
        w = self.window_size
        windows = -(-int(context_len) // w)
        shape = lambda n: (batch, n, self.n_heads, self.head_dim)
        layer = lambda: {
            "window_k": jnp.zeros(shape(w), self.dtype),
            "window_v": jnp.zeros(shape(w), self.dtype),
            "chunk_k": jnp.zeros(
                shape(windows * w // self.chunk_size), self.dtype),
            "chunk_v": jnp.zeros(
                shape(windows * w // self.chunk_size), self.dtype),
        }
        return {f"layer_{i}": layer() for i in range(self.n_layers)}

    def window_hidden(self, tokens, index, cache):
        """tokens [1, W] -> residual stream [1, W, d_model] after the
        last block, and the row's cache with this window in it."""
        with jax.named_scope("embed_head"):
            h = self.embed(tokens).astype(jnp.float32)
        new = {}
        for i, block in enumerate(self.blocks):
            h, new[f"layer_{i}"] = block.window(
                h, index, cache[f"layer_{i}"])
        return h, new

    def head_logits(self, h, heads: Optional[int] = None):
        """Float32 logits of the first ``heads`` prediction heads (all
        when None): head ``p`` is columns ``[p * vocab, (p + 1) *
        vocab)`` of the output matrix."""
        return self.head(
            self.final_norm(h),
            None if heads is None else heads * self.vocab_size)

    def prefill_window(self, tokens, n_valid, index, cache):
        """One window of a prompt: ``n_valid`` of the ``W`` tokens count.
        -> the row's cache and head 0's logits [1, vocab] at the last
        valid position (the prompt's first new byte when this is its
        last window)."""
        h, cache = self.window_hidden(tokens, index, cache)
        with jax.named_scope("embed_head"):
            last = jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
            return cache, self.head_logits(last[:, 0], heads=1)

    def decode_step(self, tok, pos, cache):
        """tok, pos [b] -> cache, head 0's logits [b, vocab]."""
        with jax.named_scope("embed_head"):
            h = self.embed(tok).astype(jnp.float32)
        new = {}
        for i, block in enumerate(self.blocks):
            h, new[f"layer_{i}"] = block.step(h, pos, cache[f"layer_{i}"])
        return new, self.head_logits(h, heads=1)

    def __call__(self, batch: Dict[str, Any], *, deterministic: bool = True):
        inputs = jnp.asarray(batch["inputs"], jnp.int32)
        b, n = inputs.shape
        w = self.window_size
        windows = -(-n // w)
        inputs = jnp.pad(inputs, ((0, 0), (0, windows * w - n)))
        rows = []
        for r in range(b):
            cache = self.blank_cache(1, windows * w)
            hs = []
            for m in range(windows):
                h, cache = self.window_hidden(
                    inputs[r:r + 1, m * w:(m + 1) * w], m, cache)
                hs.append(h)
            rows.append(jnp.concatenate(hs, axis=1))
        logits = self.head_logits(jnp.concatenate(rows, axis=0)[:, :n])
        return logits.reshape(
            b, n, self.num_pred_heads, self.vocab_size)


DEFAULT_HPARAMS = {
    # EvaByte 6.5B as published
    "vocab_size": 320,
    "d_model": 4096,
    "n_layers": 32,
    "n_heads": 32,
    "head_dim": 128,
    "d_ff": 11008,
    "window_size": 2048,
    "chunk_size": 16,
    "num_pred_heads": 8,
    "rope_theta": 100000.0,
    "rms_norm_eps": 1e-5,
    "init_std": 0.01275,
}


def build_evabyte_model(hparams: Dict, mesh=None) -> EvaByte:
    hp = {**DEFAULT_HPARAMS, **(hparams or {})}
    ints = ("vocab_size", "d_model", "n_layers", "n_heads", "head_dim",
            "d_ff", "window_size", "chunk_size", "num_pred_heads")
    return EvaByte(
        **{k: int(hp[k]) for k in ints},
        rope_theta=float(hp["rope_theta"]),
        rms_norm_eps=float(hp["rms_norm_eps"]),
        init_std=float(hp["init_std"]),
        dtype=jnp.dtype(hp.get("compute_dtype", "bfloat16")),
        param_dtype=jnp.dtype(hp.get("param_dtype", "bfloat16")),
    )


def make_continuous_decode_fns(
    model: EvaByte,
    *,
    max_decode_len: int = 32,
    eos_id: int = 1,
    pad_id: int = 0,
    max_input_len: int = 64,
):
    """EvaByte's ``DecodeContract`` (models/decode_contract.py), of the
    decoder-only family (``DecodeContract.decoder_only``).  Its own:

      - ``prefill_window_len`` is the model's attention window;
      - two kinds of cache, the window ring and the chunk table, neither
        indexed by decode position (no ``cache_positions``), both worked
        on in place (a bucket of either, copied out and set back at
        every step, would cost more than the step);
      - ``step_account``: what one step over rows at these positions
        must read of each kind (``cache_bytes``: the valid entries),
        what its attention kernel fetches for them (``cache_span_bytes``:
        whole blocks of a ring up to the one that holds ``t % W``, whole
        blocks of a table up to the last completed window's entries,
        none of an empty table; what lies behind is never read) and
        which events it holds (``tally`` is empty: the step hands none
        back; ``bucket`` is not read: the kernel stops at a row's depths
        whatever the bucket).
    """
    w, c = model.window_size, model.chunk_size
    context = int(max_input_len) + int(max_decode_len)
    entry_bytes = (
        2 * model.n_layers * model.n_heads * model.head_dim
        * jnp.dtype(model.dtype).itemsize)
    entries = {"window": w, "chunk": -(-context // w) * w // c}
    block = dict(zip(entries, ring_table_blocks(*entries.values())))

    def prefill_window(params, cache, tokens, n_valid, index):
        return model.apply(
            {"params": params}, tokens, n_valid, index, cache,
            method=EvaByte.prefill_window)

    def step(params, cache, tok, pos, encoded, enc_mask, klen: int):
        return model.apply(
            {"params": params}, tok, pos, cache, method=EvaByte.decode_step)

    def blank_cache(batch: int):
        return model.blank_cache(batch, context)

    def cache_kind_of(path) -> str:
        leaf = str(getattr(path[-1], "key", path[-1]))
        return "window" if leaf.startswith("window") else "chunk"

    def step_account(positions, tally=(), bucket=None):
        """``cache_bytes``: the valid entries, a ring to ``t % w`` and a
        table to the last completed window.  ``cache_span_bytes``: what
        ``ring_table_decode_attention`` fetches for the live rows, whole
        blocks up to the one that holds a row's last valid entry, cut at
        the array's end; of an empty table nothing."""
        ring = [t % w + 1 for t in positions]
        table = [min((t // w) * (w // c), entries["chunk"])
                 for t in positions]
        span = lambda kind, depths: sum(
            min(-(-n // block[kind]) * block[kind], entries[kind])
            for n in depths)
        return {
            "cache_bytes": {
                "window": sum(ring) * entry_bytes,
                "chunk": sum(table) * entry_bytes},
            "cache_span_bytes": {
                "window": span("window", ring) * entry_bytes,
                "chunk": span("chunk", table) * entry_bytes},
            "window_rollovers": sum(t % w == 0 for t in positions),
            "chunk_summaries": sum((t + 1) % c == 0 for t in positions),
        }

    return DecodeContract.decoder_only(
        step=step,
        prefill_window=prefill_window,
        prefill_window_len=w,
        blank_cache=blank_cache,
        cache_kinds={
            "window": CacheKind(False, written=True, in_place=True),
            "chunk": CacheKind(False, written=True, in_place=True),
        },
        cache_kind_of=cache_kind_of,
        step_account=step_account,
        max_decode_len=max_decode_len,
        eos_id=eos_id,
        pad_id=pad_id,
        max_input_len=max_input_len,
    )
