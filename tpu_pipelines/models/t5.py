"""T5 encoder-decoder seq2seq (BASELINE config 4: T5-small, JAX run_fn).

The reference's stretch config runs a T5-small seq2seq fine-tune through a
JAX ``run_fn`` (SURVEY.md §0 configs[4]).  Built from the sharded transformer
blocks with the T5 particulars: RMSNorm pre-normalization, bucketed
relative-position attention bias shared across each stack's self-attention
layers, tied input/output embedding scaled by 1/sqrt(d_model) at the logits.

Relative-position bias is an additive [h, q, k] score term, so these
attention calls take the dense path (ring attention covers unbiased
self-attention; see models/transformer.py).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

from tpu_pipelines.models.decode_contract import CacheKind, DecodeContract
from tpu_pipelines.models.transformer import (
    TRANSFORMER_PARTITION_RULES,
    TransformerBlock,
)


def relative_position_buckets(
    qlen: int, klen: int, *, bidirectional: bool, num_buckets: int = 32,
    max_distance: int = 128,
):
    """T5's log-bucketed relative positions; returns int32 [qlen, klen]."""
    ctx = np.arange(qlen)[:, None]
    mem = np.arange(klen)[None, :]
    rel = mem - ctx
    buckets = np.zeros_like(rel)
    n = num_buckets
    if bidirectional:
        n //= 2
        buckets += (rel > 0).astype(np.int64) * n
        rel = np.abs(rel)
    else:
        rel = -np.minimum(rel, 0)
    max_exact = n // 2
    is_small = rel < max_exact
    large = max_exact + (
        np.log(np.maximum(rel, 1) / max_exact)
        / np.log(max_distance / max_exact)
        * (n - max_exact)
    ).astype(np.int64)
    large = np.minimum(large, n - 1)
    buckets += np.where(is_small, rel, large)
    return jnp.asarray(buckets, jnp.int32)


class RelativePositionBias(nn.Module):
    n_heads: int
    bidirectional: bool
    num_buckets: int = 32
    max_distance: int = 128

    @nn.compact
    def __call__(self, qlen: int, klen: int, row=None):
        buckets = relative_position_buckets(
            qlen, klen, bidirectional=self.bidirectional,
            num_buckets=self.num_buckets, max_distance=self.max_distance,
        )
        table = self.param(
            "rel_embedding",
            nn.initializers.normal(stddev=1.0),
            (self.num_buckets, self.n_heads),
        )
        if row is not None:
            row = jnp.asarray(row, jnp.int32)
            if row.ndim == 0:
                # Incremental decode: only query position ``row`` is live
                # this step — slice its bucket row so the bias is
                # [1, h, 1, klen].
                buckets = jax.lax.dynamic_slice_in_dim(buckets, row, 1, axis=0)
            elif row.ndim == 1:
                # Continuous batching: each batch row sits at its OWN
                # decode position, so gather one bucket row per sequence —
                # bias [b, h, 1, klen], row i carrying position row[i]'s
                # slice of the full relative-position matrix.
                rows = jnp.take(buckets, row, axis=0)      # [b, klen]
                return jnp.transpose(
                    table[rows], (0, 2, 1)
                )[:, :, None, :].astype(jnp.float32)
            else:
                raise ValueError(
                    "row is one position, or one per sequence; got shape "
                    f"{row.shape}"
                )
        # [q, k, h] -> [1, h, q, k] additive bias
        return jnp.transpose(table[buckets], (2, 0, 1))[None].astype(jnp.float32)


class T5Stack(nn.Module):
    n_layers: int
    n_heads: int
    head_dim: int
    d_ff: int
    dropout_rate: float
    dtype: Any
    causal: bool          # True = decoder
    mesh: Optional[Mesh] = None
    # Forwarded to the attention blocks.  T5's biased self-attention always
    # takes the dense path in training/full passes; the knob matters for
    # the single-query DECODE step, where "flash"/"auto" select the
    # flash-decode kernel against the KV cache (ops/flash_attention.py).
    attn_impl: str = "dense"

    @nn.compact
    def __call__(self, x, *, encoded=None, kv_mask=None, enc_mask=None,
                 deterministic: bool = True, decode_pos=None,
                 max_decode_len: Optional[int] = None):
        rel_pos = RelativePositionBias(
            n_heads=self.n_heads, bidirectional=not self.causal,
            name="rel_pos",
        )
        # The bias is a term of every layer's scores.
        with jax.named_scope("attention_core"):
            if decode_pos is not None:
                # One-token decode step: bias is the single row of the
                # full [max_decode_len, max_decode_len] relative-position
                # matrix at this step's position; the causal structure
                # comes from the attention cache's <=pos validity mask.
                # Per-row positions (a vector) come with one token per
                # row; the attention layer refuses anything else.
                bias = rel_pos(max_decode_len, max_decode_len, row=decode_pos)
                kv_mask = None
            else:
                bias = rel_pos(x.shape[1], x.shape[1])
        for i in range(self.n_layers):
            x = TransformerBlock(
                n_heads=self.n_heads, head_dim=self.head_dim, d_ff=self.d_ff,
                dropout_rate=self.dropout_rate, dtype=self.dtype,
                causal=self.causal, prenorm=True, norm="rmsnorm",
                mlp_dropout_site="hidden",   # T5's DenseReluDense recipe
                use_cross=self.causal and encoded is not None,
                attn_impl=self.attn_impl,
                mesh=self.mesh, name=f"layer_{i}",
            )(
                x, encoded=encoded, kv_mask=kv_mask, enc_mask=enc_mask,
                self_bias=bias, deterministic=deterministic,
                decode_pos=decode_pos, max_decode_len=max_decode_len,
            )
        with jax.named_scope("norm"):
            return nn.RMSNorm(dtype=self.dtype, name="final_norm")(x)


class T5(nn.Module):
    """batch {inputs, targets [, input_mask, target_mask]} -> vocab logits.

    ``targets`` are teacher-forcing decoder inputs shifted right internally
    (BOS = 0, the T5 convention).
    """

    vocab_size: int = 32128
    d_model: int = 512
    n_layers: int = 6
    n_heads: int = 8
    head_dim: int = 64
    d_ff: int = 2048
    dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16
    mesh: Optional[Mesh] = None
    attn_impl: str = "dense"   # decode-step kernel choice; see T5Stack

    def setup(self):
        self.shared = nn.Embed(
            self.vocab_size, self.d_model, dtype=self.dtype, name="shared"
        )
        common = dict(
            n_heads=self.n_heads, head_dim=self.head_dim, d_ff=self.d_ff,
            dropout_rate=self.dropout_rate, dtype=self.dtype, mesh=self.mesh,
            attn_impl=self.attn_impl,
        )
        self.encoder = T5Stack(n_layers=self.n_layers, causal=False,
                               name="encoder", **common)
        self.decoder = T5Stack(n_layers=self.n_layers, causal=True,
                               name="decoder", **common)

    def encode(self, inputs, input_mask=None, *, deterministic=True):
        with jax.named_scope("embed_head"):
            x = self.shared(jnp.asarray(inputs, jnp.int32))
        return self.encoder(x, kv_mask=input_mask, deterministic=deterministic)

    def decode(self, decoder_input_ids, encoded, *, target_mask=None,
               enc_mask=None, deterministic=True, decode_pos=None,
               max_decode_len=None):
        with jax.named_scope("embed_head"):
            y = self.shared(jnp.asarray(decoder_input_ids, jnp.int32))
        y = self.decoder(
            y, encoded=encoded, kv_mask=target_mask, enc_mask=enc_mask,
            deterministic=deterministic, decode_pos=decode_pos,
            max_decode_len=max_decode_len,
        )
        # tied embedding as the output projection, T5's 1/sqrt(d) scaling;
        # logits in float32 for a stable softmax loss
        with jax.named_scope("embed_head"):
            y = y * (self.d_model ** -0.5)
            return jnp.einsum(
                "bld,vd->blv", y.astype(jnp.float32),
                self.shared.embedding.astype(jnp.float32),
            )

    def __call__(self, batch: Dict[str, Any], *, deterministic: bool = True):
        inputs = jnp.asarray(batch["inputs"], jnp.int32)
        targets = jnp.asarray(batch["targets"], jnp.int32)
        input_mask = batch.get("input_mask")
        decoder_inputs = jnp.pad(targets, ((0, 0), (1, 0)))[:, :-1]
        encoded = self.encode(
            inputs, input_mask, deterministic=deterministic
        )
        return self.decode(
            decoder_inputs, encoded,
            target_mask=batch.get("target_mask"), enc_mask=input_mask,
            deterministic=deterministic,
        )


DEFAULT_HPARAMS = {
    # t5-small geometry
    "vocab_size": 32128,
    "d_model": 512,
    "n_layers": 6,
    "n_heads": 8,
    "head_dim": 64,
    "d_ff": 2048,
    "dropout_rate": 0.1,
    "learning_rate": 1e-3,
    "batch_size": 64,
}


def build_t5_model(hparams: Dict, mesh: Optional[Mesh] = None) -> T5:
    hp = {**DEFAULT_HPARAMS, **(hparams or {})}
    return T5(
        vocab_size=int(hp["vocab_size"]),
        d_model=int(hp["d_model"]),
        n_layers=int(hp["n_layers"]),
        n_heads=int(hp["n_heads"]),
        head_dim=int(hp["head_dim"]),
        d_ff=int(hp["d_ff"]),
        dropout_rate=float(hp["dropout_rate"]),
        attn_impl=str(hp.get("attn_impl", "dense")),
        mesh=mesh,
    )


def t5_partition_rules():
    return list(TRANSFORMER_PARTITION_RULES) + [
        (r"rel_pos/rel_embedding", P(None, "model")),
    ]


# ---------------------------------------------------------------------------
# Autoregressive generation (the seq2seq inference path).
#
# The reference's BulkInferrer/serving story for seq2seq needs real decoding,
# not teacher forcing.  TPU-first shape discipline: the whole decode is ONE
# jitted computation — encoder forward, then a lax.scan over decode steps,
# each step a single-token decoder pass against the static-shape KV cache
# (models/transformer.py decode path).  No growing arrays, no host round
# trips per token; EOS handling is masking, not control flow.
# ---------------------------------------------------------------------------


def _decode_one(model, params, cache, tok, encoded, enc_mask, pos,
                max_decode_len: int):
    """One single-token decoder pass; returns (new_cache, logits [b, V])."""
    variables = {"params": params}
    if cache is not None:
        variables["cache"] = cache
    with jax.named_scope("embed_head"):
        one = tok[:, None]
    logits, mut = model.apply(
        variables, one, encoded, enc_mask=enc_mask,
        decode_pos=pos, max_decode_len=max_decode_len,
        method=T5.decode, mutable=["cache"],
    )
    with jax.named_scope("embed_head"):
        return mut["cache"], logits[:, 0]


def prefill_decode(model, params, inputs, input_mask, max_decode_len: int,
                   pad_id: int = 0):
    """Encoder pass + the cache-creating step-0 decoder pass, once per ROW.

    The shared front half of every decode entry point: greedy, beam
    (which tiles this result across beams instead of re-running the
    encoder K/V projections and the step-0 decoder pass per beam) and the
    continuous-batching engine's per-request prefill
    (serving/generative.py) all run the identical step-0 math through
    here.  Returns ``(cache, encoded, logits0 [b, V])`` — the cache holds
    the BOS K/V at position 0 plus the cross-attention K/V projected from
    ``encoded``.
    """
    encoded = model.apply(
        {"params": params}, inputs, input_mask, method=T5.encode
    )
    with jax.named_scope("embed_head"):
        bos = jnp.full((inputs.shape[0],), pad_id, jnp.int32)
    cache, logits0 = _decode_one(
        model, params, None, bos, encoded, input_mask, 0, max_decode_len
    )
    return cache, encoded, logits0


def make_continuous_decode_fns(
    model: T5,
    *,
    max_decode_len: int = 32,
    eos_id: int = 1,
    pad_id: int = 0,
    max_input_len: int = 64,
):
    """T5's ``DecodeContract`` (models/decode_contract.py) for the
    continuous-batching engine: an encoder-decoder with a whole-prompt
    ``prefill``.

      - ``prefill`` is ``prefill_decode``: one request's encoder pass and
        the cache-creating step-0 decoder pass, the same math greedy and
        beam step 0 run;
      - two kinds of cache array: self-attention K/V by decode position,
        which a step writes and the engine cuts to the kv bucket, beside
        the cross-attention K/V at the encoder length, which a step only
        reads;
      - a sequence's first decode position is 1, behind the BOS that
        ``prefill`` consumed (the contract's default).

    Exported modules opt their payloads into generative serving by
    defining ``make_decode_fns(model, hyperparameters)`` returning this
    (trainer/export.py wires it onto ``LoadedModel.decode_fns``).
    """
    def cache_kind_of(path) -> str:
        # models/transformer.py names the cross-attention K/V
        # ``cached_enc_key`` / ``cached_enc_value``.
        cross = any("cached_enc" in str(getattr(p, "key", p)) for p in path)
        return "cross_kv" if cross else "decode_kv"

    def prefill(params, inputs, input_mask=None):
        return prefill_decode(
            model, params, inputs, input_mask, max_decode_len, pad_id
        )

    def step(params, cache, tok, pos, encoded, enc_mask, klen: int):
        variables = {"params": params, "cache": cache}
        with jax.named_scope("embed_head"):
            one = tok[:, None]
        logits, mut = model.apply(
            variables, one, encoded, enc_mask=enc_mask,
            decode_pos=pos, max_decode_len=klen,
            method=T5.decode, mutable=["cache"],
        )
        with jax.named_scope("embed_head"):
            return mut["cache"], logits[:, 0]

    return DecodeContract(
        prefill=prefill,
        step=step,
        cache_kinds={
            "decode_kv": CacheKind(by_position=True, written=True),
            "cross_kv": CacheKind(by_position=False, written=False),
        },
        cache_kind_of=cache_kind_of,
        max_decode_len=max_decode_len,
        eos_id=eos_id,
        pad_id=pad_id,
        max_input_len=max_input_len,
    )


def make_greedy_generate(
    model: T5,
    *,
    max_decode_len: int = 32,
    eos_id: int = 1,
    pad_id: int = 0,
    temperature: float = 0.0,
):
    """Build a jitted ``fn(params, inputs, input_mask=None, rng=None) ->
    (tokens [b, max_decode_len], done [b])``.

    ``temperature == 0`` is greedy argmax; ``> 0`` samples from the scaled
    softmax (``rng`` required).  Sequences emit EOS then pad; ``done`` marks
    rows that finished within the budget.  The T5 shift-right convention
    (BOS = pad = 0) starts the decoder.
    """
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")

    def pick(logits, rng):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / jnp.asarray(temperature, logits.dtype), axis=-1
        ).astype(jnp.int32)

    def fn(params, inputs, input_mask=None, rng=None):
        if temperature > 0.0 and rng is None:
            raise ValueError("sampling (temperature > 0) requires rng")
        if rng is None:
            rng = jax.random.key(0)
        # Step 0 runs outside the scan (prefill_decode): its mutable apply
        # CREATES the cache collection, so the scan carry has a fixed
        # structure.
        rng, r0 = jax.random.split(rng)
        cache, encoded, logits0 = prefill_decode(
            model, params, inputs, input_mask, max_decode_len, pad_id
        )
        tok0 = pick(logits0, r0)
        finished0 = tok0 == eos_id

        def step(carry, t):
            cache, tok, finished, rng = carry
            rng, r = jax.random.split(rng)
            cache, logits = _decode_one(
                model, params, cache, tok, encoded, input_mask, t,
                max_decode_len,
            )
            nxt = jnp.where(finished, pad_id, pick(logits, r))
            return (cache, nxt, finished | (nxt == eos_id), rng), nxt

        (_, _, finished, _), rest = jax.lax.scan(
            step, (cache, tok0, finished0, rng),
            jnp.arange(1, max_decode_len),
        )
        tokens = jnp.concatenate([tok0[:, None], rest.T], axis=1)
        return tokens, finished

    return jax.jit(fn)


def make_beam_generate(
    model: T5,
    *,
    beam_size: int = 4,
    max_decode_len: int = 32,
    eos_id: int = 1,
    pad_id: int = 0,
    length_alpha: float = 0.6,
):
    """Build a jitted beam search ``fn(params, inputs, input_mask=None) ->
    (tokens [b, max_decode_len], score [b])``.

    Freeze-in-place beams: a finished beam may only emit pad at zero added
    log-prob, so its cumulative score is frozen while it stays a candidate —
    one topk over ``beam_size * vocab`` per step, no separate alive/finished
    sets.  Final selection maximizes ``logp / ((5 + len) / 6) ** alpha``
    (the GNMT length penalty).  Encoder runs once; beams share it via a
    flat ``batch * beam`` layout, and each step reorders the KV cache with
    one gather.
    """

    def fn(params, inputs, input_mask=None):
        b, k = inputs.shape[0], beam_size
        # Encoder + step-0 decoder run ONCE PER ROW (prefill_decode — the
        # same entry greedy and the continuous-batch engine use) and the
        # result is TILED across beams below: the k beams of a row are
        # identical at step 0, so the old flat [b*k] step 0 re-ran the
        # encoder K/V projections and the BOS decoder pass k x for
        # nothing.
        cache, encoded, logits0 = prefill_decode(
            model, params, inputs, input_mask, max_decode_len, pad_id
        )
        # Flat [b*k, ...] layout: beam j of row i lives at i*k + j.  The
        # cross-attention K/V ride inside the tiled cache; flat_encoded
        # is only the decode call's x_kv placeholder from here on (the
        # cached projections are what attention reads), so XLA DCEs it.
        flat_encoded = jnp.repeat(encoded, k, axis=0)
        flat_enc_mask = (
            None if input_mask is None else jnp.repeat(input_mask, k, axis=0)
        )

        def reorder(tree, beam_idx):
            """Permute beam rows ([b, k] indices into the beam axis).

            As a ONE-HOT EINSUM, not take_along_axis: XLA:TPU lowers an
            axis-1 gather with a broadcast index tensor to a generic
            per-element gather — measured 795 ms/step on the beam-4 T5-small
            cache (v5e) vs 1.9 ms for the equivalent one-hot contraction,
            which is a dense [k x k] mix the MXU eats.  Exact because the
            one-hot matrix is a permutation/selection of rows.

            Cross-attention K/V (``cached_enc_*``) are identical across the
            k beams of a row — built by repeating one encoder pass — so
            reordering them is a no-op and they are skipped outright."""
            oh = jax.nn.one_hot(beam_idx, k)               # [b, new, old]

            def leaf(path, x):
                if any("cached_enc" in str(getattr(p, "key", p)) for p in path):
                    return x
                y = x.reshape(b, k, -1)
                # TPU DEFAULT matmul precision rounds f32 *inputs* to bf16;
                # for f32 caches that would requantize K/V every step, so
                # force HIGHEST there (bf16 caches are exact under DEFAULT).
                out = jnp.einsum(
                    "bji,bif->bjf", oh.astype(x.dtype), y,
                    preferred_element_type=x.dtype,
                    precision=(
                        jax.lax.Precision.HIGHEST
                        if x.dtype == jnp.float32 else None
                    ),
                )
                return out.reshape(x.shape)
            return jax.tree_util.tree_map_with_path(leaf, tree)

        vocab = logits0.shape[-1]
        logprobs0 = jax.nn.log_softmax(logits0.astype(jnp.float32))  # [b, V]
        # All beams share the step-0 distribution, so one top-k over the
        # per-row vocab picks the k DISTINCT first tokens directly.
        top0, idx0 = jax.lax.top_k(logprobs0, k)
        tok0 = idx0.astype(jnp.int32)                   # [b, k]
        # Tile the shared step-0 state into the beam layout: self-KV row 0
        # (the BOS K/V) is identical across beams, and the cross-attention
        # K/V were projected once per row instead of once per beam.
        cache = jax.tree_util.tree_map(
            lambda x: jnp.repeat(x, k, axis=0), cache
        )
        logp = top0                                     # [b, k]
        finished = tok0 == eos_id
        lengths = jnp.ones((b, k), jnp.int32)
        tokens = jnp.full((b, k, max_decode_len), pad_id, jnp.int32)
        tokens = tokens.at[:, :, 0].set(tok0)

        neg_inf = jnp.float32(-1e30)
        pad_only = jnp.where(
            jnp.arange(vocab) == pad_id, 0.0, neg_inf
        )[None, None, :]                                # finished: pad, +0

        def step(carry, t):
            cache, tok, logp, lengths, finished, tokens = carry
            cache, logits = _decode_one(
                model, params, cache, tok.reshape(b * k), flat_encoded,
                flat_enc_mask, t, max_decode_len,
            )
            lp = jax.nn.log_softmax(
                logits.astype(jnp.float32)
            ).reshape(b, k, vocab)
            cand = logp[:, :, None] + jnp.where(
                finished[:, :, None], pad_only, lp
            )
            top, idx = jax.lax.top_k(cand.reshape(b, k * vocab), k)
            beam_idx = idx // vocab
            nxt = (idx % vocab).astype(jnp.int32)
            cache = reorder(cache, beam_idx)
            take = lambda a: jnp.take_along_axis(a, beam_idx, axis=1)
            was_finished = take(finished)
            lengths = take(lengths) + jnp.where(was_finished, 0, 1)
            finished = was_finished | (nxt == eos_id)
            # Token history rides the same one-hot permutation as the cache,
            # in INTEGER arithmetic: a float einsum at TPU DEFAULT precision
            # rounds its f32 inputs to bf16, corrupting ids >= 257.  The
            # array is tiny ([b, k, L] int32), so the VPU integer path costs
            # nothing next to the decoder step.
            oh = jax.nn.one_hot(beam_idx, k, dtype=jnp.int32)
            tokens = jnp.einsum("bji,bil->bjl", oh, tokens)
            tokens = tokens.at[:, :, t].set(jnp.where(was_finished, pad_id, nxt))
            return (cache, nxt, top, lengths, finished, tokens), None

        (_, _, logp, lengths, _, tokens), _ = jax.lax.scan(
            step, (cache, tok0, logp, lengths, finished, tokens),
            jnp.arange(1, max_decode_len),
        )
        penalty = ((5.0 + lengths.astype(jnp.float32)) / 6.0) ** length_alpha
        score = logp / penalty                          # [b, k]
        best = jnp.argmax(score, axis=1)
        out = jnp.take_along_axis(
            tokens, best[:, None, None], axis=1
        )[:, 0]
        return out, jnp.take_along_axis(score, best[:, None], axis=1)[:, 0]

    return jax.jit(fn)
