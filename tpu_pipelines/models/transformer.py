"""Sharded transformer building blocks (backbone for BERT/T5 configs).

TPU-first design: every matmul is a large batched einsum that XLA tiles onto
the MXU in bfloat16; parallelism is declared, not coded — heads/FFN shard
over the mesh ``model`` axis (TP) via the partition rules below, batch over
``data`` (DP), and long sequences over ``seq`` via ring attention
(parallel/ring_attention.py).  The modules themselves contain no collectives;
XLA inserts them from the shardings, except the explicit ``ppermute`` ring
inside ring attention.

The reference's BERT/T5 workloads (SURVEY.md §0 configs 3-4) run through
these blocks; its only parallelism was data-parallel NCCL allreduce
(SURVEY.md §2c) — TP and SP here are TPU-native additions, kept optional
(mesh axes default to size 1).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

from tpu_pipelines.parallel.ring_attention import dense_attention, ring_attention

Dtype = Any

# "auto" attn_impl switchover is MEASURED where a measurement exists and
# memory-feasibility-bounded always (choose_attn_impl): the autotune table
# (ops/autotune.py) stores a per-device flash-vs-dense crossover sequence
# length (``autotune.record_crossover``) — dense below it, flash
# at/above it.  With no recorded crossover the rule degrades to the
# feasibility estimate alone: dense wherever its O(L^2) score temporaries
# fit in HBM (XLA fuses the fwd score/softmax chain well; which of the two
# is faster on the chip below the crossover is in PERF.md, or "not
# measured").  Flash's unconditional win is FEASIBILITY: the dense
# temporaries scale with L^2 — at L=8192 (b=8 h=12 d=64) the fwd+bwd wants
# tens of GB and cannot compile on a 16 GB chip, while flash runs in
# O(block^2) VMEM scratch.  The feasibility estimate (the OOM guard):
#
#   temp ~= DENSE_ATTN_TEMP_FACTOR * B * H * Lq * Lkv * itemsize
#
# FACTOR=3: score + softmax-prob + dscore buffers, each [B,H,L,L], are
# live at the backward peak.
DENSE_ATTN_TEMP_FACTOR = 3.0
# Dense is chosen while its temp estimate stays under this fraction of
# device memory — headroom for params, optimizer state and activations.
# Override per-process with TPP_DENSE_ATTN_HBM_FRACTION.
DENSE_ATTN_HBM_FRACTION = 0.4
# Long-context gate for "auto" on a mesh whose 'seq' axis is populated:
# self-attention at/above this sequence length rides ring attention
# (sequence-parallel ppermute ring, parallel/ring_attention.py) inside
# the windowed train loop.  Override per-process with TPP_RING_MIN_SEQ.
RING_MIN_SEQ = 2048


def _device_memory_bytes() -> int:
    """Per-device accelerator memory, for the auto attention choice.

    TPP_HBM_BYTES overrides; otherwise the backend's own bytes_limit.
    Only the CPU backend (tests, dry runs), which reports none, is given
    a stand-in — 16 GiB, one v5e chip, so CPU runs take the branches a
    chip run would.  An accelerator that reports no limit raises: a
    guessed size would pick dense or flash for memory it does not have."""
    env = os.environ.get("TPP_HBM_BYTES")
    if env:
        return int(env)
    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if dev.platform == "cpu":
        return 16 * 1024**3
    raise RuntimeError(
        f"{dev.device_kind!r} reports no memory limit; set TPP_HBM_BYTES"
    )


def dense_attn_expected_temp_bytes(
    batch: int,
    heads: int,
    seq_q: int,
    seq_kv: int,
    itemsize: int = 2,
    mesh: Optional[Mesh] = None,
) -> int:
    """Calibrated estimate of dense attention's O(L^2) XLA temporaries
    (per shard when a mesh divides batch over ``data`` / heads over
    ``model``).  Exposed so callers that must *skip* a dense compile
    cleanly can record the number they acted on
    instead of depending on a backend error string."""
    if mesh is not None:
        shape = dict(mesh.shape)
        batch = -(-batch // max(1, shape.get("data", 1)))
        heads = -(-heads // max(1, shape.get("model", 1)))
    return int(
        DENSE_ATTN_TEMP_FACTOR * batch * heads * seq_q * seq_kv * itemsize
    )


def dense_attn_fits(
    batch: int,
    heads: int,
    seq_q: int,
    seq_kv: int,
    itemsize: int = 2,
    mesh: Optional[Mesh] = None,
) -> bool:
    """True when dense attention's O(L^2) temporaries fit comfortably —
    the OOM guard inside the "auto" attn_impl rule (see module comment
    for the calibration; ``choose_attn_impl`` layers the measured
    crossover on top).

    The estimate is PER SHARD: on a mesh, the batch dim shards over the
    ``data`` axis and heads over ``model`` (TP), so each device only
    materializes its slice of the [B, H, Lq, Lkv] score tensor.  Without
    the division, "auto" flipped to flash on multi-chip geometries where
    dense fits per-device and is ~25% faster (round-5 advisor finding)."""
    frac = float(
        os.environ.get("TPP_DENSE_ATTN_HBM_FRACTION", DENSE_ATTN_HBM_FRACTION)
    )
    temp = dense_attn_expected_temp_bytes(
        batch, heads, seq_q, seq_kv, itemsize, mesh=mesh
    )
    return temp <= frac * _device_memory_bytes()


def choose_attn_impl(
    batch: int,
    heads: int,
    seq_q: int,
    seq_kv: int,
    itemsize: int = 2,
    mesh: Optional[Mesh] = None,
) -> str:
    """The measured "auto" rule: dense vs flash from the autotune table's
    per-device crossover, with memory feasibility as the OOM guard only.

    Decision order:
      0. the mesh's ``seq`` axis is populated and the (self-attention)
         shape is long-context — ``seq_q == seq_kv`` at/above
         ``TPP_RING_MIN_SEQ`` (default 2048), or even the per-shard dense
         tile doesn't fit — => "ring": the sequence is sharded over the
         axis, so single-device kernels never see the full L; ring
         attention streams the kv blocks around the mesh with overlapped
         ``ppermute`` (the long-context window path, ISSUE 18).  Short
         sequences on a seq mesh stay on the measured rule below — the
         ring's per-hop latency only pays for itself once L is large;
      1. dense's O(L^2) temporaries don't fit => "flash" (the guard —
         feasibility, exactly what ``dense_attn_fits`` was built for);
      2. a measured crossover exists for this device_kind
         (``autotune.record_crossover``)
         => "flash" at/above it, "dense" below it;
      3. no measurement => "dense" (every probe so far measured dense
         faster wherever it fits; flash must EARN the hot path).
    """
    if (
        mesh is not None
        and mesh.shape.get("seq", 1) > 1
        and seq_q == seq_kv
    ):
        floor = int(os.environ.get("TPP_RING_MIN_SEQ", RING_MIN_SEQ))
        if seq_q >= floor or not dense_attn_fits(
            batch, heads, seq_q, seq_kv, itemsize, mesh=mesh
        ):
            return "ring"
    if not dense_attn_fits(batch, heads, seq_q, seq_kv, itemsize, mesh=mesh):
        return "flash"
    from tpu_pipelines.ops import autotune

    crossover = autotune.lookup_crossover()
    if crossover is not None and max(seq_q, seq_kv) >= crossover:
        return "flash"
    return "dense"


def choose_decode_impl(
    batch: int,
    heads: int,
    kv_len: int,
    head_dim: int,
) -> str:
    """The "auto" rule for the single-query DECODE regime (KV-cache
    attention during autoregressive generation).

    A decode step's score temporaries are [B, H, 1, L] — tiny — so there
    is no OOM guard here; the only question is measured speed.  The
    decode step streams the whole KV cache per token, a bandwidth-bound
    profile unlike the training shapes, so it gets its OWN crossover
    (``autotune.lookup_decode_crossover``): flash-decode at/above the measured cache length,
    dense below it, and dense whenever no measurement exists — the
    kernel must earn the hot path, same as training flash (PR 9).
    """
    del batch, heads, head_dim  # keyed per device kind + cache length only
    from tpu_pipelines.ops import autotune

    crossover = autotune.lookup_decode_crossover()
    if crossover is not None and kv_len >= crossover:
        return "flash"
    return "dense"


class MlpBlock(nn.Module):
    d_ff: int
    dropout_rate: float = 0.0
    dtype: Dtype = jnp.bfloat16
    activation: str = "gelu"
    # Where dropout lands, matching each family's canonical recipe:
    # "output" (BERT: HF BertOutput drops the d_model-wide projection) or
    # "hidden" (T5: DenseReluDense drops the d_ff-wide activation).  The
    # site is also a throughput lever — dropout RNG+mask is a measured
    # share of the BERT-base fine-tune step on v5e (PERF.md §5), and the
    # output site has 4x fewer mask elements than the hidden site at BERT
    # geometry.
    dropout_site: str = "output"

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        d_model = x.shape[-1]
        with jax.named_scope("mlp"):
            h = nn.Dense(self.d_ff, dtype=self.dtype, name="wi")(x)
            h = getattr(nn, self.activation)(h)
        if self.dropout_rate and self.dropout_site == "hidden":
            with jax.named_scope("dropout"):
                h = nn.Dropout(self.dropout_rate)(
                    h, deterministic=deterministic)
        with jax.named_scope("mlp"):
            out = nn.Dense(d_model, dtype=self.dtype, name="wo")(h)
        if self.dropout_rate and self.dropout_site == "output":
            with jax.named_scope("dropout"):
                out = nn.Dropout(self.dropout_rate)(
                    out, deterministic=deterministic)
        return out


class MoEMlpBlock(nn.Module):
    """Switch-Transformer-style mixture-of-experts MLP (expert parallelism).

    Top-1 routing with a fixed per-expert capacity, implemented as DENSE
    dispatch/combine einsums over a [tokens, experts, capacity] one-hot —
    the Mesh-TF/Switch algorithm: no ragged shapes, everything tiles onto
    the MXU, and sharding the expert dim of ``wi``/``wo`` over the mesh
    ``expert`` axis (TRANSFORMER_PARTITION_RULES) makes XLA insert the
    dispatch all-to-alls from the shardings alone — no hand-written
    collectives, consistent with the rest of this module.

    Tokens routed past an expert's capacity are DROPPED (output zero);
    the surrounding residual connection carries them through unchanged —
    standard Switch behavior.  Routing is PER GROUP (default: one group
    per sequence row, the Mesh-TF convention): capacity and the dispatch
    one-hot scale with the group size, not the whole flattened batch, so
    dispatch cost stays linear in total tokens.  The load-balancing
    auxiliary loss (E * sum over experts of token_fraction * prob_fraction;
    1.0 at perfect balance) is sown into the ``losses`` collection as
    ``moe_aux_loss`` — training objectives MUST consume it or routing can
    collapse onto one expert; use :func:`apply_with_moe_aux` in a loss_fn:

        logits, aux = apply_with_moe_aux(model, {"params": p}, batch, ...)
        loss = task_loss(logits) + 0.01 * aux
    """

    num_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    dtype: Dtype = jnp.bfloat16
    activation: str = "gelu"
    dropout_rate: float = 0.0
    group_size: int = 0     # tokens per routing group; 0 = sequence length

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        b, l, d = x.shape
        n = b * l
        e = self.num_experts
        g_size = self.group_size or l
        if n % g_size:
            raise ValueError(
                f"{n} tokens not divisible by MoE group_size {g_size}"
            )
        n_groups = n // g_size
        t = x.reshape(n_groups, g_size, d)
        # Router in f32: tiny matmul, and argmax ties/softmax stability
        # matter more than MXU throughput here.
        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            t.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)            # [G, g, e]
        expert = jnp.argmax(probs, axis=-1)                # [G, g]
        gate = jnp.take_along_axis(probs, expert[..., None], axis=-1)[..., 0]

        capacity = max(1, int(np.ceil(self.capacity_factor * g_size / e)))
        sel = jax.nn.one_hot(expert, e, dtype=jnp.int32)   # [G, g, e]
        # Position of each token in its expert's per-group queue.
        pos = jnp.cumsum(sel, axis=1) * sel                # 1-based where sel
        pos_in_expert = pos.sum(axis=-1) - 1               # [G, g], -1 if none
        keep = (pos_in_expert >= 0) & (pos_in_expert < capacity)
        dispatch = (
            sel.astype(self.dtype)[..., None]
            * jax.nn.one_hot(
                jnp.where(keep, pos_in_expert, capacity),
                capacity, dtype=self.dtype,
            )[:, :, None, :]
        )                                                   # [G, g, e, c]

        # batch_axis=0: fan is computed PER EXPERT slice — plain
        # lecun_normal would count the expert dim as receptive field and
        # under-scale every expert by sqrt(e) vs the dense MLP it replaces.
        expert_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=0,
        )
        wi = self.param(
            "wi", expert_init, (e, d, self.d_ff)
        ).astype(self.dtype)
        wo = self.param(
            "wo", expert_init, (e, self.d_ff, d)
        ).astype(self.dtype)
        expert_in = jnp.einsum(
            "gnec,gnd->gecd", dispatch, t.astype(self.dtype)
        )
        h = getattr(nn, self.activation)(
            jnp.einsum("gecd,edf->gecf", expert_in, wi)
        )
        expert_out = jnp.einsum("gecf,efd->gecd", h, wo)
        combine = dispatch * gate.astype(self.dtype)[..., None, None]
        out = jnp.einsum("gnec,gecd->gnd", combine, expert_out)

        # Switch aux loss: e * sum_e(fraction_of_tokens * mean_router_prob).
        frac_tokens = sel.astype(jnp.float32).mean(axis=(0, 1))  # [e]
        frac_probs = probs.mean(axis=(0, 1))                     # [e]
        self.sow(
            "losses", "moe_aux_loss",
            e * jnp.sum(frac_tokens * frac_probs),
        )
        out = out.reshape(b, l, d)
        if self.dropout_rate:
            # Same output-site dropout as the dense MlpBlock it replaces.
            with jax.named_scope("dropout"):
                out = nn.Dropout(self.dropout_rate)(
                    out, deterministic=deterministic
                )
        return out


def apply_with_moe_aux(model, variables, *args, **kwargs):
    """``model.apply`` that also returns the summed MoE auxiliary loss.

    The supported way to train MoE models: runs apply with the ``losses``
    collection mutable and sums every sown ``moe_aux_loss`` (one per MoE
    layer; 0.0 when the model has none), so loss functions can add
    ``aux_weight * aux`` without touching flax collection plumbing.
    """
    out, state = model.apply(variables, *args, mutable=["losses"], **kwargs)
    leaves = jax.tree_util.tree_leaves(state.get("losses", {}))
    aux = sum(leaves) if leaves else jnp.zeros((), jnp.float32)
    return out, aux


def _write_rows_at(cache, new, pos):
    """``cache [b, kv, heads, head_dim]`` with row ``i``'s new entry
    ``new[i] [1, heads, head_dim]`` written at position ``pos[i]``: the
    decode step's K/V, each row at its own position.

    A select on a one-hot of the position, not a scatter.  The chip keeps
    a by-position cache position-minor (``{1,3,2,0}`` for bf16 with a
    ``head_dim`` of 64, half a lane row), which is how the attention reads
    it.  ``cache.at[rows, pos].set(new)`` lowers to a scatter that wants
    the written window minor, so the compiler copies the whole bucket to
    ``{3,2,1,0}``, scatters ``b`` rows of 2 KB into it and copies it
    back, per layer, for keys and for values: two thirds of the step at
    64 x 256.  A row-wise ``dynamic_update_slice`` under ``vmap`` does the
    same in every bucket smaller than the arena.  The select has no
    preferred layout and is fused into the attention's own pass over the
    cache: one write of the leaf, no second read (PERF.md §6, PR 28;
    tests/test_tpu_compile.py holds the compiled programs to it).

    A position outside ``[0, kv)`` is dropped, as ``.at[].set`` drops
    it: a dead slot's stale ``pos`` past a smaller bucket writes nothing
    (``dynamic_update_slice`` would clamp it onto the row's last
    position).  Every row whose output the engine uses lies inside; a row
    writes only into itself."""
    at = jnp.arange(cache.shape[1])[None, :] - pos[:, None]      # [b, kv]
    return jnp.where((at == 0)[:, :, None, None], new, cache)


class MultiHeadAttention(nn.Module):
    """Self/cross attention; TP over heads, optional ring SP over sequence.

    ``attn_impl``:
      - "dense": plain XLA attention (any mask/bias/cross).
      - "ring":  sequence-parallel ring attention over the mesh ``seq``
        axis (ppermute pipeline; scales past one chip's memory).
      - "ulysses": all-to-all sequence parallelism over ``seq`` (two
        collectives, full-sequence dense math per head slice; lower latency
        at moderate lengths, needs local heads divisible by the axis).
      - "flash": the Pallas blockwise kernel (ops/flash_attention.py) — no
        O(L²) score tensor in HBM, fwd and bwd.
      - "auto":  measured flash-vs-dense choice (choose_attn_impl): dense
        below the device's recorded crossover sequence length (autotune
        table), flash at/above
        it, and always flash when dense's O(L²) score temporaries cannot
        fit (dense_attn_fits stays as the OOM guard).  With no recorded
        crossover: dense wherever it fits (flash's unconditional win is
        running at L=8192+, where dense cannot compile).
    Ring/ulysses/flash require self-attention without an additive bias;
    cross attention and biased attention (T5 relative positions) always
    take the dense path.
    """

    n_heads: int
    head_dim: int
    dropout_rate: float = 0.0
    dtype: Dtype = jnp.bfloat16
    attn_impl: str = "dense"
    mesh: Optional[Mesh] = None
    causal: bool = False

    @nn.compact
    def __call__(
        self,
        x_q,
        x_kv=None,
        *,
        kv_mask=None,
        bias=None,
        deterministic: bool = True,
        decode_pos=None,
        max_decode_len: Optional[int] = None,
    ):
        is_self = x_kv is None
        x_kv = x_q if is_self else x_kv
        proj = lambda name: nn.DenseGeneral(
            (self.n_heads, self.head_dim), axis=-1, dtype=self.dtype, name=name
        )
        out_proj = lambda: nn.DenseGeneral(
            x_q.shape[-1], axis=(-2, -1), dtype=self.dtype, name="out"
        )
        with jax.named_scope("attention_proj"):
            q = proj("query")(x_q)

        if decode_pos is not None and not is_self:
            # Cross attention during incremental decoding: the encoder output
            # is constant across decode steps, so its K/V projections are
            # computed exactly once — the variable initializer runs only on
            # the cache-creating apply (step 0) and later steps reuse the
            # stored arrays instead of re-projecting [b, enc_len, d_model]
            # through two matmuls per layer per token.
            with jax.named_scope("attention_proj"):
                cached_ek = self.variable(
                    "cache", "cached_enc_key", lambda: proj("key")(x_kv)
                )
                cached_ev = self.variable(
                    "cache", "cached_enc_value", lambda: proj("value")(x_kv)
                )
            with jax.named_scope("attention_core"):
                out = dense_attention(
                    q, cached_ek.value, cached_ev.value, causal=False,
                    kv_mask=kv_mask, bias=bias,
                )
            with jax.named_scope("attention_proj"):
                return out_proj()(out)

        with jax.named_scope("attention_proj"):
            k = proj("key")(x_kv)
            v = proj("value")(x_kv)

        if decode_pos is not None and is_self:
            # Incremental decoding: x_q is this step's single token
            # ([b, 1, d_model]); K/V land in a static-shape cache at
            # ``decode_pos`` and attention runs over the filled prefix.
            # The cache is a flax "cache" collection created on the first
            # mutable apply — static shapes keep the whole decode loop
            # jit/scan-compatible (no growing arrays).
            #
            # ``decode_pos`` may be a scalar (every row at the same step:
            # the greedy/beam scan) or a [b] vector (continuous batching:
            # each sequence in the batch sits at its OWN step, so each
            # row's K/V go to its own position, by ``_write_rows_at``'s
            # select: the chip keeps the cache position-minor and a
            # scatter would re-lay it out at every step; the validity
            # mask is per-row).  Both paths compute identical per-row math.
            if max_decode_len is None:
                raise ValueError("decode_pos requires max_decode_len")
            b = q.shape[0]
            with jax.named_scope("cache_write"):
                cached_k = self.variable(
                    "cache", "cached_key", jnp.zeros,
                    (b, max_decode_len, self.n_heads, self.head_dim),
                    k.dtype,
                )
                cached_v = self.variable(
                    "cache", "cached_value", jnp.zeros,
                    (b, max_decode_len, self.n_heads, self.head_dim),
                    v.dtype,
                )
            pos = jnp.asarray(decode_pos, jnp.int32)
            if pos.ndim == 0:
                with jax.named_scope("cache_write"):
                    cached_k.value = jax.lax.dynamic_update_slice_in_dim(
                        cached_k.value, k, pos, axis=1
                    )
                    cached_v.value = jax.lax.dynamic_update_slice_in_dim(
                        cached_v.value, v, pos, axis=1
                    )
                # Positions after ``pos`` are zeros (future steps): mask.
                with jax.named_scope("attention_core"):
                    valid = jnp.broadcast_to(
                        (jnp.arange(max_decode_len) <= pos)[None, :],
                        (b, max_decode_len),
                    )
            elif q.shape[1] == 1:
                with jax.named_scope("cache_write"):
                    cached_k.value = _write_rows_at(cached_k.value, k, pos)
                    cached_v.value = _write_rows_at(cached_v.value, v, pos)
                with jax.named_scope("attention_core"):
                    valid = (
                        jnp.arange(max_decode_len)[None, :] <= pos[:, None]
                    )
            else:
                raise ValueError(
                    "per-row decode positions come with one token per "
                    f"row, got {q.shape[1]}"
                )
            impl = self.attn_impl
            if impl == "auto":
                # Decode-regime choice: the single-query step is bandwidth-
                # bound on the KV cache, a different balance from training
                # attention — its own measured crossover applies
                # (choose_decode_impl), never the training-shape one.
                impl = choose_decode_impl(
                    b, self.n_heads, max_decode_len, self.head_dim
                )
            with jax.named_scope("attention_core"):
                if impl == "flash":
                    from tpu_pipelines.ops.flash_attention import (
                        flash_decode_attention,
                    )

                    out = flash_decode_attention(
                        q, cached_k.value, cached_v.value,
                        kv_mask=valid, bias=bias,
                    )
                else:
                    out = dense_attention(
                        q, cached_k.value, cached_v.value, causal=False,
                        kv_mask=valid, bias=bias,
                    )
            with jax.named_scope("attention_proj"):
                return out_proj()(out)

        impl = self.attn_impl
        if impl == "auto":
            # Measured crossover (autotune table) over per-shard memory
            # feasibility: dense below the device's recorded flash-vs-dense
            # crossover, flash at/above it, and always flash when dense's
            # per-shard O(L^2) score footprint cannot fit (the OOM guard).
            impl = choose_attn_impl(
                q.shape[0], self.n_heads, q.shape[1], k.shape[1],
                jnp.dtype(self.dtype).itemsize,
                mesh=self.mesh,
            )
        has_seq_axis = (
            self.mesh is not None and self.mesh.shape.get("seq", 1) > 1
        )
        use_ring = impl == "ring" and is_self and bias is None and has_seq_axis
        use_ulysses = (
            impl == "ulysses" and is_self and bias is None and has_seq_axis
        )
        use_flash = (
            impl == "flash" and is_self and bias is None
        )
        with jax.named_scope("attention_core"):
            if use_ring:
                out = ring_attention(
                    q, k, v, mesh=self.mesh, causal=self.causal,
                    kv_mask=kv_mask,
                )
            elif use_ulysses:
                from tpu_pipelines.parallel.ring_attention import (
                    ulysses_attention,
                )

                out = ulysses_attention(
                    q, k, v, mesh=self.mesh, causal=self.causal,
                    kv_mask=kv_mask,
                )
            elif use_flash:
                from tpu_pipelines.ops.flash_attention import flash_attention

                out = flash_attention(
                    q, k, v, causal=self.causal, kv_mask=kv_mask
                )
            else:
                out = dense_attention(
                    q, k, v, causal=self.causal, kv_mask=kv_mask, bias=bias
                )
        with jax.named_scope("attention_proj"):
            out = out_proj()(out)
        if self.dropout_rate:
            with jax.named_scope("dropout"):
                out = nn.Dropout(self.dropout_rate)(
                    out, deterministic=deterministic
                )
        return out


class TransformerBlock(nn.Module):
    """Pre- or post-LN encoder/decoder block (self-attn [+cross] + MLP)."""

    n_heads: int
    head_dim: int
    d_ff: int
    dropout_rate: float = 0.0
    dtype: Dtype = jnp.bfloat16
    attn_impl: str = "dense"
    mesh: Optional[Mesh] = None
    causal: bool = False
    prenorm: bool = True
    use_cross: bool = False
    norm: str = "layernorm"   # "layernorm" (BERT) or "rmsnorm" (T5)
    mlp_dropout_site: str = "output"   # see MlpBlock.dropout_site
    # > 0 replaces the dense MLP with a MoEMlpBlock of this many experts
    # (expert-parallel over the mesh ``expert`` axis).
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25

    @nn.compact
    def __call__(
        self,
        x,
        *,
        encoded=None,
        kv_mask=None,
        enc_mask=None,
        self_bias=None,
        deterministic: bool = True,
        decode_pos=None,
        max_decode_len: Optional[int] = None,
    ):
        mha = lambda name, causal: MultiHeadAttention(
            n_heads=self.n_heads, head_dim=self.head_dim,
            dropout_rate=self.dropout_rate, dtype=self.dtype,
            attn_impl=self.attn_impl, mesh=self.mesh, causal=causal,
            name=name,
        )
        norm_cls = nn.RMSNorm if self.norm == "rmsnorm" else nn.LayerNorm
        ln = lambda name: norm_cls(dtype=self.dtype, name=name)

        def normed(name, x):
            with jax.named_scope("norm"):
                return ln(f"{name}_norm")(x)

        def sub(x, name, fn):
            # The add that takes a sub-layer into the stream is booked
            # with the part that closes the sub-layer.
            part = "mlp" if name == "mlp" else "attention_proj"
            y = fn(normed(name, x) if self.prenorm else x)
            with jax.named_scope(part):
                x = x + y
            return x if self.prenorm else normed(name, x)

        x = sub(x, "attn", lambda h: mha("attn", self.causal)(
            h, kv_mask=kv_mask, bias=self_bias, deterministic=deterministic,
            decode_pos=decode_pos, max_decode_len=max_decode_len,
        ))
        if self.use_cross:
            x = sub(x, "cross", lambda h: mha("cross", False)(
                h, encoded, kv_mask=enc_mask, deterministic=deterministic,
                decode_pos=decode_pos,
            ))
        if self.moe_experts > 0:
            def moe(h):
                with jax.named_scope("mlp"):
                    return MoEMlpBlock(
                        num_experts=self.moe_experts, d_ff=self.d_ff,
                        capacity_factor=self.moe_capacity_factor,
                        dropout_rate=self.dropout_rate,
                        dtype=self.dtype, name="moe",
                    )(h, deterministic=deterministic)

            x = sub(x, "mlp", moe)
        else:
            x = sub(x, "mlp", lambda h: MlpBlock(
                d_ff=self.d_ff, dropout_rate=self.dropout_rate,
                dtype=self.dtype, dropout_site=self.mlp_dropout_site,
                name="mlp",
            )(h, deterministic=deterministic))
        return x


# Megatron-style TP rules for the blocks above (parallel/partition.py):
# QKV projections and MLP wi shard their output dim over `model`
# (column-parallel); attention out and MLP wo shard their input dim
# (row-parallel) so XLA inserts one all-reduce per block, over ICI.
TRANSFORMER_PARTITION_RULES = [
    (r"(query|key|value)/kernel", P(None, "model", None)),
    (r"attn/out/kernel", P("model", None, None)),
    (r"cross/out/kernel", P("model", None, None)),
    (r"mlp/wi/kernel", P(None, "model")),
    (r"mlp/wo/kernel", P("model", None)),
    # MoE experts shard over `expert` (EP), their ff dim over `model` (TP);
    # the router stays replicated (tiny).
    (r"moe/wi", P("expert", None, "model")),
    (r"moe/wo", P("expert", "model", None)),
    # token embeddings only (vocab dim sharded); positional/type tables are
    # small and replicate — (^|/) anchors to a whole path segment so
    # e.g. "type_embed" does not match.
    (r"(^|/)(embed|shared)/embedding", P("model", None)),
]
