"""Sequence/context parallelism over the mesh ``seq`` axis: ring + Ulysses.

Long-context scaling the TPU way (SURVEY.md §5 long-context), two
complementary strategies over the same sharding layout:

  - :func:`ring_attention` — each device holds one Q block and streams K/V
    blocks around the ring with ``ppermute`` over ICI, accumulating softmax
    online (flash-attention style running max/denominator).  Peak memory
    per chip is O(L/n · L/n) score tiles instead of O(L²), and the K/V
    transfer overlaps with the block matmuls — XLA pipelines the
    ``ppermute`` against the einsums.  Scales to sequences that never fit
    one chip; n-1 pipelined hops.

  - :func:`ulysses_attention` — two ``all_to_all`` collectives re-shard
    from sequence-parallel to HEAD-parallel and back: each device then
    holds the FULL sequence for h/n heads and runs plain dense attention
    locally.  Lower latency at moderate sequence lengths (2 collectives vs
    n-1 hops) and exactly reproduces dense attention per head; requires
    local head count divisible by the ``seq`` axis, and per-chip memory is
    O(L²/n) scores — the full-sequence tile, so the ceiling is lower than
    ring's.

No NCCL/MPI equivalents: the collectives are single ``lax.ppermute`` /
``lax.all_to_all`` ops emitted inside ``shard_map``; the same code runs on
the CPU test mesh and a TPU slice.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30  # finite mask value: exp underflows to 0, no NaN plumbing


def dense_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    kv_mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Plain attention. q,k,v: [batch, len, heads, head_dim].

    ``kv_mask``: [batch, kv_len] 1/0 validity (padding) mask.
    ``bias``: additive [*, heads, q_len, kv_len] score term (e.g. T5
    relative positions).
    """
    s = _scores(q, k, causal=causal, kv_mask=kv_mask, bias=bias,
                q_offset=0, kv_offset=0)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v
    ).astype(q.dtype)


def _scores(q, k, *, causal, kv_mask, bias, q_offset, kv_offset):
    """Masked f32 score tensor [b, h, lq, lk]; offsets give global positions
    for causal masking when q/k are blocks of a longer sequence."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        s = s + bias
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = kv_offset + jnp.arange(k.shape[1])
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, NEG_INF)
    return s


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = False,
    kv_mask: Optional[jnp.ndarray] = None,
    batch_axis: str = "data",
    head_axis: str = "model",
) -> jnp.ndarray:
    """Sequence-parallel attention over mesh axis ``axis``.

    Global shapes: q,k,v [batch, seq, heads, head_dim], sharded
    batch→``batch_axis``, seq→``axis``, heads→``head_axis``; kv_mask
    [batch, seq].  Equals :func:`dense_attention` on the gathered arrays
    (up to rows whose whole causal∩valid key set is empty — dense softmax
    leaves them uniform, ring leaves them zero).

    Call inside jit; ``shard_map`` partitions per the specs below and the
    per-device function streams K/V blocks with ``ppermute``.
    """
    n = mesh.shape[axis]
    if n == 1:
        return dense_attention(q, k, v, causal=causal, kv_mask=kv_mask)

    blk_len = q.shape[1] // n
    if blk_len * n != q.shape[1]:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by mesh axis {axis}={n}"
        )
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_mask = kv_mask is not None

    def local_fn(q, k, v, kmask):
        # q,k,v local: [b, blk, h, d]; kmask: [b, blk] or None
        idx = jax.lax.axis_index(axis)

        def body(carry, step):
            o, m, l, k, v, kmask = carry
            kv_blk = (idx - step) % n
            s = _scores(
                q, k, causal=causal, kv_mask=kmask, bias=None,
                q_offset=idx * blk_len, kv_offset=kv_blk * blk_len,
            )                                          # [b, h, lq, lk] f32
            s_max = jnp.max(s, axis=-1)                # [b, h, lq]
            m_new = jnp.maximum(m, s_max)
            corr = jnp.exp(m - m_new)                  # 0 on first real block
            p = jnp.exp(s - m_new[..., None])
            # Zero masked entries even when the whole block is masked
            # (there s == m_new == NEG_INF and the exp above gives 1).
            p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32,
            )
            o_new = o * corr.transpose(0, 2, 1)[..., None] + pv

            # Stream K/V (and padding mask, when present) to the next
            # device; the last block's rotation would only restore the
            # start state, so skip it.  `kmask` may be None — that's an
            # empty pytree, so it rides the carry/cond for free.
            def rotate(args):
                return jax.tree_util.tree_map(
                    lambda a: jax.lax.ppermute(a, axis, perm), args
                )

            k, v, kmask = jax.lax.cond(
                step < n - 1, rotate, lambda args: args, (k, v, kmask)
            )
            return (o_new, m_new, l_new, k, v, kmask), None

        b, lq, h, d = q.shape
        o0 = jnp.zeros((b, lq, h, d), jnp.float32)
        m0 = jnp.full((b, h, lq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, lq), jnp.float32)
        (o, m, l, *_), _ = jax.lax.scan(
            body, (o0, m0, l0, k, v, kmask), jnp.arange(n)
        )
        denom = l.transpose(0, 2, 1)[..., None]        # [b, lq, h, 1]
        return (o / jnp.maximum(denom, 1e-30)).astype(q.dtype)

    qkv_spec = P(batch_axis, axis, head_axis, None)
    mask_spec = P(batch_axis, axis)
    if has_mask:
        return jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
            out_specs=qkv_spec,
            check_vma=False,
        )(q, k, v, kv_mask)
    return jax.shard_map(
        lambda q, k, v: local_fn(q, k, v, None),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = False,
    kv_mask: Optional[jnp.ndarray] = None,
    batch_axis: str = "data",
    head_axis: str = "model",
) -> jnp.ndarray:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Same global shapes/shardings as :func:`ring_attention`: q,k,v
    [batch, seq, heads, head_dim] sharded batch→``batch_axis``,
    seq→``axis``, heads→``head_axis``; kv_mask [batch, seq].

    Per device: ``all_to_all`` re-shards [b, L/n, h, d] → [b, L, h/n, d]
    (full sequence, a head slice), plain dense attention runs locally —
    bit-for-bit the dense math per head — and a second ``all_to_all``
    restores sequence sharding.  Requires the LOCAL head count (after any
    ``head_axis`` TP split) to divide by the ``seq`` axis size.
    """
    n = mesh.shape[axis]
    if n == 1:
        return dense_attention(q, k, v, causal=causal, kv_mask=kv_mask)
    if q.shape[1] % n:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by mesh axis {axis}={n}"
        )

    def local_fn(q, k, v, kmask):
        # q,k,v local: [b, L/n, h_local, d]
        if q.shape[2] % n:
            raise ValueError(
                f"local head count {q.shape[2]} not divisible by mesh axis "
                f"{axis}={n} (ulysses re-shards heads across the seq axis; "
                "use ring attention for head counts below the axis size)"
            )
        a2a = lambda x, split, concat: jax.lax.all_to_all(
            x, axis, split_axis=split, concat_axis=concat, tiled=True
        )
        qf = a2a(q, 2, 1)                 # [b, L, h_local/n, d]
        kf = a2a(k, 2, 1)
        vf = a2a(v, 2, 1)
        mask_f = (
            None if kmask is None
            else jax.lax.all_gather(kmask, axis, axis=1, tiled=True)
        )
        out = dense_attention(qf, kf, vf, causal=causal, kv_mask=mask_f)
        return a2a(out, 1, 2)             # back to [b, L/n, h_local, d]

    qkv_spec = P(batch_axis, axis, head_axis, None)
    mask_spec = P(batch_axis, axis)
    if kv_mask is not None:
        return jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
            out_specs=qkv_spec,
            check_vma=False,
        )(q, k, v, kv_mask)
    return jax.shard_map(
        lambda q, k, v: local_fn(q, k, v, None),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v)

def long_context_batch_partition(sample_batch, mesh: Mesh, *, axis: str = "seq",
                                 batch_axis: str = "data"):
    """``TrainLoopConfig.batch_partition`` for a long-context run: shard
    every token-shaped input feature ``[batch, seq, ...]`` over
    ``(batch_axis, axis)`` so each device receives its own sequence slice
    at the infeed boundary and ring/ulysses attention never materialises a
    full-length activation.

    A feature counts as token-shaped when it has a second dimension
    divisible by the ``seq`` axis size; scalars-per-example (labels,
    weights) keep the plain data-parallel layout and are omitted from the
    returned dict (the train loop's default covers them).  Returns ``{}``
    on a mesh whose ``seq`` axis is unpopulated — safe to pass through
    unconditionally.
    """
    n = int(mesh.shape[axis])
    if n <= 1:
        return {}
    out = {}
    for key, v in sample_batch.items():
        shape = tuple(getattr(v, "shape", ()) or ())
        if len(shape) >= 2 and shape[1] >= n and shape[1] % n == 0:
            out[key] = P(batch_axis, axis)
    return out
