"""Flash attention: Pallas TPU kernels for the dense-attention hot path.

Blockwise online-softmax attention (the flash-attention recurrence), forward
AND backward as Pallas kernels:

  - forward: grid (batch*heads, q-blocks, kv-blocks) streams K/V blocks from
    HBM through VMEM against one resident Q block, carrying the running
    max/denominator/accumulator in VMEM scratch across the sequential kv grid
    dimension — the [L, L] score matrix never materializes and VMEM holds
    O(block_q · block_k) regardless of L.  The forward also emits the
    per-row logsumexp (LSE) used by the backward.
  - backward: two kernels recompute scores blockwise from the saved
    (q, k, v, lse) — dQ over grid (bh, q-blocks, kv-blocks), dK/dV over
    grid (bh, kv-blocks, q-blocks) — so the backward is O(block²) memory
    too; nothing O(L²) is ever saved or rebuilt (the round-1 version
    recomputed a dense [b,h,L,L] attention inside the VJP).

Both matmuls per block land on the MXU back to back; row statistics are kept
as (block_q, 128) lane-replicated tiles to satisfy TPU tiling.  Used by
models/transformer.py when ``attn_impl="flash"``; ring attention
(parallel/ring_attention.py) handles the sequence-parallel regime and
composes the same math across chips.

``grouped_attention`` (forward only, at the end of the file) is the same
recurrence for a prefill window of a grouped-query decoder: the query
heads of a key/value head share each block of keys a step fetches, and the
mask is the caller's rule over the POSITION each entry holds (a ring as it
lies, an array by position), so a block no query sees is skipped.
models/command_a.py's prefill window and whole-sequence pass run it.

``selected_attention`` (forward only, behind it) is its sibling for a
window whose queries each attend over their own set of the row's
positions, stated as a threshold over an order key a (query, position)
pair: the mask is built in the kernel from those keys, with the count of
keys equal to the threshold carried from block to block.
models/keye.py's prefill window and whole-sequence pass run it.

``latent_decode_attention`` (forward only, behind those) is
``grouped_attention``'s decode-shaped sibling for a latent cache: one query a row, the row's
heads as the rows of both products, each row's own cached rows as keys
and (their first columns) values, read once and only to the row's depth.
models/pangu_moe.py's decode step runs it.

``grouped_decode_attention`` (behind it) is the same step over a cache of
grouped heads, and ``ring_table_decode_attention`` (the last) over a ring
and a table of entries whose heads lie side by side, every head with keys
of its own, under one softmax.  models/command_a.py's and
models/evabyte.py's decode steps run them.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
LANES = 128


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret=None`` means the Pallas interpreter on the CPU backend
    ONLY (tests, dry runs).  On a TPU — and on any backend that is not the
    CPU — the kernels compile for real, so a kernel the chip's compiler
    refuses raises instead of quietly running interpreted."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _block_mask(kmask, qi, kj, block_q, block_k, causal):
    """[bq, bk] bool: allowed (key-visible and causal-visible) positions."""
    allowed = jnp.broadcast_to(kmask[None, :] > 0, (block_q, block_k))
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        kpos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        allowed = jnp.logical_and(allowed, qpos >= kpos)
    return allowed


def _causal_live(qi, kj, block_q, block_k):
    """False iff the whole KV block sits strictly above the causal diagonal."""
    return kj * block_k <= qi * block_q + block_q - 1


# --------------------------------------------------------------------- fwd

def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, causal, block_q, block_k, scale):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = _causal_live(qi, kj, block_q, block_k) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale            # [bq, d]
        k = k_ref[0].astype(jnp.float32)                    # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(                            # [bq, bk] on MXU
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        allowed = _block_mask(mask_ref[0, 0], qi, kj, block_q, block_k, causal)
        s = jnp.where(allowed, s, NEG_INF)
        m_prev = m_ref[:, 0:1]                              # [bq, 1]
        s_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, s_max)
        p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)     # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                      # [bq, 1]
        l_new = l_ref[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == n_kv - 1)
    def _final():
        l_fin = l_ref[:, 0:1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
        # Rows with an empty allowed key set keep lse = NEG_INF-ish; the
        # backward's `allowed` guard zeroes them regardless.  lse is laid out
        # [bh, L, 1] (TPU block tiling wants the block's trailing dims to
        # divide (8, 128) or equal the array's).
        lse_ref[0] = m_ref[:, 0:1] + jnp.log(jnp.maximum(l_ref[:, 0:1], 1e-30))


def _flash_forward(q, k, v, kv_mask, *, causal, block_q, block_k, interpret):
    """Returns (out [b,l,h,d], lse [b*h, l]) from folded blockwise kernels."""
    from jax.experimental.pallas import tpu as pltpu

    b, l, h, d = q.shape
    scale = d ** -0.5

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, l, d)

    qf, kf, vf = fold(q), fold(k), fold(v)
    maskf = jnp.repeat(kv_mask, h, axis=0)[:, None, :]      # [b*h, 1, l]

    grid = (b * h, l // block_q, l // block_k)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
            scale=scale,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, i, j: (bh, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, l, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, l, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, maskf)
    return out.reshape(b, h, l, d).transpose(0, 2, 1, 3), lse


# --------------------------------------------------------------------- bwd

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, mask_ref,
               dq_ref, acc_ref, *, causal, block_q, block_k, scale):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = _causal_live(qi, kj, block_q, block_k) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        allowed = _block_mask(mask_ref[0, 0], qi, kj, block_q, block_k, causal)
        p = jnp.where(allowed, jnp.exp(s - lse_ref[0]), 0.0)
        dp = jax.lax.dot_general(                            # dO V^T [bq, bk]
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dvec_ref[0])                          # [bq, bk]
        acc_ref[...] += scale * jax.lax.dot_general(         # dS K [bq, d]
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == n_kv - 1)
    def _final():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, mask_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal, block_q, block_k,
                scale):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = _causal_live(qi, kj, block_q, block_k) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        allowed = _block_mask(mask_ref[0, 0], qi, kj, block_q, block_k, causal)
        p = jnp.where(allowed, jnp.exp(s - lse_ref[0]), 0.0)
        dv_acc[...] += jax.lax.dot_general(                  # P^T dO [bk, d]
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dvec_ref[0])
        dk_acc[...] += scale * jax.lax.dot_general(          # dS^T Q [bk, d]
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _final():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, kv_mask, o, lse, g, *, causal, block_q, block_k,
                    interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, l, h, d = q.shape
    scale = d ** -0.5

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, l, d)

    qf, kf, vf, of, gf = fold(q), fold(k), fold(v), fold(o), fold(g)
    maskf = jnp.repeat(kv_mask, h, axis=0)[:, None, :]
    # D_i = rowsum(dO · O): the softmax-jacobian correction term.
    # [bh, L, 1] column layout, matching lse (see _fwd_kernel final note).
    dvec = jnp.sum(
        gf.astype(jnp.float32) * of.astype(jnp.float32), axis=-1, keepdims=True
    )

    qkv_spec = lambda which: {
        "q": pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        "k": pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0)),
    }[which]
    row_spec = pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0))
    mask_spec = pl.BlockSpec((1, 1, block_k), lambda bh, i, j: (bh, 0, j))

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, causal=causal, block_q=block_q, block_k=block_k,
            scale=scale,
        ),
        grid=(b * h, l // block_q, l // block_k),
        in_specs=[
            qkv_spec("q"), qkv_spec("k"), qkv_spec("k"), qkv_spec("q"),
            row_spec, row_spec, mask_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, l, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lse, dvec, maskf)

    # dK/dV: kv blocks own the (sequential) second grid dim, q streams third.
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0))
    row_spec2 = pl.BlockSpec((1, block_q, 1), lambda bh, j, i: (bh, i, 0))
    mask_spec2 = pl.BlockSpec((1, 1, block_k), lambda bh, j, i: (bh, 0, j))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, causal=causal, block_q=block_q, block_k=block_k,
            scale=scale,
        ),
        grid=(b * h, l // block_k, l // block_q),
        in_specs=[
            q_spec, kv_spec, kv_spec, q_spec, row_spec2, row_spec2, mask_spec2,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, l, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, l, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, gf, lse, dvec, maskf)

    def unfold(x):
        return x.reshape(b, h, l, d).transpose(0, 2, 1, 3)

    return unfold(dq), unfold(dk), unfold(dv)


# ----------------------------------------------------------------- decode

# The single query row is replicated to a full sublane tile so the [q, d]
# operand satisfies TPU tiling; all rows compute identical values and row 0
# is returned.  The waste is on the tiny q dimension only — the decode
# regime is bandwidth-bound on streaming the KV cache, which this kernel
# reads exactly once (that is the point; real flash-decode does the same).
_DECODE_QROWS = {4: 8, 2: 16, 1: 32}


def _decode_kernel(q_ref, k_ref, v_ref, mask_ref, bias_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, block_k, scale):
    kj = pl.program_id(1)
    n_kv = pl.num_programs(1)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # [qrows, d]
    k = k_ref[0].astype(jnp.float32)                    # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(                            # [qrows, bk] on MXU
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = s + bias_ref[0]                                 # [1, bk] broadcast
    allowed = mask_ref[0, 0] > 0                        # [bk]
    s = jnp.where(allowed[None, :], s, NEG_INF)
    m_prev = m_ref[:, 0:1]
    s_max = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, s_max)
    p = jnp.where(allowed[None, :], jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == n_kv - 1)
    def _final():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[:, 0:1], 1e-30)
        ).astype(o_ref.dtype)


def flash_decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    kv_mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Single-query attention against a KV cache (the decode regime).

    ``q``: [batch, 1, heads, head_dim] — this step's one token per row.
    ``k``/``v``: [batch, kv_len, heads, head_dim] — the (padded) cache.
    ``kv_mask``: [batch, kv_len] validity (<= each row's decode position).
    ``bias``: additive [1|batch, heads, 1, kv_len] score term (T5
    relative positions); broadcast over batch when its leading dim is 1.

    One grid step per KV block streams the cache through VMEM exactly
    once with the online-softmax recurrence — no [1, L] score tensor in
    HBM and no O(L) repacking per decode step.  Inference-only (no VJP:
    nothing differentiates through serving decode).  ``block_k`` defaults
    to the autotune table's ``flash_decode`` entry for this shape
    (``TPP_AUTOTUNE`` semantics identical to ``flash_attention``), then
    to the hard-coded default.
    """
    from jax.experimental.pallas import tpu as pltpu

    from tpu_pipelines.ops import autotune

    interpret = _resolve_interpret(interpret)
    b, l, h, d = k.shape
    itemsize = jnp.dtype(q.dtype).itemsize
    concrete = not isinstance(q, jax.core.Tracer)
    if block_k is None:
        cfg = autotune.get_block_config(
            "flash_decode", b, h, l, d, q.dtype, False,
            interpret=interpret, allow_sweep=concrete,
        )
        if cfg is not None:
            block_k = cfg[1]
    block_k = autotune.DEFAULT_BLOCK_K if block_k is None else block_k
    block_k = autotune.clamp_block(l, block_k, itemsize, "block_k")
    qrows = _DECODE_QROWS.get(int(itemsize), 8)
    scale = d ** -0.5

    qf = jnp.broadcast_to(
        q[:, 0].reshape(b * h, 1, d), (b * h, qrows, d)
    )
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    if kv_mask is None:
        kv_mask = jnp.ones((b, l), jnp.int32)
    maskf = jnp.repeat(jnp.asarray(kv_mask, jnp.int32), h, axis=0)[:, None, :]
    if bias is None:
        biasf = jnp.zeros((b * h, 1, l), jnp.float32)
    else:
        biasf = jnp.broadcast_to(
            bias.astype(jnp.float32)[:, :, 0, :], (b, h, l)
        ).reshape(b * h, 1, l)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k, scale=scale),
        grid=(b * h, l // block_k),
        in_specs=[
            pl.BlockSpec((1, qrows, d), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, j: (bh, 0, j)),
            pl.BlockSpec((1, 1, block_k), lambda bh, j: (bh, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, qrows, d), lambda bh, j: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, qrows, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qrows, d), jnp.float32),
            pltpu.VMEM((qrows, LANES), jnp.float32),
            pltpu.VMEM((qrows, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, maskf, biasf)
    return out[:, 0].reshape(b, h, d)[:, None]


# ------------------------------------------------------------------ custom_vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, kv_mask, causal, block_q, block_k, bwd_block_q,
           bwd_block_k, interpret):
    out, _ = _flash_forward(
        q, k, v, kv_mask, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, kv_mask, causal, block_q, block_k, bwd_block_q,
               bwd_block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, kv_mask, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out, (q, k, v, kv_mask, out, lse)


def _flash_bwd(causal, block_q, block_k, bwd_block_q, bwd_block_k, interpret,
               residuals, g):
    q, k, v, kv_mask, o, lse = residuals
    dq, dk, dv = _flash_backward(
        q, k, v, kv_mask, o, lse, g, causal=causal, block_q=bwd_block_q,
        block_k=bwd_block_k, interpret=interpret,
    )
    # int mask gets a float0 cotangent (JAX's "no gradient" for int inputs)
    import numpy as np

    dmask = np.zeros(kv_mask.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    kv_mask: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Self-attention over [batch, len, heads, head_dim] via the kernels.

    Numerically equals ``dense_attention`` (same masking semantics, modulo
    rows whose whole allowed key set is empty: dense leaves them uniform,
    flash leaves them zero).  ``interpret=None`` selects the Pallas interpreter
    on the CPU backend only (tests/dry runs); anywhere else the kernels
    compile for the device and a refusal raises.

    Block selection (ops/autotune.py): explicit ``block_q=``/``block_k=``
    (and ``bwd_block_q=``/``bwd_block_k=`` for the backward kernels, which
    tune independently) always win; otherwise the autotune table is
    consulted for this (shape, dtype, causal, device) on first trace, and
    the hard-coded defaults (128/128) apply on a miss.  ``TPP_AUTOTUNE``
    controls table behavior — cache-only by default, so jit tracing never
    times anything inside a trace.

    Every block is validated up front and auto-clamped to the largest
    L-divisible, TPU-tileable size <= the requested one (the kernels' grid
    is ``l // block``; an indivisible block used to mis-tile with an
    opaque Mosaic error).  A clear ``ValueError`` lists the valid choices
    when nothing <= the request works.
    """
    from tpu_pipelines.ops import autotune

    interpret = _resolve_interpret(interpret)
    b, l, h, d = q.shape
    itemsize = jnp.dtype(q.dtype).itemsize
    # Timing inside a jit trace would hang the trace on real device work:
    # sweeps only ever run from concrete call sites.
    concrete = not isinstance(q, jax.core.Tracer)

    def tuned(op):
        return autotune.get_block_config(
            op, b, h, l, d, q.dtype, causal,
            interpret=interpret, allow_sweep=concrete,
        )

    explicit = block_q is not None or block_k is not None
    if not explicit:
        cfg = tuned("flash_fwd")
        if cfg is not None:
            block_q, block_k = cfg
    block_q = autotune.DEFAULT_BLOCK_Q if block_q is None else block_q
    block_k = autotune.DEFAULT_BLOCK_K if block_k is None else block_k
    if not explicit and bwd_block_q is None and bwd_block_k is None:
        cfg = tuned("flash_bwd")
        if cfg is not None:
            bwd_block_q, bwd_block_k = cfg
    bwd_block_q = block_q if bwd_block_q is None else bwd_block_q
    bwd_block_k = block_k if bwd_block_k is None else bwd_block_k

    block_q = autotune.clamp_block(l, block_q, itemsize, "block_q")
    block_k = autotune.clamp_block(l, block_k, itemsize, "block_k")
    bwd_block_q = autotune.clamp_block(l, bwd_block_q, itemsize, "bwd_block_q")
    bwd_block_k = autotune.clamp_block(l, bwd_block_k, itemsize, "bwd_block_k")
    if kv_mask is None:
        kv_mask = jnp.ones((b, l), jnp.int32)
    return _flash(
        q, k, v, jnp.asarray(kv_mask, jnp.int32), causal, block_q, block_k,
        bwd_block_q, bwd_block_k, interpret,
    )


# ------------------------------------------------- grouped, by position

# Queries of one head and keys that a step of ``grouped_attention``'s grid
# holds at most, from a sweep on the chip at Command A+'s shapes (16 query
# heads x 512 positions a key/value head against a window's own keys + a
# ring, 4,608 entries, and against a by-position array of 18,432; PERF.md
# section 6, PR 37): 512 queries fetch a block of keys once a window and
# not twice; 256 keys a step cost twice the time (a step's turn-over is
# then most of it), 1,024 and 2,048 hold more keys that no query sees and
# do not divide the 4,608.
GROUPED_BLOCK_Q, GROUPED_BLOCK_K = 512, 512
# What the kernel may hold in VMEM: the g heads' float32 accumulator,
# maximum and sum, their queries and results twice over (one block in use,
# one in flight) and one head's scores; 128 MiB a core on v5e, 16 a kernel
# unless it says so.
_GROUPED_VMEM_BYTES = 64 * 1024 ** 2


def _blocks_of(n: int, most: int, tile: int):
    """-> (block, padded n): the fewest blocks of at most ``most`` that
    hold ``n``, each a multiple of ``tile``."""
    count = -(-n // most)
    block = -(-n // (count * tile)) * tile
    return block, count * block


def _across(x, n: int):
    """x [rows, LANES], every lane of a row the same -> [rows, n]."""
    return x[:, :n] if n <= LANES else jnp.tile(x, (1, n // LANES))


def _heads_step(q_ref, k, v, masked, acc_ref, m_ref, l_ref):
    """One block of keys against the ``g`` heads' queries.  q_ref
    [1, g, bq, d]; k, v [bk, d]; ``masked`` [bq, bk] float32, 0 where the
    query sees the key and NEG_INF where not; the running maximum, sum and
    accumulator a head in the three scratch references."""
    g, d = acc_ref.shape[0], acc_ref.shape[2]
    block_k = k.shape[0]

    # The heads one after another in ONE straight body, so that one
    # head's products run beside another's softmax: 16 % less time than a
    # rolled loop at 512 x 512 on the chip (PERF.md section 6, PR 37).
    for i in range(g):
        s = jax.lax.dot_general(                         # [bq, bk] on MXU
            q_ref[0, i], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + masked
        m_prev = m_ref[i]                                # [bq, LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # A masked score is exp(-1e30 - m) = 0 once a row has seen a key;
        # before that (m still NEG_INF) what it adds is wiped by ``keep``
        # = 0 when the first key comes.
        p = jnp.exp(s - _across(m_new, block_k))
        keep = jnp.exp(m_prev - m_new)
        l_ref[i] = l_ref[i] * keep + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[i] = acc_ref[i] * _across(keep, d) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[i] = m_new


def _grouped_kernel(start_ref, fetch_ref, q_ref, k_ref, v_ref, held_ref,
                    o_ref, acc_ref, m_ref, l_ref, *, sees):
    g, block_q, d = q_ref.shape[1:]
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # A step whose key block no query of the block sees was handed the
    # last live block again (nothing is fetched) and computes nothing.
    @pl.when(fetch_ref[qi * n_kv + kj] == kj)
    def _step():
        t = start_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        # One mask for the g heads: a masked score is NEG_INF to the last
        # bit (a score is far below float32's step there).
        masked = jnp.where(sees(t, held_ref[...]), 0.0, NEG_INF)
        _heads_step(q_ref, k_ref[0], v_ref[0], masked, acc_ref, m_ref, l_ref)

    @pl.when(kj == n_kv - 1)
    def _final():
        for i in range(g):
            o_ref[0, i] = (
                acc_ref[i] / _across(l_ref[i], d)).astype(o_ref.dtype)


def grouped_attention(q, k, v, held, start, sees):
    """Attention of grouped query heads over entries that each hold a
    stated position, masked by those positions alone (forward only).

    ``q``: [kv_heads, g, l, head_dim], already scaled: the ``g`` query
    heads of a key/value head, at positions ``start + [0, l)`` (``start``
    a scalar, traced or not).  ``k``/``v``: [kv_heads, entries, head_dim].
    ``held``: [entries] int32, the position each entry holds, negative
    where it holds none.  ``sees(t, u)`` -> bool for int32 arrays that
    broadcast: whether the query at ``t`` sees the key at position ``u``;
    the caller's rule, traced into the kernel.  Where the entries lie does
    not matter: a ring as it lies, wrapped or not, an array by position
    with a tail not yet written, a window's own keys put in front.  Every
    query sees at least one key.

    Grid (key/value head, query block, key block), the key block the
    sequential axis.  A step holds the ``g`` heads' ``block_q`` queries and
    ONE block of keys and values, fetched once for all of them; each head's
    [block_q, block_k] scores, their running maximum and sum and the
    accumulator are float32 and never leave VMEM, the products take their
    operands as they come (bfloat16 in, float32 out) and the weights enter
    the second in ``v``'s dtype.  A key block that no query of a query
    block sees (``sees`` over the pair, reduced outside the kernel and
    handed over by scalar prefetch) is neither computed nor fetched: its
    step's index map names the last live block again.  Any ``l`` and any
    number of entries: blocks are the fewest of at most ``GROUPED_BLOCK_Q``
    x ``GROUPED_BLOCK_K`` that hold them, and what they hold beyond is
    padding at position -1.
    -> [kv_heads, g, l, head_dim] in ``q``'s dtype.
    """
    from jax.experimental.pallas import tpu as pltpu

    kv, g, l, d = q.shape
    entries = k.shape[1]
    # a block of queries tiles by sublanes (8 rows of 32 bits), a block of
    # keys by the lanes of its scores and its positions
    block_q, l_pad = _blocks_of(
        l, GROUPED_BLOCK_Q, 32 // jnp.dtype(q.dtype).itemsize)
    block_k, e_pad = _blocks_of(entries, GROUPED_BLOCK_K, LANES)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, l_pad - l), (0, 0)))
    k, v = (jnp.pad(a, ((0, 0), (0, e_pad - entries), (0, 0)))
            for a in (k, v))
    held = jnp.pad(
        held.astype(jnp.int32), (0, e_pad - entries), constant_values=-1)
    start = jnp.asarray(start, jnp.int32)
    n_q, n_kv = l_pad // block_q, e_pad // block_k
    live = sees((start + jnp.arange(l_pad))[:, None], held[None, :]).reshape(
        n_q, block_q, n_kv, block_k).any((1, 3))
    last = jax.lax.cummax(
        jnp.where(live, jnp.arange(n_kv, dtype=jnp.int32), -1), axis=1)
    first = jnp.argmax(live, axis=1).astype(jnp.int32)[:, None]
    fetch = jnp.where(last < 0, first, last).reshape(-1)

    # the key block a step is handed: its own, or the last live one again
    at = lambda i, j, fetch_ref: fetch_ref[i * n_kv + j]
    q_spec = pl.BlockSpec(
        (1, g, block_q, d), lambda h, i, j, *_: (h, 0, i, 0))
    kv_spec = pl.BlockSpec(
        (1, block_k, d),
        lambda h, i, j, _, fetch_ref: (h, at(i, j, fetch_ref), 0))
    held_spec = pl.BlockSpec(
        (1, block_k), lambda h, i, j, _, fetch_ref: (0, at(i, j, fetch_ref)))
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, sees=sees),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(kv, n_q, n_kv),
            in_specs=[q_spec, kv_spec, kv_spec, held_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((g, block_q, d), jnp.float32),
                pltpu.VMEM((g, block_q, LANES), jnp.float32),
                pltpu.VMEM((g, block_q, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_GROUPED_VMEM_BYTES),
        interpret=_resolve_interpret(None),
        name="grouped_attention",
    )(start.reshape(1), fetch, q, k, v, held[None, :])
    return out[:, :, :l]


# ------------------------------------- grouped, under a set a query

# Queries of one head that a step of ``selected_attention``'s grid holds
# at most; the keys are ``GROUPED_BLOCK_K`` (PERF.md section 6, PR 45).
SELECTED_BLOCK_Q = 512


def selected_blocks(l: int, n: int):
    """-> the queries and the keys that a step of ``selected_attention``
    holds for ``l`` queries over ``n`` positions: the fewest blocks of at
    most ``SELECTED_BLOCK_Q`` x ``GROUPED_BLOCK_K`` that hold them."""
    return (_blocks_of(l, SELECTED_BLOCK_Q, 16)[0],
            _blocks_of(n, GROUPED_BLOCK_K, LANES)[0])


def selected_last_block(start, l: int, block_k: int):
    """The last key block that a window at positions ``start + [0, l)`` is
    handed: the one that holds its last query's own position (``start`` a
    plain number or traced)."""
    return (start + l - 1) // block_k


def selected_block(keys, at, t, kth, room=None, seen=0.0):
    """Which keys of one block of positions each query attends over, its
    set stated as a threshold: the keys at positions ``s <= t`` whose
    order key lies over the query's ``kth``, and of those equal to it the
    first ``room`` by position.  keys [q, k], in any type whose order is
    the scores'; at [1, k] int32, the positions the block holds; t, kth,
    room [q, 1]; ``seen`` [q, 1] float32, how many keys equal to ``kth``
    lie at the positions before the block.  ``room`` None says that every
    equal key has room, and nothing is counted.
    -> bool [q, k], and ``seen`` with this block's equal keys."""
    valid = at <= t
    if room is None:
        return valid & (keys >= kth), seen
    equal = valid & (keys == kth)
    # The count of equal keys up to each position, on the matrix unit: a
    # 0/1 matrix times an upper triangle of ones is exact in float32
    # (counts stay under 2^24).
    k = keys.shape[1]
    upto = jax.lax.broadcasted_iota(
        jnp.int32, (k, k), 0) <= jax.lax.broadcasted_iota(
            jnp.int32, (k, k), 1)
    ones = lambda x: jnp.where(x, 1.0, 0.0).astype(jnp.bfloat16)
    count = seen + jnp.dot(
        ones(equal), ones(upto), preferred_element_type=jnp.float32)
    fits = count <= room.astype(jnp.float32)
    return valid & ((keys > kth) | (equal & fits)), count[:, k - 1:]


def _selected_kernel(start_ref, ties_ref, q_ref, k_ref, v_ref, keys_ref,
                     kth_ref, room_ref, o_ref, acc_ref, m_ref, l_ref,
                     seen_ref, masked_ref, *, l, n):
    g, block_q, d = q_ref.shape[1:]
    block_k = k_ref.shape[0]
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        seen_ref[...] = jnp.zeros_like(seen_ref)

    # A block past the window's last position was handed the last live
    # block again (nothing is fetched) and computes nothing.
    @pl.when(kj <= selected_last_block(start_ref[0], l, block_k))
    def _step():
        t = start_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        at = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        # One mask for the g heads, as ``_grouped_kernel``'s.  Only a
        # query block in which some query has more keys equal to its
        # threshold than room counts them.
        mask = lambda sees: jnp.where(sees, 0.0, NEG_INF)

        @pl.when(ties_ref[qi] == 0)
        def _all_fit():
            masked_ref[...] = mask(selected_block(
                keys_ref[...], at, t, kth_ref[...])[0])

        @pl.when(ties_ref[qi] != 0)
        def _counted():
            sees, seen = selected_block(
                keys_ref[...], at, t, kth_ref[...], room_ref[...],
                seen_ref[:, :1])
            masked_ref[...] = mask(sees)
            seen_ref[...] = jnp.broadcast_to(seen, seen_ref.shape)

        k, v = k_ref[...], v_ref[...]
        if n % block_k:
            # past the arrays' end a block holds no number at all
            inside = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0) < n
            k, v = jnp.where(inside, k, 0), jnp.where(inside, v, 0)
        _heads_step(q_ref, k, v, masked_ref[...], acc_ref, m_ref, l_ref)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _final():
        for i in range(g):
            o_ref[:, i * d:(i + 1) * d] = (
                acc_ref[i] / _across(l_ref[i], d)).astype(o_ref.dtype)


def selected_attention(q, k, v, keys, kth, room, start):
    """Attention of grouped query heads over a row's entries by position,
    each query over its own set of them, stated as a threshold over an
    order key a (query, position) pair (forward only): a prefill window
    under a learned selection.

    ``q``: [kv_heads, g, l, head_dim], already scaled, at positions
    ``start + [0, l)`` (``start`` a scalar, traced or not).  ``k``/``v``:
    [n, kv_heads * head_dim], the row as its cache holds it, positions
    first and a position's heads side by side, written up to ``start + l``
    at least; what lies behind is finite and never used.  ``keys``: [l, n]
    uint32, entry ``[i, s]`` the order key of position ``s`` for query
    ``i``; ``kth`` [l] uint32 and ``room`` [l] int32: the query attends
    over the positions ``s <= start + i`` whose key lies over ``kth``, and
    of those whose key equals it over the first ``room`` by position
    (``selected_block``, the rule, traced into the kernel a block at a
    time).  Every query sees at least one key.

    Grid (key/value head, query block, key block), the key block the
    sequential axis.  A step holds the ``g`` heads' queries and ONE block
    of one head's keys and values, cut from the row where it lies (no
    copy of the row), and the queries' block of order keys; the mask is
    built from them in VMEM, once for the ``g`` heads; each head's
    scores, their running maximum and sum and the accumulator are float32
    and never leave VMEM, as in ``grouped_attention``.  The count of keys
    equal to the threshold is carried from block to block beside them,
    and runs only in a query block where some query has more equal keys
    than room (told by one count over the row outside the kernel).  A key
    block past the window's last position is neither computed nor
    fetched: its step's index map names the last live block again.  Any
    ``l`` and ``n``: what a block holds past an array's end is masked.
    On the chip ``head_dim`` is a multiple of 128 (a block is one head's
    columns of the row).
    -> [l, kv_heads * g * head_dim] in ``q``'s dtype, query head ``i`` of
    key/value head ``h`` at columns ``(h * g + i) * head_dim``.
    """
    from jax.experimental.pallas import tpu as pltpu

    kv, g, l, d = q.shape
    n = k.shape[0]
    block_q, block_k = selected_blocks(l, n)
    n_q, n_kv = -(-l // block_q), -(-n // block_k)
    start = jnp.asarray(start, jnp.int32)
    t = start + jnp.arange(l, dtype=jnp.int32)
    equal = jnp.sum(
        (keys == kth[:, None]) & (jnp.arange(n)[None, :] <= t[:, None]),
        axis=1, dtype=jnp.int32)
    ties = jnp.pad(equal > room, (0, n_q * block_q - l)).reshape(
        n_q, block_q).any(1).astype(jnp.int32)

    # the key block a step is handed: its own, or the last live one again
    at = lambda j, start_ref: jnp.minimum(
        j, selected_last_block(start_ref[0], l, block_k))
    q_spec = pl.BlockSpec(
        (1, g, block_q, d), lambda h, i, j, *_: (h, 0, i, 0))
    kv_spec = pl.BlockSpec(
        (block_k, d), lambda h, i, j, start_ref, _: (at(j, start_ref), h))
    keys_spec = pl.BlockSpec(
        (block_q, block_k),
        lambda h, i, j, start_ref, _: (i, at(j, start_ref)))
    query_spec = pl.BlockSpec((block_q, 1), lambda h, i, j, *_: (i, 0))
    return pl.pallas_call(
        functools.partial(_selected_kernel, l=l, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(kv, n_q, n_kv),
            in_specs=[q_spec, kv_spec, kv_spec, keys_spec, query_spec,
                      query_spec],
            out_specs=pl.BlockSpec(
                (block_q, g * d), lambda h, i, j, *_: (i, h)),
            scratch_shapes=[
                pltpu.VMEM((g, block_q, d), jnp.float32),
                pltpu.VMEM((g, block_q, LANES), jnp.float32),
                pltpu.VMEM((g, block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, block_k), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((l, kv * g * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_GROUPED_VMEM_BYTES),
        interpret=_resolve_interpret(None),
        name="selected_attention",
    )(start.reshape(1), ties, q, k, v, keys, kth[:, None],
      room.astype(jnp.int32)[:, None])


# ------------------------------------------- a row's heads, to its depth

# Cached rows that a step of ``latent_decode_attention``'s grid holds at
# most, from a sweep on the chip at openPangu's shapes (128 rows of 128
# heads x 576 over 2,560 positions at depths 128 to 2,560; PERF.md section
# 6, PR 41).
LATENT_BLOCK_K = 512


def latent_block(positions: int) -> int:
    """Cached rows a key block of ``latent_decode_attention`` holds over a
    cache of ``positions`` (a block tiles by the lanes of its scores); a
    row at position ``t`` is handed the blocks ``[0, t // block]`` and no
    other."""
    return min(LATENT_BLOCK_K, -(-positions // LANES) * LANES)


def _latent_fetch(j, depth, block_k: int):
    """The key block that step ``j`` of a row at position ``depth`` is
    handed: its own, or the row's last live one again."""
    return jnp.minimum(j, depth // block_k)


def _latent_kernel(pos_ref, q_ref, c_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   scale):
    r, block_k = acc_ref.shape[1], c_ref.shape[2]
    j = pl.program_id(1)
    depth = pos_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # A block that starts past the row's depth was handed the last live
    # block again (nothing is fetched) and computes nothing.
    @pl.when(j * block_k <= depth)
    def _step():
        # What a block holds past the row's depth, and past the array's
        # end, is no number that may be used, as a score or as a value.
        # (Masking only a row's last live block saves nothing: 0.64 ms
        # either way at openPangu's shapes; PERF.md section 6, PR 41.)
        live = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1) <= depth
        keys = c_ref[0]                                  # [width, bk]
        values = jnp.where(live, keys[:r], 0)
        s = jnp.where(live, jnp.dot(                     # [h, bk] on MXU
            q_ref[0], keys, preferred_element_type=jnp.float32
        ) * scale, NEG_INF)
        m_prev = m_ref[...]                              # [h, LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _across(m_new, block_k))
        keep = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * keep + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _across(keep, r) + jax.lax.dot_general(
            p.astype(values.dtype), values, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _final():
        o_ref[0] = (acc_ref[...] / _across(l_ref[...], r)).astype(o_ref.dtype)


def latent_decode_attention(q, cache, pos, klen: int, *, scale: float,
                            r: int):
    """One query a row and head over the row's own cached rows, to the
    row's depth (forward only): the middle of latent attention's absorbed
    form.

    ``q``: [rows, heads, width].  ``cache``: [slots, positions, width] as
    it lies, ``slots >= rows``, ``positions >= klen``: row ``i`` attends
    over ``cache[i, :pos[i] + 1]``, every column of a cached row as the
    key and its first ``r`` as the value (``r`` at most 128 or a multiple
    of it); ``pos`` [rows] int32, each under ``klen``.  What lies past a
    row's depth is never used, whatever it holds.

    Grid (row, key block), the key block the sequential axis.  A step
    holds the row's heads as the M side of both products and ONE block of
    the row's cached rows, fetched once.  Scores [heads, block], their
    running maximum and sum and the accumulator are float32 and never
    leave VMEM; the scale multiplies the float32 scores; the weights enter
    the second product in the cache's dtype.  A key block that starts past
    ``pos[i]`` (handed over by scalar prefetch) is neither computed nor
    fetched: its step's index map names the row's last live block again.

    The kernel is handed the cache with its positions last.  That is how
    the chip keeps an array whose rows are no multiple of 128 numbers wide
    and whose positions are (openPangu's 576 x 2,560: no padding that
    way), so the view is the array where it lies and nothing is moved
    (tests/test_tpu_compile.py holds the compiled step to that); a block
    of keys is then the right-hand side of the first product as it comes.
    -> [rows, heads, r] in ``q``'s dtype.
    """
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, width = q.shape
    block_k = latent_block(cache.shape[1])
    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, -(-klen // block_k)),
            in_specs=[
                pl.BlockSpec((1, heads, width), lambda i, j, pos: (i, 0, 0)),
                pl.BlockSpec(
                    (1, width, block_k),
                    lambda i, j, pos: (
                        i, 0, _latent_fetch(j, pos[i], block_k))),
            ],
            out_specs=pl.BlockSpec(
                (1, heads, r), lambda i, j, pos: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((heads, r), jnp.float32),
                pltpu.VMEM((heads, LANES), jnp.float32),
                pltpu.VMEM((heads, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, heads, r), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_resolve_interpret(None),
        name="latent_decode_attention",
    )(pos.astype(jnp.int32), q, jnp.swapaxes(cache, 1, 2))


# ------------------------------- a row's grouped heads, to its depth

# Entries that a step of ``grouped_decode_attention``'s grid holds at
# most, of every key/value head at once, from a sweep on the chip at
# Command A+'s shapes (32 rows of 8 x 16 heads x 128 over rings of 4,096
# and arrays of 18,432 entries; PERF.md section 6, PR 43).
GROUPED_DECODE_BLOCK_K = 512


def grouped_decode_block(entries: int) -> int:
    """Entries a key block of ``grouped_decode_attention`` holds over an
    array of ``entries`` (a block tiles by the lanes of its scores); a row
    of depth ``t`` is handed the blocks ``[0, t // block]`` and no
    other."""
    return min(GROUPED_DECODE_BLOCK_K, -(-entries // LANES) * LANES)


def _grouped_decode_kernel(depth_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                           m_ref, l_ref, *, entries):
    kv, block_k, d = k_ref.shape[1:]
    j = pl.program_id(1)
    # what lies past the array's end is no more an entry than what lies
    # past the row's depth
    depth = jnp.minimum(depth_ref[pl.program_id(0)], entries - 1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # A block that starts past the row's depth was handed the last live
    # block again (nothing is fetched) and computes nothing.
    @pl.when(j * block_k <= depth)
    def _step():
        # What a block holds past the depth may be a former occupant's
        # numbers or no number at all: it is used neither as a score nor
        # as a value (0 x NaN is NaN).
        at = lambda shape, axis: j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, shape, axis) <= depth
        live_key, live_value = at((1, block_k), 1), at((block_k, 1), 0)

        # The key/value heads one after another in ONE straight body, so
        # that one head's products run beside another's softmax, as
        # ``_grouped_kernel`` unrolls its query heads.
        for h in range(kv):
            values = jnp.where(live_value, v_ref[0, h], 0)
            s = jnp.where(live_key, jax.lax.dot_general(  # [g, bk] on MXU
                q_ref[0, h], k_ref[0, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ), NEG_INF)
            m_prev = m_ref[h]                            # [g, LANES]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _across(m_new, block_k))
            keep = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * keep + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * _across(keep, d) + jax.lax.dot_general(
                p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _final():
        for h in range(kv):
            o_ref[0, h] = (
                acc_ref[h] / _across(l_ref[h], d)).astype(o_ref.dtype)


def grouped_decode_attention(q, k, v, depth, klen: int):
    """One query a row and head over the row's own entries, to the row's
    depth, the ``g`` query heads of a key/value head sharing each entry
    they fetch (forward only): a decode step's attention over a cache of
    grouped heads, a ring or an array by position alike.

    ``q``: [rows, kv_heads, g, head_dim], already scaled.  ``k``/``v``:
    [slots, kv_heads, entries, head_dim] as they lie, ``slots >= rows``,
    ``entries >= klen``: row ``i`` attends over entries ``[0, depth[i]]``
    of slot ``i``, and ``klen`` bounds every depth (the grid covers the
    first ``klen`` entries); ``depth`` [rows] int32.  ``head_dim`` at most
    128 or a multiple of it.  What lies past a row's depth, in its own
    slot or in another, is never used, whatever it holds.  The caller
    says how deep a row's valid entries go: a position for an array
    written by position, the last entry written for a ring that has not
    wrapped, the whole ring for one that has.

    Grid (row, key block), the key block the sequential axis.  A step
    holds the row's queries and ONE block of entries of EVERY key/value
    head, keys and values, fetched once; a head's [g, block] scores, their
    running maximum and sum and the accumulator are float32 and never
    leave VMEM, the products take their operands as they come and the
    weights enter the second in ``v``'s dtype.  A key block that starts
    past ``depth[i]`` (handed over by scalar prefetch) is neither computed
    nor fetched: its step's index map names the row's last live block
    again.  Any number of entries: a block is the fewest lanes that hold
    them (``grouped_decode_block``), and what it holds past the array's
    end is masked like what lies past the depth.
    -> [rows, kv_heads, g, head_dim] in ``q``'s dtype.
    """
    from jax.experimental.pallas import tpu as pltpu

    rows, kv, g, d = q.shape
    entries = k.shape[2]
    block_k = grouped_decode_block(entries)
    q_spec = pl.BlockSpec((1, kv, g, d), lambda i, j, depth: (i, 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, kv, block_k, d),
        lambda i, j, depth: (i, 0, _latent_fetch(j, depth[i], block_k), 0))
    return pl.pallas_call(
        functools.partial(_grouped_decode_kernel, entries=entries),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, -(-klen // block_k)),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((kv, g, d), jnp.float32),
                pltpu.VMEM((kv, g, LANES), jnp.float32),
                pltpu.VMEM((kv, g, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_resolve_interpret(None),
        name="grouped_decode_attention",
    )(depth.astype(jnp.int32), q, k, v)


# ------------------- a row's heads over its ring and its table, to depth

# Entries that a step of ``ring_table_decode_attention``'s grid holds at
# most, of a ring or of a table, every head of an entry at once, from two
# sweeps on the chip at EvaByte's shapes (8 rows of 32 heads x 128 over
# rings of 2,048 and tables of 896 entries): the kernel takes as long an
# entry whatever the block, so the fewest entries past a row's depth win
# (PERF.md section 6, PR 46).
RING_TABLE_BLOCK_K = 128


def ring_table_blocks(ring: int, table: int):
    """Entries a block of ``ring_table_decode_attention`` holds over a
    ring of ``ring`` entries and over a table of ``table``; a row that
    attends over the first ``n`` entries of either is handed its blocks
    ``[0, (n - 1) // block]`` and no other, and none where ``n`` is 0."""
    return tuple(
        min(RING_TABLE_BLOCK_K, -(-n // 8) * 8) for n in (ring, table))


def _ring_table_fetch(i, j, ring_n, table_n, came_from, blocks,
                      ring_steps: int):
    """-> ((slot, block) of the ring, (slot, block) of the table) that step
    ``j`` of row ``i`` is handed: its own block, else the last one
    handed before it again, which is then not fetched.  For the table that
    is the row's first block while the row's ring goes by, and for a row
    whose table is empty whatever the row before it left
    (``came_from``: the last row up to this one that has a table, row 0
    where none has)."""
    ring = jnp.minimum(j, (ring_n[i] - 1) // blocks[0])
    src = came_from[i]
    last = jnp.maximum(table_n[src] - 1, 0) // blocks[1]
    table = jnp.where(
        table_n[i] > 0, jnp.clip(j - ring_steps, 0, last), last)
    return (i, ring), (src, table)


def _ring_table_kernel(ring_n_ref, table_n_ref, from_ref, q_ref, rk_ref,
                       rv_ref, tk_ref, tv_ref, o_ref, acc_ref, m_ref, l_ref,
                       *, scale, ring_steps):
    heads, d = acc_ref.shape
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def block(k_ref, v_ref, first, n):
        """The entries ``[first, first + block)`` of which those under
        ``n`` count.  An entry's heads lie side by side, so the block's
        (entry, head) pairs are the columns of ONE product against the
        row's heads, of which a head keeps its own entries' and no other
        head's: the cache is read where it lies, and the products run on
        the MXU as in the siblings."""
        width = k_ref.shape[1] * heads
        # ``_across`` for any width (a fixture's is no multiple of LANES)
        across = lambda x, n: jnp.broadcast_to(x[:, :1], (heads, n))
        # What a block holds past the row's depth (the window before's
        # entries, a former occupant's numbers, no number at all) is used
        # neither as a score nor as a value (0 x NaN is NaN).
        live = (n - first) * heads
        at = lambda shape, axis: jax.lax.broadcasted_iota(
            jnp.int32, shape, axis)
        column = at((heads, width), 1)
        own = (jax.lax.rem(column, heads) == at((heads, width), 0)) & (
            column < live)
        values = jnp.where(
            at((width, 1), 0) < live, v_ref[0].reshape(width, d), 0)
        s = jnp.where(own, jax.lax.dot_general(       # [h, bk * h] on MXU
            q_ref[0], k_ref[0].reshape(width, d), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale, NEG_INF)
        m_prev = m_ref[...]                              # [h, LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - across(m_new, width))
        keep = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * keep + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * across(keep, d) + jax.lax.dot_general(
            p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    # A block that starts past the row's depth was handed the last live
    # block again (nothing is fetched) and computes nothing.
    ring_n, table_n = ring_n_ref[i], table_n_ref[i]
    ring_first = j * rk_ref.shape[1]
    table_first = (j - ring_steps) * tk_ref.shape[1]
    pl.when((j < ring_steps) & (ring_first < ring_n))(
        lambda: block(rk_ref, rv_ref, ring_first, ring_n))
    pl.when((j >= ring_steps) & (table_first < table_n))(
        lambda: block(tk_ref, tv_ref, table_first, table_n))

    @pl.when(j == pl.num_programs(1) - 1)
    def _final():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def ring_table_decode_attention(q, ring_k, ring_v, table_k, table_v, ring_n,
                                table_n, *, scale: float):
    """One query a row and head over the row's own entries of a ring AND
    of a table, each to the row's depth, under ONE softmax, every head
    with keys and values of its own (forward only): a decode step of
    chunked linearised attention (models/evabyte.py).

    ``q``: [rows, heads, head_dim].  ``ring_k``/``ring_v``: [slots, w,
    heads, head_dim] and ``table_k``/``table_v``: [slots, t, heads,
    head_dim] as they lie, an entry's heads side by side, ``slots >=
    rows``: row ``i`` attends over the ring's entries ``[0, ring_n[i])`` and
    the table's ``[0, table_n[i])`` of slot ``i``; ``ring_n`` [rows] int32
    in ``[1, w]``, ``table_n`` [rows] int32 in ``[0, t]``.  What lies past
    a row's depth, in its own slot or in another, is never used, whatever
    it holds.

    Grid (row, block), the block the sequential axis: the ring's blocks,
    then the table's, of 128 entries each.  A step holds the row's queries
    and ONE block of entries, keys and values, every head of an entry at
    once as it lies (no copy, no transposition); the scores [heads, block
    x heads], their running maximum and sum and the accumulator are
    float32 and never leave VMEM; the scale multiplies the float32 scores;
    the weights enter the second product in the cache's dtype.  A block
    that starts past a row's depth (handed over by scalar prefetch) is
    neither computed nor fetched: its step's index map names the last
    block handed before it again, so a row whose table is empty reads no
    block of it (a call's very first step is handed a block of each array
    whatever it names: the table's first, of slot 0, where row 0 has
    none).
    -> [rows, heads, head_dim] in ``q``'s dtype.
    """
    return _ring_table_call(
        q, ring_k, ring_v, table_k, table_v, ring_n, table_n, scale=scale,
        blocks=ring_table_blocks(ring_k.shape[1], table_k.shape[1]),
        interpret=_resolve_interpret(None))


# A program's layers call the kernel on arrays of one shape: under ``jit``
# they share ONE lowering of it (a step of 16 layers lowered 16 kernel
# bodies, a second a program, before; PERF.md section 6, PR 46).
@functools.partial(jax.jit, static_argnames=("scale", "blocks", "interpret"))
def _ring_table_call(q, ring_k, ring_v, table_k, table_v, ring_n, table_n, *,
                     scale: float, blocks, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, d = q.shape
    steps = [-(-a.shape[1] // n) for a, n in zip((ring_k, table_k), blocks)]
    ring_n, table_n = ring_n.astype(jnp.int32), table_n.astype(jnp.int32)
    came_from = jax.lax.cummax(
        jnp.where(table_n > 0, jnp.arange(rows, dtype=jnp.int32), 0))

    fetch = lambda which: lambda i, j, *depths: _ring_table_fetch(
        i, j, *depths, blocks, steps[0])[which] + (0, 0)
    q_spec = pl.BlockSpec((1, heads, d), lambda i, j, *_: (i, 0, 0))
    ring_spec = pl.BlockSpec((1, blocks[0], heads, d), fetch(0))
    table_spec = pl.BlockSpec((1, blocks[1], heads, d), fetch(1))
    return pl.pallas_call(
        functools.partial(
            _ring_table_kernel, scale=scale, ring_steps=steps[0]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, sum(steps)),
            in_specs=[q_spec, ring_spec, ring_spec, table_spec, table_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((heads, d), jnp.float32),
                pltpu.VMEM((heads, LANES), jnp.float32),
                pltpu.VMEM((heads, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ring_table_decode_attention",
    )(ring_n, table_n, came_from, q, ring_k, ring_v, table_k, table_v)
