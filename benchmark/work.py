"""Operations and bytes a step needs, computed from shapes.

Model FLOPs only: what the forward and backward passes require, with no
recomputation, dropout mask, norm or optimizer arithmetic counted.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def count_params(params: Any) -> Dict[str, int]:
    """Total parameters and those that take part in a matrix product
    (everything but the embedding tables, which are looked up)."""
    import jax

    total = embed = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        keys = "/".join(str(getattr(k, "key", k)) for k in path)
        if "embed" in keys and keys.endswith("embedding"):
            embed += n
    return {"total": total, "matmul": total - embed}


def encoder_train_flops_per_step(
    *, matmul_params: int, batch: int, seq_len: int, n_layers: int,
    d_model: int,
) -> int:
    """6·N·T for the weight matmuls (forward 2NT, backward 4NT) plus the
    attention score and value products, 4·L·d FLOPs per token pair forward
    and three times that with the backward pass."""
    tokens = batch * seq_len
    return (
        6 * matmul_params * tokens
        + 12 * n_layers * batch * seq_len * seq_len * d_model
    )


def t5_decoder_weight_bytes(
    *, d_model: int, d_ff: int, n_layers: int, n_heads: int, head_dim: int,
    vocab_size: int, weight_itemsize: int,
) -> int:
    """Bytes of decoder and tied-embedding weights one decode step reads:
    per layer self- and cross-attention projections (q, k, v, out; the
    cross k and v projections are cached, so only q and out of the cross
    block are read) and the two MLP matrices, plus the embedding table for
    the output projection."""
    inner = n_heads * head_dim
    self_attn = 4 * d_model * inner
    cross_attn = 2 * d_model * inner
    mlp = 2 * d_model * d_ff
    return weight_itemsize * (
        n_layers * (self_attn + cross_attn + mlp) + vocab_size * d_model
    )


def t5_decode_kv_bytes(
    *, self_positions: int, cross_positions: int, n_layers: int,
    n_heads: int, head_dim: int, kv_itemsize: int,
) -> int:
    """Bytes of cached keys and values one step reads, given the number
    of filled self-attention positions and of encoder positions summed
    over the live rows."""
    per_position = 2 * n_layers * n_heads * head_dim * kv_itemsize
    return per_position * (self_positions + cross_positions)
