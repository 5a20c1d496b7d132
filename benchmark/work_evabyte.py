"""Operations and bytes of EvaByte's two device programs, from shapes.

What a program MUST do, not what it does: weights at their stored width,
cache entries that are valid, tokens that are a prompt's own.  A share
computed from these errs low wherever the program does more (the pad of a
prompt's last window, the ring's masked entries).
"""

from __future__ import annotations


def layer_matmul_params(*, d_model: int, d_ff: int, n_heads: int,
                        head_dim: int) -> int:
    """Parameters of one block that take part in a matrix product: the
    four attention projections and the gated MLP's three."""
    return 4 * d_model * n_heads * head_dim + 3 * d_model * d_ff


def decode_weight_bytes(*, d_model: int, d_ff: int, n_layers: int,
                        n_heads: int, head_dim: int, vocab_size: int,
                        weight_itemsize: int) -> int:
    """Bytes of weights one greedy decode step reads: every block's
    matrices, and the ``vocab_size`` columns of prediction head 0 (the
    step reads no other head; an embedding row per token is nothing)."""
    per_layer = layer_matmul_params(
        d_model=d_model, d_ff=d_ff, n_heads=n_heads, head_dim=head_dim)
    return weight_itemsize * (n_layers * per_layer + vocab_size * d_model)


def prefill_window_flops(*, tokens: float, d_model: int, d_ff: int,
                         n_layers: int, n_heads: int, head_dim: int) -> float:
    """Model FLOPs of one prefill window that holds ``tokens`` of a
    prompt: 2 per parameter per token in the blocks' matrices, and 4 per
    head dimension per pair of a token with a key of its own window at
    or before it (scores and values).  The summaries of earlier windows
    are left out (at most 640 keys beside 1,024 on average: under 3 % of
    a window), and so is the one row of the output head."""
    per_layer = layer_matmul_params(
        d_model=d_model, d_ff=d_ff, n_heads=n_heads, head_dim=head_dim)
    pairs = tokens * (tokens + 1) / 2
    return n_layers * (
        2 * per_layer * tokens + 4 * n_heads * head_dim * pairs)
