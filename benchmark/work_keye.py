"""Operations and bytes of Keye-VL-2.0-30B-A3B's two device programs, from
shapes: ``hp`` is the ``hparams`` block of the configuration file.

What a program MUST do, not what it does, the same work whatever
implements it.  A decode step reads the weights it touches at their stored
width (every layer's attention, indexer and router, the matrices of the
experts that a live row chose, the head), the index keys of each live row
UP TO THE ROW'S DEPTH (every one of them is scored), and of the keys and
values only the ``min(t + 1, index_topk)`` entries a row selected: a step
that read every valid key and value would read ``kv_entry_bytes`` a
position more and is not what the model asks for.  A window computes the
products of its tokens, the index scores of every pair and attention over
the selected keys only.  A share computed from these errs low wherever the
program does more (index keys read to the bucket's end, a window's
attention computed over every key under a mask, the pad of a prompt's last
window, the rows of a grouped product's tile that hold no assignment).
"""

from __future__ import annotations

from typing import Dict

# grouped-query attention (Wq and Wo over the query heads, Wk and Wv over the
# key/value heads) and a gated expert are counted as Command A+'s are
from benchmark.work_command_a import attention_params, expert_params


def indexer_params(hp: Dict) -> int:
    """One layer's indexer: WqI, WkI and Ww."""
    return hp["d_model"] * (
        hp["index_heads"] * hp["index_dim"] + hp["index_dim"]
        + hp["index_heads"])


def fixed_params(hp: Dict) -> int:
    """One layer outside its routed experts: attention, the indexer, the
    router; there is no shared expert."""
    return (attention_params(hp) + indexer_params(hp)
            + hp["d_model"] * hp["n_experts"])


def held_params(hp: Dict) -> int:
    """Every parameter held that takes part in a product: the layers with
    all their experts, the embedding and the head."""
    return hp["n_layers"] * (
        fixed_params(hp) + hp["experts_held"] * expert_params(hp)) \
        + 2 * hp["d_model"] * hp["vocab_size"]


def kv_entry_bytes(hp: Dict, kv_itemsize: int) -> int:
    """Bytes a cached position's keys and values hold in one layer."""
    return 2 * hp["n_kv_heads"] * hp["head_dim"] * kv_itemsize


def index_entry_bytes(hp: Dict, kv_itemsize: int) -> int:
    """Bytes a cached position's index key holds in one layer."""
    return hp["index_dim"] * kv_itemsize


def decode_weight_bytes(hp: Dict, weight_itemsize: int,
                        experts_touched: float) -> float:
    """Bytes of weights one decode step reads: every layer's matrices
    outside the routed experts, the ``experts_touched`` experts (summed
    over layers) that a live row chose, and the head; an embedding row a
    token is nothing."""
    return weight_itemsize * (
        hp["n_layers"] * fixed_params(hp)
        + experts_touched * expert_params(hp)
        + hp["d_model"] * hp["vocab_size"])


def decode_step_bytes(hp: Dict, weight_itemsize: int, experts_touched: float,
                      index_bytes: float, selected_bytes: float) -> float:
    """Everything one step must move: the weights it touches, the index
    keys that are valid for its rows and the keys and values its rows
    selected."""
    return decode_weight_bytes(hp, weight_itemsize, experts_touched) \
        + index_bytes + selected_bytes


def prefill_window_flops(hp: Dict, tokens: float) -> float:
    """Model FLOPs of one prefill window that holds ``tokens`` of a
    prompt: 2 a parameter a token outside the routed experts, the routed
    experts at a token's choices (all are held), and for each pair of a
    token with a position of its own window at or before it, in every
    layer, ``2 index_heads index_dim`` for the index score and ``4 n_heads
    head_dim`` for attention (a window is no longer than ``index_topk``,
    so every such pair is selected).  Earlier windows' positions are left
    out (the reader knows a window's tokens, not its index): at the cell's
    mean depth of 6 windows their index scores are 11 times the window's
    own and their selected keys up to as many again, so the share errs
    low; and so is the one row of the head."""
    chosen_here = hp["experts_per_token"] * hp["experts_held"] \
        / hp["n_experts"]
    pairs = tokens * (tokens + 1) / 2
    return hp["n_layers"] * (
        2.0 * fixed_params(hp) * tokens
        + 2.0 * expert_params(hp) * chosen_here * tokens
        + (2.0 * hp["index_heads"] * hp["index_dim"]
           + 4.0 * hp["n_heads"] * hp["head_dim"]) * pairs)
