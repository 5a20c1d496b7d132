"""Operations and bytes of openPangu-Ultra-MoE's two device programs, from
shapes: ``hp`` is the ``hparams`` block of the configuration file.

What a program MUST do, not what it does: weights at their stored width,
latent rows that are valid, tokens that are a prompt's own, the expert
products of the assignments made.  A share computed from these errs low
wherever the program does more (the pad of a prompt's last window, cache
positions past a row's depth, the rows of a grouped product's tile that
hold no assignment).
"""

from __future__ import annotations

from typing import Dict, Sequence


def attention_params(hp: Dict) -> int:
    """One layer's latent attention: the two query matrices, the down
    projection to latent and rotary key, ``W_uk``, ``W_uv``, the output."""
    d, h = hp["d_model"], hp["n_heads"]
    qk = hp["qk_nope_head_dim"] + hp["qk_rope_head_dim"]
    return (
        d * hp["q_lora_rank"] + hp["q_lora_rank"] * h * qk
        + d * (hp["kv_lora_rank"] + hp["qk_rope_head_dim"])
        + hp["kv_lora_rank"] * h * (hp["qk_nope_head_dim"] + hp["v_head_dim"])
        + h * hp["v_head_dim"] * d)


def expert_params(hp: Dict) -> int:
    """One expert: a gated MLP at the expert width."""
    return 3 * hp["d_model"] * hp["d_expert"]


def layer_params(hp: Dict, routed: bool) -> int:
    """Parameters of one block held here that take part in a product."""
    if not routed:
        return attention_params(hp) + 3 * hp["d_model"] * hp["d_ff"]
    return (
        attention_params(hp) + hp["d_model"] * hp["n_experts"]
        + (hp["n_shared_experts"] + hp["experts_held"]) * expert_params(hp))


def row_width(hp: Dict) -> int:
    """Numbers a cached position holds in one layer."""
    return hp["kv_lora_rank"] + hp["qk_rope_head_dim"]


def decode_weight_bytes(hp: Dict, weight_itemsize: int) -> int:
    """Bytes of weights one decode step reads: every block's matrices,
    every held expert (at 4 assignments an expert a step none is idle),
    and the head over the ids held; an embedding row a token is nothing."""
    dense = hp["n_dense_layers"]
    return weight_itemsize * (
        dense * layer_params(hp, False)
        + (hp["n_layers"] - dense) * layer_params(hp, True)
        + hp["d_model"] * hp["vocab_size"])


def decode_latent_bytes(hp: Dict, depths: Sequence[int],
                        kv_itemsize: int) -> int:
    """Bytes of latent rows one step reads for rows that hold ``depths``
    positions each (the one being written with them)."""
    return sum(depths) * hp["n_layers"] * row_width(hp) * kv_itemsize


def decode_step_flops(hp: Dict, depths: Sequence[int],
                      assignments: float) -> float:
    """Model FLOPs of one decode step: 2 a parameter a row outside the
    routed experts, 2 an expert parameter an assignment, and in the
    absorbed form ``heads * (row_width + kv_lora_rank)`` multiply-adds a
    cached position a layer."""
    rows = len(depths)
    dense = hp["n_dense_layers"]
    routed = hp["n_layers"] - dense
    fixed = (
        dense * layer_params(hp, False)
        + routed * (layer_params(hp, True)
                    - hp["experts_held"] * expert_params(hp))
        + hp["d_model"] * hp["vocab_size"])
    attend = 2 * hp["n_heads"] * (row_width(hp) + hp["kv_lora_rank"])
    return (
        2.0 * fixed * rows + 2.0 * expert_params(hp) * assignments
        + float(attend) * hp["n_layers"] * sum(depths))


def prefill_window_flops(hp: Dict, tokens: float) -> float:
    """Model FLOPs of one prefill window that holds ``tokens`` of a
    prompt: 2 a parameter a token outside the routed experts; the routed
    experts at the share of a token's choices that is held here; and in
    the expanded form, per pair of a token with a position of its own
    window at or before it, ``4 heads (nope + rope + v) / 2`` for scores
    and values, plus the expansion of each of the window's positions
    into its keys and values.  Earlier windows' positions are left out
    (the reader knows a window's tokens, not its index), and so is the
    one row of the head."""
    dense = hp["n_dense_layers"]
    routed = hp["n_layers"] - dense
    fixed = (
        dense * layer_params(hp, False)
        + routed * (layer_params(hp, True)
                    - hp["experts_held"] * expert_params(hp)))
    chosen_here = hp["experts_per_token"] * hp["experts_held"] \
        / hp["n_experts"]
    h = hp["n_heads"]
    widths = (hp["qk_nope_head_dim"] + hp["qk_rope_head_dim"]
              + hp["v_head_dim"])
    expand = 2 * hp["kv_lora_rank"] * h * (
        hp["qk_nope_head_dim"] + hp["v_head_dim"])
    pairs = tokens * (tokens + 1) / 2
    return (
        2.0 * fixed * tokens
        + 2.0 * routed * expert_params(hp) * chosen_here * tokens
        + hp["n_layers"] * (2.0 * h * widths * pairs + expand * tokens))
