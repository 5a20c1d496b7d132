"""Operations and bytes of Command A+'s two device programs and of the
grouped expert product, from shapes: ``hp`` is the ``hparams`` block of the
configuration file.

What a program MUST do, not what it does, the same work whatever
implements it: weights at their stored width and only those a step
touches (a held expert with no assignment in a step need not be read),
cache entries that are valid (a ring's ``min(t + 1, window)``, a full
layer's ``t + 1``), tokens that are a prompt's own, the expert products of
the assignments made.  A share computed from these errs low wherever the
program does more (a ring or a by-position array read whole, the pad of a
prompt's last window, the rows of a grouped product's tile that hold no
assignment).
"""

from __future__ import annotations

from typing import Dict, Sequence


def attention_params(hp: Dict) -> int:
    """One layer's attention: Wq and Wo over all query heads, Wk and Wv
    over the key/value heads."""
    d, width = hp["d_model"], hp["head_dim"]
    return 2 * d * hp["n_heads"] * width + 2 * d * hp["n_kv_heads"] * width


def expert_params(hp: Dict) -> int:
    """One expert: a gated MLP at the expert width."""
    return 3 * hp["d_model"] * hp["d_expert"]


def fixed_params(hp: Dict) -> int:
    """One layer outside its routed experts: attention, the router, the
    shared experts (the norm's gain is nothing)."""
    return (attention_params(hp) + hp["d_model"] * hp["n_experts"]
            + hp["n_shared_experts"] * expert_params(hp))


def layer_kinds(hp: Dict):
    """(window layers, full layers) among the ``n_layers`` built."""
    full = sum(i % hp["full_every"] == hp["full_every"] - 1
               for i in range(hp["n_layers"]))
    return hp["n_layers"] - full, full


def entry_bytes(hp: Dict, kv_itemsize: int) -> int:
    """Bytes a cached position holds in one layer: a key and a value for
    each key/value head."""
    return 2 * hp["n_kv_heads"] * hp["head_dim"] * kv_itemsize


def decode_weight_bytes(hp: Dict, weight_itemsize: int,
                        experts_touched: float) -> float:
    """Bytes of weights one decode step reads: every layer's matrices
    outside the routed experts, the ``experts_touched`` held experts
    (summed over layers) that a live row chose, and the tied embedding's
    rows held, as the head."""
    return weight_itemsize * (
        hp["n_layers"] * fixed_params(hp)
        + experts_touched * expert_params(hp)
        + hp["d_model"] * hp["vocab_size"])


def decode_cache_entries(hp: Dict, depths: Sequence[int]) -> Dict[str, int]:
    """Valid entries one step reads, by kind, for rows that hold ``depths``
    positions each (the one being written with them), layers summed."""
    window, full = layer_kinds(hp)
    return {
        "window": window * sum(min(t, hp["window_size"]) for t in depths),
        "full": full * sum(depths)}


def decode_step_flops(hp: Dict, depths: Sequence[int],
                      assignments: float) -> float:
    """Model FLOPs of one decode step: 2 a parameter a row outside the
    routed experts, 2 an expert parameter an assignment, and ``4 n_heads
    head_dim`` a valid cached entry a layer (scores and values)."""
    entries = decode_cache_entries(hp, depths)
    fixed = hp["n_layers"] * fixed_params(hp) \
        + hp["d_model"] * hp["vocab_size"]
    return (
        2.0 * fixed * len(depths) + 2.0 * expert_params(hp) * assignments
        + 4.0 * hp["n_heads"] * hp["head_dim"] * sum(entries.values()))


def prefill_window_flops(hp: Dict, tokens: float) -> float:
    """Model FLOPs of one prefill window that holds ``tokens`` of a
    prompt: 2 a parameter a token outside the routed experts; the routed
    experts at the share of a token's choices that is held here; and ``4
    n_heads head_dim`` for each pair of a token with a position of its
    own window at or before it, in every layer.  Earlier windows'
    positions are left out (the reader knows a window's tokens, not its
    index: at the cell's mean depth they are as much again in a window
    layer and three times that in the full one), and so is the one row
    of the head."""
    chosen_here = hp["experts_per_token"] * hp["experts_held"] \
        / hp["n_experts"]
    pairs = tokens * (tokens + 1) / 2
    return hp["n_layers"] * (
        2.0 * fixed_params(hp) * tokens
        + 2.0 * expert_params(hp) * chosen_here * tokens
        + 4.0 * hp["n_heads"] * hp["head_dim"] * pairs)


def grouped_product_work(k: int, n: int, experts_touched: float,
                         assignments: float, itemsize: int = 2):
    """(bytes, FLOPs) of one grouped product ``[rows, k] x [experts, k, n]``
    in which ``assignments`` rows fall to ``experts_touched`` experts: each
    touched expert's matrix once, the rows in and out, 2 k n a row."""
    return (
        itemsize * (experts_touched * k * n + assignments * k)
        + 4 * assignments * n,
        2.0 * assignments * k * n)


def roofline_seconds(work, peaks: Dict) -> float:
    """The least time the chip could take for ``(bytes, FLOPs)``."""
    n_bytes, flops = work
    return max(n_bytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
