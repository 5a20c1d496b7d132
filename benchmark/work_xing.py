"""Operations and bytes of Xing4.0-29B-A4B's two device programs, from
shapes: ``hp`` is the ``hparams`` block of the configuration file.  The
attention, the experts and the latent rows are counted by
benchmark/work_pangu_moe.py's functions (the classes are the same); added
here are the stream mixing and the experts a step TOUCHES.

What a program MUST do, not what it does: a step reads the matrices of the
experts that a live row chose (128 assignments over 64 experts leave about
8 idle in a layer, and the grouped product visits no tile of theirs), not
all 64; the mixing reads a token's streams once and writes them once a
sub-layer (a token's ``n x C`` float32 fit the chip's fast memory, so the
norm, the coefficients and the write-back need no second pass), beside its
own matrix.  A share computed from these errs low wherever the program
does more.
"""

from __future__ import annotations

from typing import Dict, Sequence

from benchmark import work_pangu_moe as latent

STREAM_ITEMSIZE = 4          # the streams are float32


def mix_params(hp: Dict) -> int:
    """One sub-layer's mixing: ``phi [n C, 2n + n^2]``, the two bias
    vectors, the bias matrix and three scalars."""
    n = hp["hc_mult"]
    return n * hp["d_model"] * (2 * n + n * n) + 2 * n + n * n + 3


def fixed_params(hp: Dict) -> int:
    """Parameters that every token's products read whatever it chose: the
    attention, both mixings, the dense MLP or the router and the shared
    expert of every block, and the head; no routed expert."""
    dense = hp["n_dense_layers"]
    routed = hp["n_layers"] - dense
    return (
        hp["n_layers"] * 2 * mix_params(hp)
        + dense * latent.layer_params(hp, False)
        + routed * (latent.layer_params(hp, True)
                    - hp["experts_held"] * latent.expert_params(hp))
        + hp["d_model"] * hp["vocab_size"])


def held_params(hp: Dict) -> int:
    """Every parameter held that takes part in a product."""
    routed = hp["n_layers"] - hp["n_dense_layers"]
    return fixed_params(hp) + hp["d_model"] * hp["vocab_size"] \
        + routed * hp["experts_held"] * latent.expert_params(hp)


def stream_bytes(hp: Dict, tokens: float) -> float:
    """Bytes of streams the mixing must move for ``tokens`` tokens: one
    read and one write of ``n x C`` float32 a sub-layer."""
    return (2.0 * 2 * hp["n_layers"] * tokens * hp["hc_mult"]
            * hp["d_model"] * STREAM_ITEMSIZE)


def decode_weight_bytes(hp: Dict, weight_itemsize: int,
                        experts_touched: float) -> float:
    """Bytes of weights one decode step reads: ``fixed_params`` and the
    matrices of ``experts_touched`` experts (all expert layers together);
    an embedding row a token is nothing."""
    return weight_itemsize * (
        fixed_params(hp) + experts_touched * latent.expert_params(hp))


def decode_step_bytes(hp: Dict, weight_itemsize: int, experts_touched: float,
                      latent_bytes: float, rows: float) -> float:
    """Everything one step must move: weights, valid latent rows, and the
    streams of its ``rows`` through the mixing."""
    return (decode_weight_bytes(hp, weight_itemsize, experts_touched)
            + latent_bytes + stream_bytes(hp, rows))


def mix_flops(hp: Dict, tokens: float) -> float:
    """The mixing's operations for ``tokens`` tokens: the product with
    ``phi`` and, per stream element, ``n`` multiply-adds for ``u``'s share
    and the write-back's ``n + 1``; the 20 normalisations of 16 numbers a
    token are nothing beside them."""
    n, c = hp["hc_mult"], hp["d_model"]
    per_sub_layer = 2.0 * n * c * (2 * n + n * n) + 2.0 * n * c * (n + 2)
    return 2 * hp["n_layers"] * tokens * per_sub_layer


def decode_step_flops(hp: Dict, depths: Sequence[int],
                      assignments: float) -> float:
    return latent.decode_step_flops(hp, depths, assignments) \
        + mix_flops(hp, len(depths))


def prefill_window_flops(hp: Dict, tokens: float) -> float:
    """Model FLOPs of one prefill window that holds ``tokens`` of a
    prompt: openPangu's count (2 a parameter a token outside the routed
    experts, the routed experts at the choices held, the window's own
    pairs in the expanded form) and the mixing.  Earlier windows'
    positions are left out (the reader knows a window's tokens, not its
    index), and the program attends to them in the absorbed form, which
    costs more a pair: the share errs low on both counts."""
    return latent.prefill_window_flops(hp, tokens) + mix_flops(hp, tokens)
