"""The decode contract: what a model hands the continuous-batching engine
(serving/generative.py) to be served by it, stated once.

A model's ``make_continuous_decode_fns`` returns a ``DecodeContract``; an
exported module's ``make_decode_fns(model, hyperparameters)`` returns the
same (trainer/export.py keeps it as ``LoadedModel.decode_fns``).  The
engine reads nothing of a model but these fields.  This module imports
nothing from ``serving/``: the models depend on it, and the engine does.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple


class CacheKind(NamedTuple):
    """What a decode contract states about one kind of cache array
    (``fns.cache_kinds[fns.cache_kind_of(path)]``), every array being
    ``[slots, entries, ...]``.

    ``by_position``: axis 1 is the decode position.  A step's
    ``(b, kv)`` bucket is then the first ``kv`` entries of the first
    ``b`` rows, and entries at or past a row's position hold nothing
    (nothing wrote them).  (An ``in_place`` array is never cut: the
    engine indexes its slot axis alone, ``kv`` reaches the step as a
    number, and the axes behind the slot may lie as the step reads
    them.)  Otherwise what is valid in a row is the
    contract's own business, and a step is handed its ``b`` rows whole.
    ``written``: a step returns the array changed, and the engine sets it
    back into the arena; otherwise a step only reads it.
    ``in_place``: a step is handed the array of EVERY slot, reads and
    writes the first ``b`` rows where they lie and returns the array: no
    bucket is cut out and none set back.  For arrays too large to copy
    a bucket of at every step."""

    by_position: bool
    written: bool
    in_place: bool = False


def behind_bos(input_mask) -> int:
    """A sequence's first decode position where ``prefill`` consumed a
    BOS at position 0 and the cache holds the emitted tokens alone."""
    return 1


def prompt_length(input_mask):
    """A sequence's first decode position where the cache holds the
    prompt too: the count of the prompt's tokens."""
    import jax.numpy as jnp

    return jnp.sum(jnp.asarray(input_mask, jnp.int32))


@dataclasses.dataclass
class DecodeContract:
    """Every field the engine reads, with the value it takes where a
    model states none.

    **The programs.**  ``params`` is the model's parameter tree, ``cache``
    a tree of arrays ``[rows, entries, ...]``, ``V`` the vocabulary.

      - ``step(params, cache, tok [b], pos [b], encoded [b, ...],
        enc_mask [b, max_input_len], klen)`` -> ``(cache, logits [b, V])``:
        ONE decode step for ``b`` rows, row ``i`` feeding token ``tok[i]``
        at its own position ``pos[i]``.  ``klen`` is the step's static kv
        bucket: a by-position array that is not ``in_place`` arrives cut
        to its first ``klen`` entries, an ``in_place`` one whole with
        ``klen`` as the bound of what a row may attend.  Per-row masking
        makes the result independent of ``klen`` as long as every live
        position fits.  Where ``step_tally_len > 0`` the step returns a
        third value, ``[b, step_tally_len]`` int32 (per row, which held
        experts it chose in each expert layer); the engine sums it over
        the live rows and hands the sums to ``step_account``.
      - Exactly one of the two prefills:
        ``prefill(params, inputs [1, max_input_len], input_mask)`` ->
        ``(cache, encoded, logits0 [1, V])``: one request's whole prompt
        as one program (an encoder is bidirectional, not cut by token).
        The arena's shapes are read off what it returns.
        ``prefill_window(params, cache, tokens [1, W], n_valid, index)``
        -> ``(cache, logits [1, V])``: window ``index`` of one prompt, its
        first ``n_valid`` tokens real, against that row's cache; ``W`` is
        ``prefill_window_len``.  A prompt of ``L`` tokens costs
        ``ceil(L / W)`` calls, decode steps of the live rows run between
        them, and its last call's logits give its first new token.  Such
        a contract gives ``blank_cache(batch)``, the cache's arrays for
        ``batch`` rows, since no whole-prompt program shows their shapes,
        and takes no prefix cache.

    **The cache.**  ``cache_kinds`` names the kinds of array and what each
    is (``CacheKind``); ``cache_kind_of(path)`` maps a leaf's tree path to
    its kind's name.  Neither given: every array is key/value by decode
    position, written by every step.  ``cache_positions``: how many
    positions a by-position array holds where the cache keeps the prompt
    too, prompt and new tokens together; a row's depth then counts from
    the prompt's first token (``first_decode_pos`` + tokens held).  None:
    the cache begins behind a BOS and holds ``max_decode_len`` positions.
    ``first_decode_pos(input_mask [1, max_input_len])``: a sequence's
    first decode position (traced inside the insert program).
    ``encoded_shape``: one row's encoder output where there is no
    ``prefill`` to return one; ``(0,)``, no rows, for a decoder alone.

    **The accounts** (host side, for the telemetry; None: nothing booked).

      - ``step_account(positions, tally, bucket)`` -> dict: what one step
        read.  ``positions``: the position each live row fed; ``tally``:
        the summed tally as a list (empty where ``step_tally_len`` is 0);
        ``bucket``: the step's ``(b, kv)``.  Keys:
        ``cache_bytes`` {kind: bytes valid for the live rows} (required),
        ``cache_entries`` {kind: entries}, ``cache_span_bytes`` {kind:
        bytes the attention kernel fetches for them, whole blocks},
        ``window_rollovers``, ``chunk_summaries``, ``expert_assignments``,
        ``experts_touched``, ``expert_load_ratio``, ``selected_entries``.
      - ``window_account(index)`` -> ``{"key_blocks": {state: count}}``:
        what window ``index`` of a prompt visits and holds.

    **Geometry.**  ``max_decode_len`` (new tokens a sequence may hold),
    ``eos_id``, ``pad_id``, ``max_input_len`` (the longest prompt).
    """

    step: Callable
    max_decode_len: int
    eos_id: int
    pad_id: int
    max_input_len: int = 64
    prefill: Optional[Callable] = None
    prefill_window: Optional[Callable] = None
    prefill_window_len: int = 0
    blank_cache: Optional[Callable] = None
    cache_kinds: Optional[Dict[str, CacheKind]] = None
    cache_kind_of: Optional[Callable[[Any], str]] = None
    cache_positions: Optional[int] = None
    first_decode_pos: Callable = behind_bos
    encoded_shape: Tuple[int, ...] = (0,)
    step_account: Optional[Callable] = None
    window_account: Optional[Callable] = None
    step_tally_len: int = 0

    def __post_init__(self):
        if (self.prefill is None) == (self.prefill_window is None):
            raise ValueError(
                "a decode contract gives exactly one of prefill and "
                "prefill_window")
        self.prefill_window_len = int(self.prefill_window_len)
        if (self.prefill_window_len > 0) != (self.prefill_window is not None):
            raise ValueError(
                "prefill_window_len > 0 goes with prefill_window, and "
                "only with it")
        if self.prefill_window is not None and self.blank_cache is None:
            raise ValueError(
                "a contract prefilled by window gives blank_cache")
        if (self.cache_kinds is None) != (self.cache_kind_of is None):
            raise ValueError("cache_kinds and cache_kind_of go together")
        self.max_decode_len = int(self.max_decode_len)
        self.eos_id = int(self.eos_id)
        self.pad_id = int(self.pad_id)
        self.max_input_len = int(self.max_input_len)

    @classmethod
    def decoder_only(cls, **theirs) -> "DecodeContract":
        """The contract of a decoder-only model, prefilled by window: a
        sequence's first decode position is its prompt's length, and
        there are no encoder rows.  ``theirs`` is what differs from model
        to model: ``step``, ``prefill_window`` and its length,
        ``blank_cache``, the kinds, the accounts, ``cache_positions``,
        ``step_tally_len``, the geometry."""
        return cls(
            first_decode_pos=prompt_length, encoded_shape=(0,), **theirs)


def window_positions(
    max_input_len: int, max_decode_len: int, window: int
) -> Tuple[int, int]:
    """``(span, positions)`` of a by-position cache that holds the prompt:
    the positions a longest prompt's windows write (a whole number of
    windows, the last one written whole), and those a row must hold: the
    larger of that and the longest prompt with every new token."""
    span = -(-int(max_input_len) // window) * window
    return span, max(span, int(max_input_len) + int(max_decode_len))
