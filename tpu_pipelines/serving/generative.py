"""Continuous batching for autoregressive decode — the generative engine.

The PR 10 fleet batches *whole requests*: an autoregressive request owns
its replica for its entire decode, so one long generation stalls every
request co-batched behind it, and a replica decoding a 4-token reply and
one decoding a 500-token reply cost the router the same.  This engine
batches at the *decode-step* level instead (iteration-level scheduling,
Orca OSDI '22): sequences join the running batch the moment a slot is
free and leave the moment they emit EOS or hit ``max_new_tokens`` —
every device step serves exactly the sequences that still need tokens.

Mechanics (the vLLM/PagedAttention shape of the idea, on the repo's
static-shape substrate):

  * **Arena.**  One device-resident state pool sized ``max_batch_size``:
    the decode cache, per-slot last token, position, live flag, encoder
    output and mask.  What the cache's arrays are is the contract's to
    say (``DecodeContract.cache_kinds``, models/decode_contract.py).
    Live sequences occupy the compacted prefix ``[0, n_live)``; a
    departure moves the last live
    row into the hole (one scatter), an arrival lands at ``n_live`` (one
    scatter) — no host-side repacking of the cache, ever.  There is ONE
    arena and it is updated where it lies: every program that takes it
    has it donated (``_jit_program``), so a scatter of one row writes
    one row and a step writes its bucket, not a fresh copy of
    everything else; ``self._arena`` is only ever rebound to what a
    program returned.
  * **One step ahead.**  A step needs only counts of the host, never
    a token's value: the row bucket from the live count, the kv bucket
    from the deepest row's count of tokens, the budgets; its token and
    position inputs are in the arena.  So the worker thread runs one
    decode step ahead of what it has read.  A round with rows live
    dispatches step k, keeping with its output a snapshot of which
    sequence sat in which row (``_Flight``); retires the rows whose
    budget step k fills (retire by count: the last token is in step k's
    output, which no arena program touches, and the slot is free for
    this round's admission); reads step k - 1's tokens and hands them
    out by THAT step's snapshot; admits behind step k (the host's part
    of an admission, milliseconds for the dispatch of a prefill alone,
    runs while the device steps); and reads the first tokens of the
    admissions dispatched ahead of step k.  Every wait is for a program
    the device has left behind or is about to, with step k queued:
    between two decode steps the device never waits for the host.  An
    EOS is learnt one step late: the row has ridden step k, whose token
    for it is dropped (``serving_decode_wasted_row_steps_total``, one
    row-step per EOS ending, none per budget ending).  A prefill's first
    token stays on the device and ``insert`` takes it as the device
    scalar it is; what the host does with it (onto the handle,
    ``first_token_s``, EOS, the prefix entry's host copy) it does at its
    read.  Until a token is read its row counts as holding it
    (``_Sequence.held``), and the engine owes it (``idle``, ``close``,
    a dying worker).
    ``serving_decode_step_dispatch_total`` says how each step was
    dispatched (``behind_step``: with the one before it unread;
    ``alone``), ``serving_decode_first_token_reads_total`` how each
    admission's token came: ``behind_step``, ``known`` (a prefix-cache
    hit) or ``blocking`` (nothing was there to step).
  * **Bucketed steps.**  Each decode step runs one pre-compiled program
    keyed ``(batch_bucket, kv_bucket)``: the batch bucket is the smallest
    power-of-two >= the live count (serving/batching.py's bucket rule),
    the KV bucket the smallest page multiple covering the deepest live
    position (counted from the prompt's first token where the cache
    holds the prompt: ``_depth``).  ``warm()`` compiles every
    combination up front — the fleet's canary gate calls it BEFORE a
    version becomes eligible, so no decode step pays an XLA compile
    mid-traffic (``compiles_after_warm``
    is the auditable contract).  Pages are an allocation/accounting unit:
    ``serving_decode_cache_pages_in_use`` is what capacity planning reads.
  * **Identity.**  The per-row decode math is exactly the scalar-position
    math greedy/beam run (models/transformer.py vector ``decode_pos``;
    the batch dimension is bitwise row-independent), so a sequence's
    token stream is bit-identical to an isolated single-request greedy
    decode regardless of who it shared steps with.  KV bucketing keeps
    masked positions at exact zero contribution, but XLA tiles a
    contraction differently per length, so *across different KV buckets*
    logits can drift by ~1 ulp — the same property every paged-attention
    kernel has.  ``page_size=0`` (one bucket = the whole cache) makes the
    stream bitwise under any schedule; the identity test pins that mode.
  * **Per-token SLO.**  Admission control counts outstanding *tokens*
    (``max_queue_tokens``), not requests: a queued 500-token generation
    is 125x the work of a 4-token one and the door should know.  With
    ``slo_ms_per_token`` each sequence carries a token-proportional
    deadline (serving/batching.py ``token_deadline_s``); ``hard_deadline``
    evicts a sequence that blows it (``GenerationEvicted``), freeing its
    slot for work that can still meet SLO.

Decode optimisations (ISSUE 16) — two composable levers behind the
same ``DecodeContract``, each off by default.  (A third,
speculative decoding on a second, mirrored arena, only ever drafted with
the target itself and was removed; a real draft comes back as proposals
from the served model's own extra heads on the one arena, ROADMAP R7.)

  * **Prefix caching** (``prefix_cache_entries > 0``).  Prompts are
    hashed as a chain of ``page_size``-granular token blocks
    (:meth:`PrefixCache.key_of`); a full-chain hit means an identical
    (masked-inputs, mask) prompt already ran prefill, so ``_admit``
    reuses the cached device-resident prefill result — cache row,
    encoder output, first token — and skips the encoder pass entirely.
    Entries are REFCOUNTED: every live sequence admitted from an entry
    holds a reader reference, and an entry's pages are freed only when
    its last reader retires (LRU eviction considers only entries with
    zero readers).  Hits are bitwise-exact: the cached arrays are the
    actual outputs of the same compiled prefill program on the same
    input, so greedy logits equal the uncached path exactly (the ~1 ulp
    cross-KV-bucket caveat above is unchanged).
  * **Chunked prefill** (``prefill_chunk_pages > 0``).  Admission work
    is metered in prompt pages: each decode step earns the scheduler
    ``prefill_chunk_pages`` credits, and an admission costs the prompt's
    page count (1 for a prefix-cache hit) — so a burst of long-prompt
    arrivals is spread across decode steps instead of running
    back-to-back and stalling every live sequence's token deadline.
    Whether ONE prompt's prefill can be cut is the contract's to say.  A
    contract with a whole-prompt ``prefill`` (an encoder is
    bidirectional: not token-chunkable without changing the math) runs
    it as a single device program, and chunking bounds the admission
    work *between* steps; a contract with ``prefill_window`` (a causal
    decoder) is prefilled a window of ``prefill_window_len`` tokens per
    program call, a page is a window, each call costs one credit, and
    decode steps of the live rows run between one prompt's windows.
    The compiled programs are identical with the knob on or off, which
    keeps token streams bitwise-identical either way.

Metrics (``serving_decode_*``, labeled per replica; catalog in
docs/SERVING.md): steps/s, tokens/s, batch occupancy, cache pages in
use, active/queued sequences + outstanding tokens, per-token latency
histogram, evictions, step-time EWMA (what the router reads), prefix
cache hits/misses/resident pages, and the worker thread's time by
phase (``ENGINE_PHASES``: exact sums of self seconds and occurrences)
with each request's queue wait and time to first token.

Tracing: every phase of the worker thread is also a
``jax.profiler.TraceAnnotation`` named ``engine.<phase>`` (plus
``engine.prefill.wait`` and ``engine.step.wait``, the device-to-host
reads of a first token and of a step's tokens; the latter says which
step it reads, the one before the step just dispatched).  Annotations are
recorded only while a profiler session runs, into the same trace and on
the same clock as the device's operations; the device side of a phase
is its program's event, under the names in ``PROGRAM_NAMES``
(docs/OBSERVABILITY.md "Engine phases in a profile").
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from tpu_pipelines.models.decode_contract import CacheKind, DecodeContract
from tpu_pipelines.serving.batching import (
    bucket_sizes,
    token_deadline_s,
    validate_generation_params,
)

log = logging.getLogger("tpu_pipelines.serving")

# What the worker thread does, as the ``phase`` label of
# ``serving_decode_engine_{seconds,phase}_total`` and, prefixed with
# ``engine.``, as span names in a profile.  Every moment of the worker's
# life belongs to exactly one (a nested phase's time is taken out of its
# parent's), so the sums add up to the thread's lifetime.  ``prefill`` is
# a whole-prompt prefill, ``prefill.window`` one window of a contract
# that is prefilled by window; an engine books one of the two.
ENGINE_PHASES = (
    "idle", "admit", "prefill", "insert", "step", "emit", "retire",
    "prefill.window",
)

# How an admission's first token reaches the host: the ``read`` label of
# ``serving_decode_first_token_reads_total``.
FIRST_TOKEN_READS = ("behind_step", "known", "blocking")

# How a step was dispatched: the ``queued`` label of
# ``serving_decode_step_dispatch_total``.  ``behind_step``: with the
# step before it still unread, so the device has it queued when that
# one ends; ``alone``: with nothing in flight (a run's first step).
STEP_DISPATCHES = ("behind_step", "alone")

# The engine's device programs as a profile's "XLA Modules" line names
# them: ``jit_`` + the ``__name__`` of the function handed to jax.jit.
# ``jit_run`` is the bucketed step.  Readers of traces find programs by
# these names (the benchmark's decode_step_hbm_share.serve reads
# ``jit_run``): renaming one of the inner functions is a change to this
# tuple and to those readers.
PROGRAM_NAMES = (
    "jit_prefill", "jit_insert", "jit_move", "jit_clear", "jit_run",
)
# The one program more that a contract prefilled by window has, in place
# of ``jit_prefill`` (benchmark/layer_metrics/eva_prefill_mfu.serve and
# prefill_device_share.serve read it).
WINDOW_PROGRAM_NAME = "jit_prefill_window"


# A contract that states nothing: every array is K/V by decode position.
_POSITION_KV = CacheKind(by_position=True, written=True)


def _kind_reader(fns: DecodeContract):
    """``path -> CacheKind`` for one contract's cache leaves."""
    kinds = fns.cache_kinds
    if kinds is None:
        return lambda path: _POSITION_KV
    return lambda path: kinds[fns.cache_kind_of(path)]


def _jit_program(fn):
    """``jax.jit`` for one of the engine's device programs, held to the
    names a trace reader looks for.  A program that takes the arena (its
    ``state`` argument) is given it for good: the argument is donated,
    every leaf of it is aliased to the matching leaf of the state the
    program returns, and the program updates the arena where it lies
    instead of writing a new one.  The caller's old arena is dead after
    the call (reading it raises), so every call site reads
    ``arena = prog(arena, ...)``.  Nothing else is donated: not the
    parameters, not the prefill results ``insert`` copies a row from (a
    prefix-cache entry is inserted many times)."""
    import inspect

    import jax

    if "jit_" + fn.__name__ not in PROGRAM_NAMES + (WINDOW_PROGRAM_NAME,):
        raise ValueError(
            f"engine program {fn.__name__!r} is not in PROGRAM_NAMES"
        )
    # ``row_cache`` is the one row a window-by-window prefill builds up:
    # given for good like the arena, and for the same reason.
    donated = tuple(
        name for name in ("state", "row_cache")
        if name in inspect.signature(fn).parameters)
    return jax.jit(fn, donate_argnames=donated)


class EngineOverloaded(RuntimeError):
    """Token-level admission control refused the sequence: outstanding
    decode work (live + queued tokens) already exceeds the configured
    bound.  Maps to HTTP 429 + Retry-After, like ``ServerOverloaded`` —
    shed at the door, counted, never dropped mid-decode."""

    retry_after_s = 1


class GenerationEvicted(RuntimeError):
    """The sequence was evicted before finishing — its per-token SLO
    deadline passed under ``hard_deadline=True``, or the engine closed.
    Maps to a retriable 503: the server is healthy, this generation lost
    its latency race."""


class DecodeSessionLost(RuntimeError):
    """A replica died with generations in flight.  Raised by the
    supervised fleet's decode path instead of the raw worker-death
    exception, carrying each sequence's progress (the tokens the engine
    had already committed) so the fleet can re-prefill prompt + accepted
    tokens onto a surviving replica and continue the streams — greedy
    decode is deterministic, so the recovered stream is bitwise
    identical to an uninterrupted one."""

    def __init__(self, cause, partial_tokens=None, unfinished=0):
        super().__init__(
            f"decode session lost: {type(cause).__name__}: {cause}"
        )
        self.cause = cause
        self.partial_tokens = list(partial_tokens or [])
        self.unfinished = int(unfinished)


@dataclass
class _Sequence:
    """Host-side bookkeeping for one generation (the engine's unit of
    scheduling).  ``tokens`` is what the host has read of the device's
    state: ``held``, its length plus the tokens only the device has yet
    (``first_unread``, ``in_flight``), IS the sequence's next decode
    position.

    The request's timeline rides on the handle: ``arrival_s`` (submit),
    ``admitted_s`` (the ``_admit`` turn that took it off the queue),
    ``first_token_s`` (its first token on the host) and ``done_s``
    (finished, whichever way), each ``time.monotonic()`` and ``None``
    until reached.  On Linux ``time.monotonic()`` and
    ``time.perf_counter()`` read the same clock (``CLOCK_MONOTONIC``,
    see ``time.get_clock_info``), so a caller that stamps its own
    events with ``perf_counter`` may subtract them from these."""

    inputs: np.ndarray              # [max_input_len] padded token ids
    input_mask: np.ndarray          # [max_input_len] 1/0 validity
    max_new_tokens: int
    arrival_s: float
    deadline_s: Optional[float]
    tokens: List[int] = field(default_factory=list)
    _done: threading.Event = field(default_factory=threading.Event)
    result: Optional[np.ndarray] = None
    error: Optional[BaseException] = None
    # Request-trace context (None = untraced): rides the sequence across
    # the client->worker thread boundary so decode-step slot events land
    # on the originating request's trace.
    ctx: Any = None
    arrival_wall_s: float = 0.0
    # Prefix-cache entry this live sequence holds a reader reference on
    # (None = admitted without the cache, or reference already released).
    prefix_entry: Any = None
    # Per-engine request number: the ``seq`` argument of this request's
    # ``engine.*`` spans and of its ``decode.join`` trace event.
    seq_id: int = 0
    admitted_s: Optional[float] = None
    first_token_s: Optional[float] = None
    done_s: Optional[float] = None
    # Position of the first token a decode step feeds: 1 behind a BOS,
    # the prompt's length under a decoder-only contract (set where the
    # engine keeps the account of what steps read, ``step_account``).
    first_pos: int = 1
    # Admitted, its prefill dispatched, its first token not yet read to
    # the host: the token is in the arena (``insert`` took it as the
    # device scalar it was), not in ``tokens``.
    first_unread: bool = False
    # Tokens of this sequence that dispatched steps hold and the host has
    # not read yet: 1 while it rides the step in flight, 2 between the
    # next step's dispatch and that read.
    in_flight: int = 0
    # Its row of the arena while it has one (the worker thread's; a
    # retirement elsewhere may move it).
    slot: Optional[int] = None

    @property
    def held(self) -> int:
        """Tokens this sequence holds, counting those only the device
        has yet (the unread first one, those of steps in flight): what
        scheduling counts (bucket, pages, budget, tokens owed)."""
        return len(self.tokens) + self.first_unread + self.in_flight

    def finish(self, error: Optional[BaseException] = None) -> None:
        if self._done.is_set():
            return
        self.done_s = time.monotonic()
        if error is not None:
            self.error = error
        else:
            self.result = np.asarray(self.tokens, np.int32)
        self._done.set()

    def wait(self, timeout_s: float) -> np.ndarray:
        if not self._done.wait(timeout_s):
            raise TimeoutError("generation did not complete in time")
        if self.error is not None:
            raise self.error
        return self.result


class _Flight(NamedTuple):
    """A step that is dispatched and whose tokens the host has not read:
    what the read needs of the moment of dispatch, because the rows may
    have moved, left or been joined by others since."""

    index: int                      # the engine's n-th step, from 1
    nxt: Any                        # the step's output, on the device
    rows: Tuple[_Sequence, ...]     # who sat in row 0, 1, ... of the step
    positions: List[int]            # the position each row fed ([]: no account)
    b: int
    kv: int
    pages: int                      # cache pages the rows covered
    t0: float                       # ``perf_counter`` before the dispatch


def kv_bucket_sizes(max_decode_len: int, page_size: int) -> List[int]:
    """KV-cache length buckets: page, 2*page, 4*page, ... capped at the
    full cache.  ``page_size <= 0`` means one bucket — the whole cache —
    which is also the bitwise-exact mode (see module docstring)."""
    max_decode_len = int(max_decode_len)
    if max_decode_len <= 0:
        raise ValueError(
            f"max_decode_len must be positive, got {max_decode_len}"
        )
    if page_size <= 0 or page_size >= max_decode_len:
        return [max_decode_len]
    out = []
    k = int(page_size)
    while k < max_decode_len:
        out.append(k)
        k *= 2
    out.append(max_decode_len)
    return sorted(set(out))


def _bucket_of(cache, b: int, kv: int, kind_of):
    """The arena's cache cut to one step program's static bucket: the
    first ``b`` rows, and of the arrays indexed by decode position their
    first ``kv`` entries; an array its steps work on in place is handed
    over whole (``kind_of``: ``_kind_reader`` of the contract)."""
    import jax

    def cut(p, x):
        kind = kind_of(p)
        if kind.in_place:
            return x
        return x[:b, :kv] if kind.by_position else x[:b]

    return jax.tree_util.tree_map_with_path(cut, cache)


def _write_back(cache, new_sub, b: int, kv: int, kind_of):
    """The arena's cache with ``new_sub``, what the contract's ``step``
    returned for the bucket ``_bucket_of(cache, b, kv)``, set
    back where the bucket was cut from; an array the contract's steps
    only read stays as it is.  The arena is donated, so this is one
    in-place write of the bucket per written array, and over the whole
    arena (``b`` and ``kv`` its own sizes, or an array worked on in
    place) none at all: ``new_sub`` then IS the arena, written into by
    the step itself."""
    import jax

    def put(p, a, n):
        kind = kind_of(p)
        if not kind.written:
            return a
        if kind.in_place:
            return n
        return a.at[:b, :kv].set(n) if kind.by_position else a.at[:b].set(n)

    return jax.tree_util.tree_map_with_path(put, cache, new_sub)


class _PrefixEntry:
    """One cached prompt prefix: the device-resident prefill result plus
    refcount/LRU bookkeeping.  ``pages`` is the prompt's page-granular
    block count — the unit ``serving_decode_prefix_pages_in_use``
    reports and admission credits are charged in."""

    __slots__ = (
        "key", "pages", "readers", "tok0", "tok0_host", "cache", "encoded",
        "tick",
    )

    def __init__(self, key, pages, tok0, cache, encoded):
        self.key = key
        self.pages = int(pages)
        self.readers = 0
        # The first token as the prefill program returned it, which is
        # what ``insert`` is handed on a hit as on a miss; and on the
        # host once the miss that made the entry has read it (a hit
        # after that reads nothing).
        self.tok0 = tok0
        self.tok0_host: Optional[int] = None
        self.cache = cache
        self.encoded = encoded
        self.tick = 0


class PrefixCache:
    """Refcounted cache of prefill results keyed by page-granular block
    hashes of the prompt.

    The key is a CHAIN of block hashes — block ``i``'s digest folds the
    previous block's digest with ``page`` positions of (masked-inputs,
    mask) — so two prompts collide only when every block matches, i.e.
    the model-visible prompt is identical (masked positions are zeroed
    before hashing: their values never reach a logit — padding K/V is
    masked at exact zero weight — so they must not split the key).

    Refcounting is the page-lifetime contract: every live sequence
    admitted from an entry holds a reader reference, ``trim`` may evict
    only entries with ZERO readers (LRU among those), and an over-
    capacity entry is therefore freed exactly when its last reader
    retires.  Single-threaded by design: the engine's worker thread owns
    every lookup/insert/acquire, and release happens on the worker or
    after it has been joined (``close``)."""

    def __init__(self, capacity: int, page: int):
        self.capacity = max(1, int(capacity))
        self.page = max(1, int(page))
        self._entries: Dict[bytes, _PrefixEntry] = {}
        self._tick = 0
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key_of(
        inputs: np.ndarray, input_mask: np.ndarray, page: int
    ) -> Tuple[bytes, int]:
        """(chain-tip digest, valid-prefix page count) for one padded
        prompt.  Hashing covers the full padded width so mask structure
        (including interior zeros, which shift relative positions) is
        part of the identity; the page count covers only valid tokens —
        the prefill work a hit actually skips."""
        page = max(1, int(page))
        mask = (np.asarray(input_mask) > 0)
        toks = np.asarray(inputs, np.int64) * mask
        m8 = mask.astype(np.int8)
        n_valid = int(mask.sum())
        pages = max(1, -(-n_valid // page))
        h = b""
        for i in range(0, max(toks.size, 1), page):
            h = hashlib.blake2b(
                h + toks[i:i + page].tobytes() + m8[i:i + page].tobytes(),
                digest_size=16,
            ).digest()
        return h, pages

    def __len__(self) -> int:
        return len(self._entries)

    def peek(self, key: bytes) -> Optional[_PrefixEntry]:
        return self._entries.get(key)

    def touch(self, entry: _PrefixEntry) -> None:
        self._tick += 1
        entry.tick = self._tick

    def insert(self, key, pages, tok0, cache, encoded) -> _PrefixEntry:
        entry = self._entries.get(key)
        if entry is None:
            entry = _PrefixEntry(key, pages, tok0, cache, encoded)
            self._entries[key] = entry
        self.touch(entry)
        self.trim()
        return entry

    def acquire(self, entry: _PrefixEntry) -> None:
        entry.readers += 1

    def release(self, entry: _PrefixEntry) -> None:
        entry.readers = max(0, entry.readers - 1)
        self.trim()

    def trim(self) -> None:
        """Evict LRU zero-reader entries down to capacity.  Entries with
        live readers are PINNED — the cache may run over capacity while
        readers hold pages, and shrinks the moment the last one lets go.
        The most-recently-touched entry is never the victim: without that
        rule a fresh insert into a cache whose capacity is held by pinned
        entries would evict ITSELF (it is the only zero-reader), killing
        the hot prompt's residency exactly when sharing is highest."""
        while len(self._entries) > self.capacity:
            newest = max(self._entries.values(), key=lambda e: e.tick)
            victims = [
                e for e in self._entries.values()
                if e.readers == 0 and e is not newest
            ]
            if not victims:
                return
            victim = min(victims, key=lambda e: e.tick)
            del self._entries[victim.key]

    def pages_in_use(self) -> int:
        return sum(e.pages for e in self._entries.values())


class GenerativeEngine:
    """One continuous-batching decode engine over one (model, params).

    ``fns`` is the model's ``DecodeContract``
    (models/decode_contract.py): its programs, what its cache's arrays
    are, its accounts and its geometry.  The engine owns a single worker thread; all
    device work — prefill, bucketed steps, arena scatters — happens
    there, so the jit-compiled programs never race.  ``submit`` blocks
    like ``RequestBatcher.submit``; ``submit_nowait`` returns a handle
    the fleet uses to run one request's rows concurrently.
    """

    # EWMA smoothing for the observed decode-step wall time (the router's
    # cost signal); same constant family as RequestBatcher.
    STEP_EWMA_ALPHA = 0.25

    def __init__(
        self,
        fns: DecodeContract,
        params,
        *,
        max_batch_size: int = 8,
        page_size: int = 0,
        max_queue_tokens: int = 0,
        slo_ms_per_token: float = 0.0,
        hard_deadline: bool = False,
        prefix_cache_entries: int = 0,
        prefill_chunk_pages: int = 0,
        device: Any = None,
        telemetry: Optional["DecodeTelemetry"] = None,
        registry=None,
        replica: str = "0",
        fault_hook: Any = None,
    ):
        # Supervision seam: called once per worker-loop round while work
        # is live; an exception here kills the worker exactly like a
        # device fault (the fleet's injected-kill path for decode).
        self._fault_hook = fault_hook
        self.fns = fns
        self.params = params
        self.max_decode_len = fns.max_decode_len
        self.eos_id = fns.eos_id
        self.pad_id = fns.pad_id
        self.max_input_len = fns.max_input_len
        self.max_batch_size = max(1, int(max_batch_size))
        self.page_size = int(page_size)
        self.max_queue_tokens = max(0, int(max_queue_tokens))
        self.slo_ms_per_token = max(0.0, float(slo_ms_per_token))
        self.hard_deadline = bool(hard_deadline)
        self.device = device
        self.batch_buckets = bucket_sizes(self.max_batch_size)
        # Positions a by-position array holds.  A decoder-only contract
        # that keeps its prompt there too states them
        # (``cache_positions``), and a row's depth then counts from the
        # prompt's first token (``_depth``); otherwise the cache begins
        # behind a BOS and holds the emitted tokens alone.
        positions = fns.cache_positions
        self._prompt_cached = positions is not None
        positions = int(positions or self.max_decode_len)
        self.kv_buckets = kv_bucket_sizes(positions, self.page_size)
        self._page = (
            self.page_size if 0 < self.page_size < positions else positions
        )
        # A contract prefilled by window (``prefill_window``) has no
        # whole-prompt prefill program; 0 = a whole-prompt ``prefill``.
        self._window_len = fns.prefill_window_len
        # Prompt-side page unit (prefix hashing + admission credits): one
        # prefill window, else the configured page size, or the whole
        # prompt when unpaged.
        self._ppage = self._window_len or (
            self.page_size if self.page_size > 0 else self.max_input_len
        )
        if self._window_len and prefix_cache_entries:
            # The cache keeps what ONE prefill program returned; the
            # windows of a prompt build one row's cache up in place.
            raise ValueError(
                "a contract prefilled by window takes no prefix cache"
            )
        self._account = fns.step_account
        self._window_account = fns.window_account
        # What ``insert`` is handed as a row's encoder output where the
        # contract has no whole-prompt prefill to return one.
        self._no_encoded = np.zeros(
            (1,) + tuple(fns.encoded_shape), np.float32
        )
        self.prefix_cache_entries = max(0, int(prefix_cache_entries))
        self._prefix = (
            PrefixCache(self.prefix_cache_entries, self._ppage)
            if self.prefix_cache_entries > 0 else None
        )
        self.prefill_chunk_pages = max(0, int(prefill_chunk_pages))
        self._admit_credits = 0
        self.telemetry = telemetry or DecodeTelemetry(registry, replica)

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: "collections.deque[_Sequence]" = collections.deque()
        self._slots: List[Optional[_Sequence]] = (
            [None] * self.max_batch_size
        )
        self._n_live = 0
        self._n_submitted = 0
        # Seconds of the phases already closed inside the phase that is
        # open now (worker thread only; see _phase).
        self._child_s = 0.0
        self._closed = False
        # Worker died (device fault / injected kill): reject new submits
        # immediately instead of queueing work nothing will ever serve.
        self._dead = False
        self._arena = None
        self._warmed = False
        self.compiles_after_warm = 0
        self.steps_run = 0
        self.step_ewma_s: Optional[float] = None

        self._step_fns: Dict[Tuple[int, int], Any] = {}
        self._jit_prefill = None
        self._jit_insert = None
        self._jit_move = None
        self._jit_clear = None
        self._jit_prefill_window = None
        # The one row that a prompt's windows are prefilled into (a
        # contract with ``prefill_window``): built up in place, copied
        # into a slot by ``insert``, used again for the next prompt.
        self._row_cache = None
        # (sequence, windows done): the queue's head while its windows
        # run; it leaves the queue with its last one.
        self._partial: Optional[Tuple[_Sequence, int]] = None
        # (sequence, first token on the device, prefix entry or None) of
        # the admissions whose first token the host has not read yet, in
        # the order of their prefills (worker thread only).
        self._unread: "collections.deque[tuple]" = collections.deque()
        # The steps dispatched and not read yet, oldest first: one while
        # the thread runs ahead, two between a step's dispatch and the
        # read of the step before it (appended and popped by the worker
        # thread under the lock; ``idle`` and ``close`` look at it).
        self._flights: "collections.deque[_Flight]" = collections.deque()
        self._steps_dispatched = 0
        self._last_read_s = 0.0

        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    # ---------------------------------------------------------- device ctx

    def _dev(self):
        if self.device is None:
            return contextlib.nullcontext()
        import jax

        return jax.default_device(self.device)

    # ------------------------------------------------------- compiled fns

    def _build_jits(self) -> None:
        """The prefill (or prefill-window), insert, move and clear
        programs of the engine's contract; the bucketed steps are built
        as they are asked for (``_step_for``)."""
        import jax
        import jax.numpy as jnp

        fns = self.fns

        def first_token(logits):
            with jax.named_scope("sample"):
                return jnp.argmax(logits[0], -1).astype(jnp.int32)

        def prefill(params, inputs, input_mask):
            cache, encoded, logits = fns.prefill(params, inputs, input_mask)
            return cache, encoded, first_token(logits)

        # The three programs below are arena work from end to end.
        def insert(state, pcache, encoded, enc_mask, tok0, slot):
            cache, tok, pos, live, enc, mask = state
            with jax.named_scope("arena"):
                cache = jax.tree_util.tree_map(
                    lambda a, p: a.at[slot].set(p[0].astype(a.dtype)),
                    cache, pcache,
                )
                return (
                    cache,
                    tok.at[slot].set(tok0),
                    pos.at[slot].set(fns.first_decode_pos(enc_mask)),
                    live.at[slot].set(True),
                    enc.at[slot].set(encoded[0].astype(enc.dtype)),
                    mask.at[slot].set(jnp.asarray(enc_mask[0], mask.dtype)),
                )

        def move(state, src, dst):
            with jax.named_scope("arena"):
                return tuple(
                    jax.tree_util.tree_map(
                        lambda a: a.at[dst].set(a[src]), part)
                    for part in state
                )

        def clear(state, slot):
            cache, tok, pos, live, enc, mask = state
            with jax.named_scope("arena"):
                return (
                    cache,
                    tok.at[slot].set(self.pad_id),
                    pos.at[slot].set(0),
                    live.at[slot].set(False),
                    enc,
                    mask,
                )

        if fns.prefill is not None:
            self._jit_prefill = _jit_program(prefill)
        self._jit_insert = _jit_program(insert)
        self._jit_move = _jit_program(move)
        self._jit_clear = _jit_program(clear)
        if self._window_len:

            def prefill_window(params, row_cache, tokens, n_valid, index):
                # One window of one prompt against the row being built;
                # the token is the prompt's first new one when this was
                # its last window.
                row_cache, logits = fns.prefill_window(
                    params, row_cache, tokens, n_valid, index)
                return row_cache, first_token(logits)

            self._jit_prefill_window = _jit_program(prefill_window)

    def _build_step(self, b: int, kv: int, fns):
        # ``fns`` stays a parameter: the benchmark's tests wrap this
        # method under this signature.
        import jax
        import jax.numpy as jnp

        pad = self.pad_id
        kind_of = _kind_reader(fns)
        # Numbers a step hands back per row beside its token (which held
        # experts the row chose; ``step_tally_len`` of them): summed over
        # the live rows here, read with the tokens, handed to
        # ``step_account``.
        tallied = bool(fns.step_tally_len)

        def run(params, state):
            cache, tok, pos, live, encoded, enc_mask = state
            # The bucket cut out, the bucket set back and the rows'
            # bookkeeping are the arena's share of a step (its re-layout,
            # where the compiler makes one, is booked there).
            with jax.named_scope("arena"):
                sub = _bucket_of(cache, b, kv, kind_of)
                rows = tok[:b], pos[:b], encoded[:b], enc_mask[:b]
            new_sub, logits, *tally = fns.step(params, sub, *rows, kv)
            with jax.named_scope("sample"):
                nxt = jnp.where(
                    live[:b], jnp.argmax(logits, -1).astype(jnp.int32), pad
                )
            with jax.named_scope("arena"):
                cache = _write_back(cache, new_sub, b, kv, kind_of)
                tok = tok.at[:b].set(nxt)
                pos = pos.at[:b].set(pos[:b] + live[:b].astype(jnp.int32))
                out = nxt
                if tallied:
                    # The live rows' tally rides behind the tokens: one
                    # array, one device-to-host read.
                    out = jnp.concatenate([nxt, jnp.sum(
                        jnp.where(live[:b, None], tally[0], 0), 0,
                        jnp.int32)])
            return (cache, tok, pos, live, encoded, enc_mask), out

        return _jit_program(run)

    def _step_for(self, b: int, kv: int):
        fn = self._step_fns.get((b, kv))
        if fn is None:
            if self._warmed:
                # The warmup contract: every (batch, kv) bucket program is
                # compiled before traffic.  A post-warm build means a
                # bucket the warmup missed — counted, loud, and the
                # warmup-contract test's assertion.
                self.compiles_after_warm += 1
                self.telemetry.on_compile_after_warm()
                log.warning(
                    "generative engine: compiling step (%d, %d) AFTER "
                    "warmup — bucket missed by warm()", b, kv,
                )
            fn = self._build_step(b, kv, self.fns)
            self._step_fns[(b, kv)] = fn
        return fn

    # ------------------------------------------------------------- arena

    def _ensure_arena(self) -> None:
        if self._arena is not None:
            return
        import jax
        import jax.numpy as jnp

        if self._jit_insert is None:
            self._build_jits()
        with self._dev():
            # Commit params AND the arena to one device up front.  The
            # jit program cache keys on each argument's placement, not
            # just its shape: an exported payload's params arrive
            # COMMITTED (orbax restore), so step outputs — the next
            # step's arena — are committed too, and a warmup that ran on
            # an uncommitted pristine arena would silently recompile
            # every bucket program on its first real-traffic step (~1 s
            # stalls that defeat the whole warm() contract).  One
            # explicit placement makes warm and traffic byte-identical
            # cache keys — the warmup-contract test pins this.
            dev = self.device
            if dev is None:
                dev = jax.local_devices()[0]
            self.params = jax.device_put(self.params, dev)
            zin = jnp.full((1, self.max_input_len), self.pad_id, jnp.int32)
            zmask = jnp.zeros((1, self.max_input_len), jnp.int32)
            B = self.max_batch_size
            if self._window_len:
                # No whole-prompt prefill to read the shapes off: the
                # contract gives the cache's arrays itself, and the
                # encoder rows it keeps (none: ``(0,)``).
                cache = self.fns.blank_cache(B)
                encoded1 = self._no_encoded
                if self._row_cache is None:
                    self._row_cache = jax.device_put(
                        self.fns.blank_cache(1), dev
                    )
            else:
                cache1, encoded1, _ = self._jit_prefill(
                    self.params, zin, zmask
                )
                cache = jax.tree_util.tree_map(
                    lambda x: jnp.zeros((B,) + x.shape[1:], x.dtype),
                    cache1,
                )
            # Free rows keep an all-ONES encoder mask: cross-attention
            # over their zero K/V then averages zeros instead of
            # softmaxing an all-masked row into NaN.  Live rows
            # overwrite it on insert.
            self._arena = jax.device_put((
                cache,
                jnp.full((B,), self.pad_id, jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), bool),
                jnp.zeros((B,) + encoded1.shape[1:], encoded1.dtype),
                jnp.ones((B, self.max_input_len), jnp.int32),
            ), dev)

    def warm(self) -> None:
        """Pre-compile every program traffic can pose: prefill (or one
        prefill window), insert / move / clear, and one step per
        ``(batch_bucket, kv_bucket)``.
        The fleet's canary gate runs this BEFORE a version becomes
        eligible — the decode analog of the predict-bucket warmup — so a
        hot-swap never pays an XLA compile mid-traffic.  Every arena
        program runs in place on the arena it is handed (``_jit_program``
        donates it), so the calls below thread the engine's own arena
        through one another and traffic is then handed a fresh blank
        one, built the way the first was: same device, same commitment,
        same program cache keys.  That is why warming needs an engine
        with nothing in flight (``RuntimeError`` otherwise), and why
        the engine's lock is held throughout: a submit waits.
        Arguments mirror the traffic paths exactly — host numpy inputs,
        the committed arena — so every call lands on the SAME program
        cache key traffic will use (see _ensure_arena on placement)."""
        import jax

        with self._lock, self._dev():
            if self._n_live or self._queue:
                raise RuntimeError(
                    "warm() runs its programs on the arena itself: "
                    "the engine must have no sequence live or queued"
                )
            self._ensure_arena()
            zin = np.full((1, self.max_input_len), self.pad_id, np.int32)
            zmask = np.zeros((1, self.max_input_len), np.int32)
            slot = np.int32(0)
            if self._window_len:
                self._row_cache, tok0 = self._jit_prefill_window(
                    self.params, self._row_cache,
                    np.full((1, self._window_len), self.pad_id, np.int32),
                    np.int32(1), np.int32(0),
                )
                cache1, encoded1 = self._row_cache, self._no_encoded
            else:
                cache1, encoded1, tok0 = self._jit_prefill(
                    self.params, zin, zmask
                )
            # tok0 goes to insert as the DEVICE scalar the prefill
            # returned, never read: a miss hands it on so, a prefix-cache
            # hit hands on the entry's (``_PrefixEntry.tok0``), and the
            # program cache keys on an argument's placement, so a host
            # int32 here would leave traffic's first insert to compile.
            self._arena = self._jit_insert(
                self._arena, cache1, encoded1, zmask, tok0, slot,
            )
            self._arena = self._jit_clear(
                self._jit_move(self._arena, slot, slot), slot
            )
            for b in self.batch_buckets:
                for kv in self.kv_buckets:
                    self._arena, _ = self._step_for(b, kv)(
                        self.params, self._arena
                    )
            # Let go of the warmed arena (and of the prefill result that
            # was inserted) before its blank successor is allocated: the
            # chip need not hold two.  A buffer is freed only once the
            # last program that uses it has finished, so wait for that.
            jax.block_until_ready(self._arena)
            del cache1, encoded1
            self._arena = None
            self._ensure_arena()
        self._warmed = True

    # ------------------------------------------------------------- client

    def outstanding_tokens(self) -> int:
        """Decode work still owed: remaining tokens of live sequences plus
        every queued sequence's full budget — the admission-control and
        routing unit."""
        with self._lock:
            return self.outstanding_tokens_locked()

    def active_sequences(self) -> int:
        with self._lock:
            return self._n_live + len(self._queue)

    def idle(self) -> bool:
        # An unread token is an unfinished request even where it holds
        # no slot (a first token under a budget of one; the last token of
        # a row retired when its step was dispatched): not idle, not to
        # be closed under it.
        with self._lock:
            return (
                self._n_live == 0 and not self._queue
                and not self._unread and not self._flights
            )

    def submit_nowait(
        self,
        inputs,
        *,
        max_new_tokens: Optional[int] = None,
        input_mask=None,
        ctx=None,
    ) -> _Sequence:
        params = validate_generation_params(
            {} if max_new_tokens is None
            else {"max_new_tokens": max_new_tokens},
            max_decode_len=self.max_decode_len,
        )
        m = params["max_new_tokens"]
        inputs = np.asarray(inputs, np.int32).reshape(-1)
        if inputs.size == 0 or inputs.size > self.max_input_len:
            raise ValueError(
                f"input length must be in [1, {self.max_input_len}], "
                f"got {inputs.size}"
            )
        if input_mask is None:
            mask = np.ones(inputs.shape, np.int32)
        else:
            mask = np.asarray(input_mask, np.int32).reshape(-1)
        pad = self.max_input_len - inputs.size
        inputs = np.pad(inputs, (0, pad), constant_values=self.pad_id)
        mask = np.pad(mask, (0, pad))
        if self.max_queue_tokens > 0:
            owed = self.outstanding_tokens()
            if owed + m > self.max_queue_tokens:
                self.telemetry.on_shed()
                raise EngineOverloaded(
                    f"outstanding decode tokens {owed} + {m} exceed the "
                    f"bound {self.max_queue_tokens}"
                )
        now = time.monotonic()
        seq = _Sequence(
            inputs=inputs,
            input_mask=mask,
            max_new_tokens=m,
            arrival_s=now,
            deadline_s=token_deadline_s(now, m, self.slo_ms_per_token),
            ctx=ctx,
            arrival_wall_s=time.time(),
        )
        with self._cond:
            if self._closed:
                raise RuntimeError("engine is closed")
            if self._dead:
                raise RuntimeError("engine worker died")
            self._n_submitted += 1
            seq.seq_id = self._n_submitted
            self._queue.append(seq)
            self.telemetry.on_queue(self.outstanding_tokens_locked())
            self._cond.notify_all()
        return seq

    def outstanding_tokens_locked(self) -> int:
        # Caller holds self._lock (the condition's underlying lock).
        live = sum(
            max(0, s.max_new_tokens - s.held)
            for s in self._slots[: self._n_live] if s is not None
        )
        return live + sum(s.max_new_tokens for s in self._queue)

    def submit(
        self,
        inputs,
        *,
        max_new_tokens: Optional[int] = None,
        input_mask=None,
        timeout_s: float = 300.0,
    ) -> np.ndarray:
        """Blocking generate for one sequence; returns the emitted token
        ids (EOS included when hit within budget)."""
        return self.submit_nowait(
            inputs, max_new_tokens=max_new_tokens, input_mask=input_mask
        ).wait(timeout_s)

    def close(self, timeout_s: float = 5.0, *, final_error=None) -> None:
        """Reject new submits and fail everything unfinished.  Sequences
        mid-decode get ``GenerationEvicted`` (the zero-drop contract is
        the fleet's: it only closes engines after the drain) —
        ``final_error`` overrides that verdict, which the supervised
        rebuild uses so racing waiters recover instead of surfacing a
        503."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        self._worker.join(timeout=timeout_s)
        with self._lock:
            pending = self._take_unfinished()
            self._slots = [None] * self.max_batch_size
        for seq in pending:
            self._release_prefix(seq)
            self._trace_end(seq, "evicted")
            seq.finish(final_error or GenerationEvicted("engine closed"))
        if not self._worker.is_alive():
            # A closed engine holds no device memory: its programs'
            # closures and the engine refer to each other, so the arena
            # would otherwise live until the collector finds the cycle
            # (6.9 GB of a 16 GB chip under a long-context contract).
            self._arena = self._row_cache = None

    # ------------------------------------------------------------- worker

    @contextlib.contextmanager
    def _phase(self, phase: str, **args):
        """One phase of the worker thread (``ENGINE_PHASES``): a span
        ``engine.<phase>`` in a profile, and its self seconds and one
        occurrence on the telemetry's counters.  The annotation records
        only while a profiler session runs and costs a flag test when
        none does; the counters are always on.  Phases nest by time, so
        a phase's self time is its own less that of the phases closed
        inside it.  Yields the annotation: ``set_metadata(**kw)`` adds
        arguments that are known only inside the span."""
        from jax.profiler import TraceAnnotation

        outer, self._child_s = self._child_s, 0.0
        t0 = time.perf_counter()
        try:
            with TraceAnnotation("engine." + phase, **args) as span:
                yield span
        finally:
            dt = time.perf_counter() - t0
            self.telemetry.on_phase(phase, max(0.0, dt - self._child_s))
            self._child_s = outer + dt

    def _run(self) -> None:
        try:
            while True:
                with self._cond:
                    while (
                        not self._closed
                        and not self._queue
                        and self._n_live == 0
                        and not self._unread
                        and not self._flights
                    ):
                        with self._phase("idle"):
                            self._cond.wait()
                    if self._closed:
                        return
                if self._fault_hook is not None:
                    self._fault_hook()
                if self._n_live:
                    # The step first, for the rows that are seated: the
                    # read of the step before it and admission's host
                    # work go behind it.
                    self._step_once()
                    if self.prefill_chunk_pages > 0:
                        # Each decode step EARNS admission credits
                        # (chunked prefill's meter), capped at one full
                        # prompt so idle decode can't bank a stall-sized
                        # prefill burst.
                        cap = max(
                            self.prefill_chunk_pages,
                            -(-self.max_input_len // self._ppage),
                        )
                        self._admit_credits = min(
                            cap,
                            self._admit_credits + self.prefill_chunk_pages,
                        )
                    continue
                # No row to step: the last step's tokens, if it is still
                # unread (its rows all left as it was dispatched), then
                # admission; the rows that are seated ride the next
                # round's step, and their first tokens are read behind it.
                with self._dev():
                    self._land()
                self._admit()
                if not self._n_live and self._unread:
                    # Still nothing to step: what was admitted (now, or
                    # behind the last step) ends at its first token,
                    # and its read has nothing to go behind.
                    with self._phase("emit", live=0), self._dev():
                        self._read_first_tokens("blocking")
        except Exception as e:  # noqa: BLE001 — device fault: fail loudly
            log.exception("generative engine worker died")
            with self._lock:
                self._dead = True
                # The program that failed may have taken the arena with
                # it (a donated argument is gone whether or not its
                # program ran to the end): nothing may use it again.
                self._arena = None
                pending = self._take_unfinished()
            for seq in pending:
                self._release_prefix(seq)
                self._trace_end(seq, "error")
                seq.finish(e)

    def _take_unfinished(self) -> List[_Sequence]:
        """Every sequence the engine still owes an end, taken off the
        queue, out of the slots, off the list of unread first tokens and
        out of the steps in flight (caller holds ``self._lock``).  A
        token that was never read stays on the device: the handle's
        ``tokens`` are what the host has seen, which is what a recovery
        re-prefills from."""
        # A request can be owed an end and hold no slot: a budget of one
        # token (on ``_unread`` alone), or a row retired when its last
        # step was dispatched (in that step's snapshot alone).
        owed = (
            list(self._queue)
            + [s for s in self._slots[: self._n_live] if s is not None]
            + [seq for seq, _, _ in self._unread]
            + [seq for flight in self._flights for seq in flight.rows]
        )
        pending = list({
            id(s): s for s in owed if not s._done.is_set()
        }.values())
        self._queue.clear()
        self._unread.clear()
        self._flights.clear()
        self._n_live = 0
        return pending

    def _prompt_pages(self, seq: _Sequence) -> int:
        n_valid = int((seq.input_mask > 0).sum())
        return max(1, -(-n_valid // self._ppage))

    def _admit(self) -> None:
        """Iteration-level admission: fill free slots from the queue NOW —
        between two decode steps — instead of waiting for the batch to
        drain.  One prefill (encoder + step-0 decode, the greedy math)
        per admitted sequence — or an arena scatter alone when the
        prefix cache already holds this prompt — metered by chunked-
        prefill credits when live sequences could starve.

        Admission only DISPATCHES, and while rows are live it does so
        behind a step that is already queued (``_step_once`` calls it),
        so the device works through the host's part of it.  A prefill's
        first token goes to ``insert`` as the device scalar it is and
        onto ``self._unread``; the host reads it once the NEXT step is
        queued behind it, so the device always has work queued behind
        what the thread waits for."""
        # The unlocked look at the queue only keeps a round with nothing
        # to admit from counting as an ``admit`` turn; ``_admit_one``
        # decides under the lock.
        while self._queue and self._n_live < self.max_batch_size:
            with self._phase("admit") as span:
                if not self._admit_one(span):
                    return

    def _admit_one(self, span) -> bool:
        """One turn of admission, for the sequence at the head of the
        queue.  False when the head stays queued: chunked prefill has no
        credits for it (or ``close`` emptied the queue meanwhile)."""
        if self._window_len:
            return self._admit_window(span)
        with self._lock:
            if not self._queue or self._n_live >= self.max_batch_size:
                return False
            seq = self._queue[0]
            entry = key = None
            if self._prefix is not None:
                key, pages = PrefixCache.key_of(
                    seq.inputs, seq.input_mask, self._ppage
                )
                entry = self._prefix.peek(key)
            else:
                pages = self._prompt_pages(seq)
            span.set_metadata(
                seq=seq.seq_id, prefix_hit=int(entry is not None)
            )
            cost = 1 if entry is not None else pages
            if (
                self.prefill_chunk_pages > 0
                and self._n_live > 0
                and cost > self._admit_credits
            ):
                # Not enough credits between steps: leave the head
                # queued, decode earns more, admission resumes next
                # round — a long prompt never skips a live
                # sequence's token deadline.
                return False
            self._queue.popleft()
            if self.prefill_chunk_pages > 0 and self._n_live > 0:
                self._admit_credits -= cost
        seq.admitted_s = time.monotonic()
        self.telemetry.on_admitted(seq.admitted_s - seq.arrival_s)
        with self._dev():
            self._ensure_arena()
            if entry is not None:
                self._prefix.hits += 1
                self._prefix.touch(entry)
                self.telemetry.on_prefix_hit(entry.pages)
                cache1, enc1, tok0 = entry.cache, entry.encoded, entry.tok0
            else:
                with self._phase(
                    "prefill", seq=seq.seq_id,
                    prompt_tokens=int((seq.input_mask > 0).sum()),
                ):
                    # Dispatched, not waited for: the token is read once
                    # the round's step is queued behind it.
                    cache1, enc1, tok0 = self._jit_prefill(
                        self.params, seq.inputs[None], seq.input_mask[None]
                    )
                if self._prefix is not None:
                    self._prefix.misses += 1
                    self.telemetry.on_prefix_miss()
                    entry = self._prefix.insert(
                        key, pages, tok0, cache1, enc1
                    )
            return self._seat(seq, cache1, enc1, tok0, entry)

    def _admit_window(self, span) -> bool:
        """One turn of admission under a contract that is prefilled by
        window: ONE window of the prompt at the head of the queue, into
        the engine's row cache.  The head stays queued while its windows
        run (each costs one chunked-prefill credit, so decode steps of
        the live rows run in between) and takes its slot with the last.
        The prompt is its mask's count of tokens from the left."""
        W = self._window_len
        with self._lock:
            if not self._queue or self._n_live >= self.max_batch_size:
                return False
            seq = self._queue[0]
            if self.prefill_chunk_pages > 0 and self._n_live > 0:
                if self._admit_credits < 1:
                    return False
                self._admit_credits -= 1
        if self._partial is None or self._partial[0] is not seq:
            self._partial = (seq, 0)
            seq.admitted_s = time.monotonic()
            self.telemetry.on_admitted(seq.admitted_s - seq.arrival_s)
        index = self._partial[1]
        n_prompt = seq.first_pos = int((seq.input_mask > 0).sum())
        count = min(W, n_prompt - index * W)
        span.set_metadata(seq=seq.seq_id, prefix_hit=0, window=index)
        with self._dev():
            self._ensure_arena()
            with self._phase(
                "prefill.window", seq=seq.seq_id, window=index, tokens=count,
            ):
                tokens = np.full((1, W), self.pad_id, np.int32)
                tokens[0, :count] = seq.inputs[index * W:index * W + count]
                self._row_cache, tok0 = self._jit_prefill_window(
                    self.params, self._row_cache, tokens,
                    np.int32(count), np.int32(index),
                )
                self.telemetry.on_prefill_window(
                    count, self._window_account and self._window_account(
                        index))
                if (index + 1) * W < n_prompt:
                    self._partial = (seq, index + 1)
                    return True
            # The last window: its token is the prompt's first new one.
            self._partial = None
            with self._lock:
                if not self._queue or self._queue[0] is not seq:
                    return False      # ``close`` took the queue meanwhile
                self._queue.popleft()
            return self._seat(
                seq, self._row_cache, self._no_encoded, tok0, None
            )

    def _seat(self, seq, cache1, enc1, tok0, entry) -> bool:
        """A prefilled sequence's slot in the arena (under
        ``self._dev()``).  ``tok0`` is its first token as the prefill
        program returned it, on the device; the host knows its value
        only where a prefix-cache entry has kept it, and otherwise
        reads it later (``_read_first_tokens``).  A sequence that the
        host already knows to end at its first token takes no slot: a
        known EOS, or a budget of one token."""
        if self._prefix is not None:
            self.telemetry.on_prefix_pages(self._prefix.pages_in_use())
        if entry is not None and entry.tok0_host is not None:
            self._first_token(seq, entry.tok0_host, "known")
            if self._ended(seq):
                self._complete(seq)
                return True
        else:
            seq.first_unread = True
            self._unread.append((seq, tok0, entry))
            if seq.max_new_tokens <= 1:
                return True             # ends where its token is read
        if entry is not None:
            self._prefix.acquire(entry)
            seq.prefix_entry = entry
        slot = self._n_live
        with self._phase("insert", seq=seq.seq_id, slot=slot):
            self._arena = self._jit_insert(
                self._arena, cache1, enc1, seq.input_mask[None],
                tok0, np.int32(slot),
            )
        if seq.ctx is not None:
            # Slot event: the sequence joined the continuous batch —
            # the wait it paid in the queue is arrival -> now.
            seq.ctx.span_from_mono(
                "decode.join", seq.arrival_s,
                slot=slot, budget_tokens=seq.max_new_tokens,
                prefix_hit=seq.prefix_entry is not None,
                seq=seq.seq_id,
            )
        with self._lock:
            self._slots[slot] = seq
            seq.slot = slot
            self._n_live += 1
        return True

    def _ended(self, seq: _Sequence) -> bool:
        """Whether the last token the host has of ``seq`` ended it."""
        return (
            seq.tokens[-1] == self.eos_id
            or len(seq.tokens) >= seq.max_new_tokens
        )

    def _first_token(self, seq: _Sequence, t0: int, read: str) -> None:
        """``seq``'s first token has reached the host (``read``: how, the
        label of ``serving_decode_first_token_reads_total``)."""
        seq.first_unread = False
        seq.first_token_s = time.monotonic()
        self.telemetry.on_first_token(seq.first_token_s - seq.arrival_s)
        self.telemetry.on_first_token_read(read)
        seq.tokens.append(t0)

    def _read_first_tokens(
        self, read: str, count: Optional[int] = None
    ) -> None:
        """Read to the host the oldest ``count`` (all by default) of the
        first tokens still on the device alone, in the order of their
        prefills (``read``: ``behind_step`` with a step queued behind
        them, ``blocking`` with nothing).  Each read returns when its
        prefill has run.  A sequence that ends at its first token ends
        here: under a budget of one token it took no slot; where the
        token is EOS it has a row and rides the step in flight, so the
        row is retired now and that step's read drops its token."""
        from jax.profiler import TraceAnnotation

        for _ in range(len(self._unread) if count is None else count):
            seq, tok0, entry = self._unread[0]
            with TraceAnnotation("engine.prefill.wait", seq=seq.seq_id):
                t0 = int(tok0)
            self._unread.popleft()
            if entry is not None:
                entry.tok0_host = t0
            self._first_token(seq, t0, read)
            if self._ended(seq):
                self._end(seq)

    def _depth(self, seq: _Sequence) -> int:
        """Positions of a by-position cache that ``seq`` holds once the
        step being dispatched has written its own: the emitted tokens
        behind a BOS, or behind the prompt where the cache holds that
        too (``first_pos`` is then the prompt's length).  What the kv
        bucket has to cover and what ``serving_decode_cache_pages_in_use``
        counts."""
        return (seq.first_pos if self._prompt_cached else 1) + seq.held

    def _step_once(self) -> None:
        """One round with rows live, the thread one step ahead of what
        it has read: dispatch the rows' step k, retire the rows whose
        budget it fills, read and hand out step k - 1's tokens
        (``_land``), admit behind step k, read the first tokens of the
        prefills dispatched AHEAD of step k.  Step k needs only counts
        of the host (the row bucket, the deepest row, the budgets); its
        token and position inputs are in the arena.  Both waits of the
        round are for programs the device has left behind or is about
        to, with step k queued behind them: between two steps the device
        never waits for the host.  What is admitted behind step k rides
        step k + 1."""
        n = self._n_live
        with self._phase("step") as span, self._dev():
            rows = tuple(self._slots[:n])
            depths = [self._depth(s) for s in rows]
            b = next(bk for bk in self.batch_buckets if bk >= n)
            kv = next(k for k in self.kv_buckets if k >= max(depths))
            queued = "behind_step" if self._flights else "alone"
            self._steps_dispatched += 1
            span.set_metadata(
                step=self._steps_dispatched, live=n, b=b, kv=kv,
                queued=queued,
            )
            fn = self._step_for(b, kv)
            t0 = time.perf_counter()
            self._arena, nxt = fn(self.params, self._arena)
            self.telemetry.on_step_dispatch(queued)
            flight = _Flight(
                self._steps_dispatched, nxt, rows,
                # The position each row fed, for the contract's account
                # of the step (``step_account``), where it keeps one.
                [s.first_pos + s.held - 1 for s in rows]
                if self._account is not None else [],
                b, kv, sum(-(-d // self._page) for d in depths), t0,
            )
            with self._lock:
                self._flights.append(flight)
                for seq in rows:
                    seq.in_flight += 1
            # Retire by count: a row whose budget this step fills needs
            # no further step whatever its token is, and the token is in
            # ``nxt``, which no arena program touches.  Its slot is free
            # for this round's admission.
            for slot in range(n - 1, -1, -1):
                if rows[slot].held >= rows[slot].max_new_tokens:
                    self._retire(slot)
            if len(self._flights) > 1:
                self._land()
            ahead = len(self._unread)
            self._admit()
            # Each is there when its prefill is, and that ran before
            # the step just dispatched.
            self._read_first_tokens("behind_step", ahead)

    def _land(self) -> None:
        """Read the oldest step in flight (none: nothing to do) and hand
        its tokens out by ITS snapshot of the rows: a row that moved
        since, or left, still gets its own.  A sequence that ended
        before this read (its previous token was EOS, its first was, it
        was evicted) rode the step for nothing: its token is dropped and
        the row-step counted as wasted.  One that ends here by EOS or by
        a hard deadline has its row retired now, having ridden the step
        dispatched since (the next read drops that one)."""
        from jax.profiler import TraceAnnotation

        if not self._flights:
            return
        flight = self._flights[0]
        n, b, kv = len(flight.rows), flight.b, flight.kv
        with self._phase("emit", step=flight.index, live=n):
            with TraceAnnotation("engine.step.wait", step=flight.index):
                toks = np.asarray(flight.nxt)
            # The step period as callers see it: read to read while the
            # thread runs ahead, dispatch to read for a step alone.
            now_s = time.perf_counter()
            dt = now_s - max(flight.t0, self._last_read_s)
            self._last_read_s = now_s
            if self.step_ewma_s is None:
                self.step_ewma_s = dt
            else:
                a = self.STEP_EWMA_ALPHA
                self.step_ewma_s = (1 - a) * self.step_ewma_s + a * dt
            self.steps_run += 1
            self.telemetry.on_step(
                dt, self.step_ewma_s, n, b, flight.pages, n
            )
            if self._account is not None:
                # What this step read of each kind of cache, by the
                # contract's own account of the rows' positions, of the
                # step's tally (empty where it hands none back) and of
                # the bucket it ran in.
                self.telemetry.on_cache(self._account(
                    flight.positions, toks[b:].tolist(), (b, kv)))
            now = time.monotonic()
            wasted = 0
            for slot in range(n - 1, -1, -1):
                seq = flight.rows[slot]
                seq.in_flight -= 1
                if seq._done.is_set():
                    wasted += 1
                    continue
                seq.tokens.append(int(toks[slot]))
                self.telemetry.on_token()
                if seq.ctx is not None:
                    # Per decode-step slot event: which step, which
                    # program bucket pair — the trace shows exactly
                    # which steps this sequence rode and with how much
                    # co-batched company.
                    seq.ctx.instant(
                        "decode.step", slot=slot, token=len(seq.tokens),
                        batch_bucket=b, kv_bucket=kv, live=n,
                        step_s=round(dt, 6),
                    )
                self._settle(seq, now)
            if wasted:
                self.telemetry.on_wasted_row_steps(wasted)
            with self._lock:
                self._flights.popleft()

    def _settle(self, seq: _Sequence, now: float) -> None:
        """After a step's token is appended: end a sequence that hit
        EOS or its budget, evict one past its hard deadline (its row,
        like an EOS ending's, has ridden the step dispatched since),
        leave the rest where they are."""
        if self._ended(seq):
            self._end(seq)
        elif (
            self.hard_deadline
            and seq.deadline_s is not None
            and now > seq.deadline_s
        ):
            self.telemetry.on_evicted()
            slot = seq.slot
            if slot is not None:        # else its last step was in flight
                self._retire(slot)
            self._evict_seq(
                seq, slot,
                f"per-token SLO deadline exceeded after "
                f"{len(seq.tokens)}/{seq.max_new_tokens} tokens",
            )

    def _end(self, seq: _Sequence) -> None:
        """``seq``'s last token is on the host: complete it, its row
        retired first if it still holds one (an EOS ending; a budget's
        row left when its last step was dispatched)."""
        # Retire the slot BEFORE waking the waiter: the client thread
        # resumes to consistent accounting (outstanding_tokens of a
        # finished sequence is already 0, its slot already free).
        if seq.ctx is not None and seq.tokens[-1] == self.eos_id:
            seq.ctx.instant(
                "decode.eos", slot=seq.slot, tokens=len(seq.tokens)
            )
        if seq.slot is not None:
            self._retire(seq.slot)
        self._complete(seq)

    def _retire(self, slot: int) -> None:
        last = self._n_live - 1
        seq = self._slots[slot]
        with self._phase(
            "retire", seq=seq.seq_id, slot=slot, moved=int(slot != last),
        ):
            with self._dev():
                if slot != last:
                    self._arena = self._jit_move(
                        self._arena, np.int32(last), np.int32(slot)
                    )
                self._arena = self._jit_clear(self._arena, np.int32(last))
            with self._lock:
                if slot != last:
                    moved = self._slots[slot] = self._slots[last]
                    moved.slot = slot
                self._slots[last] = None
                seq.slot = None
                self._n_live -= 1

    def _release_prefix(self, seq: _Sequence) -> None:
        """Drop this sequence's reader reference on its prefix-cache
        entry (no-op when it holds none).  The LAST reader's release is
        what makes an over-capacity entry evictable — the refcount
        contract the accounting test pins."""
        entry = seq.prefix_entry
        if entry is None or self._prefix is None:
            return
        seq.prefix_entry = None
        self._prefix.release(entry)
        self.telemetry.on_prefix_pages(self._prefix.pages_in_use())

    def _complete(self, seq: _Sequence) -> None:
        self._release_prefix(seq)
        latency = time.monotonic() - seq.arrival_s
        self.telemetry.on_done(latency, len(seq.tokens))
        self._trace_end(seq, "complete")
        seq.finish()

    def _evict_seq(self, seq: _Sequence, slot: int, reason: str) -> None:
        self._release_prefix(seq)
        if seq.ctx is not None:
            seq.ctx.instant(
                "decode.evict", slot=slot, tokens=len(seq.tokens),
                reason=reason,
            )
        self._trace_end(seq, "evicted")
        seq.finish(GenerationEvicted(reason))

    def _trace_end(self, seq: _Sequence, status: str) -> None:
        """The whole-lifetime ``decode`` span (arrival -> end): emitted
        for EVERY terminal edge — EOS, budget, eviction, engine death —
        so a stream's trace always covers its full decode lifetime."""
        if seq.ctx is None:
            return
        seq.ctx.complete_span(
            "decode", seq.arrival_wall_s, seq.arrival_s,
            time.monotonic() - seq.arrival_s,
            status=status, tokens=len(seq.tokens),
            budget_tokens=seq.max_new_tokens,
        )


class DecodeTelemetry:
    """The ``serving_decode_*`` family, shared by every engine of one
    replica (one label set per replica, however many versions are
    resident mid-drain).  All methods are no-ops without a registry."""

    def __init__(self, registry=None, replica: str = "0"):
        self.replica = str(replica)
        self._steps = self._tokens = self._seqs = self._evicted = None
        self._shed = self._occ = self._pages = self._active = None
        self._queue_tokens = self._step_s = self._per_token = None
        self._compiles = None
        self._prefix_hits = self._prefix_misses = None
        self._prefix_hit_pages = self._prefix_pages = None
        self._phase_s = self._phase_n = None
        self._queue_wait = self._ttft = self._first_reads = None
        self._dispatches = self._wasted = None
        self._prefill_tokens = self._prefill_windows = None
        self._window_key_blocks = None
        self._rollovers = self._summaries = None
        self._cache_bytes = self._cache_read = None
        self._cache_entries = self._cache_span = None
        self._expert_assignments = self._experts_touched = None
        self._expert_load_sum = self._expert_load_count = None
        if registry is None:
            return
        from tpu_pipelines.observability.metrics import fine_latency_buckets

        lab = ("replica",)
        self._steps = registry.counter(
            "serving_decode_steps_total",
            "Continuous-batch decode steps executed.", labels=lab,
        ).labels(self.replica)
        self._tokens = registry.counter(
            "serving_decode_tokens_total",
            "Tokens emitted by the continuous-batch engine.", labels=lab,
        ).labels(self.replica)
        self._seqs = registry.counter(
            "serving_decode_sequences_total",
            "Generations completed (EOS or max_new_tokens).", labels=lab,
        ).labels(self.replica)
        self._evicted = registry.counter(
            "serving_decode_evicted_total",
            "Sequences evicted before finishing (per-token SLO deadline "
            "or engine shutdown).", labels=lab,
        ).labels(self.replica)
        self._shed = registry.counter(
            "serving_decode_shed_total",
            "Sequences refused by token-level admission control.",
            labels=lab,
        ).labels(self.replica)
        self._occ = registry.gauge(
            "serving_decode_batch_occupancy",
            "Live sequences / batch bucket of the most recent step.",
            labels=lab,
        ).labels(self.replica)
        self._pages = registry.gauge(
            "serving_decode_cache_pages_in_use",
            "KV-cache pages covering every live sequence's positions.",
            labels=lab,
        ).labels(self.replica)
        self._active = registry.gauge(
            "serving_decode_sequences_active",
            "Sequences live in the decode arena.", labels=lab,
        ).labels(self.replica)
        self._queue_tokens = registry.gauge(
            "serving_decode_queue_tokens",
            "Outstanding decode tokens (live remainder + queued budgets).",
            labels=lab,
        ).labels(self.replica)
        self._step_s = registry.gauge(
            "serving_decode_step_seconds",
            "EWMA of the decode step period: from the later of a step's "
            "dispatch and the previous step's read to its own read.",
            labels=lab,
        ).labels(self.replica)
        # Fine sqrt(2) ladder (metrics.fine_latency_buckets, 25µs to
        # ~1.6s): per-token latency spans from a tiny model's tens of µs
        # to a large one's tens of ms (T5-large on a v5e: a 34 ms step,
        # ~50 ms per token at the tail), and the default x2 ladder both
        # floors at 100µs and quantizes a scraped quantile by up to 2x.
        self._per_token = registry.histogram(
            "serving_decode_per_token_latency_seconds",
            "Completed-generation latency divided by tokens emitted — "
            "the per-token SLO judge (fine sqrt(2) buckets).",
            labels=lab, buckets=fine_latency_buckets(),
        ).labels(self.replica)
        self._compiles = registry.counter(
            "serving_decode_compiles_after_warm_total",
            "Decode-step programs compiled AFTER warm() — each one is a "
            "broken warmup contract (an XLA compile paid mid-traffic); "
            "the SLO monitor treats any increase as a breach.",
            labels=lab,
        ).labels(self.replica)
        self._prefix_hits = registry.counter(
            "serving_decode_prefix_hit_total",
            "Admissions served from the prefix cache (prefill skipped).",
            labels=lab,
        ).labels(self.replica)
        self._prefix_misses = registry.counter(
            "serving_decode_prefix_miss_total",
            "Admissions that ran a full prefill with the prefix cache "
            "enabled.", labels=lab,
        ).labels(self.replica)
        self._prefix_hit_pages = registry.counter(
            "serving_decode_prefix_hit_pages_total",
            "Prompt pages whose prefill was skipped via prefix-cache "
            "hits — the work the cache saved.", labels=lab,
        ).labels(self.replica)
        self._prefix_pages = registry.gauge(
            "serving_decode_prefix_pages_in_use",
            "Prompt pages resident in the prefix cache (readers pin "
            "entries past capacity until the last one retires).",
            labels=lab,
        ).labels(self.replica)
        phase_lab = ("replica", "phase")
        seconds = registry.counter(
            "serving_decode_engine_seconds_total",
            "Self seconds of the engine's worker thread by phase (a "
            "nested phase's time is taken out of its parent's): the "
            "phases add up to the thread's lifetime.", labels=phase_lab,
        )
        occurrences = registry.counter(
            "serving_decode_engine_phase_total",
            "Occurrences of each phase of the engine's worker thread.",
            labels=phase_lab,
        )
        self._phase_s = {
            p: seconds.labels(self.replica, p) for p in ENGINE_PHASES
        }
        self._phase_n = {
            p: occurrences.labels(self.replica, p) for p in ENGINE_PHASES
        }
        self._prefill_tokens = registry.counter(
            "serving_decode_prefill_tokens_total",
            "Prompt tokens prefilled a window at a time.", labels=lab,
        ).labels(self.replica)
        self._prefill_windows = registry.counter(
            "serving_decode_prefill_windows_total",
            "Prefill-window programs run: a prompt of L tokens costs "
            "ceil(L / window).", labels=lab,
        ).labels(self.replica)
        self._window_key_blocks = registry.counter(
            "serving_decode_window_key_blocks_total",
            "Key blocks that the prefill windows' attention visited and "
            "that their rows held, layers together, as the contract's "
            "window_account states them.", labels=("replica", "state"),
        )
        self._rollovers = registry.counter(
            "serving_decode_window_rollovers_total",
            "Decode steps of a row that began a new attention window "
            "(the ring is written from its start again).", labels=lab,
        ).labels(self.replica)
        self._summaries = registry.counter(
            "serving_decode_chunk_summaries_total",
            "Chunk summaries stored by decode steps (one per row per "
            "chunk closed).", labels=lab,
        ).labels(self.replica)
        kind_lab = ("replica", "kind")
        self._cache_bytes = registry.gauge(
            "serving_decode_cache_bytes",
            "Bytes of each kind of cache that the live rows of the most "
            "recent decode step read (their valid entries).",
            labels=kind_lab,
        )
        self._cache_read = registry.counter(
            "serving_decode_cache_read_bytes_total",
            "serving_decode_cache_bytes summed over the decode steps run.",
            labels=kind_lab,
        )
        self._cache_entries = registry.gauge(
            "serving_decode_cache_entries",
            "Entries of each kind of cache that are valid for the live "
            "rows of the most recent decode step, layers summed (a "
            "contract whose layers differ in kind).", labels=kind_lab,
        )
        self._cache_span = registry.counter(
            "serving_decode_cache_span_bytes_total",
            "Bytes of each kind of cache that the decode steps move for "
            "their live rows, as the contract's step_account states them "
            "(the whole key blocks that a kernel reading to each row's "
            "depth fetches), summed over the steps run, beside "
            "serving_decode_cache_read_bytes_total, what is valid.",
            labels=kind_lab,
        )
        self._expert_assignments = registry.counter(
            "serving_decode_expert_assignments_total",
            "Assignments of live rows to the experts this replica holds, "
            "summed over expert layers and decode steps.", labels=lab,
        ).labels(self.replica)
        self._experts_touched = registry.counter(
            "serving_decode_experts_touched_total",
            "Held experts with at least one assignment in a decode step "
            "(the experts whose weights that step must read), summed over "
            "expert layers and decode steps.", labels=lab,
        ).labels(self.replica)
        self._expert_load_sum = registry.counter(
            "serving_decode_expert_load_ratio_sum",
            "Per decode step, the fullest held expert's assignments over "
            "the mean of the held experts (expert layers averaged), "
            "summed over the steps counted by "
            "serving_decode_expert_load_ratio_count.", labels=lab,
        ).labels(self.replica)
        self._expert_load_count = registry.counter(
            "serving_decode_expert_load_ratio_count",
            "Decode steps with an assignment to a held expert.", labels=lab,
        ).labels(self.replica)
        self._queue_wait = registry.histogram(
            "serving_decode_queue_wait_seconds",
            "Submit to the admission turn that took the sequence off "
            "the queue (fine sqrt(2) buckets; _sum and _count exact).",
            labels=lab, buckets=fine_latency_buckets(),
        ).labels(self.replica)
        self._ttft = registry.histogram(
            "serving_decode_ttft_seconds",
            "Submit to the sequence's first token on the host: queue "
            "wait plus prefill, or plus nothing on a prefix-cache hit "
            "(fine sqrt(2) buckets; _sum and _count exact).",
            labels=lab, buckets=fine_latency_buckets(),
        ).labels(self.replica)

        reads = registry.counter(
            "serving_decode_first_token_reads_total",
            "How each admission's first token reached the host: "
            "behind_step (read with the round's step queued behind its "
            "prefill), known (a prefix-cache hit: nothing read), "
            "blocking (read with nothing queued behind it).",
            labels=("replica", "read"),
        )
        self._first_reads = {
            r: reads.labels(self.replica, r) for r in FIRST_TOKEN_READS
        }

        dispatches = registry.counter(
            "serving_decode_step_dispatch_total",
            "Decode steps dispatched, by what the device had queued: "
            "behind_step (the step before it still unread: the thread "
            "runs one step ahead), alone (nothing in flight).  The two "
            "add up to serving_decode_steps_total once every step is "
            "read.", labels=("replica", "queued"),
        )
        self._dispatches = {
            q: dispatches.labels(self.replica, q) for q in STEP_DISPATCHES
        }
        self._wasted = registry.counter(
            "serving_decode_wasted_row_steps_total",
            "Rows that rode a decode step after their sequence's end "
            "(an EOS or an eviction is learnt one step late): row-steps "
            "whose token was dropped.", labels=lab,
        ).labels(self.replica)

    def on_first_token_read(self, read: str) -> None:
        if self._first_reads is not None:
            self._first_reads[read].inc()

    def on_step_dispatch(self, queued: str) -> None:
        if self._dispatches is not None:
            self._dispatches[queued].inc()

    def on_wasted_row_steps(self, n_rows: int) -> None:
        if self._wasted is not None:
            self._wasted.inc(n_rows)

    def on_prefill_window(self, n_tokens: int, account=None) -> None:
        """One prefill window of ``n_tokens``, and its account by the
        contract (``window_account``) where it keeps one."""
        if self._prefill_windows is None:
            return
        self._prefill_windows.inc()
        self._prefill_tokens.inc(n_tokens)
        for state, n in (account or {}).get("key_blocks", {}).items():
            self._window_key_blocks.labels(self.replica, state).inc(n)

    def on_cache(self, account: Dict[str, Any]) -> None:
        """One decode step's account by the contract (``step_account``):
        bytes of each kind of cache its rows read, and the events among
        its rows."""
        if self._cache_bytes is None:
            return
        for kind, n_bytes in account["cache_bytes"].items():
            self._cache_bytes.labels(self.replica, kind).set(n_bytes)
            self._cache_read.labels(self.replica, kind).inc(n_bytes)
        for kind, n in account.get("cache_entries", {}).items():
            self._cache_entries.labels(self.replica, kind).set(n)
        for kind, n_bytes in account.get("cache_span_bytes", {}).items():
            self._cache_span.labels(self.replica, kind).inc(n_bytes)
        self._rollovers.inc(account.get("window_rollovers", 0))
        self._summaries.inc(account.get("chunk_summaries", 0))
        self._expert_assignments.inc(account.get("expert_assignments", 0))
        self._experts_touched.inc(account.get("experts_touched", 0))
        ratio = account.get("expert_load_ratio")
        if ratio is not None:
            self._expert_load_sum.inc(ratio)
            self._expert_load_count.inc()

    def on_step(self, dt, ewma, live, bucket, pages, active) -> None:
        if self._steps is None:
            return
        self._steps.inc()
        self._occ.set(live / max(1, bucket))
        self._pages.set(pages)
        self._active.set(active)
        self._step_s.set(ewma)

    def on_token(self) -> None:
        if self._tokens is not None:
            self._tokens.inc()

    def on_done(self, latency_s: float, n_tokens: int) -> None:
        if self._seqs is None:
            return
        self._seqs.inc()
        self._per_token.observe(latency_s / max(1, n_tokens))

    def on_phase(self, phase: str, seconds: float) -> None:
        if self._phase_s is not None:
            self._phase_s[phase].inc(seconds)
            self._phase_n[phase].inc()

    def on_admitted(self, queue_wait_s: float) -> None:
        if self._queue_wait is not None:
            self._queue_wait.observe(queue_wait_s)

    def on_first_token(self, ttft_s: float) -> None:
        if self._ttft is not None:
            self._ttft.observe(ttft_s)

    def on_evicted(self) -> None:
        if self._evicted is not None:
            self._evicted.inc()

    def on_shed(self) -> None:
        if self._shed is not None:
            self._shed.inc()

    def on_queue(self, outstanding_tokens: int) -> None:
        if self._queue_tokens is not None:
            self._queue_tokens.set(outstanding_tokens)

    def on_compile_after_warm(self) -> None:
        if self._compiles is not None:
            self._compiles.inc()

    def on_prefix_hit(self, pages: int) -> None:
        if self._prefix_hits is not None:
            self._prefix_hits.inc()
            self._prefix_hit_pages.inc(pages)

    def on_prefix_miss(self) -> None:
        if self._prefix_misses is not None:
            self._prefix_misses.inc()

    def on_prefix_pages(self, pages: int) -> None:
        if self._prefix_pages is not None:
            self._prefix_pages.set(pages)
