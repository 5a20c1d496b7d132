"""Continuous batching for autoregressive decode (ISSUE 11).

Two layers, both tier-1-safe (``generative`` marker):

* **Engine semantics on a stub decode contract** — a deterministic
  token-chain "model" (next token is a pure function of the input seed,
  the cache contents and the position) exercises the iteration-level
  scheduler exactly: join/leave/EOS edges, the warmup compile contract,
  token-level admission, per-token SLO eviction, and the token-identity
  acceptance (randomized arrival schedules must reproduce the isolated
  single-request stream bit for bit — ints, so bitwise IS equality).
* **A real tiny T5** — the engine's token streams must be bitwise equal
  to isolated ``make_greedy_generate`` decode (the vector ``decode_pos``
  arena path vs the scalar scan path), plus the flash-decode kernel's
  parity against dense attention and the decode-regime crossover rule.

The fleet/REST layer runs on the stub-loader seam like
tests/test_serving_fleet.py: real version manager, canary gate, engines,
HTTP surface — no model export, no heavyweight jit.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

pytestmark = pytest.mark.generative

VOCAB = 16
EOS = 4  # with the chain below: ~half the seeds terminate, half run full


# --------------------------------------------------------- stub contract


def make_stub_fns(max_decode_len=12, eos_id=EOS, pad_id=0, max_input_len=6):
    """A deterministic autoregressive chain as a ``DecodeContract`` that
    states nothing but its programs and geometry: the next token depends
    on the input seed, every token the cache has accumulated, and the
    decode position — so any arena slot mix-up, stale cache row, or wrong
    position corrupts the stream."""
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models.decode_contract import DecodeContract

    def prefill(params, inputs, input_mask=None):
        if input_mask is None:
            input_mask = jnp.ones_like(inputs)
        seed = (inputs * input_mask).sum(axis=1)                    # [1]
        off = params.get("offset", 0) if isinstance(params, dict) else 0
        cache = {"toks": jnp.zeros((1, max_decode_len), jnp.int32)}
        logits = jax.nn.one_hot((seed * 3 + 1 + off) % VOCAB, VOCAB)
        encoded = seed[:, None].astype(jnp.float32)                 # [1, 1]
        return cache, encoded, logits

    def step(params, cache, tok, pos, encoded, enc_mask, klen):
        rows = jnp.arange(tok.shape[0])
        off = params.get("offset", 0) if isinstance(params, dict) else 0
        toks = cache["toks"].at[rows, pos].set(tok)
        seed = encoded[:, 0].astype(jnp.int32)
        # Nonlinear in the last token (tok*tok) so the chain never
        # collapses to a seed-independent tail: every sequence walks its
        # own trajectory, and any cross-row cache contamination shows.
        nxt = (
            seed * 2 + tok * tok + toks.sum(axis=1) * 3 + pos * 11 + off
        ) % VOCAB
        return {"toks": toks}, jax.nn.one_hot(nxt, VOCAB)

    return DecodeContract(
        prefill=prefill, step=step,
        max_decode_len=max_decode_len, eos_id=eos_id,
        pad_id=pad_id, max_input_len=max_input_len,
    )


def ref_stream(inputs, max_new_tokens, max_decode_len=12, offset=0):
    """Pure-python reference for one isolated sequence of the stub chain."""
    seed = int(np.asarray(inputs).sum())
    t = (seed * 3 + 1 + offset) % VOCAB
    out = [t]
    toks = [0] * max_decode_len
    pos = 1
    while t != EOS and len(out) < max_new_tokens:
        toks[pos] = t
        t = (seed * 2 + t * t + sum(toks) * 3 + pos * 11 + offset) % VOCAB
        out.append(t)
        pos += 1
    return out


# ------------------------------------------------------------- unit math


def test_kv_bucket_sizes():
    from tpu_pipelines.serving.generative import kv_bucket_sizes

    # Unpaged (0 or page >= cache): one bucket, the whole cache.
    assert kv_bucket_sizes(32, 0) == [32]
    assert kv_bucket_sizes(32, 32) == [32]
    assert kv_bucket_sizes(32, 64) == [32]
    # Paged: page, 2p, 4p, ... capped at the cache length.
    assert kv_bucket_sizes(32, 4) == [4, 8, 16, 32]
    # Non-power-of-two cache still terminates exactly at the cache.
    assert kv_bucket_sizes(24, 4) == [4, 8, 16, 24]
    # Page edges around the cache length (ISSUE 16): exactly equal is the
    # one-bucket degenerate case, one below yields the tight {page, cache}
    # pair, one above collapses to the whole cache.
    assert kv_bucket_sizes(32, 31) == [31, 32]
    assert kv_bucket_sizes(32, 33) == [32]
    # A decode budget must be positive — a negative (or zero) cache length
    # would silently produce an empty bucket list and an engine whose
    # every program set is degenerate.
    with pytest.raises(ValueError, match="max_decode_len"):
        kv_bucket_sizes(-1, 4)
    with pytest.raises(ValueError, match="max_decode_len"):
        kv_bucket_sizes(0, 0)


def test_validate_generation_params():
    from tpu_pipelines.serving.batching import validate_generation_params

    # Default fills the full decode budget.
    assert validate_generation_params(None, max_decode_len=32) == {
        "max_new_tokens": 32
    }
    assert validate_generation_params(
        {"max_new_tokens": 4}, max_decode_len=32
    ) == {"max_new_tokens": 4}
    with pytest.raises(ValueError, match="unknown generation parameter"):
        validate_generation_params({"temperature": 1.0}, max_decode_len=32)
    with pytest.raises(ValueError, match="must be an integer"):
        validate_generation_params(
            {"max_new_tokens": "8"}, max_decode_len=32
        )
    with pytest.raises(ValueError, match="must be an integer"):
        validate_generation_params(
            {"max_new_tokens": True}, max_decode_len=32
        )
    with pytest.raises(ValueError, match=r"in \[1, 32\]"):
        validate_generation_params({"max_new_tokens": 0}, max_decode_len=32)
    with pytest.raises(ValueError, match=r"in \[1, 32\]"):
        validate_generation_params(
            {"max_new_tokens": 33}, max_decode_len=32
        )


def test_token_deadline_math():
    from tpu_pipelines.serving.batching import token_deadline_s

    assert token_deadline_s(10.0, 100, 0.0) is None
    assert token_deadline_s(10.0, 100, 2.0) == pytest.approx(10.2)


# ------------------------------------------------------ engine semantics


def test_engine_identity_under_randomized_join_leave():
    """Acceptance: token streams under a randomized arrival/departure
    schedule are identical to isolated single-request decode.  Tokens are
    ints, so equality IS bitwise."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    fns = make_stub_fns()
    rng = np.random.default_rng(11)
    reqs = [
        (
            rng.integers(1, VOCAB, size=(int(rng.integers(2, 6)),)).astype(
                np.int32
            ),
            int(rng.integers(1, 12)),
        )
        for _ in range(24)
    ]
    engine = GenerativeEngine(fns, {}, max_batch_size=4, page_size=0)
    try:
        engine.warm()
        handles = []
        for i, (inp, m) in enumerate(reqs):
            handles.append(engine.submit_nowait(inp, max_new_tokens=m))
            # Randomized arrivals: bursts, pauses, mid-decode joins.
            if rng.random() < 0.4:
                time.sleep(float(rng.random()) * 0.01)
        outs = [h.wait(30.0) for h in handles]
    finally:
        engine.close()
    for (inp, m), out in zip(reqs, outs):
        assert [int(t) for t in out] == ref_stream(inp, m)
    # Departures compacted the batch: with 24 sequences through 4 slots,
    # slots were recycled many times.
    assert engine.steps_run > 0


def test_engine_paged_kv_buckets_identity_and_pages():
    """Paged mode (page_size=2 over a 12-deep cache): same streams, and
    the telemetry pages gauge tracks ceil((len+1)/page) per live row."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    fns = make_stub_fns()
    engine = GenerativeEngine(fns, {}, max_batch_size=2, page_size=2)
    try:
        assert engine.kv_buckets == [2, 4, 8, 12]
        engine.warm()
        assert engine.compiles_after_warm == 0
        inp = np.asarray([3, 5], np.int32)
        out = engine.submit(inp, max_new_tokens=10, timeout_s=30.0)
        assert [int(t) for t in out] == ref_stream(inp, 10)
        # Every step ran pre-compiled (bucket sweep covered the schedule).
        assert engine.compiles_after_warm == 0
    finally:
        engine.close()


def test_engine_eos_and_budget_edges():
    from tpu_pipelines.serving.generative import GenerativeEngine

    fns = make_stub_fns()
    engine = GenerativeEngine(fns, {}, max_batch_size=2, page_size=0)
    try:
        # max_new_tokens=1: completes at prefill, never occupies a slot.
        inp = np.asarray([2, 2], np.int32)
        out = engine.submit(inp, max_new_tokens=1, timeout_s=30.0)
        assert len(out) == 1
        assert [int(out[0])] == ref_stream(inp, 1)
        assert engine.idle()

        # A seed whose chain hits EOS: stream ends WITH the EOS token.
        for seed_try in range(1, 40):
            ref = ref_stream(np.asarray([seed_try], np.int32), 12)
            if ref[-1] == EOS and len(ref) > 1:
                inp = np.asarray([seed_try], np.int32)
                out = engine.submit(inp, max_new_tokens=12, timeout_s=30.0)
                assert [int(t) for t in out] == ref
                break
        else:
            pytest.fail("no EOS-terminating seed in range")

        # Full budget without EOS: exactly max_new_tokens emitted.
        for seed_try in range(1, 40):
            ref = ref_stream(np.asarray([seed_try], np.int32), 5)
            if ref[-1] != EOS and len(ref) == 5:
                inp = np.asarray([seed_try], np.int32)
                out = engine.submit(inp, max_new_tokens=5, timeout_s=30.0)
                assert len(out) == 5
                assert [int(t) for t in out] == ref
                break
        else:
            pytest.fail("no budget-bound seed in range")
    finally:
        engine.close()


def test_engine_input_validation_is_submit_time():
    from tpu_pipelines.serving.generative import GenerativeEngine

    fns = make_stub_fns(max_input_len=4)
    engine = GenerativeEngine(fns, {}, max_batch_size=2)
    try:
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit_nowait(np.asarray([1], np.int32), max_new_tokens=0)
        with pytest.raises(ValueError, match="input length"):
            engine.submit_nowait(np.asarray([], np.int32))
        with pytest.raises(ValueError, match="input length"):
            engine.submit_nowait(np.arange(5, dtype=np.int32))
        # Nothing joined the engine: malformed requests cannot poison a
        # shared decode step.
        assert engine.idle()
    finally:
        engine.close()


def test_engine_token_admission_shed():
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import (
        EngineOverloaded,
        GenerativeEngine,
    )

    reg = MetricsRegistry()
    fns = make_stub_fns()
    engine = GenerativeEngine(
        fns, {}, max_batch_size=2, max_queue_tokens=5, registry=reg,
        replica="0",
    )
    try:
        with pytest.raises(EngineOverloaded, match="exceed the bound"):
            engine.submit_nowait(np.asarray([3], np.int32), max_new_tokens=8)
        shed = reg.get("serving_decode_shed_total")
        assert shed.labels("0").get() == 1
        # Within the bound the same request is admitted.
        out = engine.submit(
            np.asarray([3], np.int32), max_new_tokens=5, timeout_s=30.0
        )
        assert len(out) >= 1
    finally:
        engine.close()


def test_engine_hard_deadline_eviction():
    """A sequence that blows its token-proportional deadline under
    ``hard_deadline`` is evicted with ``GenerationEvicted`` and its slot
    freed; without the flag the same SLO only prices the deadline."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import (
        GenerationEvicted,
        GenerativeEngine,
    )

    reg = MetricsRegistry()
    fns = make_stub_fns()
    # Pick a seed whose isolated stream does NOT terminate early.
    inp = None
    for seed_try in range(1, 40):
        cand = np.asarray([seed_try], np.int32)
        if len(ref_stream(cand, 10)) == 10:
            inp = cand
            break
    assert inp is not None
    engine = GenerativeEngine(
        fns, {}, max_batch_size=2, slo_ms_per_token=1e-6,
        hard_deadline=True, registry=reg, replica="0",
    )
    try:
        h = engine.submit_nowait(inp, max_new_tokens=10)
        with pytest.raises(GenerationEvicted, match="deadline"):
            h.wait(30.0)
        assert reg.get("serving_decode_evicted_total").labels("0").get() == 1
        deadline = time.monotonic() + 5
        while not engine.idle() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine.idle()  # the slot was freed for admissible work
    finally:
        engine.close()

    # Same SLO without hard_deadline: the generation completes.
    engine2 = GenerativeEngine(
        fns, {}, max_batch_size=2, slo_ms_per_token=1e-6,
        hard_deadline=False,
    )
    try:
        out = engine2.submit(inp, max_new_tokens=10, timeout_s=30.0)
        assert [int(t) for t in out] == ref_stream(inp, 10)
    finally:
        engine2.close()


def test_engine_close_fails_pending():
    from tpu_pipelines.serving.generative import (
        GenerationEvicted,
        GenerativeEngine,
    )

    fns = make_stub_fns()
    engine = GenerativeEngine(fns, {}, max_batch_size=2)
    engine.close()
    with pytest.raises(RuntimeError, match="closed"):
        engine.submit_nowait(np.asarray([1], np.int32))

    # Pending work at close is failed with the eviction verdict, not
    # left hanging.
    engine2 = GenerativeEngine(fns, {}, max_batch_size=1)
    hs = [
        engine2.submit_nowait(np.asarray([s], np.int32), max_new_tokens=12)
        for s in (3, 4, 5, 6)
    ]
    engine2.close(timeout_s=5.0)
    evicted = 0
    for h in hs:
        try:
            h.wait(5.0)
        except GenerationEvicted:
            evicted += 1
    # The engine was closed mid-schedule: at least the queued tail cannot
    # have finished.
    assert evicted >= 1


def test_engine_warmup_contract_and_telemetry():
    """No decode step compiles after ``warm()`` (the no-mid-traffic-XLA
    acceptance), and the serving_decode_* family is published."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    reg = MetricsRegistry()
    fns = make_stub_fns()
    engine = GenerativeEngine(
        fns, {}, max_batch_size=4, page_size=4, registry=reg, replica="0",
    )
    try:
        engine.warm()
        rng = np.random.default_rng(5)
        handles = [
            engine.submit_nowait(
                rng.integers(1, VOCAB, size=(3,)).astype(np.int32),
                max_new_tokens=int(rng.integers(2, 12)),
            )
            for _ in range(10)
        ]
        for h in handles:
            h.wait(30.0)
    finally:
        engine.close()
    assert engine.compiles_after_warm == 0
    assert engine.steps_run > 0
    assert reg.get("serving_decode_steps_total").labels("0").get() == (
        engine.steps_run
    )
    assert reg.get("serving_decode_tokens_total").labels("0").get() > 0
    assert reg.get("serving_decode_sequences_total").labels("0").get() == 10
    occ = reg.get("serving_decode_batch_occupancy").labels("0").get()
    assert 0.0 < occ <= 1.0
    assert reg.get("serving_decode_cache_pages_in_use") is not None
    scrape = reg.to_prometheus()
    assert (
        'serving_decode_per_token_latency_seconds_count{replica="0"} 10'
        in scrape
    )


# ------------------------------------- decode-path optimisations (ISSUE 16)


def test_prefix_cache_refcount_and_trim():
    """Unit contract of the refcounted prefix cache: a page shared by live
    readers is PINNED — trim may evict only zero-reader entries (LRU), so
    an over-capacity entry is freed exactly when its last reader lets
    go."""
    from tpu_pipelines.serving.generative import PrefixCache

    cache = PrefixCache(capacity=1, page=2)
    key_a, pages_a = PrefixCache.key_of(
        np.asarray([3, 5, 7, 0], np.int64), np.asarray([1, 1, 1, 0]), 2
    )
    assert pages_a == 2  # 3 valid tokens / page 2, ceil
    a = cache.insert(key_a, pages_a, tok0=9, cache={}, encoded=None)
    cache.acquire(a)
    cache.acquire(a)  # two live readers share the pages

    # Over capacity while A is pinned: B inserts, trim must evict B's
    # fellow zero-reader (B itself once C lands), never A.
    key_b, _ = PrefixCache.key_of(
        np.asarray([4, 4, 4, 4], np.int64), np.asarray([1, 1, 1, 1]), 2
    )
    cache.insert(key_b, 2, tok0=1, cache={}, encoded=None)
    assert cache.peek(key_a) is a  # pinned past capacity
    key_c, _ = PrefixCache.key_of(
        np.asarray([8, 8, 0, 0], np.int64), np.asarray([1, 1, 0, 0]), 2
    )
    cache.insert(key_c, 1, tok0=2, cache={}, encoded=None)
    assert cache.peek(key_b) is None   # LRU zero-reader went
    assert cache.peek(key_a) is a      # still pinned

    # First release: one reader remains, the pages stay.
    cache.release(a)
    assert cache.peek(key_a) is a
    assert cache.pages_in_use() == pages_a + 1  # A + C resident
    # LAST reader retires: trim shrinks to capacity, A's pages freed.
    cache.release(a)
    assert cache.peek(key_a) is None
    assert len(cache) == 1
    assert cache.pages_in_use() == 1  # only C


def test_prefix_cache_key_is_mask_and_content_sensitive():
    from tpu_pipelines.serving.generative import PrefixCache

    toks = np.asarray([3, 5, 7, 9], np.int64)
    ones = np.asarray([1, 1, 1, 1])
    k1, p1 = PrefixCache.key_of(toks, ones, 2)
    # Identical prompt: identical key.
    assert PrefixCache.key_of(toks.copy(), ones.copy(), 2) == (k1, p1)
    # Different content, different mask structure: different keys.
    assert PrefixCache.key_of(toks + 1, ones, 2)[0] != k1
    assert PrefixCache.key_of(toks, np.asarray([1, 1, 1, 0]), 2)[0] != k1
    # Masked positions are zeroed before hashing: their (never model-
    # visible) values must not split the key.
    half = np.asarray([1, 1, 0, 0])
    ka, _ = PrefixCache.key_of(np.asarray([3, 5, 99, 42], np.int64), half, 2)
    kb, _ = PrefixCache.key_of(np.asarray([3, 5, 7, 11], np.int64), half, 2)
    assert ka == kb


# The two default-off levers, as the grid the options make.
_LEVERS = {
    "prefix": {"prefix_cache_entries": 4},
    "chunked": {"prefill_chunk_pages": 1},
    "both": {"prefix_cache_entries": 4, "prefill_chunk_pages": 1},
}


@pytest.mark.parametrize("page_size", [0, 2])
@pytest.mark.parametrize("levers", sorted(_LEVERS))
def test_engine_decode_levers_bitwise_identity(levers, page_size):
    """Acceptance (ISSUE 16): greedy streams with prefix caching, chunked
    prefill, or both on are identical to the plain engine's, on the paged
    and on the single-bucket engine — both optimisations reuse/reschedule
    the exact same compiled programs, they never change the math."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    fns = make_stub_fns()
    rng = np.random.default_rng(23)
    shared = rng.integers(1, VOCAB, size=(5,)).astype(np.int32)
    reqs = []
    for i in range(16):
        if i % 2 == 0:  # every other request rides the shared prompt
            reqs.append((shared, int(rng.integers(2, 12))))
        else:
            reqs.append((
                rng.integers(1, VOCAB, size=(int(rng.integers(2, 6)),))
                .astype(np.int32),
                int(rng.integers(1, 12)),
            ))

    engine = GenerativeEngine(
        fns, {}, max_batch_size=3, page_size=page_size, **_LEVERS[levers]
    )
    try:
        engine.warm()
        handles = [
            engine.submit_nowait(inp, max_new_tokens=m) for inp, m in reqs
        ]
        outs = [h.wait(30.0) for h in handles]
    finally:
        engine.close()
    assert engine.compiles_after_warm == 0
    for (inp, m), out in zip(reqs, outs):
        assert [int(t) for t in out] == ref_stream(inp, m)
    if "prefix_cache_entries" in _LEVERS[levers]:
        # The shared prompt actually hit: one miss funded every later
        # reader.
        assert engine._prefix.hits > 0
        assert engine._prefix.misses >= 1
    else:
        assert engine._prefix is None


def test_engine_prefix_cache_lifecycle_and_telemetry():
    """Engine-level refcount lifecycle: capacity-1 cache across two
    prompts — the resident entry swaps only after its readers retire, and
    the hit/miss/pages telemetry matches the schedule."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    reg = MetricsRegistry()
    fns = make_stub_fns()
    engine = GenerativeEngine(
        fns, {}, max_batch_size=4, page_size=2,
        prefix_cache_entries=1, registry=reg, replica="0",
    )
    p1 = np.asarray([3, 5, 7], np.int32)
    p2 = np.asarray([2, 9], np.int32)
    try:
        engine.warm()
        # Concurrent shared-prefix burst: admissions are sequential on the
        # worker, so the first P1 misses and every later P1 hits its entry.
        handles = [
            engine.submit_nowait(p1, max_new_tokens=6) for _ in range(3)
        ]
        outs = [h.wait(30.0) for h in handles]
        for out in outs:
            assert [int(t) for t in out] == ref_stream(p1, 6)
        assert engine._prefix.hits == 2
        assert engine._prefix.misses == 1
        # Switch prompts: P2 misses, its insert evicts P1 (zero readers
        # now) from the capacity-1 cache; a second P2 hits.
        assert [int(t) for t in engine.submit(
            p2, max_new_tokens=4, timeout_s=30.0
        )] == ref_stream(p2, 4)
        assert [int(t) for t in engine.submit(
            p2, max_new_tokens=7, timeout_s=30.0
        )] == ref_stream(p2, 7)
    finally:
        engine.close()
    assert len(engine._prefix) == 1
    assert engine._prefix.hits == 3
    assert engine._prefix.misses == 2
    assert reg.get(
        "serving_decode_prefix_hit_total"
    ).labels("0").get() == 3
    assert reg.get(
        "serving_decode_prefix_miss_total"
    ).labels("0").get() == 2
    # P2 (2 valid tokens, page 2) is the lone resident entry: 1 page.
    assert reg.get(
        "serving_decode_prefix_pages_in_use"
    ).labels("0").get() == 1


def test_engine_pages_accounting_under_admit_retire_move_mix():
    """The pages-in-use figure published at every step equals the sum of
    live sequences' ceil((emitted+1)/page) — through a schedule that
    forces admissions, retirements, and slot moves."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    page = 2
    fns = make_stub_fns()
    engine = GenerativeEngine(fns, {}, max_batch_size=3, page_size=page)
    observed = []
    real_on_step = engine.telemetry.on_step

    def spy(dt, ewma, live, bucket, pages, active):
        # Same worker thread, at the read of the step: the rows are the
        # step's own snapshot (the slot table has moved on: the thread
        # runs a step ahead).  Lengths EXCLUDE the token this step is
        # about to append — the published figure covers the post-step
        # cache footprint, hence the +1.
        rows = engine._flights[0].rows
        assert len(rows) == live
        lengths = [len(s.tokens) for s in rows]
        observed.append((int(pages), tuple(lengths)))
        return real_on_step(dt, ewma, live, bucket, pages, active)

    engine.telemetry.on_step = spy
    rng = np.random.default_rng(7)
    reqs = [
        (
            rng.integers(1, VOCAB, size=(int(rng.integers(2, 6)),))
            .astype(np.int32),
            int(rng.integers(2, 12)),
        )
        for _ in range(12)
    ]
    try:
        engine.warm()
        handles = []
        for i, (inp, m) in enumerate(reqs):
            handles.append(engine.submit_nowait(inp, max_new_tokens=m))
            if i % 4 == 0:
                time.sleep(0.005)
        outs = [h.wait(30.0) for h in handles]
    finally:
        engine.close()
    for (inp, m), out in zip(reqs, outs):
        assert [int(t) for t in out] == ref_stream(inp, m)
    assert observed, "no decode steps recorded"
    for pages, lengths in observed:
        assert pages == sum(-(-(n + 1) // page) for n in lengths)
    # 12 mixed-budget sequences through 3 slots: some steps ran partially
    # occupied (retire + move recycled slots mid-schedule).
    assert any(len(ls) < 3 for _, ls in observed)
    assert any(len(ls) == 3 for _, ls in observed)


# ----------------------------------------------------- real-model parity


@pytest.fixture(scope="module")
def tiny_t5():
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models.t5 import T5

    tiny = dict(
        vocab_size=48, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, dropout_rate=0.0, dtype=jnp.float32,
    )
    model = T5(**tiny)
    batch = {
        "inputs": np.arange(12, dtype=np.int32).reshape(2, 6) % 13 + 2,
        "targets": np.ones((2, 5), np.int32),
    }
    params = model.init(jax.random.key(0), batch)["params"]
    return model, params


def test_engine_bitwise_identity_vs_isolated_greedy_t5(tiny_t5):
    """Acceptance: the continuous-batch arena path (vector ``decode_pos``,
    bucketed steps, slot moves) reproduces isolated
    ``make_greedy_generate`` token streams BITWISE on a real T5, under a
    staggered arrival schedule, with zero post-warm compiles."""
    from tpu_pipelines.models.t5 import (
        make_continuous_decode_fns,
        make_greedy_generate,
    )
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = tiny_t5
    L = 8
    fns = make_continuous_decode_fns(
        model, max_decode_len=L, eos_id=1, max_input_len=6
    )
    greedy = make_greedy_generate(model, max_decode_len=L, eos_id=1)
    rng = np.random.default_rng(0)
    reqs = [
        rng.integers(2, 40, size=(int(rng.integers(2, 7)),)).astype(np.int32)
        for _ in range(8)
    ]
    iso = []
    for r in reqs:
        toks, _ = greedy(params, r[None], np.ones((1, len(r)), np.int32))
        row = [int(t) for t in np.asarray(toks)[0]]
        if 1 in row:
            row = row[: row.index(1) + 1]
        iso.append(row)

    engine = GenerativeEngine(fns, params, max_batch_size=4, page_size=0)
    try:
        engine.warm()
        handles = []
        for i, r in enumerate(reqs):
            handles.append(engine.submit_nowait(r, max_new_tokens=L))
            if i % 3 == 0:
                time.sleep(0.01)
        outs = [h.wait(60.0) for h in handles]
    finally:
        engine.close()
    assert engine.compiles_after_warm == 0
    for out, ref in zip(outs, iso):
        assert [int(t) for t in out] == ref


@pytest.mark.parametrize("page_size", [0, 4])
@pytest.mark.parametrize("prefix_entries", [0, 8])
def test_engine_t5_decode_opts_bitwise_identity(
    tiny_t5, prefix_entries, page_size
):
    """Acceptance (ISSUE 16) on a real T5: chunked prefill, alone and
    with prefix caching, paged and unpaged, reproduces isolated greedy
    streams bitwise with zero post-warm compiles."""
    from tpu_pipelines.models.t5 import (
        make_continuous_decode_fns,
        make_greedy_generate,
    )
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = tiny_t5
    L = 8
    fns = make_continuous_decode_fns(
        model, max_decode_len=L, eos_id=1, max_input_len=6
    )
    greedy = make_greedy_generate(model, max_decode_len=L, eos_id=1)
    rng = np.random.default_rng(3)
    shared = rng.integers(2, 40, size=(5,)).astype(np.int32)
    reqs = [shared] + [
        rng.integers(2, 40, size=(int(rng.integers(2, 7)),)).astype(np.int32)
        for _ in range(4)
    ] + [shared, shared]
    iso = []
    for r in reqs:
        toks, _ = greedy(params, r[None], np.ones((1, len(r)), np.int32))
        row = [int(t) for t in np.asarray(toks)[0]]
        if 1 in row:
            row = row[: row.index(1) + 1]
        iso.append(row)

    engine = GenerativeEngine(
        fns, params, max_batch_size=4, page_size=page_size,
        # Capacity covers every distinct prompt: the shared entry must
        # survive until its later readers arrive.
        prefix_cache_entries=prefix_entries, prefill_chunk_pages=1,
    )
    try:
        engine.warm()
        handles = [
            engine.submit_nowait(r, max_new_tokens=L) for r in reqs
        ]
        outs = [h.wait(60.0) for h in handles]
    finally:
        engine.close()
    assert engine.compiles_after_warm == 0
    for out, ref in zip(outs, iso):
        assert [int(t) for t in out] == ref
    if prefix_entries:
        assert engine._prefix.hits >= 2


# -------------------------------------- the arena in place (ISSUE 26)


def _greedy_rows(model, params, reqs, L, eos_id):
    """Isolated ``make_greedy_generate`` stream of each prompt."""
    from tpu_pipelines.models.t5 import make_greedy_generate

    greedy = make_greedy_generate(model, max_decode_len=L, eos_id=eos_id)
    rows = []
    for r in reqs:
        toks, _ = greedy(params, r[None], np.ones((1, len(r)), np.int32))
        rows.append([int(t) for t in np.asarray(toks)[0]])
    return rows


# (prompt index, max_new_tokens) in queue order, for three slots and
# KV buckets 4 / 8 / 16.  A, B, C are admitted together; B leaves the
# MIDDLE slot when the step of its third token is dispatched (move +
# clear: retired by count) and D lands in the slot that freed in that
# same round, so no step runs with it empty; A and C leave together at
# 5 with D behind them, so the deepest live position falls back under a
# bucket boundary it had crossed, and D then grows through both
# boundaries.  E and F repeat B's prompt: a stored prefix entry is
# inserted a second and third time.
_STAGGERED = [(0, 5), (1, 3), (2, 5), (3, 12), (1, 4), (1, 7)]


@pytest.mark.parametrize("prefix_entries", [0, 8])
@pytest.mark.parametrize("page_size", [0, 4])
def test_engine_in_place_arena_matches_isolated_greedy_t5(
    tiny_t5, page_size, prefix_entries
):
    """Every program updates the arena it is handed in place (donated
    state; the step writes back only the positions it produced), and
    the streams stay those of isolated greedy decode, row for row:
    across KV buckets downward and upward, through a middle-slot
    retirement and an admission into the freed slot, with a prefix
    entry inserted more than once."""
    from tpu_pipelines.models.t5 import make_continuous_decode_fns
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = tiny_t5
    L, eos = 16, 10_000                 # EOS outside the vocabulary
    fns = make_continuous_decode_fns(
        model, max_decode_len=L, eos_id=eos, max_input_len=6
    )
    rng = np.random.default_rng(26)
    prompts = [
        rng.integers(2, 40, size=(n,)).astype(np.int32)
        for n in (3, 6, 2, 5)
    ]
    iso = _greedy_rows(model, params, prompts, L, eos)

    gate = threading.Event()            # hold the first round back
    engine = GenerativeEngine(
        fns, params, max_batch_size=3, page_size=page_size,
        prefix_cache_entries=prefix_entries, fault_hook=gate.wait,
    )
    steps, moves = [], []
    try:
        engine.warm()
        step_for, move = engine._step_for, engine._jit_move
        engine._step_for = lambda b, kv: (
            steps.append((b, kv)) or step_for(b, kv)
        )
        engine._jit_move = lambda arena, src, dst: (
            moves.append((int(src), int(dst))) or move(arena, src, dst)
        )
        handles = [
            engine.submit_nowait(prompts[i], max_new_tokens=m)
            for i, m in _STAGGERED
        ]
        gate.set()
        outs = [h.wait(60.0) for h in handles]
    finally:
        gate.set()
        engine.close()
    assert engine.compiles_after_warm == 0
    for (i, m), out in zip(_STAGGERED, outs):
        assert [int(t) for t in out] == iso[i][:m], (i, m)
    # The schedule the comment above describes did run.
    assert moves[0] == (2, 1)           # C into B's middle slot
    kvs = [kv for _, kv in steps]
    if page_size:
        assert kvs[:6] == [4, 4, 4, 8, 4, 8] and kvs[-1] == 16
        # B's slot was free for the round of B's last step: D rode the
        # next one, and none ran with the slot empty
        assert [b for b, _ in steps][:4] == [3, 3, 3, 3]
    else:
        assert set(kvs) == {L}
    if prefix_entries:
        assert engine._prefix.hits == 2 and engine._prefix.misses == 4


# ----------------------------- admit without waiting (ISSUE 30)
#
# A prefill's first token stays on the device: ``insert`` takes it as the
# device scalar it is, the round's step is dispatched behind every
# prefill and insert of the round, and only then does the host read:
# first tokens, then the step's.  Both contracts: a whole-prompt prefill
# (tiny T5) and prefill by window (tiny EvaByte).


def _t5_kit(tiny_t5):
    from tpu_pipelines.models.t5 import make_continuous_decode_fns

    model, params = tiny_t5
    L, none = 12, 10_000                # ``none``: outside the vocabulary
    rng = np.random.default_rng(30)
    prompts = [
        rng.integers(2, 40, size=(n,)).astype(np.int32)
        for n in (3, 6, 2, 5, 4)
    ]
    rows = _greedy_rows(model, params, prompts, L, none)
    return SimpleNamespace(
        params=params, prompts=prompts, windowed=False,
        greedy=lambda i, m: rows[i][:m],
        fns=lambda eos: make_continuous_decode_fns(
            model, max_decode_len=L, eos_id=eos, max_input_len=6),
    )


def _evabyte_kit():
    import test_evabyte as eva
    from tpu_pipelines.models.evabyte import make_continuous_decode_fns

    model, params = eva.build()
    # 1 to 3 windows of 32: a prompt's last window hands the token on
    prompts = [eva.prompt(300 + i, n) for i, n in enumerate(
        (40, 7, 64, 33, 90))]
    fns = eva.decode_fns(model)
    rows = [
        eva.through_the_cache(model, params, fns, p, 12)[0].tolist()
        for p in prompts
    ]
    return SimpleNamespace(
        params=params, prompts=prompts, windowed=True,
        greedy=lambda i, m: rows[i][:m],
        fns=lambda eos: make_continuous_decode_fns(
            model, max_decode_len=eva.MAX_OUT, eos_id=eos,
            max_input_len=eva.MAX_IN),
    )


@pytest.fixture(scope="module", params=["t5", "evabyte"])
def kit(request):
    """One model's decode contract by EOS id, its prompts, and the
    model's own greedy stream of each prompt (no EOS)."""
    if request.param == "t5":
        return _t5_kit(request.getfixturevalue("tiny_t5"))
    return _evabyte_kit()


def _own_stream(kit, i, m, eos):
    """What the model alone emits for prompt ``i``: greedy, cut after
    the first EOS."""
    row = kit.greedy(i, m)
    return row[: row.index(eos) + 1] if eos in row else row


class _Log:
    """The engine's programs wrapped: every dispatch and every
    device-to-host read the worker makes, in order.  A prefill's token
    and a step's tokens come back inside proxies that log when the host
    converts them (``int(tok0)``, ``np.asarray(nxt)``: the only ways
    the engine reads), and ``insert`` is handed what the proxy holds."""

    class _Proxy:
        def __init__(self, log, what, value):
            self._log, self._what, self.value = log, what, value
            self._made_at = len(log)    # events before its program's

        def __int__(self):
            self._log.append(("read", self._what, self._made_at))
            return int(self.value)

        def __array__(self, *a, **k):
            self._log.append(("read", self._what, self._made_at))
            return np.asarray(self.value)

    @staticmethod
    def is_device_array(x) -> bool:
        import jax

        return isinstance(x, jax.Array)

    def __init__(self, engine, before_step=None):
        self.events = events = []
        self.insert_tok0 = []
        engine._ensure_arena()          # the programs exist
        proxy = self._Proxy
        prefill, window = engine._jit_prefill, engine._jit_prefill_window
        insert, step_for = engine._jit_insert, engine._step_for

        def logged(name, fn):
            def call(*a):
                events.append(("dispatch", name))
                return fn(*a)
            return call

        def wrap_prefill(*a):
            tok = proxy(events, "tok0", None)
            events.append(("dispatch", "prefill"))
            cache, enc, tok.value = prefill(*a)
            return cache, enc, tok

        def wrap_window(*a):
            tok = proxy(events, "tok0", None)
            events.append(("dispatch", "prefill_window"))
            row, tok.value = window(*a)
            return row, tok

        def wrap_insert(arena, cache, enc, mask, tok0, slot):
            events.append(("dispatch", "insert"))
            tok0 = tok0.value if isinstance(tok0, proxy) else tok0
            self.insert_tok0.append(tok0)
            return insert(arena, cache, enc, mask, tok0, slot)

        def wrap_step_for(b, kv):
            fn = step_for(b, kv)

            def run(params, arena):
                if before_step is not None:
                    before_step()
                tok = proxy(events, "nxt", None)
                events.append(("dispatch", "run"))
                arena, tok.value = fn(params, arena)
                return arena, tok
            return run

        if prefill is not None:
            engine._jit_prefill = wrap_prefill
        if window is not None:
            engine._jit_prefill_window = wrap_window
        engine._jit_insert = wrap_insert
        engine._jit_move = logged("move", engine._jit_move)
        engine._jit_clear = logged("clear", engine._jit_clear)
        engine._step_for = wrap_step_for


def _first_reads(reg):
    from tpu_pipelines.serving.generative import FIRST_TOKEN_READS

    series = reg.snapshot()[
        "serving_decode_first_token_reads_total"]["series"]
    assert set(series) <= {("0", r) for r in FIRST_TOKEN_READS}
    return {r: series.get(("0", r), 0.0) for r in FIRST_TOKEN_READS}


def _step_dispatches(reg):
    from tpu_pipelines.serving.generative import STEP_DISPATCHES

    series = reg.snapshot()[
        "serving_decode_step_dispatch_total"]["series"]
    assert set(series) <= {("0", q) for q in STEP_DISPATCHES}
    return {q: series.get(("0", q), 0.0) for q in STEP_DISPATCHES}


# (prompt, budget) of the first burst: a first token that is EOS (prompt
# 0's, by the choice of EOS), budgets of 1 and 2, one prompt twice in one
# round (with a prefix cache: a miss, and a hit on an entry whose token
# nobody has read yet), and more admissions than one before the first
# step.  The second burst repeats prompts whose entries now hold a host
# token: a hit that reads nothing, one of them an EOS known at once.
_BURST_1 = [(0, 8), (1, 1), (2, 2), (3, 6), (3, 6), (4, 9)]
_BURST_2 = [(3, 5), (0, 8), (4, 3)]


@pytest.mark.parametrize("hard_deadline", [False, True],
                         ids=["no-deadline", "hard-deadline"])
@pytest.mark.parametrize("lever", [False, True],
                         ids=["plain", "cache-or-credits"])
def test_engine_admits_without_waiting_and_serves_the_models_own_tokens(
    kit, lever, hard_deadline
):
    """Per request, the tokens on the handle are the model's own greedy
    decode, whatever the first token's read was moved behind; under a
    hard deadline nobody can meet, what an evicted handle holds is that
    stream's beginning.  ``lever``: the option of admission that the
    contract takes, a prefix cache (whole-prompt prefill) or
    chunked-prefill credits (prefill by window, which takes no cache)."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import (
        GenerationEvicted,
        GenerativeEngine,
    )

    prefix_entries = 8 if lever and not kit.windowed else 0
    credits = 1 if lever and kit.windowed else 0
    eos = kit.greedy(0, 1)[0]           # prompt 0 ends at its first token
    gate = threading.Event()            # hold the first round back
    reg = MetricsRegistry()
    engine = GenerativeEngine(
        kit.fns(eos), kit.params, max_batch_size=4, page_size=4,
        prefix_cache_entries=prefix_entries, prefill_chunk_pages=credits,
        hard_deadline=hard_deadline,
        slo_ms_per_token=1e-6 if hard_deadline else 0.0,
        fault_hook=gate.wait, registry=reg,
    )
    try:
        engine.warm()
        log = _Log(engine)
        first = [
            engine.submit_nowait(kit.prompts[i], max_new_tokens=m)
            for i, m in _BURST_1
        ]
        gate.set()
        for h in first:
            h._done.wait(120.0)
        second = [
            engine.submit_nowait(kit.prompts[i], max_new_tokens=m)
            for i, m in _BURST_2
        ]
        for h in second:
            h._done.wait(120.0)
    finally:
        gate.set()
        engine.close()
    assert engine.compiles_after_warm == 0
    evicted = 0
    for (i, m), h in zip(_BURST_1 + _BURST_2, first + second):
        own = _own_stream(kit, i, m, eos)
        assert h._done.is_set()
        if h.error is None:
            assert [int(t) for t in h.result] == own, (i, m)
        else:
            assert isinstance(h.error, GenerationEvicted), h.error
            assert hard_deadline and len(own) > 2
            # evicted after the one step it rode: the stream's beginning
            assert h.tokens == own[:2], (i, m)
            evicted += 1
        # (d) the first token reached the host on every handle, after
        # the request was taken off the queue
        assert h.arrival_s <= h.admitted_s <= h.first_token_s <= h.done_s
    assert (evicted > 0) == hard_deadline
    # More admissions than one went ahead of the first step (credits
    # let one prompt in per step once a row is live).
    names = [ev[1] for ev in log.events if ev[0] == "dispatch"]
    assert names[: names.index("run")].count("insert") >= (
        1 if credits else 2)
    reads = _first_reads(reg)
    admitted = reg.snapshot()[
        "serving_decode_queue_wait_seconds"]["series"][("0",)]["count"]
    assert sum(reads.values()) == admitted == len(_BURST_1 + _BURST_2)
    assert reads["blocking"] == 0
    if prefix_entries:
        # the same-round repeat is a hit whose token nobody had read
        assert engine._prefix.hits == 4 and engine._prefix.misses == 5
        assert reads["known"] == 3
    else:
        assert reads["known"] == 0


def test_no_read_between_the_dispatches_of_a_round(kit):
    """The order of a round, with the programs wrapped: its step, the
    retirements by count, the read of the step BEFORE it, then the
    prefills and inserts it admits, dispatched back to back with no
    device-to-host read between them, then the first tokens of the
    prefills ahead of the step.  Every read comes with a step
    dispatched after the program that made its value (the thread never
    waits for a program with nothing queued behind it), but for the
    run's last step; and ``insert`` is handed the prefill's own device
    scalar, never a host integer."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    gate = threading.Event()
    reg = MetricsRegistry()
    engine = GenerativeEngine(
        kit.fns(10_000), kit.params, max_batch_size=2, page_size=4,
        fault_hook=gate.wait, registry=reg,
    )
    budgets = [5, 3, 6, 2, 4]
    try:
        engine.warm()
        log = _Log(engine)
        handles = [
            engine.submit_nowait(p, max_new_tokens=m)
            for p, m in zip(kit.prompts, budgets)
        ]
        gate.set()
        outs = [h.wait(120.0) for h in handles]
    finally:
        gate.set()
        engine.close()
    for i, (m, out) in enumerate(zip(budgets, outs)):
        assert [int(t) for t in out] == kit.greedy(i, m)
    # ``unread``: steps dispatched whose tokens the host has not read.
    # Two of them: the older is read next, before anything but the
    # retirements of the rows whose budget the newer one fills.
    unread = 0
    reads = {"tok0": 0, "nxt": 0}
    ahead = 0                           # steps read with the next queued
    for at, (kind, name, *made_at) in enumerate(log.events):
        if kind == "read":
            reads[name] += 1
            # steps dispatched since the program that made the value
            # (a step's own dispatch is the first of them)
            since = log.events[made_at[0]:at].count(("dispatch", "run"))
            if name == "tok0":
                assert unread >= 1 and since >= 1, (at, log.events)
            else:
                assert unread in (1, 2) and since == unread, (at, log.events)
                ahead += unread == 2
                unread -= 1
        elif name == "run":
            assert unread <= 1, (at, log.events)
            unread += 1
        elif name not in ("move", "clear"):  # prefill(_window), insert
            assert unread <= 1, (at, log.events)
    assert unread == 0
    assert reads["tok0"] == len(handles)
    assert reads["nxt"] == engine.steps_run > 0
    # a row was live from the first admission to the last token: every
    # step but the first went behind one unread, every read but the
    # last had the next step queued
    assert ahead == engine.steps_run - 1
    assert _step_dispatches(reg) == {
        "behind_step": engine.steps_run - 1, "alone": 1}
    assert reg.get(
        "serving_decode_wasted_row_steps_total").labels("0").get() == 0
    assert len(log.insert_tok0) == len(handles)
    assert all(
        log.is_device_array(t) and t.shape == () and t.dtype == np.int32
        for t in log.insert_tok0)
    assert _first_reads(reg) == {
        "behind_step": len(handles), "known": 0, "blocking": 0}
    # Under the cache keys warm() left: the one insert program.
    assert engine.compiles_after_warm == 0


def _killed_with_unread_first_tokens(kit, how):
    """Two requests ride a step; two more are admitted behind the
    second, and the worker stops at the dispatch of the third, the one
    that the second step and their first tokens would have been read
    behind: ``how`` "dies" (the program raises: a device fault, an
    injected kill) or "hangs" (it never returns and ``close`` gives up
    on the thread).  -> handles."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    second, go, third, hold = (threading.Event() for _ in range(4))
    state = {"steps": 0}

    def before_step():
        state["steps"] += 1
        if state["steps"] == 2:
            second.set()
            go.wait(30.0)               # two more arrive meanwhile
        elif state["steps"] == 3:
            third.set()
            if how == "dies":
                raise RuntimeError("injected kill")
            hold.wait(30.0)

    gate = threading.Event()            # both of a pair, or neither
    engine = GenerativeEngine(
        kit.fns(10_000), kit.params, max_batch_size=4, page_size=4,
        fault_hook=gate.wait,
    )
    try:
        engine.warm()
        _Log(engine, before_step)
        handles = [
            engine.submit_nowait(kit.prompts[i], max_new_tokens=8)
            for i in (0, 1)
        ]
        gate.set()
        assert second.wait(60.0)
        handles += [
            engine.submit_nowait(kit.prompts[i], max_new_tokens=8)
            for i in (2, 3)
        ]
        go.set()
        assert third.wait(60.0)
        if how == "hangs":
            engine.close(timeout_s=0.2)
    finally:
        for event in (gate, go, hold):
            event.set()
        engine.close()
    return handles


@pytest.mark.parametrize("how", ["dies", "hangs"])
def test_an_unread_first_token_does_not_outlive_its_engine(kit, how):
    """A dying worker and ``close()`` finish every handle, also one
    whose first token the host never read and one with a step in
    flight; what ``DecodeSessionLost`` would carry of it (the fleet
    builds ``partial_tokens`` from the handles' ``tokens``) is what the
    host has read: of the unread first token nothing, of the step in
    flight not its token."""
    from tpu_pipelines.serving.generative import (
        DecodeSessionLost,
        GenerationEvicted,
    )

    handles = _killed_with_unread_first_tokens(kit, how)
    for h in handles:
        assert h._done.wait(30.0)
        if how == "dies":
            assert "injected kill" in str(h.error)
        else:
            assert isinstance(h.error, GenerationEvicted)
    lost = DecodeSessionLost(
        handles[0].error,
        partial_tokens=[[int(t) for t in h.tokens] for h in handles],
        unfinished=sum(1 for h in handles if h.result is None),
    )
    assert lost.unfinished == 4
    for i, (h, part) in enumerate(zip(handles, lost.partial_tokens)):
        assert part == kit.greedy(i, len(part))
    # the first two rode two steps, the second of them still unread
    # (the third goes ahead of its read); the last two were seated,
    # unread
    assert [len(p) for p in lost.partial_tokens] == [2, 2, 0, 0]
    assert [h.in_flight for h in handles] == [1, 1, 0, 0]
    assert all(h.first_unread for h in handles[2:])
    assert all(h.first_token_s is None for h in handles[2:])
    assert all(h.first_token_s is not None for h in handles[:2])


def test_first_token_reads_add_up_to_the_admissions():
    """``serving_decode_first_token_reads_total``: one count per
    admission, under ``blocking`` only where nothing was there to step
    (a budget of one token on an idle engine), under ``known`` only
    where a prefix entry already held the token on the host."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    reg = MetricsRegistry()
    engine = GenerativeEngine(
        make_stub_fns(), {}, max_batch_size=2, prefix_cache_entries=4,
        registry=reg,
    )
    a, b = np.asarray([3, 5], np.int32), np.asarray([2, 7, 1], np.int32)
    try:
        engine.warm()
        # alone on an idle engine, ended by its first token: blocking
        assert [int(t) for t in engine.submit(a, max_new_tokens=1)] == (
            ref_stream(a, 1))
        # the entry holds the token now: known, nothing read
        assert [int(t) for t in engine.submit(a, max_new_tokens=6)] == (
            ref_stream(a, 6))
        # a miss that rides a step: behind_step
        assert [int(t) for t in engine.submit(b, max_new_tokens=6)] == (
            ref_stream(b, 6))
        assert [int(t) for t in engine.submit(b, max_new_tokens=1)] == (
            ref_stream(b, 1))
    finally:
        engine.close()
    reads = _first_reads(reg)
    assert reads == {"behind_step": 1, "known": 2, "blocking": 1}
    admitted = reg.snapshot()[
        "serving_decode_queue_wait_seconds"]["series"][("0",)]["count"]
    assert sum(reads.values()) == admitted == 4
    assert reg.snapshot()["serving_decode_ttft_seconds"]["series"][
        ("0",)]["count"] == 4
    assert 'serving_decode_first_token_reads_total{' in reg.to_prometheus()


def _arena_is_blank(engine, arena) -> bool:
    import jax

    cache, tok, pos, live, enc, mask = arena
    return (
        not np.asarray(live).any()
        and not np.asarray(pos).any()
        and (np.asarray(tok) == engine.pad_id).all()
        and (np.asarray(mask) == 1).all()
        and not np.asarray(enc).any()
        and not any(
            np.asarray(x).any() for x in jax.tree_util.tree_leaves(cache)
        )
    )


@pytest.mark.parametrize("warms", [1, 2])
def test_engine_warm_leaves_a_blank_arena_and_every_program_cached(warms):
    """``warm()`` runs every program in place on the arena and hands
    traffic a blank one — once or twice over — under the cache keys it
    warmed: traffic then builds no bucket program
    (``compiles_after_warm``) and misses no jit cache either."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    engine = GenerativeEngine(
        make_stub_fns(), {}, max_batch_size=4, page_size=4,
        prefix_cache_entries=4,
    )
    try:
        for _ in range(warms):
            engine.warm()
            assert _arena_is_blank(engine, engine._arena)
        programs = [
            engine._jit_prefill, engine._jit_insert, engine._jit_move,
            engine._jit_clear, *engine._step_fns.values(),
        ]
        assert len(programs) == 4 + 3 * 3
        warmed = [f._cache_size() for f in programs]
        rng = np.random.default_rng(5)
        reqs = [
            (
                rng.integers(1, VOCAB, size=(1 + i % 3,)).astype(np.int32),
                int(rng.integers(2, 12)),
            )
            for i in range(10)
        ]
        handles = [
            engine.submit_nowait(inp, max_new_tokens=m) for inp, m in reqs
        ]
        outs = [h.wait(30.0) for h in handles]
        assert [f._cache_size() for f in programs] == warmed
    finally:
        engine.close()
    assert engine.compiles_after_warm == 0
    for (inp, m), out in zip(reqs, outs):
        assert [int(t) for t in out] == ref_stream(inp, m)


def test_engine_warm_refuses_an_engine_with_work_in_flight():
    """Warming runs on the arena itself, so it needs an engine with
    nothing live or queued; the sequence in flight is not disturbed."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    gate = threading.Event()
    engine = GenerativeEngine(
        make_stub_fns(), {}, max_batch_size=2, fault_hook=gate.wait
    )
    try:
        engine.warm()
        inp = np.array([3, 5], np.int32)
        h = engine.submit_nowait(inp, max_new_tokens=6)
        with pytest.raises(RuntimeError, match="live or queued"):
            engine.warm()
        gate.set()
        assert [int(t) for t in h.wait(30.0)] == ref_stream(inp, 6)
        engine.warm()                   # idle again: allowed
    finally:
        gate.set()
        engine.close()


def test_engine_worker_death_lets_go_of_the_arena():
    """A program that fails may have taken its donated arena with it:
    the dead worker keeps no arena, fails what was in flight and takes
    no more."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    boom = threading.Event()

    def hook():
        if boom.is_set():
            raise RuntimeError("injected device fault")

    engine = GenerativeEngine(
        make_stub_fns(), {}, max_batch_size=2, fault_hook=hook
    )
    try:
        engine.warm()
        inp = np.array([2, 7], np.int32)
        assert [int(t) for t in engine.submit(inp, max_new_tokens=4)] \
            == ref_stream(inp, 4)
        assert engine._arena is not None
        boom.set()
        h = engine.submit_nowait(inp, max_new_tokens=4)
        with pytest.raises(RuntimeError, match="injected device fault"):
            h.wait(30.0)
        assert engine._arena is None
        with pytest.raises(RuntimeError, match="worker died"):
            engine.submit_nowait(inp, max_new_tokens=4)
    finally:
        engine.close()


# Which positional argument of each program is the state it is given
# for good: the arena, or the one row a prompt's windows are prefilled
# into.  On both contracts the engine serves: the tiny T5 (K/V by
# position, buckets cut out and set back) and the tiny EvaByte of
# tests/test_evabyte.py (ring and chunk table, worked on in place).
_ARENA_PROGRAMS = {
    "insert": 0, "move": 0, "clear": 0, "step": 1, "step, whole arena": 1,
    "evabyte insert": 0, "evabyte move": 0, "evabyte clear": 0,
    "evabyte step": 1, "evabyte step, whole arena": 1,
    "evabyte prefill_window": 1,
}


@pytest.fixture(scope="module")
def lowered_arena_programs(tiny_t5):
    """``{name: (lowered program, its arguments)}`` for every program
    that is given an arena or a row cache, on the tiny T5 and the tiny
    EvaByte, plus T5's prefill."""
    from test_evabyte import build, decode_fns

    from tpu_pipelines.models.t5 import make_continuous_decode_fns
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = tiny_t5
    fns = make_continuous_decode_fns(
        model, max_decode_len=8, eos_id=1, max_input_len=6
    )
    eva_model, eva_params = build()
    out = {}
    engine = GenerativeEngine(fns, params, max_batch_size=4, page_size=2)
    eva = GenerativeEngine(decode_fns(eva_model), eva_params, max_batch_size=4)
    try:
        engine._ensure_arena()          # lowering needs no warm program
        eva._ensure_arena()
        zin = np.zeros((1, 6), np.int32)
        c1, e1, _ = engine._jit_prefill(engine.params, zin, zin)
        slot, one = np.int32(0), np.int32(1)
        a, ea = engine._arena, eva._arena
        ezin = np.zeros((1, eva.max_input_len), np.int32)
        whole = eva.max_decode_len
        calls = {
            "insert": (engine._jit_insert, (a, c1, e1, zin, one, slot)),
            "move": (engine._jit_move, (a, slot, slot)),
            "clear": (engine._jit_clear, (a, slot)),
            "step": (engine._step_for(2, 4), (engine.params, a)),
            "step, whole arena": (
                engine._step_for(4, 8), (engine.params, a)),
            "evabyte insert": (eva._jit_insert, (
                ea, eva._row_cache, eva._no_encoded, ezin, one, slot)),
            "evabyte move": (eva._jit_move, (ea, slot, slot)),
            "evabyte clear": (eva._jit_clear, (ea, slot)),
            "evabyte step": (eva._step_for(2, whole), (eva.params, ea)),
            "evabyte step, whole arena": (
                eva._step_for(4, whole), (eva.params, ea)),
            "evabyte prefill_window": (eva._jit_prefill_window, (
                eva.params, eva._row_cache,
                np.zeros((1, eva._window_len), np.int32), one, slot)),
        }
        for name, (prog, args) in calls.items():
            out[name] = (prog.lower(*args), args)
        out["prefill"] = (
            engine._jit_prefill.lower(engine.params, zin, zin), ())
    finally:
        engine.close()
        eva.close()
    return out


@pytest.mark.parametrize("name", sorted(_ARENA_PROGRAMS))
def test_arena_program_aliases_every_state_leaf(
    name, lowered_arena_programs
):
    """The engagement check of the in-place arena, static and exact:
    the program donates its arena (``prefill_window``: its row cache)
    and nothing else (not the parameters, not the prefill results
    ``insert`` copies from), and the lowered program aliases every leaf
    of it to an output of its own."""
    import re

    import jax

    lowered, args = lowered_arena_programs[name]
    state_at = _ARENA_PROGRAMS[name]
    want = []
    for i, arg in enumerate(args):
        want += [i == state_at] * len(jax.tree_util.tree_leaves(arg))
    got = [
        info.donated for info in jax.tree_util.tree_leaves(lowered.args_info)
    ]
    assert got == want
    text = lowered.as_text()
    aliased = re.findall(r"tf\.aliasing_output = (\d+)", text)
    assert len(aliased) == sum(want)
    assert len(set(aliased)) == len(aliased)
    assert "jax.buffer_donor" not in text   # donated, aliased to nothing


def test_prefill_donates_nothing(lowered_arena_programs):
    import jax

    lowered, _ = lowered_arena_programs["prefill"]
    assert not any(
        i.donated for i in jax.tree_util.tree_leaves(lowered.args_info)
    )
    assert "tf.aliasing_output" not in lowered.as_text()


def test_arena_programs_run_in_place_and_kill_the_arena_they_took():
    """At run time: the arena handed to a program is dead afterwards,
    and the arena it returns lies in the same buffers."""
    import jax

    from tpu_pipelines.serving.generative import GenerativeEngine

    engine = GenerativeEngine(
        make_stub_fns(), {}, max_batch_size=4, page_size=4
    )
    try:
        engine.warm()
        zin = np.ones((1, engine.max_input_len), np.int32)
        c1, e1, t0 = engine._jit_prefill(engine.params, zin, zin)
        slot = np.int32(1)
        leaves = jax.tree_util.tree_leaves
        for call in (
            lambda a: engine._jit_insert(
                a, c1, e1, zin, np.int32(int(t0)), slot),
            lambda a: engine._jit_move(a, slot, np.int32(0)),
            lambda a: engine._step_for(2, 4)(engine.params, a)[0],
            lambda a: engine._step_for(4, 12)(engine.params, a)[0],
            lambda a: engine._jit_clear(a, slot),
        ):
            old = leaves(engine._arena)
            where = [x.unsafe_buffer_pointer() for x in old]
            engine._arena = call(engine._arena)
            assert all(x.is_deleted() for x in old)
            there = [x.unsafe_buffer_pointer() for x in leaves(engine._arena)]
            assert where == there
        # The prefill results insert copied from are still alive: a
        # prefix-cache entry is inserted many times.
        assert not any(x.is_deleted() for x in leaves((c1, e1)))
    finally:
        engine.close()


def test_flash_decode_kernel_matches_dense():
    """The single-query flash-decode kernel (online-softmax over KV
    blocks) matches dense cache attention with per-row validity masks and
    both broadcast and per-batch relative-position bias."""
    import jax.numpy as jnp

    from tpu_pipelines.models.transformer import dense_attention
    from tpu_pipelines.ops.flash_attention import flash_decode_attention

    rng = np.random.default_rng(0)
    b, l, h, d = 3, 128, 2, 8
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    pos = np.array([5, 63, 127])
    mask = jnp.asarray(
        (np.arange(l)[None, :] <= pos[:, None]).astype(np.int32)
    )
    for bias_shape in (None, (1, h, 1, l), (b, h, 1, l)):
        bias = (
            None if bias_shape is None
            else jnp.asarray(rng.standard_normal(bias_shape), jnp.float32)
        )
        ref = dense_attention(
            q, k, v, causal=False, kv_mask=mask, bias=bias
        )
        got = flash_decode_attention(
            q, k, v, kv_mask=mask, bias=bias, block_k=32, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


def test_choose_decode_impl_uses_measured_crossover(tmp_path, monkeypatch):
    """The decode-regime "auto" rule: dense with no measurement, flash
    at/above a recorded crossover KV length, dense below it — its OWN
    table entry, independent of the training-shape crossover."""
    from tpu_pipelines.models.transformer import choose_decode_impl
    from tpu_pipelines.ops import autotune

    monkeypatch.setenv("TPP_AUTOTUNE_CACHE", str(tmp_path / "cache"))
    kind = autotune.current_device_kind()
    # Never measured: the kernel has not earned the hot path.
    assert choose_decode_impl(4, 8, 4096, 64) == "dense"
    autotune.record_decode_crossover(kind, 1024, {"heads": 8})
    assert autotune.lookup_decode_crossover(kind) == 1024
    assert choose_decode_impl(4, 8, 4096, 64) == "flash"
    assert choose_decode_impl(4, 8, 1024, 64) == "flash"
    assert choose_decode_impl(4, 8, 512, 64) == "dense"
    # Measured-no-crossover (dense won everywhere): explicit None.
    autotune.record_decode_crossover(kind, None)
    assert autotune.lookup_decode_crossover(kind) is None
    assert choose_decode_impl(4, 8, 8192, 64) == "dense"


def test_sweep_decode_times_block_k(monkeypatch):
    """The decode sweep times real kernels (interpret mode on CPU) over a
    1-D block_k grid and returns a best entry."""
    monkeypatch.setenv("TPP_AUTOTUNE_ITERS", "1")
    import jax.numpy as jnp

    from tpu_pipelines.ops import autotune

    out = autotune.sweep_decode(
        2, 2, 128, 8, jnp.float32, True,
        pairs=[(8, 64), (8, 128)], iters=1,
    )
    res = out["flash_decode"]
    assert res["best"] is not None
    assert res["best"]["block_k"] in (64, 128)
    assert all("ms" in r or "error" in r for r in res["swept"])


# ------------------------------------ one step ahead (ISSUE 34)
#
# The worker dispatches step k + 1 before it reads step k's tokens: a
# step needs only counts of the host.  A row whose budget a step fills
# leaves as that step is dispatched; an EOS is learnt one step late and
# its row rides that one step for nothing.  Tokens are handed out by the
# snapshot of the rows taken at the step's dispatch.


def _pangu_kit():
    import test_pangu_moe as pg
    from tpu_pipelines.models.pangu_moe import make_continuous_decode_fns

    model, params = pg.build()
    # 1 to 4 windows of 16; the by-position cache holds the prompt
    prompts = [pg.prompt(400 + i, n) for i, n in enumerate(
        (40, 7, 16, 33, 50))]
    fns = pg.decode_fns(model)
    rows = [
        pg.through_the_cache(params, fns, p, 12)[0].tolist()
        for p in prompts
    ]
    return SimpleNamespace(
        params=params, prompts=prompts, windowed=True,
        greedy=lambda i, m: rows[i][:m],
        fns=lambda eos: make_continuous_decode_fns(
            model, max_decode_len=pg.MAX_OUT, eos_id=eos,
            max_input_len=pg.MAX_IN, prefill_window_len=pg.WINDOW),
        # one cached position of one layer, in bytes (f32), and the layers
        latent_row_bytes=pg.ROW * 4 * pg.HP["n_layers"],
    )


_AHEAD_KITS = {}


def _ahead_kit(which, request):
    """The kits of ``kit`` and tiny Pangu, each built once a module."""
    if which not in _AHEAD_KITS:
        _AHEAD_KITS[which] = (
            _t5_kit(request.getfixturevalue("tiny_t5")) if which == "t5"
            else _evabyte_kit() if which == "evabyte" else _pangu_kit())
    return _AHEAD_KITS[which]


# An EOS INSIDE the vocabulary, by model: a token some of the five greedy
# streams reach in mid-stream and others never do.
_EOS_INSIDE = {"t5": 29, "evabyte": 29, "pangu": 88}
# (prompt, budget): three slots; the first six are queued when the first
# round runs, the last three arrive while rows are live.  Budgets of 2
# and 3 leave within a step or two of being seated.
_MIXED_1 = [(0, 9), (1, 9), (2, 5), (3, 12), (4, 3), (1, 6)]
_MIXED_2 = [(2, 2), (0, 4), (3, 7)]


@pytest.mark.parametrize("which,engine_kw", [
    ("t5", dict(page_size=0)),
    ("t5", dict(page_size=4)),
    ("t5", dict(page_size=0, prefix_cache_entries=8)),
    ("t5", dict(page_size=4, prefix_cache_entries=8)),
    ("evabyte", dict(page_size=4)),
    ("evabyte", dict(page_size=4, prefill_chunk_pages=1)),
    ("pangu", dict(page_size=16, prefill_chunk_pages=1)),
    ("pangu", dict(page_size=0)),
], ids=[
    "t5-unpaged", "t5-paged", "t5-unpaged-prefix", "t5-paged-prefix",
    "evabyte-windows", "evabyte-credits", "pangu-paged-credits",
    "pangu-unpaged",
])
def test_one_step_ahead_streams_are_isolated_greedy(
    which, engine_kw, request
):
    """EOS inside the vocabulary and budget endings under mid-stream
    admissions: every stream is the model's own greedy decode cut at its
    EOS; every step was dispatched once, all but the first of a busy
    spell behind one still unread; an EOS ending wasted exactly one
    row-step and a budget ending none; and where the contract keeps an
    account (``step_account``), it is of the positions the rows held
    when their step was DISPATCHED, the wasted rides included."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    kit = _ahead_kit(which, request)
    eos = _EOS_INSIDE[which]
    reqs = _MIXED_1 + _MIXED_2
    own = [_own_stream(kit, i, m, eos) for i, m in reqs]
    by_eos = [o[-1] == eos and len(o) < m for o, (_, m) in zip(own, reqs)]
    # the choice of EOS still does what it was chosen for
    assert any(e and len(o) > 2 for e, o in zip(by_eos, own))
    assert sum(not e for e in by_eos) >= 3
    gate = threading.Event()
    reg = MetricsRegistry()
    engine = GenerativeEngine(
        kit.fns(eos), kit.params, max_batch_size=3,
        fault_hook=gate.wait, registry=reg, **engine_kw,
    )
    try:
        engine.warm()
        first = [
            engine.submit_nowait(kit.prompts[i], max_new_tokens=m)
            for i, m in _MIXED_1
        ]
        gate.set()
        first[0].wait(120.0)
        second = [
            engine.submit_nowait(kit.prompts[i], max_new_tokens=m)
            for i, m in _MIXED_2
        ]
        outs = [[int(t) for t in h.wait(120.0)] for h in first + second]
        _wait_idle(engine)
    finally:
        gate.set()
        engine.close()
    assert outs == own
    assert engine.compiles_after_warm == 0
    get = lambda name, *lab: reg.get(name).labels("0", *lab).get()
    steps = get("serving_decode_steps_total")
    dispatched = _step_dispatches(reg)
    assert sum(dispatched.values()) == steps == engine.steps_run
    assert 1 <= dispatched["alone"] <= 3 and dispatched["behind_step"] > 0
    # Never seated: a budget of one token (none here), or a first token
    # that a prefix entry already held on the host and that was EOS.
    seated = get("serving_decode_engine_phase_total", "insert")
    assert get("serving_decode_engine_phase_total", "retire") == seated
    wasted = get("serving_decode_wasted_row_steps_total")
    assert wasted == sum(by_eos) - (len(reqs) - seated)
    # every row of every step emitted a token or is counted as wasted
    tokens = get("serving_decode_tokens_total")
    assert tokens == sum(len(o) - 1 for o in own)
    ridden = [
        len(kit.prompts[i]) + t
        for (i, _), o, e in zip(reqs, own, by_eos)
        for t in range(len(o) - 1 + e)
    ]
    assert len(ridden) == tokens + sum(by_eos)
    if which == "pangu":
        # a step at position t reads t + 1 cached positions a layer
        assert wasted == sum(by_eos)
        assert get("serving_decode_cache_read_bytes_total", "latent") == (
            sum(t + 1 for t in ridden) * kit.latent_row_bytes)
        assert 0 < get("serving_decode_expert_load_ratio_count") <= steps


def _wait_idle(engine, timeout_s=30.0):
    """The last step dispatched may be unread when the last handle is
    done (a row that ended by EOS rode it): idle once it is read."""
    deadline = time.monotonic() + timeout_s
    while not engine.idle() and time.monotonic() < deadline:
        time.sleep(0.001)
    assert engine.idle()


def _stub_seeds(want_eos, n, budget=12):
    """``n`` one-token prompts of the stub chain whose stream of
    ``budget`` tokens ends by EOS in mid-stream (at its third token or
    later), or never reaches EOS."""
    found = []
    for seed in range(1, 200):
        ref = ref_stream(np.asarray([seed], np.int32), budget)
        ends = ref[-1] == EOS and len(ref) < budget
        if (ends and len(ref) >= 3) if want_eos else (
                EOS not in ref and len(ref) == budget):
            found.append(np.asarray([seed], np.int32))
            if len(found) == n:
                return found
    raise AssertionError("the stub chain has no such seeds")


def _rows_by_step(engine):
    """Spy on the dispatch of every step: the request numbers that sat in
    its rows, in row order (the worker thread calls ``_step_for`` with
    the rows it is about to step)."""
    steps = []
    step_for = engine._step_for

    def spy(b, kv):
        steps.append(tuple(
            s.seq_id for s in engine._slots[:engine._n_live]))
        return step_for(b, kv)

    engine._step_for = spy
    return steps


@pytest.mark.parametrize("ending", ["budget", "eos"])
def test_a_budget_ending_wastes_nothing_and_an_eos_ending_one_row_step(
    ending
):
    """Two slots, a queue.  ``budget``: a row leaves as the step of its
    last token is dispatched and the queue's head is seated in that same
    round, so no step runs with a slot empty while anyone waits, and no
    row rides a step after its end.  ``eos``: the row rides exactly one
    step more than it emitted tokens for, and the slot is taken in the
    round that read the EOS."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    plain = _stub_seeds(False, 5)
    if ending == "budget":
        reqs = list(zip(plain, [3, 6, 4, 2, 5]))
    else:
        reqs = [(_stub_seeds(True, 1)[0], 12)] + list(
            zip(plain[:3], [9, 4, 5]))
    refs = [ref_stream(inp, m) for inp, m in reqs]
    gate = threading.Event()
    reg = MetricsRegistry()
    engine = GenerativeEngine(
        make_stub_fns(), {}, max_batch_size=2, page_size=4,
        fault_hook=gate.wait, registry=reg,
    )
    try:
        engine.warm()
        steps = _rows_by_step(engine)
        handles = [
            engine.submit_nowait(inp, max_new_tokens=m) for inp, m in reqs
        ]
        gate.set()
        outs = [[int(t) for t in h.wait(30.0)] for h in handles]
        _wait_idle(engine)
    finally:
        gate.set()
        engine.close()
    assert outs == refs
    get = lambda name, *lab: reg.get(name).labels("0", *lab).get()
    ridden = {
        h.seq_id: sum(h.seq_id in rows for rows in steps) for h in handles}
    lives = [len(rows) for rows in steps]
    assert get("serving_decode_steps_total") == len(steps)
    if ending == "budget":
        assert get("serving_decode_wasted_row_steps_total") == 0
        assert ridden == {h.seq_id: len(h.result) - 1 for h in handles}
        # full until the queue is empty: a request's first token is not a
        # step's, so the last one seated makes the tail of one row
        assert lives == sorted(lives, reverse=True) and lives[0] == 2
        assert sum(lives) == sum(len(o) - 1 for o in outs)
        # seats in order of arrival, each in the round its slot freed:
        # request 3 rides the step after request 1's last (its third
        # token's: the second step), request 2 moved into its slot
        assert steps[:3] == [(1, 2), (1, 2), (2, 3)]
    else:
        assert refs[0][-1] == EOS and 3 <= len(refs[0]) < 12
        assert get("serving_decode_wasted_row_steps_total") == 1
        assert ridden[handles[0].seq_id] == len(refs[0])
        assert all(
            ridden[h.seq_id] == len(h.result) - 1 for h in handles[1:])
        # the step after its last ride already has its successor seated
        last = max(k for k, rows in enumerate(steps) if 1 in rows)
        assert steps[last + 1] == (2, 3)
    assert _step_dispatches(reg) == {
        "behind_step": len(steps) - 1, "alone": 1}


def test_rows_moved_after_a_dispatch_still_get_their_own_tokens():
    """Three rows; the one in slot 0 leaves as the first step is
    dispatched (a budget of two), so slot 2's row is moved into slot 0
    with that step's tokens unread: the read hands them out by the
    step's own snapshot of the rows, not by where the rows are now."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    seeds = _stub_seeds(False, 3)
    reqs = list(zip(seeds, [2, 7, 9]))
    gate = threading.Event()
    engine = GenerativeEngine(
        make_stub_fns(), {}, max_batch_size=3, page_size=0,
        fault_hook=gate.wait,
    )
    try:
        engine.warm()
        log = _Log(engine)
        handles = [
            engine.submit_nowait(inp, max_new_tokens=m) for inp, m in reqs
        ]
        gate.set()
        outs = [[int(t) for t in h.wait(30.0)] for h in handles]
    finally:
        gate.set()
        engine.close()
    assert outs == [ref_stream(inp, m) for inp, m in reqs]
    # the first step's dispatch, the move, and only then its read
    first_run = log.events.index(("dispatch", "run"))
    move = log.events.index(("dispatch", "move"))
    read = next(
        at for at, ev in enumerate(log.events)
        if ev[:2] == ("read", "nxt"))
    assert first_run < move < read
    assert log.events[read][2] == first_run   # it is that step's tokens


@pytest.mark.parametrize("how", ["read", "close", "fault"])
def test_a_step_in_flight_is_owed(how):
    """One request with a budget of two: its row leaves as its one step
    is dispatched, so the engine holds no row, no queue and no unread
    first token, and still owes the step's token.  ``idle()`` says so;
    ``read``: the next round reads it and the handle completes;
    ``close``: the handle is evicted with what the host has seen;
    ``fault``: a fault hook that raises kills the worker, which fails
    the handle the same way."""
    from tpu_pipelines.serving.generative import (
        GenerationEvicted,
        GenerativeEngine,
    )

    inp = _stub_seeds(False, 1)[0]
    ref = ref_stream(inp, 2)
    reached, release = threading.Event(), threading.Event()
    rounds = {"n": 0}

    def hook():
        # round 1 admits, round 2 steps, round 3 would read the step
        rounds["n"] += 1
        if rounds["n"] == 3:
            reached.set()
            release.wait(30.0)
            if how == "fault":
                raise RuntimeError("injected kill")

    engine = GenerativeEngine(
        make_stub_fns(), {}, max_batch_size=2, fault_hook=hook)
    try:
        engine.warm()
        h = engine.submit_nowait(inp, max_new_tokens=2)
        assert reached.wait(30.0)
        assert engine.steps_run == 0 and len(engine._flights) == 1
        assert h.slot is None and h.in_flight == 1 and h.held == 2
        assert h.tokens == ref[:1] and not h._done.is_set()
        assert engine.active_sequences() == 0
        assert engine.outstanding_tokens() == 0
        assert not engine.idle()
        if how == "close":
            engine.close(timeout_s=0.2)
        release.set()
        assert h._done.wait(30.0)
        if how == "read":
            assert [int(t) for t in h.result] == ref
            _wait_idle(engine)
            assert engine.steps_run == 1
        else:
            assert h.result is None and h.tokens == ref[:1]
            if how == "close":
                assert isinstance(h.error, GenerationEvicted)
            else:
                assert "injected kill" in str(h.error)
                engine._worker.join(30.0)
                assert engine._arena is None and not engine._flights
                with pytest.raises(RuntimeError, match="worker died"):
                    engine.submit_nowait(inp, max_new_tokens=2)
    finally:
        release.set()
        engine.close()


# ------------------------------------------------- fleet / REST surface


class FakeGenLoaded:
    """Stub LoadedModel carrying the continuous-decode contract: the
    per-version ``offset`` shifts every token, so streams prove WHICH
    version served them (the drain-across-hot-swap evidence)."""

    def __init__(self, offset):
        self.offset = offset
        self.params = {"offset": int(offset)}
        self.decode_fns = make_stub_fns()
        self.generate = None
        self.transform = None

    def predict(self, batch):
        return np.asarray(batch["inputs"], np.float64) + self.offset

    predict_transformed = predict


def _gen_payload(base, version, offset):
    vdir = base / str(version)
    vdir.mkdir(parents=True)
    (vdir / "offset.txt").write_text(str(offset))
    return str(vdir)


def _gen_loader(version_dir):
    import os

    with open(os.path.join(version_dir, "offset.txt")) as f:
        return FakeGenLoaded(int(f.read()))


@pytest.fixture
def gen_loader(monkeypatch):
    monkeypatch.setattr(
        "tpu_pipelines.serving.fleet.versions._default_loader", _gen_loader
    )
    return _gen_loader


def _post(url, body=b"{}", timeout=30):
    req = urllib.request.Request(url, data=body)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_replica_engines_drain_and_prune_across_versions(
    tmp_path, gen_loader
):
    """The engine half of drain-then-evict: each resident version gets
    its own warmed engine; once a version drains out of residency and
    its engine idles, the engine is pruned."""
    from tpu_pipelines.serving.fleet import ServingFleet

    base = tmp_path / "m"
    d1 = _gen_payload(base, 1, 0)
    d2 = _gen_payload(base, 2, 3)
    fleet = ServingFleet(
        "m", str(base), replicas=1, max_versions=1,
        model_type="generative", max_batch_size=2,
    )
    try:
        fleet.load_version(d1)
        replica = fleet.pool.replicas[0]
        assert set(replica._engines) == {"1"}
        out1 = fleet.generate_submit(
            {"inputs": np.asarray([[3, 5]], np.int32)},
            {"max_new_tokens": 6},
        )
        assert [int(t) for t in out1[0]] == ref_stream(
            np.asarray([3, 5]), 6
        )
        # Hot-swap: v2 becomes active (and with max_versions=1, v1 left
        # residency the moment its lease count hit zero).
        fleet.load_version(d2)
        out2 = fleet.generate_submit(
            {"inputs": np.asarray([[3, 5]], np.int32)},
            {"max_new_tokens": 6},
        )
        assert [int(t) for t in out2[0]] == ref_stream(
            np.asarray([3, 5]), 6, offset=3
        )
        # The request that leased v2 also pruned v1's idle engine.
        assert set(replica._engines) == {"2"}
        assert fleet.health()["outstanding_decode_tokens"] == 0
    finally:
        fleet.close()


def test_generative_rest_surface(tmp_path, gen_loader):
    """REST e2e on the generative model type: token streams, submit-time
    4xx for malformed generation params, and decode telemetry on the
    server's own scrape."""
    from tpu_pipelines.serving import ModelServer

    base = tmp_path / "m"
    _gen_payload(base, 1, 0)
    server = ModelServer(
        "toy", str(base), model_type="generative", max_batch_size=4,
    )
    assert server._fleet is not None and server._fleet.generative
    port = server.start()
    url = f"http://127.0.0.1:{port}/v1/models/toy:generate"
    try:
        # Mixed true lengths ride a padded batch + mask (REST instances
        # are columnar); the engine decodes each row to its OWN length.
        body = json.dumps({
            "instances": [
                {"inputs": [3, 5, 0], "input_mask": [1, 1, 0]},
                {"inputs": [2, 2, 4], "input_mask": [1, 1, 1]},
            ],
            "params": {"max_new_tokens": 6},
        }).encode()
        status, out = _post(url, body)
        assert status == 200
        rows = out["outputs"]
        ref0 = ref_stream(np.asarray([3, 5]), 6)
        ref1 = ref_stream(np.asarray([2, 2, 4]), 6)
        width = max(len(ref0), len(ref1))
        assert rows[0] == ref0 + [0] * (width - len(ref0))
        assert rows[1] == ref1 + [0] * (width - len(ref1))

        # Malformed generation params: a 400 at submit time.
        for bad in (
            {"max_new_tokens": 0},
            {"max_new_tokens": 99},
            {"temperature": 0.7},
        ):
            bad_body = json.dumps({
                "instances": [{"inputs": [3, 5]}], "params": bad,
            }).encode()
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(url, bad_body)
            assert err.value.code == 400

        # Health + scrape carry the decode family.
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ) as r:
            health = json.loads(r.read())
        assert health["fleet"]["model_type"] == "generative"
        assert health["fleet"]["outstanding_decode_tokens"] == 0
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape = r.read().decode()
        assert 'serving_decode_steps_total{replica="0"}' in scrape
        assert 'serving_decode_sequences_total{replica="0"} 2' in scrape
        assert "serving_decode_per_token_latency_seconds" in scrape
    finally:
        server.stop()


def test_generative_hot_swap_with_inflight_generations(
    tmp_path, gen_loader
):
    """Acceptance: a generate hammer runs ACROSS a version hot-swap —
    zero non-200 anywhere, every stream valid for the version that
    served it (v1 or v2, never a mix), and the new version serves after
    the swap."""
    from tpu_pipelines.serving import ModelServer

    base = tmp_path / "m"
    _gen_payload(base, 1, 0)
    server = ModelServer(
        "toy", str(base), model_type="generative", max_batch_size=4,
        max_versions=2,
    )
    port = server.start()
    url = f"http://127.0.0.1:{port}/v1/models/toy:generate"
    inp = [3, 5]
    ref_v1 = ref_stream(np.asarray(inp), 8)
    ref_v2 = ref_stream(np.asarray(inp), 8, offset=3)
    body = json.dumps({
        "instances": [{"inputs": inp}], "params": {"max_new_tokens": 8},
    }).encode()
    errors, streams = [], []
    lock = threading.Lock()

    def fire(n):
        for _ in range(n):
            try:
                status, out = _post(url, body)
                with lock:
                    if status != 200:
                        errors.append(status)
                    else:
                        streams.append(out["outputs"][0])
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(repr(e))

    try:
        fire(2)  # warm the path
        threads = [
            threading.Thread(target=fire, args=(20,)) for _ in range(3)
        ]
        for t in threads:
            t.start()
        _gen_payload(base, 2, 3)
        status, reply = _post(f"http://127.0.0.1:{port}/v1/models/toy:reload")
        assert (status, reply["version"]) == (200, "2")
        for t in threads:
            t.join()
        assert errors == []
        # Every stream is a complete, valid decode of exactly one version
        # — an in-flight generation finished on the version it started on.
        for s in streams:
            assert s in (ref_v1, ref_v2), s
        # Post-swap traffic decodes on v2.
        _, out = _post(url, body)
        assert out["outputs"][0] == ref_v2
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape = r.read().decode()
    finally:
        server.stop()
    import re

    assert not re.search(r'serving_requests_total\{[^}]*code="5', scrape)


def test_generative_token_admission_429(tmp_path, gen_loader):
    """The generate door counts outstanding TOKENS: with a 1-token bound
    and a wedged... rather, a tiny bound, concurrent long generations
    shed with 429 + Retry-After instead of queueing into the SLO cliff."""
    from tpu_pipelines.serving import ModelServer

    base = tmp_path / "m"
    _gen_payload(base, 1, 0)
    server = ModelServer(
        "toy", str(base), model_type="generative", max_batch_size=1,
        max_queue_tokens=4,
    )
    port = server.start()
    url = f"http://127.0.0.1:{port}/v1/models/toy:generate"
    try:
        # One request whose token budget exceeds the engine bound: the
        # ENGINE sheds it (EngineOverloaded -> 429 + Retry-After).
        body = json.dumps({
            "instances": [{"inputs": [3, 5]}],
            "params": {"max_new_tokens": 8},
        }).encode()
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(url, body)
        assert err.value.code == 429
        assert err.value.headers.get("Retry-After") is not None
        # Within the bound: served.
        ok_body = json.dumps({
            "instances": [{"inputs": [3, 5]}],
            "params": {"max_new_tokens": 3},
        }).encode()
        status, out = _post(url, ok_body)
        assert status == 200
        assert out["outputs"][0] == ref_stream(np.asarray([3, 5]), 3)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape = r.read().decode()
        assert 'serving_decode_shed_total{replica="0"} 1' in scrape
    finally:
        server.stop()


def test_generative_env_knobs(tmp_path, gen_loader, monkeypatch):
    from tpu_pipelines.serving import ModelServer

    base = tmp_path / "m"
    _gen_payload(base, 1, 0)
    monkeypatch.setenv("TPP_SERVING_MODEL_TYPE", "generative")
    monkeypatch.setenv("TPP_SERVING_PAGE_SIZE", "4")
    monkeypatch.setenv("TPP_SERVING_MAX_TOKENS", "64")
    monkeypatch.setenv("TPP_SERVING_SLO_MS_PER_TOKEN", "5")
    server = ModelServer("toy", str(base), max_batch_size=2)
    try:
        assert server.model_type == "generative"
        assert server.decode_page_size == 4
        assert server.max_queue_tokens == 64
        assert server.slo_ms_per_token == 5.0
        assert server._fleet is not None and server._fleet.generative
        eng = server._fleet.pool.replicas[0]._engines["1"]
        assert eng.page_size == 4
        assert eng.max_queue_tokens == 64
        assert eng.slo_ms_per_token == 5.0
    finally:
        server.stop()


def test_non_generative_payload_refused_by_canary(tmp_path, monkeypatch):
    """A generative fleet refuses a payload with no decode contract at
    the CANARY gate: the push is a 4xx-class verdict, serving state
    untouched."""
    from tpu_pipelines.serving.fleet import CanaryRefused, ServingFleet

    class NoDecode:
        params = {}
        decode_fns = None
        generate = None
        transform = None

        def predict(self, batch):
            return np.asarray(batch["inputs"], np.float64)

        predict_transformed = predict

    monkeypatch.setattr(
        "tpu_pipelines.serving.fleet.versions._default_loader",
        lambda vdir: NoDecode(),
    )
    base = tmp_path / "m"
    vdir = base / "1"
    vdir.mkdir(parents=True)
    fleet = ServingFleet(
        "m", str(base), replicas=1, model_type="generative",
        max_batch_size=2,
    )
    try:
        with pytest.raises(CanaryRefused, match="generative warmup"):
            fleet.load_version(str(vdir))
        assert fleet.active_version is None
    finally:
        fleet.close()


# ------------------------------------ decode-session recovery (ISSUE 17)


def test_decode_session_recovered_bitwise_on_kill(tmp_path, gen_loader):
    """A replica dies mid-decode: the fleet re-prefills the lost
    sequences onto a survivor and the caller receives the EXACT token
    streams an undisturbed decode produces (greedy determinism), with
    the recovery counted; the dead replica then heals through the
    supervisor and serves identical streams again."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.fleet import ServingFleet
    from tpu_pipelines.testing.faults import (
        KILL_REPLICA,
        REPLICA_KEY,
        FaultPlan,
        NodeFault,
    )

    base = tmp_path / "m"
    d1 = _gen_payload(base, 1, 0)
    reg = MetricsRegistry()
    fleet = ServingFleet(
        "m", str(base), replicas=2, max_versions=1,
        model_type="generative", max_batch_size=2, registry=reg,
        supervisor_interval_s=0.05,
    )
    fleet.supervisor.stop()  # heal on command, not on a timer
    try:
        fleet.load_version(d1)
        batch = {"inputs": np.asarray([[3, 5], [2, 7]], np.int32)}
        expect = [
            ref_stream(np.asarray([3, 5]), 8),
            ref_stream(np.asarray([2, 7]), 8),
        ]
        def rows(out):
            # Engine output is padded to the longest stream in the
            # request: compare the real tokens, require pad after.
            got = []
            for row, exp in zip(np.asarray(out), expect):
                assert all(int(t) == 0 for t in row[len(exp):])
                got.append([int(t) for t in row[: len(exp)]])
            return got

        clean = fleet.generate_submit(batch, {"max_new_tokens": 8})
        assert rows(clean) == expect
        plan = FaultPlan({REPLICA_KEY: NodeFault(KILL_REPLICA)})
        with plan.activate():
            out = fleet.generate_submit(batch, {"max_new_tokens": 8})
            assert rows(out) == expect
            recovered = reg.get(
                "serving_decode_sessions_recovered_total"
            ).get()
            assert recovered >= 1
            killed = [
                v.split(":", 1)[1] for _, v in plan.log
                if v.startswith("kill_replica:")
            ]
            assert len(killed) == 1
            # Eject + rebuild the dead replica, then decode through it.
            for _ in range(3):
                fleet.supervisor.probe_once()
            assert fleet.health()["replica_states"] == {
                "0": "healthy", "1": "healthy"
            }
            for _ in range(4):  # both replicas see traffic post-heal
                again = fleet.generate_submit(
                    batch, {"max_new_tokens": 8}
                )
                assert rows(again) == expect
        assert fleet.health()["outstanding_decode_tokens"] == 0
    finally:
        fleet.close()


def test_decode_session_recovered_bitwise_t5(tmp_path, monkeypatch, tiny_t5):
    """The same kill-mid-stream recovery on a real tiny T5: the
    recovered streams are bitwise identical to the uninterrupted ones —
    re-prefill (prompt + accepted tokens) plus greedy continuation
    reproduces the lost state exactly."""
    from tpu_pipelines.models.t5 import make_continuous_decode_fns
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.fleet import ServingFleet
    from tpu_pipelines.testing.faults import (
        KILL_REPLICA,
        REPLICA_KEY,
        FaultPlan,
        NodeFault,
    )

    model, params = tiny_t5

    class T5Loaded:
        def __init__(self):
            self.params = params
            self.decode_fns = make_continuous_decode_fns(
                model, max_decode_len=8, eos_id=1, max_input_len=6
            )
            self.generate = None
            self.transform = None

        def predict(self, batch):
            return np.asarray(batch["inputs"], np.float64)

        predict_transformed = predict

    monkeypatch.setattr(
        "tpu_pipelines.serving.fleet.versions._default_loader",
        lambda d: T5Loaded(),
    )
    base = tmp_path / "m"
    (base / "1").mkdir(parents=True)
    reg = MetricsRegistry()
    fleet = ServingFleet(
        "m", str(base), replicas=2, max_versions=1,
        model_type="generative", max_batch_size=2, registry=reg,
        supervisor_interval_s=0.05,
    )
    fleet.supervisor.stop()
    try:
        fleet.load_version(str(base / "1"))
        rng = np.random.default_rng(7)
        batch = {
            "inputs": rng.integers(2, 40, size=(2, 5)).astype(np.int32)
        }
        clean = fleet.generate_submit(batch, {"max_new_tokens": 8})
        plan = FaultPlan({REPLICA_KEY: NodeFault(KILL_REPLICA)})
        with plan.activate():
            out = fleet.generate_submit(batch, {"max_new_tokens": 8})
        assert np.array_equal(np.asarray(out), np.asarray(clean))
        assert reg.get(
            "serving_decode_sessions_recovered_total"
        ).get() >= 1
    finally:
        fleet.close()
