"""The generative engine's own account of its time (ISSUE 25): phase
spans in a ``jax.profiler`` trace, exact per-phase counters, and each
request's timeline on its handle.

All on the stub decode contract of tests/test_generative.py: what is
checked is the scheduler's bookkeeping, not a model.
"""

import glob
import inspect
import re
import time

import numpy as np
import pytest

from test_generative import EOS, VOCAB, _wait_idle, make_stub_fns

pytestmark = pytest.mark.generative


def run_traffic(n_requests=12, seed=7, pause_s=0.0, **engine_kw):
    """A warmed stub engine fed ``n_requests`` seeded requests (three
    distinct prompts, so a prefix cache has something to hit), closed.
    Returns the engine, its private registry, the finished handles and
    the wall time from before the constructor to after ``close``."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    reg = MetricsRegistry()
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(1, VOCAB, size=(int(rng.integers(2, 6)),))
        .astype(np.int32)
        for _ in range(3)
    ]
    engine_kw.setdefault("max_batch_size", 4)
    engine_kw.setdefault("page_size", 2)
    t0 = time.perf_counter()
    engine = GenerativeEngine(
        make_stub_fns(), {}, registry=reg, replica="0", **engine_kw
    )
    try:
        engine.warm()
        handles = []
        for i in range(n_requests):
            handles.append(engine.submit_nowait(
                prompts[i % 3], max_new_tokens=int(rng.integers(1, 12))
            ))
            if pause_s and i % 4 == 3:
                time.sleep(pause_s)     # let the worker go idle in between
        for h in handles:
            h.wait(30.0)
        _wait_idle(engine)      # an EOS ending leaves a step unread
    finally:
        engine.close()
    return engine, reg, handles, time.perf_counter() - t0


def phase_series(reg, family):
    from tpu_pipelines.serving.generative import ENGINE_PHASES

    series = reg.snapshot()[family]["series"]
    assert set(series) <= {("0", p) for p in ENGINE_PHASES}
    return {p: series.get(("0", p), 0.0) for p in ENGINE_PHASES}


def test_phase_seconds_add_up_to_the_workers_lifetime(monkeypatch):
    """Self seconds: a nested phase's time is taken out of its parent's,
    so the seven sums are the worker thread's life, warm-up (idle) and
    all.  That life is timed where it is lived, inside the thread and on
    the clock ``_phase`` reads: the thread's start in the constructor and
    its join in ``close`` belong to the caller, not to a phase."""
    from tpu_pipelines.serving.generative import GenerativeEngine

    run, stamps = GenerativeEngine._run, []

    def stamped_run(self):
        stamps.append(time.perf_counter())
        try:
            run(self)
        finally:
            stamps.append(time.perf_counter())

    monkeypatch.setattr(GenerativeEngine, "_run", stamped_run)
    engine, reg, handles, outside_s = run_traffic(pause_s=0.05)
    assert not engine._worker.is_alive() and len(stamps) == 2
    lifetime_s = stamps[1] - stamps[0]
    seconds = phase_series(reg, "serving_decode_engine_seconds_total")
    assert all(s >= 0.0 for s in seconds.values())
    assert seconds["idle"] > 0.0 and seconds["step"] > 0.0
    assert sum(seconds.values()) == pytest.approx(lifetime_s, rel=0.02)
    # the step's sum is what the EWMA gauge could never give
    assert seconds["step"] <= lifetime_s <= outside_s


@pytest.mark.parametrize("engine_kw", [
    {},
    {"prefix_cache_entries": 4},
    {"prefill_chunk_pages": 1},
    {"page_size": 0},
    {"prefix_cache_entries": 4, "prefill_chunk_pages": 1},
], ids=["plain", "prefix-cache", "chunked", "unpaged", "prefix-and-chunked"])
def test_phase_occurrences_are_what_the_engine_did(engine_kw):
    engine, reg, handles, _ = run_traffic(**engine_kw)
    count = phase_series(reg, "serving_decode_engine_phase_total")
    get = lambda name: reg.get(name).labels("0").get()
    assert count["step"] == get("serving_decode_steps_total")
    assert count["step"] == engine.steps_run
    # every step was dispatched once, behind the one before it or alone
    dispatched = reg.snapshot()[
        "serving_decode_step_dispatch_total"]["series"]
    assert sum(dispatched.values()) == count["step"]
    assert dispatched[("0", "alone")] >= 1
    # an ``emit`` reads every step; one more stands around each read of
    # first tokens that had no step to go behind
    blocking = reg.get("serving_decode_first_token_reads_total").labels(
        "0", "blocking").get()
    assert count["step"] <= count["emit"] <= count["step"] + blocking
    hits = engine._prefix.hits if engine._prefix is not None else 0
    assert count["prefill"] == len(handles) - hits
    if engine._prefix is not None:
        assert hits > 0
        assert hits == get("serving_decode_prefix_hit_total")
    # A sequence with a budget of one token never took a slot; every
    # other one was inserted once and retired once.  (One whose first
    # token is EOS takes a slot too unless a prefix entry already held
    # that token on the host: the prefill's token is not read before
    # the insert.  This traffic has none.)
    assert all(h.result[0] != EOS for h in handles)
    took_a_slot = sum(1 for h in handles if h.max_new_tokens > 1)
    assert 0 < took_a_slot <= len(handles)
    assert count["insert"] == count["retire"] == took_a_slot
    if engine.prefill_chunk_pages:
        # a turn that leaves the head queued for lack of credits counts
        assert count["admit"] >= len(handles)
    else:
        assert count["admit"] == len(handles)
    assert count["idle"] >= 1


@pytest.mark.parametrize("engine_kw", [
    {}, {"prefix_cache_entries": 4},
    {"prefix_cache_entries": 4, "prefill_chunk_pages": 1},
], ids=["plain", "prefix-cache", "prefix-and-chunked"])
def test_timeline_on_the_handle_is_what_the_histograms_observed(engine_kw):
    engine, reg, handles, _ = run_traffic(pause_s=0.02, **engine_kw)
    assert [h.seq_id for h in handles] == list(range(1, len(handles) + 1))
    for h in handles:
        assert h.arrival_s <= h.admitted_s <= h.first_token_s <= h.done_s
    snap = reg.snapshot()
    for family, end in (
        ("serving_decode_queue_wait_seconds", "admitted_s"),
        ("serving_decode_ttft_seconds", "first_token_s"),
    ):
        state = snap[family]["series"][("0",)]
        assert state["count"] == len(handles)
        # admissions are first come, first served: the same order of sums
        assert state["sum"] == pytest.approx(
            sum(getattr(h, end) - h.arrival_s for h in handles), rel=1e-9
        )
    assert (
        'serving_decode_ttft_seconds_count{replica="0"} %d' % len(handles)
        in reg.to_prometheus()
    )


def test_a_handle_failed_in_the_queue_was_never_admitted():
    import threading

    from tpu_pipelines.serving.generative import (
        GenerationEvicted, GenerativeEngine,
    )

    release = threading.Event()
    # The hook runs on the worker before each round's admission.
    engine = GenerativeEngine(
        make_stub_fns(), {}, max_batch_size=1,
        fault_hook=lambda: release.wait(10.0),
    )
    try:
        h = engine.submit_nowait(np.asarray([1, 2], np.int32))
        engine.close(timeout_s=0.05)
    finally:
        release.set()
    with pytest.raises(GenerationEvicted):
        h.wait(1.0)
    assert h.seq_id == 1
    assert h.admitted_s is None and h.first_token_s is None
    assert h.done_s >= h.arrival_s
    engine._worker.join(5.0)
    assert not engine._worker.is_alive()


def test_the_request_trace_join_event_carries_the_request_number():
    from tpu_pipelines.serving.generative import GenerativeEngine

    class Ctx:
        def __init__(self):
            self.events = []

        def span_from_mono(self, name, start_s, **args):
            self.events.append((name, args))

        def instant(self, name, **args):
            self.events.append((name, args))

        def complete_span(self, name, wall_s, start_s, dur_s, **args):
            self.events.append((name, args))

    engine = GenerativeEngine(make_stub_fns(), {}, max_batch_size=2)
    try:
        ctxs = [Ctx(), Ctx()]
        handles = [
            engine.submit_nowait(
                np.asarray([3, 1 + i], np.int32), max_new_tokens=8, ctx=c)
            for i, c in enumerate(ctxs)
        ]
        for h in handles:
            h.wait(30.0)
    finally:
        engine.close()
    for h, c in zip(handles, ctxs):
        joins = [args for name, args in c.events if name == "decode.join"]
        assert len(joins) == (1 if len(h.result) > 1 else 0)
        assert all(args["seq"] == h.seq_id for args in joins)
    assert {h.seq_id for h in handles} == {1, 2}


def engine_events(trace_dir):
    """``{line name: [(name, start_ns, end_ns, stats)]}`` for the
    ``engine.*`` events of the host planes of the newest trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        str(trace_dir) + "/plugins/profile/*/*.xplane.pb"))[-1]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                 dict(ev.stats))
                for ev in line.events if ev.name.startswith("engine.")
            ]
            if found:
                lines[f"{plane.name}/{line.name}"] = found
    return lines


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_spans_in_a_profiler_session_and_the_same_counters_without(tmp_path):
    import jax

    # The schedule of steps depends on thread timing; what each request
    # needs of the engine does not.
    steady = ("admit", "prefill", "insert", "retire")
    _, reg_off, handles_off, _ = run_traffic()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _, reg_on, handles, _ = run_traffic()
    finally:
        jax.profiler.stop_trace()
    for family in ("serving_decode_engine_phase_total",
                   "serving_decode_engine_seconds_total"):
        off, on = phase_series(reg_off, family), phase_series(reg_on, family)
        assert set(off) == set(on)
    off = phase_series(reg_off, "serving_decode_engine_phase_total")
    on = phase_series(reg_on, "serving_decode_engine_phase_total")
    assert {p: off[p] for p in steady} == {p: on[p] for p in steady}
    assert [list(h.result) for h in handles_off] == [
        list(h.result) for h in handles]

    lines = engine_events(tmp_path)
    assert len(lines) == 1, sorted(lines)     # the worker's line alone
    (events,) = lines.values()
    by_name = {}
    for name, start, end, stats in events:
        by_name.setdefault(name, []).append((start, end, stats))
    assert set(by_name) == {
        "engine.idle", "engine.admit", "engine.prefill", "engine.insert",
        "engine.step", "engine.step.wait", "engine.emit", "engine.retire",
        "engine.prefill.wait",
    }
    for name in ("admit", "prefill", "insert", "retire", "step", "emit"):
        assert len(by_name["engine." + name]) == on[name], name

    def within(span, parents):
        return any(
            p0 <= span[0] and span[1] <= p1
            for p0, p1, _ in by_name["engine." + parents])

    def inside(child, parent, same_seq):
        for c0, c1, c_stats in by_name[child]:
            assert any(
                p0 <= c0 and c1 <= p1
                and (not same_seq or p_stats["seq"] == c_stats["seq"])
                for p0, p1, p_stats in by_name[parent]
            ), (child, c_stats)

    inside("engine.prefill", "engine.admit", True)
    inside("engine.insert", "engine.admit", True)
    # A row leaves by count inside the round that dispatched its last
    # step, or inside the ``emit`` that read its EOS.
    assert all(
        within(r, "step") or within(r, "emit")
        for r in by_name["engine.retire"])
    # A step is read inside an ``emit``, which says which step it reads;
    # every step dispatched was read, once, in the order of dispatch,
    # and but for a run's last steps with the next one dispatched first.
    inside("engine.step.wait", "engine.emit", False)
    step_of = lambda evs: [stats["step"] for _, _, stats in sorted(evs)]
    steps = step_of(by_name["engine.step"])
    assert steps == list(range(1, len(steps) + 1))
    assert step_of(by_name["engine.step.wait"]) == steps
    assert step_of(
        [e for e in by_name["engine.emit"] if e[2]["live"]]) == steps
    dispatched_at = {
        stats["step"]: start for start, _, stats in by_name["engine.step"]}
    ahead = [
        stats["step"] for start, _, stats in by_name["engine.step.wait"]
        if dispatched_at.get(stats["step"] + 1, float("inf")) < start]
    queued = [stats["queued"] for _, _, stats in sorted(
        by_name["engine.step"])]
    assert [k + 1 for k in ahead] == [
        k for k, q in zip(steps, queued) if q == "behind_step"]
    assert ahead and queued[0] == "alone"
    # A first token is read with a step queued behind its prefill, or,
    # where nothing was there to step, in an ``emit`` of its own; one
    # read per admission that no prefix entry answered.
    assert all(
        within(w, "step") or within(w, "emit")
        for w in by_name["engine.prefill.wait"])
    reads = reg_on.snapshot()[
        "serving_decode_first_token_reads_total"]["series"]
    assert len(by_name["engine.prefill.wait"]) == (
        reads.get(("0", "behind_step"), 0) + reads.get(("0", "blocking"), 0)
    ) == len(handles)
    # a request's wait begins after its own prefill was dispatched
    dispatched = {
        stats["seq"]: end for _, end, stats in by_name["engine.prefill"]}
    assert all(
        dispatched[stats["seq"]] <= start
        for start, _, stats in by_name["engine.prefill.wait"])
    # top-level phases follow one another on the one thread; an
    # admission turn or an ``emit`` is top-level only while no row is
    # live, and otherwise lies behind the dispatch of a step, inside
    # its span
    behind = [
        x for n in ("admit", "emit") for x in by_name["engine." + n]
        if within(x, "step")]
    for n in ("admit", "emit"):
        mine = [x for x in by_name["engine." + n] if x in behind]
        assert mine and len(mine) < len(by_name["engine." + n])
    top = sorted(
        (s, e) for n in ("idle", "admit", "step", "emit")
        for s, e, *_ in by_name["engine." + n]
        if (s, e) not in {(a[0], a[1]) for a in behind})
    assert all(a[1] <= b[0] for a, b in zip(top, top[1:]))

    # one identifier per request, shared by its spans
    for h in handles:
        spans = {
            name for name, evs in by_name.items()
            for _, _, stats in evs if stats.get("seq") == h.seq_id
        }
        want = {"engine.admit"}
        if len(h.result) > 1:
            want |= {"engine.insert", "engine.retire"}
        want |= {"engine.prefill.wait"}
        assert want <= spans <= want | {"engine.prefill"}, (h.seq_id, spans)
    assert all(
        set(stats) == {"step", "live", "b", "kv", "queued"}
        and 1 <= stats["live"] <= stats["b"]
        for _, _, stats in by_name["engine.step"])
    assert all(
        stats["prompt_tokens"] in (2, 3, 4, 5)
        for _, _, stats in by_name["engine.prefill"])


def test_program_names_are_the_lowered_programs_names():
    from tpu_pipelines.serving import generative
    from tpu_pipelines.serving.generative import GenerativeEngine

    engine = GenerativeEngine(make_stub_fns(), {}, max_batch_size=2)
    try:
        engine.warm()
        # Every jitted program the engine holds, found and not listed:
        # warm() built and ran each, and they are PROGRAM_NAMES exactly.
        built = [f for f in vars(engine).values() if hasattr(f, "lower")]
        built += engine._step_fns.values()
        assert all(f._cache_size() >= 1 for f in built)
        assert sorted({"jit_" + f.__name__ for f in built}) \
            == sorted(generative.PROGRAM_NAMES)
        zin = np.zeros((1, engine.max_input_len), np.int32)
        cache1, enc1, tok0 = engine._jit_prefill(engine.params, zin, zin)
        slot = np.int32(0)
        b, kv = engine.batch_buckets[0], engine.kv_buckets[0]
        lowered = {
            "prefill": engine._jit_prefill.lower(engine.params, zin, zin),
            "insert": engine._jit_insert.lower(
                engine._arena, cache1, enc1, zin, np.int32(1), slot),
            "move": engine._jit_move.lower(engine._arena, slot, slot),
            "clear": engine._jit_clear.lower(engine._arena, slot),
            "step": engine._step_for(b, kv).lower(
                engine.params, engine._arena),
        }
    finally:
        engine.close()
    names = {
        what: re.search(r"module @(\w+)", low.as_text()).group(1)
        for what, low in lowered.items()
    }
    assert names["step"] == "jit_run"   # the benchmark finds it by this name
    assert tuple(names.values()) == generative.PROGRAM_NAMES

    def renamed(x):
        return x

    with pytest.raises(ValueError, match="PROGRAM_NAMES"):
        generative._jit_program(renamed)


def test_monotonic_and_perf_counter_are_one_clock():
    """The handle's timestamps are ``time.monotonic()``; the benchmark's
    ``due`` and ``sent`` are ``time.perf_counter()``.  They may be
    subtracted from one another only because both read CLOCK_MONOTONIC."""
    mono = time.get_clock_info("monotonic")
    perf = time.get_clock_info("perf_counter")
    assert mono.implementation == perf.implementation
    assert "CLOCK_MONOTONIC" in mono.implementation
    a, b, c = time.monotonic(), time.perf_counter(), time.monotonic()
    assert a <= b <= c


def test_old_hooks_keep_their_signatures_and_new_ones_are_noops_alone():
    from tpu_pipelines.serving.generative import DecodeTelemetry

    params = lambda fn: list(inspect.signature(fn).parameters)
    assert params(DecodeTelemetry.on_step) == [
        "self", "dt", "ewma", "live", "bucket", "pages", "active"]
    assert params(DecodeTelemetry.on_done) == [
        "self", "latency_s", "n_tokens"]
    assert params(DecodeTelemetry.on_token) == ["self"]
    bare = DecodeTelemetry()            # no registry: nothing to count into
    bare.on_phase("step", 0.01)
    bare.on_admitted(0.0)
    bare.on_first_token(0.0)
