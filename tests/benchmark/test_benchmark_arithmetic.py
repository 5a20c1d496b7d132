"""Percentiles, lag, window events, trace reduction and the FLOP and byte
functions, each on hand-made samples."""

import math

import numpy as np
import pytest

from benchmark import stats, trace_reduce, window_events, work


@pytest.mark.parametrize("q,want", [(50, 5), (95, 10), (10, 1), (100, 10)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(list(range(1, 11)), q) == want


def test_percentile_counts_a_failed_request_as_the_worst():
    samples = [1.0] * 18 + [float("inf")] * 2
    assert stats.percentile(samples, 90) == 1.0
    assert math.isinf(stats.percentile(samples, 95))
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_quartile_spread_is_the_contracts():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4) on six values: q1 100.75, q3 104.25
    assert stats.quartile_spread(values) == pytest.approx(3.5 / 102.5)


def test_lag_is_never_negative():
    assert stats.lag_samples([1.0, 2.0, 3.0], [1.5, 1.9, 3.0]) == [
        0.5, 0.0, 0.0]
    with pytest.raises(ValueError):
        stats.lag_samples([1.0], [])


def _window(at, window_s=1.0, steps=8, infeed=0.01, host=0.02):
    # The trainer's estimated split of the device span (device_compute,
    # device_collective) is left out: the reduction may not read it.
    return {"at": at, "window_s": window_s, "window_steps": steps,
            "infeed_wait": infeed, "host": host}


def test_windows_cut_by_either_end_are_dropped():
    events = [_window(10.5), _window(11.5), _window(12.5), _window(13.5)]
    inside = window_events.windows_inside(events, 10.0, 13.0)
    assert [w["at"] for w in inside] == [11.5, 12.5]


def test_window_reduction():
    events = [_window(1.0), _window(2.0, window_s=1.2, host=0.22)]
    out = window_events.reduce_windows(events, batch_size=256, chips=1)
    assert out["steps"] == 16 and out["wall_s"] == pytest.approx(2.2)
    assert out["examples_per_s_per_chip"] == pytest.approx(16 * 256 / 2.2)
    assert out["host_share"] == pytest.approx(100 * 0.24 / 2.2)
    assert out["infeed_wait_share"] == pytest.approx(100 * 0.02 / 2.2)
    assert out["step_ms"] == pytest.approx(1e3 * 0.97 / 8)
    halved = window_events.reduce_windows(events, batch_size=256, chips=4)
    assert halved["examples_per_s_per_chip"] == pytest.approx(
        out["examples_per_s_per_chip"] / 4)
    with pytest.raises(ValueError):
        window_events.reduce_windows([], batch_size=256, chips=1)


EVENTS = [
    ("while", 0.0, 1.0), ("dot", 0.1, 0.2), ("add", 0.5, 0.4),
    ("dot", 1.5, 0.5),
]


def test_busy_union_and_idle_gaps():
    assert trace_reduce.busy_seconds(EVENTS, 0.0, 2.5) == pytest.approx(1.5)
    assert trace_reduce.idle_gaps(EVENTS, 0.0, 2.5) == [
        (1.0, 1.5), (2.0, 2.5)]
    assert trace_reduce.busy_seconds(EVENTS, 0.5, 1.75) == pytest.approx(0.75)


def test_an_enclosing_operation_counts_only_its_own_time():
    own = trace_reduce.self_times(EVENTS)
    assert own == pytest.approx({"while": 0.4, "dot": 0.7, "add": 0.4})
    assert trace_reduce.top_operations(EVENTS, 2)[0] == [
        "dot", pytest.approx(0.7)]


def test_gaps_are_labelled_by_the_hosts_spans():
    gaps = trace_reduce.idle_gaps(EVENTS, 0.0, 2.5)
    labelled = dict(map(tuple, trace_reduce.label_gaps(
        gaps, [("admission", 0.9, 1.2), ("between steps", 2.0, 3.0)])))
    assert labelled["admission"] == pytest.approx(0.2)
    assert labelled["between steps"] == pytest.approx(0.5)
    assert labelled["host, no span"] == pytest.approx(0.3)


def _every_gap_against_every_span(gaps, host_spans, n=10,
                                 unlabelled="host, no span"):
    """``label_gaps`` as it stood until PR 39: the double loop."""
    totals = {}
    for a, b in gaps:
        covered = 0.0
        for label, s, e in host_spans:
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                totals[label] = totals.get(label, 0.0) + overlap
                covered += overlap
        rest = (b - a) - covered
        if rest > 0:
            totals[unlabelled] = totals.get(unlabelled, 0.0) + rest
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[label, secs] for label, secs in ranked[:n]]


@pytest.mark.parametrize("seed", range(8))
def test_gaps_labelled_in_one_pass_read_what_the_double_loop_read(seed):
    """Random gaps against random spans, in no order: spans that overlap
    and nest, spans of no length, spans over several gaps, gaps that no
    span covers and gaps outside every span.  The same totals to the last
    bit, in the same ranking."""
    rng = np.random.default_rng(seed)
    for _ in range(25):
        spans = []
        for _ in range(int(rng.integers(0, 40))):
            start = float(rng.uniform(0, 10))
            length = float(rng.choice([0.0, 0.01, 0.3, 3.0, 8.0])
                           * rng.random())
            spans.append((str(rng.choice(list("abcde"))), start,
                          start + length))
        gaps = []
        for _ in range(int(rng.integers(0, 60))):
            start = float(rng.uniform(-1, 11))
            gaps.append((start, start + float(
                rng.choice([0.001, 0.1, 2.0]) * rng.random())))
        assert trace_reduce.label_gaps(gaps, spans) == \
            _every_gap_against_every_span(gaps, spans)
        assert trace_reduce.label_gaps(gaps, spans, n=2) == \
            _every_gap_against_every_span(gaps, spans, n=2)


def test_a_traced_windows_worth_of_gaps_is_labelled_in_seconds():
    """10**5 gaps against 10**3 spans: every gap against every span is
    10**8 comparisons (a minute of this interpreter); the walk looks at a
    span or two a gap."""
    import time

    spans = [("s%d" % (i % 2), i * 0.003, i * 0.003 + 0.0029)
             for i in range(1000)]
    gaps = [(i * 3e-5, i * 3e-5 + 1e-5) for i in range(100_000)]
    t0 = time.perf_counter()
    labelled = dict(trace_reduce.label_gaps(gaps, spans))
    assert time.perf_counter() - t0 < 20.0
    assert sum(labelled.values()) == pytest.approx(1.0)
    # of every hundred gaps, three lie in the 0.1 ms between two spans
    assert labelled["host, no span"] == pytest.approx(0.03)


def test_reduce_trace_averages_planes_and_shifts_the_clock():
    planes = {
        "/device:TPU:0": {"XLA Ops": EVENTS, "XLA Modules": [("jit_run", 0, 1)]},
        "/device:TPU:1": {"XLA Ops": [("dot", 0.0, 2.0)]},
        "/device:TPU:2": {"Steps": []},
    }
    out = trace_reduce.reduce_trace(
        planes, [("x", 100.0, 103.0)], host_clock_offset=100.0)
    assert out["window_s"] == pytest.approx(2.0)
    assert out["busy_s"] == pytest.approx((1.5 + 2.0) / 2)
    assert out["idle_gaps"] == []       # the busiest plane has no gap
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"/device:TPU:0": {"Steps": []}})


def test_operation_kind_from_a_whole_hlo_line():
    line = ("%convolution_add_fusion.12 = (bf16[256,128,3072]{2,1,0}, "
            "f32[8]) fusion(bf16[2] %x), kind=kOutput")
    assert trace_reduce.op_kind(line) == (
        "convolution_add_fusion bf16[256,128,3072]")
    assert trace_reduce.op_kind("ThunkExecutor::Execute") == (
        "ThunkExecutor::Execute")


def test_encoder_flops_against_a_hand_count():
    # One layer, d 4, ff 8, no biases counted: q, k, v, out 4*16, mlp 2*32.
    matmul = 4 * 16 + 2 * 32
    got = work.encoder_train_flops_per_step(
        matmul_params=matmul, batch=2, seq_len=3, n_layers=1, d_model=4)
    assert got == 6 * matmul * 6 + 12 * 1 * 2 * 3 * 3 * 4


def test_count_params_leaves_out_embedding_tables():
    tree = {"encoder": {"embed": {"embedding": np.zeros((10, 4))},
                        "layer_0": {"mlp": {"wi": {"kernel": np.zeros((4, 8)),
                                                   "bias": np.zeros(8)}}}}}
    assert work.count_params(tree) == {"total": 80, "matmul": 40}


def test_decode_step_bytes_against_a_hand_count():
    kw = dict(n_layers=2, n_heads=2, head_dim=4)
    weights = work.t5_decoder_weight_bytes(
        d_model=8, d_ff=16, vocab_size=10, weight_itemsize=4, **kw)
    per_layer = 4 * 8 * 8 + 2 * 8 * 8 + 2 * 8 * 16
    assert weights == 4 * (2 * per_layer + 10 * 8)
    kv = work.t5_decode_kv_bytes(
        self_positions=5, cross_positions=7, kv_itemsize=2, **kw)
    assert kv == (2 * 2 * 2 * 4 * 2) * 12


def _offered(due, sent, done, n_tokens):
    """A request as the engine driver's generator records it."""
    from types import SimpleNamespace

    from benchmark.drivers.engine import Offered

    o = Offered(SimpleNamespace(prompt=np.ones(4, np.int32), index=0), due)
    o.sent, o.done = sent, done
    o.handle = SimpleNamespace(result=list(range(n_tokens)))
    return o


@pytest.mark.parametrize("profiler_from,lag_ms,samples", [
    (None, 500.0, 3),      # untraced: every request of the window counts
    (12.0, 1.0, 1),        # traced: those the profiler's start held do not
])
def test_requests_alive_under_the_profiler_leave_the_layer_numbers(
        profiler_from, lag_ms, samples):
    from benchmark.drivers.engine import reduce_requests

    offered = [
        _offered(10.0, 10.001, 11.0, 10),    # over before the profiler
        _offered(11.5, 11.5, 13.0, 10),      # finished under it
        _offered(12.1, 12.6, 14.0, 10),      # sent late by its start
    ]
    out = reduce_requests(offered, 10.0, 15.0, (10.0, 0), (15.0, 30),
                          profiler_from=profiler_from)
    assert (out["attempted"], out["failed"]) == (3, 0)
    assert out["per_request_samples"] == samples
    assert out["generator_lag_p95_ms"] == pytest.approx(lag_ms)
    assert out["tokens_per_s"] == pytest.approx(6.0)
    want = 190.0 if profiler_from is None else 100.0
    assert out["ms_per_token_p95"] == pytest.approx(want)


def test_a_cache_entry_without_its_access_time_is_cleared(tmp_path):
    """A run ended between JAX's two writes leaves such an entry, and
    every later write to the directory fails on it."""
    import jax

    from benchmark import harness

    cache = tmp_path / ".cache" / "xla"
    cache.mkdir(parents=True)
    (cache / "jit_whole-1-cache").write_bytes(b"x")
    (cache / "jit_whole-1-atime").write_bytes(b"\0" * 8)
    (cache / "jit_cut_short-2-cache").write_bytes(b"x")
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_compilation_cache_max_size")}
    try:
        harness.configure_jax(str(tmp_path))
    finally:
        for key, value in before.items():
            jax.config.update(key, value)
    assert sorted(p.name for p in cache.iterdir()) == [
        "jit_whole-1-atime", "jit_whole-1-cache"]
