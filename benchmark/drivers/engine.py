"""Driver for cells that serve through ``serving.generative.GenerativeEngine``.

Load is offered from this one thread: a closed loop keeps a fixed number
of callers outstanding, an open loop sends on a schedule drawn from the
seed whether or not earlier requests have finished, and times each
request from when it was due.  The engine's own telemetry object is
extended, not replaced: ``StepRecorder`` keeps what ``DecodeTelemetry``
counts and also keeps each step's clocked time, and wakes the generator
when a generation completes.
"""

from __future__ import annotations

import importlib
import threading
import time
import zlib
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import harness, peaks, stats, traffic as traffic_mod
from benchmark import weights, work
from benchmark.harness import say


# The profiler runs over the end of the window: this long, or half the
# window where that is shorter.
TRACE_S = 3.0


def make_step_recorder(registry):
    from tpu_pipelines.serving.generative import DecodeTelemetry

    class StepRecorder(DecodeTelemetry):
        def __init__(self):
            super().__init__(registry, "0")
            self.steps: List[tuple] = []   # (end, seconds, live, bucket)
            self.completions = 0
            self.wake = threading.Semaphore(0)

        def on_step(self, dt, ewma, live, bucket, pages, active):
            super().on_step(dt, ewma, live, bucket, pages, active)
            self.steps.append((time.perf_counter(), dt, live, bucket))

        def on_done(self, latency_s, n_tokens):
            super().on_done(latency_s, n_tokens)
            self.completions += 1
            self.wake.release()

        def counters(self) -> Dict[str, float]:
            return {"tokens": self._tokens.get(), "steps": self._steps.get()}

    return StepRecorder()


class Offered:
    """One request as the generator saw it."""

    __slots__ = ("request", "due", "sent", "done", "handle", "error")

    def __init__(self, request, due):
        self.request = request
        self.due = due
        self.sent: Optional[float] = None
        self.done: Optional[float] = None
        self.handle = None
        self.error: Optional[BaseException] = None

    @property
    def tokens(self) -> int:
        if self.done is None or self.error is not None:
            return 0
        return int(len(self.handle.result))


def offer_load(engine, recorder, stream, mix, *, seconds: float,
               on_tick=None) -> Dict[str, Any]:
    """Settle, measure for ``seconds``, drain.  Returns every request
    offered and the window's bounds on the host clock."""
    closed = mix["loop"] == "closed"
    settle_s = float(mix["settle_s"])
    t_begin = time.perf_counter()
    t_w0 = t_begin + settle_s
    t_w1 = t_w0 + seconds
    t_give_up = t_w1 + float(mix["drain_s"])
    offered: List[Offered] = []
    outstanding: List[Offered] = []
    seen = 0
    marks: Dict[str, Any] = {}

    def send(item: Offered) -> None:
        item.sent = time.perf_counter()
        try:
            item.handle = engine.submit_nowait(
                item.request.prompt,
                max_new_tokens=item.request.max_new_tokens)
            outstanding.append(item)
        except Exception as e:  # noqa: BLE001 — a refused request fails
            item.error, item.done = e, item.sent
        offered.append(item)

    emitted_done = 0

    def emitted() -> int:
        """Output tokens handed out so far, by finished and live requests
        alike: a live handle's ``tokens`` grow as the engine emits them."""
        return emitted_done + sum(len(o.handle.tokens) for o in outstanding)

    def collect(now: float) -> int:
        nonlocal seen, emitted_done
        found = 0
        for item in list(outstanding):
            h = item.handle
            if h._done.is_set():
                item.done = now
                item.error = h.error
                emitted_done += len(h.tokens)
                outstanding.remove(item)
                found += 1
        seen += found
        return found

    next_due = t_begin
    pending = None
    if closed:
        for _ in range(int(mix["callers"])):
            send(Offered(next(stream), time.perf_counter()))
    else:
        pending = next(stream)
        next_due = t_begin + pending.gap_s

    while True:
        now = time.perf_counter()
        found = collect(now)
        for edge, t_edge in (("w0", t_w0), ("w1", t_w1)):
            if "counters_" + edge not in marks and now >= t_edge:
                marks["counters_" + edge] = recorder.counters()
                marks["emitted_" + edge] = (now, emitted())
        if on_tick is not None:
            on_tick(now, t_w0, t_w1)
        if seen < recorder.completions and not found:
            # A completion was counted whose handle is not set yet: the
            # worker is between the two; give it the interpreter.
            time.sleep(0)
            continue
        sending = now < t_w1
        if closed:
            if sending:
                for _ in range(found):
                    send(Offered(next(stream), now))
        else:
            while sending and next_due <= now:
                send(Offered(pending, next_due))
                pending = next(stream)
                next_due += pending.gap_s
        if not sending and not outstanding:
            break
        if now >= t_give_up:
            break
        waits = [0.05, t_give_up - now]
        if sending:
            waits.append(t_w1 - now)
            if now < t_w0:
                waits.append(t_w0 - now)
            if not closed:
                waits.append(next_due - now)
        recorder.wake.acquire(timeout=max(0.0, min(waits)))
    return {"offered": offered, "t_w0": t_w0, "t_w1": t_w1, **marks}


def reduce_requests(offered: List[Offered], t_w0: float, t_w1: float,
                    emitted_w0, emitted_w1,
                    profiler_from: Optional[float] = None) -> Dict:
    """``profiler_from``: in a traced run, when the profiler started.  Its
    start holds the generator's thread for some tenths of a second, so the
    lag and the per-token times there are taken over the requests that
    were sent, or had finished, before it; ``attempted`` and ``failed``
    count every request due in the window all the same."""
    due_in = [o for o in offered if t_w0 <= o.due < t_w1]
    if not due_in:
        raise ValueError("no request was due inside the measured window")
    before = (lambda t: True) if profiler_from is None else (
        lambda t: t is not None and t < profiler_from)
    per_token = []
    failed = 0
    for o in due_in:
        if o.done is None or o.error is not None or o.tokens == 0:
            failed += 1
            per_token.append(float("inf"))
        elif before(o.done):
            per_token.append(1e3 * (o.done - o.due) / o.tokens)
    completed = [
        o for o in offered
        if o.done is not None and o.error is None and t_w0 <= o.done <= t_w1
    ]
    sent = [o for o in due_in if before(o.sent)]
    lag = stats.lag_samples([o.due for o in sent], [o.sent for o in sent])
    pct = lambda xs, q: stats.percentile(xs, q) if xs else None
    lag_p95 = pct(lag, 95)
    return {
        "attempted": len(due_in),
        "failed": failed,
        "completed_in_window": len(completed),
        "tokens_per_s": (emitted_w1[1] - emitted_w0[1])
        / (emitted_w1[0] - emitted_w0[0]),
        "completed_tokens_per_s": sum(
            o.tokens for o in completed) / (t_w1 - t_w0),
        "requests_per_s": len(completed) / (t_w1 - t_w0),
        "per_request_samples": len(per_token),
        "ms_per_token_p95": pct(per_token, 95),
        "ms_per_token_p50": pct(per_token, 50),
        "generator_lag_p95_ms": None if lag_p95 is None else 1e3 * lag_p95,
        "self_positions_read": sum(
            o.tokens * (o.tokens + 1) // 2 for o in completed),
        "cross_positions_read": sum(
            o.tokens * len(o.request.prompt) for o in completed),
    }


def step_host_spans(steps: List[tuple]):
    spans = []
    last_end = None
    for end, dt, _live, _bucket in steps:
        start = end - dt
        if last_end is not None and start > last_end:
            spans.append((
                "engine: between steps (admission, prefill, retire)",
                last_end, start))
        spans.append(("engine: step dispatched, waiting for its tokens",
                      start, end))
        last_end = end
    return spans


# What the driver hands the program itself; a file may not set them.
DRIVERS_OWN = ("eos_id", "device", "telemetry")


def split_engine_options(options: Dict, make_fns, engine_cls):
    """The engine's geometry and options, from the configuration's
    ``engine`` block with the traffic file's laid over it, split into the
    keywords of the decode-function factory and those of the engine.  A
    key that neither takes, or that the driver sets itself, is an error,
    and so is a value that is not a plain number."""
    import inspect

    takes = lambda fn: {
        name for name, p in inspect.signature(fn).parameters.items()
        if p.kind in (p.KEYWORD_ONLY, p.POSITIONAL_OR_KEYWORD)
        and p.default is not p.empty}
    fns_takes, engine_takes = takes(make_fns), takes(engine_cls.__init__)
    harness.check_option_keys(
        "engine", options, fns_takes | engine_takes, DRIVERS_OWN)
    for_fns, for_engine = {}, {}
    for key, value in options.items():
        if not isinstance(value, (bool, int, float)):
            raise TypeError(
                f"engine option {key!r} must be a number or a truth "
                f"value, not {value!r}")
        (for_fns if key in fns_takes else for_engine)[key] = value
    return for_fns, for_engine


def build(ctx) -> Dict[str, Any]:
    import jax

    from tpu_pipelines.observability.metrics import default_registry
    from tpu_pipelines.serving.generative import GenerativeEngine

    config = ctx.config
    program = importlib.import_module(config["program"]["module"])
    hp = config["hparams"]
    model = getattr(program, config["program"]["build"])(hp)
    make_fns = getattr(program, config["program"]["decode_fns"])
    geometry = {**config["engine"], **ctx.traffic.get("engine", {})}
    say("engine options from the traffic file:",
        ctx.traffic.get("engine", {}))
    for_fns, for_engine = split_engine_options(
        geometry, make_fns, GenerativeEngine)
    sample = {
        "inputs": np.ones((1, geometry["max_input_len"]), np.int32),
        "targets": np.ones((1, 8), np.int32),
    }
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), sample)["params"])
    params = weights.make_weights(shapes, config["weights"], ctx.seed)
    fns = make_fns(
        model, eos_id=int(hp["vocab_size"]),  # outside the vocabulary
        **for_fns)
    recorder = make_step_recorder(default_registry())
    engine = GenerativeEngine(
        fns, params, telemetry=recorder, device=ctx.devices[0],
        **for_engine)
    return {"engine": engine, "recorder": recorder, "params": params,
            "shapes": shapes, "geometry": geometry}


def check_served(ctx, checks, params, sample: List[Offered],
                 geometry: Dict) -> None:
    """Teacher-forced plain pass over each sampled prompt with its served
    tokens: by how much a served token's logit lies below the best."""
    import jax
    import jax.numpy as jnp

    config = ctx.config
    ref = importlib.import_module(
        "benchmark.reference." + config["reference"])
    ref_params = ref.from_served_tree(
        weights.flat_leaves(params), int(config["hparams"]["n_layers"]))
    del params
    max_in = int(geometry["max_input_len"])
    max_out = int(geometry["max_decode_len"])

    def padded(item: Offered):
        prompt = item.request.prompt
        served = np.asarray(item.handle.result, np.int32)
        inp = np.zeros((1, max_in), np.int32)
        inp[0, :len(prompt)] = prompt
        mask = np.zeros((1, max_in), np.int32)
        mask[0, :len(prompt)] = 1
        tg = np.zeros((1, max_out), np.int32)
        tg[0, :len(served)] = served
        return jnp.asarray(inp), jnp.asarray(mask), jnp.asarray(tg), served

    passes = {
        mode: jax.jit(
            lambda p, i, m, t, mode=mode: ref.logits(p, i, m, t, mode)[0])
        for mode in ["f32"] + (
            list(config["check"]["control_modes"]) if ctx.control else [])
    }
    gaps: List[np.ndarray] = []
    low_gaps: Dict[str, List[np.ndarray]] = {
        m: [] for m in passes if m != "f32"}
    for item in sample:
        inp, mask, tg, served = padded(item)
        n = len(served)
        if n != item.request.max_new_tokens or served.min() < 0 \
                or served.max() >= int(config["hparams"]["vocab_size"]):
            raise RuntimeError(
                f"request {item.request.index}: bad token stream")
        logits = passes["f32"](ref_params, inp, mask, tg)[:n]
        g = np.asarray(ref.token_gaps(logits, jnp.asarray(served)))
        gaps.append(g)
        say(f"  request {item.request.index}: prompt "
            f"{len(item.request.prompt)}, served {n} (crc "
            f"{zlib.crc32(served.tobytes())}), widest gap "
            f"{float(g.max())!r}, mean {float(g.mean())!r}")
        for mode in low_gaps:
            low = passes[mode](ref_params, inp, mask, tg)[:n]
            first = jnp.argmax(low, axis=-1)
            low_gaps[mode].append(
                np.asarray(ref.token_gaps(logits, first)))
    limits = config["check"]["limits"]
    allg = np.concatenate(gaps)
    say(f"check: {len(sample)} requests, {allg.size} served tokens, "
        f"{int((allg == 0).sum())} of them the reference's best")
    compare_gaps(checks, "", allg, limits)
    for mode, parts in low_gaps.items():
        control = harness.Checks()
        compare_gaps(control, f"control[{mode}].", np.concatenate(parts),
                     limits)
        say(f"control[{mode}] correct: {control.ok}")


# What ``check.limits`` of a configuration may name: a statistic of the
# gaps of every served token of the sample.
GAP_STATISTICS = {
    "served_token_gap.widest": lambda g: float(g.max()),
    "served_token_gap.mean": lambda g: float(g.mean()),
    "served_token_gap.p99": lambda g: stats.percentile(g.tolist(), 99),
}


def compare_gaps(checks, prefix: str, gaps: np.ndarray,
                 limits: Dict[str, float]) -> None:
    """Hold every statistic that ``limits`` names to its limit, and print
    the others beside them.  A name that is no statistic is an error."""
    unknown = sorted(set(limits) - set(GAP_STATISTICS))
    if unknown or not limits:
        raise KeyError(
            f"check.limits names {unknown}; the statistics of a served "
            f"sample are {sorted(GAP_STATISTICS)}")
    for name, statistic in GAP_STATISTICS.items():
        value = statistic(gaps)
        if name in limits:
            checks.at_most(prefix + name, value, limits[name])
        else:
            say(f"  {prefix}{name}: {value!r} (not compared)")


# The longest request of a sample is looked for among this share of the
# indices offered: requests sent well before the window's end.
WELL_INSIDE = 0.75


def draw_sample(offered: List[Offered], finished: List[Offered], seed: int,
                k: int) -> List[Offered]:
    """``k`` of the ``finished`` requests, by request index: the one that
    asked for the longest answer among the first three quarters of the
    indices offered (the lowest index among equals), then the first
    ``k - 1`` of an order over indices drawn from the seed.  The order
    gives index ``i`` the same place whatever was offered or finished
    behind it, so two runs on one seed sample the same requests unless one
    of them finished a sampled request and the other did not."""
    n = 1 + max(o.request.index for o in offered)
    early = [o for o in finished if o.request.index < WELL_INSIDE * n]
    longest = min(early or finished, key=lambda o: (
        -o.request.max_new_tokens, o.request.index))
    place = np.random.default_rng([int(seed), 77]).random(n)
    rest = sorted((o for o in finished if o is not longest),
                  key=lambda o: place[o.request.index])
    return [longest] + rest[:k - 1]


def run(ctx) -> Dict[str, Any]:
    config, mix = ctx.config, ctx.traffic
    import jax

    stage = lambda what: harness.stage(ctx, what)
    stage("imports done")
    built = build(ctx)
    engine, recorder = built["engine"], built["recorder"]
    geometry = built["geometry"]
    jax.block_until_ready(built["params"])
    stage("model built, weights made")
    engine.warm()
    stage("engine warmed")
    say("after warm-up:")
    harness.memory_peak_bytes(ctx.devices)

    stream = traffic_mod.requests(
        mix, ctx.seed, int(config["hparams"]["vocab_size"]))
    device_trace = harness.DeviceTrace(ctx.out_dir) if ctx.trace else None
    trace_s = min(TRACE_S, ctx.seconds / 2)
    trace_at = {}

    def on_tick(now, t_w0, t_w1):
        if device_trace is None:
            return
        if not device_trace.started and now >= t_w1 - trace_s:
            trace_at["from"] = now
            device_trace.start()
        elif device_trace.started and not device_trace.stopped \
                and now >= t_w1:
            device_trace.stop()
            trace_at["to"] = time.perf_counter()

    try:
        load = offer_load(engine, recorder, stream, mix,
                          seconds=ctx.seconds, on_tick=on_tick)
        compiles_after_warm = int(engine.compiles_after_warm)
    finally:
        if device_trace is not None and device_trace.started \
                and not device_trace.stopped:
            device_trace.stop()
        engine.close()
    peak_bytes = harness.memory_peak_bytes(ctx.devices)
    stage("window closed, requests drained, engine closed")
    t_w0, t_w1 = load["t_w0"], load["t_w1"]
    reduced = reduce_requests(load["offered"], t_w0, t_w1,
                              load["emitted_w0"], load["emitted_w1"],
                              profiler_from=trace_at.get("from"))
    say("requests:", reduced)

    until = trace_at.get("from", t_w1)
    steps = [s for s in recorder.steps if t_w0 <= s[0] <= min(t_w1, until)]
    c0, c1 = load["counters_w0"], load["counters_w1"]
    step_delta = c1["steps"] - c0["steps"]
    facts: Dict[str, Any] = {
        "serve_requests": reduced,
        "serve_steps": {
            "count": len(steps),
            "seconds": sum(s[1] for s in steps),
            "counter_steps": step_delta,
            "counter_tokens": c1["tokens"] - c0["tokens"],
            "max_batch_size": int(geometry["max_batch_size"]),
        },
        "serve_model": {
            **{k: config["hparams"][k] for k in (
                "d_model", "d_ff", "n_layers", "n_heads", "head_dim",
                "vocab_size")},
            "weight_itemsize": 4 if config["weight_dtype"] == "float32" else 2,
            "kv_itemsize": 2 if config["compute_dtype"] == "bfloat16" else 4,
        },
        "peaks": (None if ctx.rehearse
                  else peaks.peaks_for(ctx.devices[0].device_kind)),
    }
    say("steps:", facts["serve_steps"])

    failed = reduced["failed"] + compiles_after_warm
    finished = [
        o for o in load["offered"]
        if o.done is not None and o.error is None and o.done <= t_w1
        and o.tokens > 0
    ]
    checks = harness.Checks()
    del engine, built["engine"]      # the arena goes before the reference
    if finished:
        sample = draw_sample(
            load["offered"], finished, ctx.seed,
            int(config["check"]["sample_requests"]))
        say("check: sampled request indices",
            [o.request.index for o in sample],
            f"of {len(finished)} finished, {len(load['offered'])} offered")
        check_served(ctx, checks, built.pop("params"), sample, geometry)
    stage("served tokens compared with the reference")

    out: Dict[str, Any] = {
        "correct": checks.ok and failed == 0,
        "attempted": reduced["attempted"], "failed": failed,
        "end_to_end": {
            "serve_tokens_per_s": reduced["tokens_per_s"],
            "serve_ms_per_token_p95": reduced["ms_per_token_p95"],
            "setup_s": t_w0 - ctx.t_process_start,
        },
        "facts": facts, "memory_peak_bytes": peak_bytes,
        "checks": checks.rows,
    }
    if device_trace is not None:
        traced_steps = [
            s for s in recorder.steps
            if trace_at["from"] <= s[0] <= trace_at.get("to", t_w1)
        ]
        facts["trace"] = device_trace.reduce(
            step_host_spans(traced_steps), ctx.rehearse)
    return out
