"""Benchmark: flagship BERT-base fine-tune throughput + MFU on one chip.

Run by the driver on real TPU hardware at the end of each round; prints ONE
JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}.

Survivability contract (this file must never produce nothing):
  - each workload runs inside its own try/except with retries on transient
    runtime errors (utils/transient.py); a leg that still fails is recorded
    in ``errors`` and the process exits non-zero after the last flush;
  - the cheap taxi workload runs FIRST and the flagship BERT measurement
    SECOND, so a later crash can never zero the round's headline evidence;
  - after EVERY workload a COMPACT headline-only JSON line (<= ~600 bytes)
    is flushed to stdout and the FULL cumulative report to
    BENCH_PARTIAL.json, so even a SIGKILL leaves the last flush behind.
    The split matters: the driver captures only the last 2,000 bytes of
    stdout and JSON-parses the final line — rounds 1-4 lost their headline
    because the full report (3.7 KB by round 4) overflowed that tail;
  - a global wall-clock budget (``BENCH_BUDGET_S``, default 900) is checked
    between workloads: legs whose estimated cost exceeds the remaining
    budget are recorded as ``{"skipped_budget": true}`` instead of risking
    the driver's timeout — partial evidence beats rc=124 with nothing;
  - SIGTERM (what ``timeout`` sends first) triggers an immediate flush of
    whatever has been measured, then exit;
  - mid-run orbax checkpointing is disabled in the e2e legs
    (TPP_DISABLE_MID_CHECKPOINT=1): blocking save waits serialize against
    µs-scale train steps and burn the budget without changing the result.

Primary metric (BASELINE.json north star, "TFX Trainer examples/sec/chip"):
steady-state examples/sec/chip of the framework train loop on BERT-base
(seq 128 classification fine-tune, the reference's configs[3] workload).
The headline number is **sync-anchored**: every ``anchor_every`` steps the
loop forces a device-to-host read of that step's loss (a transfer of the
step's output cannot complete before the step executes), and throughput is
the median over those anchored windows.  Host-clock-only figures (batch-fetch
windows, whole-run average) are reported as secondaries: JAX dispatch is
asynchronous, so an un-anchored host clock can run ahead of the device.

``vs_baseline`` is the ratio against a published-band A100 reference for the
same workload (north star ">=90% of A100 examples/sec" => vs_baseline >= 0.9):
A100 BERT-base fine-tune at seq 128 with mixed precision lands in the 1-2k
examples/sec band (NVIDIA DeepLearningExamples BERT-base numbers); we take
1500 ex/s as the reference point.

Also reported:
  - ``mfu``: model-flops utilization — analytic train FLOPs per step
    (6 * matmul_params * tokens, plus the attention score/value matmuls the
    6NT rule excludes) divided by elapsed * chip peak bf16 FLOPs.  The
    peak comes from the device-kind table (``chip.peak_source``); a
    device that is not in it is an error, never an assumed v5e.
  - ``taxi``: the cheap secondary workload.
  - ``flash_probe``: flash vs dense attention fwd+bwd across a seq-length
    sweep — tuned-vs-default-vs-dense step times, XLA temp-memory (the
    O(block^2) claim), the measured flash/dense crossover persisted into
    the autotune table (ops/autotune.py), and the empty-cache cache-only
    cold-run proof.

Env: BENCH_SMOKE=1 shrinks the model/steps for a CPU smoke test of the
bench code path itself (numbers meaningless).
"""

import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PARTIAL_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_PARTIAL.json"
)

A100_BERT_BASE_EX_PER_SEC = 1500.0
# The comparison config behind the 1500 figure, pinned so vs_baseline is
# auditable (VERDICT r3 weak#5): which workload, on what, from where.
A100_REFERENCE = {
    "ex_per_sec": A100_BERT_BASE_EX_PER_SEC,
    "model": "BERT-base (110M params)",
    "task": "sequence classification fine-tune",
    "seq_len": 128,
    "batch_size": "per-GPU 32-128 (band, not a single config)",
    "precision": "mixed precision (TF32/FP16), A100-SXM 80GB",
    "source": (
        "NVIDIA DeepLearningExamples BERT fine-tuning published numbers: "
        "single-A100 BERT-base seq-128 lands in the 1-2k examples/sec band; "
        "pinned at 1500 as the midpoint"
    ),
    "provenance": (
        "builder-pinned from public recollection; this environment has no "
        "network access to re-verify (SURVEY.md section 0), so the +-30% "
        "band is the honest uncertainty on vs_baseline"
    ),
}

# Peak bf16 matmul FLOPs per chip by device kind (dense, no sparsity).
PEAK_BF16_FLOPS = [
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]


def chip_info() -> dict:
    """Device kind + its peak from the table, so MFU's denominator is
    auditable.  An explicit ``TPP_PEAK_FLOPS`` (the train loop's own
    override) is taken as stated and recorded as such; a device that is in
    neither raises — an assumed peak would publish a made-up MFU."""
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind
    info = {"device_kind": kind, "platform": dev.platform}
    env = os.environ.get("TPP_PEAK_FLOPS", "").strip()
    if env:
        return {**info, "peak_bf16_flops": float(env),
                "peak_source": "env TPP_PEAK_FLOPS"}
    for key, peak in PEAK_BF16_FLOPS:
        if key in kind.lower():
            return {**info, "peak_bf16_flops": peak,
                    "peak_source": "PEAK_BF16_FLOPS table"}
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {kind!r} "
        f"(platform {dev.platform!r}): add it to PEAK_BF16_FLOPS with its "
        "source, or state one with TPP_PEAK_FLOPS"
    )


def _count_params(params) -> dict:
    """Total and matmul-participating (non-embedding-table) param counts."""
    import jax

    total = 0
    embed = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        keys = "/".join(str(getattr(k, "key", k)) for k in path)
        if "embed" in keys and keys.endswith("embedding"):
            embed += n
    return {"total": total, "matmul": total - embed}


def _windowed_eps(fetch_t, batch: int, window: int = 8):
    """Median examples/sec over sliding ``window``-step spans of host batch
    fetches — a host-clock-only secondary (can overstate if the host runs
    ahead of the device; the anchored number is primary).  The first two
    fetches bracket compile and are skipped."""
    t = fetch_t[2:]
    if len(t) <= window:
        return None
    spans = [t[i + window] - t[i] for i in range(len(t) - window)]
    spans.sort()
    med = spans[len(spans) // 2]
    return round(window * batch / med, 2) if med > 0 else None


# Flagship non-smoke batch size; the goodput leg's step-sizing math reads
# the SAME constant, so the two can't drift.
BERT_BENCH_BATCH = 256


def bench_bert(
    smoke: bool,
    steps_override: int = 0,
    cost_analysis: bool = True,
) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_pipelines.models.bert import DEFAULT_HPARAMS, build_bert_model
    from tpu_pipelines.trainer import TrainLoopConfig, train_loop

    seq_len = 128
    batch = 8 if smoke else BERT_BENCH_BATCH
    steps = steps_override or (6 if smoke else 64)
    hp = {
        **DEFAULT_HPARAMS,
        "max_len": seq_len,
        "attn_impl": "auto",
        "num_classes": 2,
    }
    if smoke:
        hp.update({"d_model": 64, "n_layers": 2, "n_heads": 4, "d_ff": 128,
                   "vocab_size": 512})
    model = build_bert_model(hp)

    rng = np.random.default_rng(0)
    ids = rng.integers(4, hp["vocab_size"], size=(batch, seq_len), dtype=np.int64)
    data = {
        "input_ids": ids.astype(np.int32),
        "attention_mask": np.ones((batch, seq_len), np.int32),
        "label": (ids[:, 0] % 2).astype(np.int32),
    }

    fetch_t = []

    def batches():
        while True:
            fetch_t.append(time.perf_counter())
            yield data

    def features(b):
        return {k: v for k, v in b.items() if k != "label"}

    def loss_fn(params, b, step_rng):
        logits = model.apply(
            {"params": params}, features(b),
            deterministic=False, rngs={"dropout": step_rng},
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(b["label"], jnp.int32)
        ).mean()
        return loss, {}

    def init_fn(init_rng, b):
        return model.init(init_rng, features(b))["params"]

    params, result = train_loop(
        loss_fn=loss_fn,
        init_params_fn=init_fn,
        optimizer=optax.adamw(2e-5),
        train_iter=batches(),
        config=TrainLoopConfig(
            train_steps=steps, batch_size=batch, log_every=0,
            anchor_every=2 if smoke else 8,
            collect_cost_analysis=cost_analysis,
        ),
    )

    # Host-loop-tax datapoint (ISSUE 8): the same fine-tune through the
    # windowed device-resident path at the bench log window (log_every=8,
    # so {1, 8} covers window_steps ∈ {1, 8, log_every}).  BERT's ~ms-scale
    # step is device-bound, so the win here is expected to be small —
    # taxi_window is the µs-scale leg where the tax dominates.  Skipped for
    # steps_override callers (the goodput leg must not pay the extra
    # compile).
    window_sweep = None
    w_log = 2 if smoke else 8
    if not steps_override:
        _, wres = train_loop(
            loss_fn=loss_fn,
            init_params_fn=init_fn,
            optimizer=optax.adamw(2e-5),
            train_iter=batches(),
            config=TrainLoopConfig(
                train_steps=steps, batch_size=batch, log_every=0,
                window_steps=w_log,
            ),
        )
        window_sweep = {
            str(w_log): (
                wres.anchored_examples_per_sec_per_chip
                or wres.examples_per_sec_per_chip
            ),
        }

    counts = _count_params(params)
    tokens_per_step = batch * seq_len
    # 6NT for the weight matmuls (fwd 2NT + bwd 4NT), plus the attention
    # score/value einsums (QK^T and PV: 4*L*d_model FLOPs per token fwd,
    # x3 with backward) which 6NT does not cover.
    flops_per_step = (
        6 * counts["matmul"] * tokens_per_step
        + 12 * int(hp["n_layers"]) * batch * seq_len * seq_len * int(hp["d_model"])
    )
    eps_avg = result.examples_per_sec_per_chip
    eps_anchored = result.anchored_examples_per_sec_per_chip
    eps_fetch = _windowed_eps(fetch_t, batch)
    eps = eps_anchored or eps_fetch or eps_avg
    steps_per_sec = eps / batch if batch else 0.0
    peak = chip_info()["peak_bf16_flops"]
    mfu = flops_per_step * steps_per_sec / peak
    # XLA's own FLOP count for the compiled step — the cross-check that
    # makes the analytic numerator falsifiable (VERDICT r4 weak#3).  The
    # two counts differ in kind: the analytic one is model FLOPs (the MFU
    # definition — useful work only), XLA's counts every op in the
    # executable including dropout masks, layernorm and optimizer update,
    # so mfu_xla >= mfu is the expected direction; mfu far ABOVE mfu_xla
    # would mean the analytic numerator over-counts.
    xla_flops = result.cost_analysis_flops_per_step
    mfu_xla = (
        round(xla_flops * steps_per_sec / peak, 4) if xla_flops else None
    )
    out = {
        "examples_per_sec_per_chip": eps,
        "throughput_source": (
            "sync_anchored" if eps_anchored
            else ("host_fetch_window" if eps_fetch else "wholerun")
        ),
        "examples_per_sec_per_chip_anchored": eps_anchored,
        "anchor_windows": result.anchor_windows,
        "examples_per_sec_per_chip_hostfetch": eps_fetch,
        "examples_per_sec_per_chip_wholerun": eps_avg,
        "mfu": round(mfu, 4),
        "mfu_xla": mfu_xla,
        "flops_per_step_analytic": flops_per_step,
        "flops_per_step_xla": xla_flops,
        "cost_analysis_source": result.cost_analysis_source,
        "params_total": counts["total"],
        "params_matmul": counts["matmul"],
        "batch_size": batch,
        "seq_len": seq_len,
        "steps_timed": result.steps_completed - 1,  # step 1 absorbs compile
        # Strict goodput counts one-time compile as badput, so a 64-step
        # bench reads ~0.07; the post-compile figure is the steady state a
        # long run converges to (VERDICT r3 weak#7).
        "goodput": result.goodput,
        "goodput_post_compile": result.goodput_post_compile,
        "attn_impl": hp["attn_impl"],
    }
    if window_sweep is not None:
        window_sweep = {"1": eps, **window_sweep}
        out["window_sweep"] = window_sweep
        out["window_steps_log_every"] = w_log
        out["window_speedup"] = (
            round(window_sweep[str(w_log)] / eps, 4) if eps else None
        )
    return out


def _taxi_rows(n: int) -> dict:
    """Synthetic rows at the taxi transform's output schema (one array per
    feature, ``n`` rows) — shared by the host-fed and device-resident legs."""
    rng = np.random.default_rng(0)
    return {
        "miles_z": rng.normal(size=n).astype(np.float32),
        "fare_01": rng.random(size=n).astype(np.float32),
        "log_fare_z": rng.normal(size=n).astype(np.float32),
        "tip_ratio": rng.random(size=n).astype(np.float32),
        "hour_bucket": rng.integers(0, 4, size=n).astype(np.int32),
        "company_id": rng.integers(0, 6, size=n).astype(np.int32),
        "payment_onehot": np.eye(2, dtype=np.float32)[rng.integers(0, 2, size=n)],
        "is_cash": rng.integers(0, 2, size=n).astype(np.float32),
        "label_big_tip": rng.integers(0, 2, size=n).astype(np.float32),
    }


def bench_bert_goodput(
    smoke: bool,
    budget_s: float = 0.0,
    eps_hint: float = 0.0,
) -> dict:
    """Converged strict goodput: the longest BERT leg the budget allows.

    The 64-step flagship leg reads strict goodput ~0.09 because one-time
    compile dominates a 10-second run.  Strict goodput converges as
    steps/(compile + steps): with ~34 s of init+compile, ~600 steps
    (~98 s) read 0.74 (round-5 measurement) and ~1,800 steps (~295 s)
    cross 0.9.  Step pace varies run to run, so the step count ADAPTS:
    from the flagship leg's measured examples/sec and the remaining
    budget (minus a 90 s init/compile/margin reserve), capped at 1,800 —
    the leg runs whenever its budget floor is met and converges as far as
    the round's budget actually permits, instead of gambling a fixed size
    against a slow day.  With no throughput hint (flagship leg failed
    or skipped) it falls back to the 600-step size measured to fit any
    budget that admits the leg at all.  goodput_post_compile isolates the
    steady state (~0.98 at every scale)."""
    if budget_s and eps_hint:
        steps = int(
            max(64, min(1800, (budget_s - 90) * eps_hint / BERT_BENCH_BATCH))
        )
    else:
        steps = 600
    out = bench_bert(
        smoke, steps_override=4 if smoke else steps, cost_analysis=False,
    )
    keep = (
        "goodput", "goodput_post_compile", "steps_timed",
        "examples_per_sec_per_chip", "batch_size",
    )
    return {k: out[k] for k in keep if k in out}


def bench_taxi(smoke: bool) -> dict:
    import jax.numpy as jnp
    import optax

    from tpu_pipelines.models.taxi import DEFAULT_HPARAMS, build_taxi_model
    from tpu_pipelines.trainer import TrainLoopConfig, train_loop

    batch = 256 if smoke else 8192
    steps = 6 if smoke else 60
    n = batch * 8
    data = _taxi_rows(n)

    fetch_t = []

    def batches():
        i = 0
        while True:
            fetch_t.append(time.perf_counter())
            rows = np.arange(i, i + batch) % n
            yield {k: v[rows] for k, v in data.items()}
            i = (i + batch) % n

    model = build_taxi_model(
        {**DEFAULT_HPARAMS, "hidden_dims": [256, 128, 64]}
    )

    def loss_fn(params, b, _rng):
        logits = model.apply({"params": params}, b)
        labels = jnp.asarray(b["label_big_tip"], jnp.float32)
        return optax.sigmoid_binary_cross_entropy(logits, labels).mean(), {}

    _, result = train_loop(
        loss_fn=loss_fn,
        init_params_fn=lambda r, b: model.init(r, b)["params"],
        optimizer=optax.adam(1e-3),
        train_iter=batches(),
        config=TrainLoopConfig(
            train_steps=steps, batch_size=batch, log_every=0,
            anchor_every=2 if smoke else 8,
        ),
    )
    eps_anchored = result.anchored_examples_per_sec_per_chip
    eps_fetch = _windowed_eps(fetch_t, batch, window=16)
    eps = eps_anchored or eps_fetch or result.examples_per_sec_per_chip
    out = {
        "examples_per_sec_per_chip": eps,
        "throughput_source": (
            "sync_anchored" if eps_anchored
            else ("host_fetch_window" if eps_fetch else "wholerun")
        ),
        "examples_per_sec_per_chip_anchored": eps_anchored,
        "anchor_windows": result.anchor_windows,
        "examples_per_sec_per_chip_hostfetch": eps_fetch,
        "examples_per_sec_per_chip_wholerun": (
            result.examples_per_sec_per_chip
        ),
    }
    return out


def bench_taxi_device(smoke: bool) -> dict:
    """Chip-bound taxi throughput: device-resident input, loop on device.

    A µs-scale host-fed step is bound by per-step dispatch and transfer,
    so it measures the host, not the chip.  This leg measures the CHIP:
    the batch is staged on device once, N optimizer steps run inside ONE
    jitted ``lax.fori_loop`` dispatch, and the per-step time is taken from
    the DIFFERENCE between an n2-step and an n1-step call — the per-call
    dispatch constant cancels exactly.  Repeats; the relative spread is
    recorded.
    """
    import jax.numpy as jnp
    import optax

    from tpu_pipelines.models.taxi import DEFAULT_HPARAMS, build_taxi_model

    model = build_taxi_model(
        {**DEFAULT_HPARAMS, "hidden_dims": [256, 128, 64]}
    )

    def loss(params, b):
        logits = model.apply({"params": params}, b)
        labels = jnp.asarray(b["label_big_tip"], jnp.float32)
        return optax.sigmoid_binary_cross_entropy(logits, labels).mean()

    batch = 256 if smoke else 8192
    return _device_resident_eps(
        loss=loss,
        init_params=lambda rng, b: model.init(rng, b)["params"],
        batch_data=_taxi_rows(batch),
        batch=batch,
        optimizer=optax.adam(1e-3),
        # Long loops on purpose: a taxi step is µs-scale, so the n2-n1
        # difference must be hundreds of ms of device time or per-call
        # dispatch variance dominates the subtraction.
        n1=3 if smoke else 500,
        n2=9 if smoke else 2500,
        repeats=2 if smoke else 5,
    )


def bench_taxi_window(smoke: bool) -> dict:
    """Host-loop-tax closure: the REAL train_loop pipeline path (host
    batches in, telemetry on, checkpoints possible) swept over
    ``TrainLoopConfig.window_steps`` ∈ {1, 8, log_every}.

    On µs-scale steps the per-step train_loop path sits far below the
    device-resident fori_loop — a gap that is pure host orchestration
    (its size on the current machine: not measured).  The windowed loop
    dispatches the whole
    log_every window as ONE compiled scan over a device-staged batch
    stack, so this leg measures how much of that gap the pipeline path
    now recovers; ``taxi_device`` is the published ceiling and
    ``gap_to_device_ceiling`` (attached in main()) is the ratio to chase
    toward 1.0 in every future BENCH_*.json.
    """
    import jax.numpy as jnp
    import optax

    from tpu_pipelines.models.taxi import DEFAULT_HPARAMS, build_taxi_model
    from tpu_pipelines.trainer import TrainLoopConfig, train_loop

    batch = 256 if smoke else 8192
    steps = 6 if smoke else 240
    log_window = 3 if smoke else 60
    windows = [1, 2, log_window] if smoke else [1, 8, log_window]
    n = batch * 8
    data = _taxi_rows(n)
    model = build_taxi_model(
        {**DEFAULT_HPARAMS, "hidden_dims": [256, 128, 64]}
    )

    def loss_fn(params, b, _rng):
        logits = model.apply({"params": params}, b)
        labels = jnp.asarray(b["label_big_tip"], jnp.float32)
        return optax.sigmoid_binary_cross_entropy(logits, labels).mean(), {}

    def batches():
        i = 0
        while True:
            rows = np.arange(i, i + batch) % n
            yield {k: v[rows] for k, v in data.items()}
            i = (i + batch) % n

    sweep = {}
    for w in windows:
        _, result = train_loop(
            loss_fn=loss_fn,
            init_params_fn=lambda r, b: model.init(r, b)["params"],
            optimizer=optax.adam(1e-3),
            train_iter=batches(),
            config=TrainLoopConfig(
                train_steps=steps, batch_size=batch, log_every=0,
                window_steps=w,
                # Windowed runs anchor at every window fetch (a forced
                # device read); the per-step run keeps the taxi leg's
                # explicit anchors so both are sync-anchored figures.
                anchor_every=(2 if smoke else 8) if w == 1 else 0,
            ),
        )
        sweep[str(w)] = (
            result.anchored_examples_per_sec_per_chip
            or result.examples_per_sec_per_chip
        )
    base = sweep[str(windows[0])]
    best = max(windows, key=lambda w: sweep[str(w)] or 0.0)
    # The telemetry-plane acceptance drill rides the same model/batches
    # at the log_every window: 3 windows (first absorbs compile, the
    # rest are attributed + steady-state).
    telemetry = _train_window_telemetry_drill(
        loss_fn, lambda r, b: model.init(r, b)["params"], batches,
        batch, steps=3 * log_window, window_steps=log_window,
    )
    return {
        "examples_per_sec_per_chip": sweep[str(best)],
        "window_sweep": sweep,
        "window_steps_swept": windows,
        "window_steps_log_every": log_window,
        "best_window_steps": best,
        "window_speedup": round(sweep[str(best)] / base, 4) if base else None,
        "batch_size": batch,
        "steps_per_run": steps,
        "train_telemetry": telemetry,
        "method": "train_loop_pipeline_path_window_sweep",
    }


def _train_window_telemetry_drill(
    loss_fn, init_params_fn, batches_fn, batch: int, steps: int,
    window_steps: int, mesh=None, dp_kwargs=None,
) -> dict:
    """ISSUE 19 acceptance drill: ONE windowed run with the whole
    training-telemetry plane on — federation spool + durable snapshot
    ring + a live federated ``/metrics`` endpoint — judged from the
    scrape, the RunTrace, and the ring, not from in-process state.

    Green contract: the scraped four-phase attribution sums to the
    trace-recorded window wall-clock within 5% (two independent sinks —
    the registry counters vs the ``window_breakdown`` instants),
    compiles-after-warm == 0 at steady state (every window compiles the
    same scan), the scrape is the MERGED federated endpoint, and the run
    leaves a replayable snapshot ring whose headline feeds
    ``trace diff`` without tripping its own regression flags.
    """
    import shutil
    import tempfile
    import urllib.request

    import optax

    from tpu_pipelines.observability import (
        TraceRecorder,
        activate,
        read_events,
    )
    from tpu_pipelines.observability import federation as fed
    from tpu_pipelines.observability.export import diff_metrics
    from tpu_pipelines.observability.metrics import (
        default_registry,
        start_http_server,
    )
    from tpu_pipelines.observability.metrics_history import MetricsHistory
    from tpu_pipelines.trainer import TrainLoopConfig, train_loop

    root = tempfile.mkdtemp(prefix="tpp-telemetry-")
    run_id = "telemetry-drill"
    saved = {
        k: os.environ.get(k)
        for k in (fed.ENV_FEDERATION_DIR, "TPP_METRICS_HISTORY")
    }
    os.environ[fed.ENV_FEDERATION_DIR] = os.path.join(root, "spool")
    os.environ["TPP_METRICS_HISTORY"] = "1"

    phases = ("infeed_wait", "device_compute", "device_collective", "host")
    reg = default_registry()
    c_phase = reg.counter("train_window_time_seconds", labels=("phase",))
    base = {ph: c_phase.labels(ph).get() for ph in phases}
    base_compiles = reg.counter("train_compiles_after_warm_total").get()

    server = start_http_server(fed.FederatedRegistry(reg), port=0)
    rec = TraceRecorder(os.path.join(root, ".runs", run_id), run_id)
    try:
        t0 = time.perf_counter()
        with activate(rec):
            _, result = train_loop(
                loss_fn=loss_fn,
                init_params_fn=init_params_fn,
                optimizer=optax.adam(1e-3),
                train_iter=batches_fn(),
                config=TrainLoopConfig(
                    train_steps=steps, batch_size=batch, log_every=0,
                    window_steps=window_steps,
                    pipeline_root=root, run_id=run_id,
                    **(dp_kwargs or {}),
                ),
                **({"mesh": mesh} if mesh is not None else {}),
            )
        wall_s = time.perf_counter() - t0
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=30
        ) as r:
            scrape = r.read().decode()
    finally:
        rec.close()
        server.close()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # Phase attribution, from the federated scrape (delta vs the
    # process-cumulative counters the earlier sweep already advanced).
    scraped = {
        ph: _parse_prom_counter(
            scrape, "train_window_time_seconds", f'phase="{ph}"'
        ) - base[ph]
        for ph in phases
    }
    attributed = sum(scraped.values())
    compiles = int(
        _parse_prom_counter(scrape, "train_compiles_after_warm_total")
        - base_compiles
    )
    federated = "federation_sources" in scrape

    # Independent wall-clock sink: the RunTrace's per-window instants.
    events = read_events(rec.events_path)
    windows_total_s = sum(
        e["args"]["window_s"] for e in events
        if e["name"] == "window_breakdown"
    )

    # Durable ring: replayable headline the trace-diff path consumes.
    hist = MetricsHistory.for_pipeline_root(root)
    snapshots = len(hist.entries(run_id))
    head = hist.headline(run_id)
    self_flags = diff_metrics(
        {"train_telemetry": head}, {"train_telemetry": head}
    )["regression_flags"]

    phase_sum_ok = (
        attributed > 0
        and windows_total_s > 0
        and abs(attributed - windows_total_s) <= 0.05 * windows_total_s
        and attributed <= wall_s
    )
    green = (
        phase_sum_ok
        and compiles == 0
        and federated
        and snapshots >= 2
        and "window_phase_seconds" in head
        and self_flags == []
    )
    shutil.rmtree(root, ignore_errors=True)
    return {
        "green": green,
        "phase_seconds": {ph: round(v, 4) for ph, v in scraped.items()},
        "attributed_s": round(attributed, 4),
        "trace_windows_s": round(windows_total_s, 4),
        "wall_s": round(wall_s, 4),
        "phase_sum_within_5pct": phase_sum_ok,
        "infeed_wait_pct": (
            round(100.0 * scraped["infeed_wait"] / attributed, 2)
            if attributed else None
        ),
        "compiles_after_warm": compiles,
        "mfu": result.mfu,
        "federated_scrape": federated,
        "federation_sources": int(
            _parse_prom_gauge_value(scrape, "federation_sources") or 0
        ),
        "history_snapshots": snapshots,
        "history_headline_keys": sorted(head),
        "window_steps": window_steps,
        "steps": steps,
    }


def bench_taxi_window_mesh(smoke: bool) -> dict:
    """Multi-chip windowed training (ISSUE 15): the PR 8 window swept on
    the FULL n-device mesh with the explicit bucketed-psum collective
    (``dp_collective="psum_bucketed"``: grad buckets all-reduce inside the
    scan body, overlappable with backward compute), versus the same
    windowed loop on ONE device.

    Keys: ``mesh_window_speedup`` (best window vs window_steps=1 on the
    SAME mesh — the windowing win must survive the collective),
    ``scaling_efficiency`` (mesh per-chip throughput / 1-device
    throughput; 1.0 = perfect DP scaling), and — attached in main() next
    to ``taxi_device`` — ``gap_to_ceiling``.  Honest-box note: on a host
    with fewer cores than devices the n "chips" are virtual and share
    cores, so ``scaling_efficiency`` reads ~1/n there and only the
    recorded ``host_cpus`` makes the figure interpretable (the same
    caveat PRs 1/3 recorded for their parallelism legs); real-chip
    figures land with BENCH_R6.

    One process per chip: a backend that exposes ONE device has no mesh
    to sweep, and this leg records ``skipped: needs >1 device`` — it never
    starts a child on virtual CPU devices to stand in for chips.  The
    result names the ``platform`` it ran on; figures from a CPU mesh
    (tests) stay in the full report and off the compact line.
    """
    import jax

    if len(jax.devices()) <= 1:
        return {"skipped": "needs >1 device"}
    result = _taxi_window_mesh_measure(smoke)
    result["platform"] = jax.devices()[0].platform
    return result


def _taxi_window_mesh_measure(smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_pipelines.models.taxi import DEFAULT_HPARAMS, build_taxi_model
    from tpu_pipelines.parallel.mesh import MeshConfig, make_mesh
    from tpu_pipelines.trainer import TrainLoopConfig, train_loop

    devices = jax.devices()
    n_dev = len(devices)
    batch = 256 if smoke else 8192
    if batch % n_dev:
        batch = ((batch + n_dev - 1) // n_dev) * n_dev
    steps = 6 if smoke else 240
    log_window = 3 if smoke else 60
    windows = [1, 2, log_window] if smoke else [1, 8, log_window]
    n = batch * 8
    data = _taxi_rows(n)
    model = build_taxi_model(
        {**DEFAULT_HPARAMS, "hidden_dims": [256, 128, 64]}
    )

    def loss_fn(params, b, _rng):
        logits = model.apply({"params": params}, b)
        labels = jnp.asarray(b["label_big_tip"], jnp.float32)
        return optax.sigmoid_binary_cross_entropy(logits, labels).mean(), {}

    def batches():
        i = 0
        while True:
            rows = np.arange(i, i + batch) % n
            yield {k: v[rows] for k, v in data.items()}
            i = (i + batch) % n

    def run(device_list, w):
        _, result = train_loop(
            loss_fn=loss_fn,
            init_params_fn=lambda r, b: model.init(r, b)["params"],
            optimizer=optax.adam(1e-3),
            train_iter=batches(),
            config=TrainLoopConfig(
                train_steps=steps, batch_size=batch, log_every=0,
                window_steps=w,
                dp_collective="psum_bucketed",
                collective_buckets=2,
                anchor_every=(2 if smoke else 8) if w == 1 else 0,
            ),
            mesh=make_mesh(MeshConfig(), devices=device_list),
        )
        return (
            result.anchored_examples_per_sec_per_chip
            or result.examples_per_sec_per_chip
        )

    sweep = {str(w): run(devices, w) for w in windows}
    base = sweep[str(windows[0])]
    best = max(windows, key=lambda w: sweep[str(w)] or 0.0)
    # 1-device reference at the best window: the scaling denominator.
    # Same global batch — scaling efficiency compares per-chip throughput
    # at equal work, not small-batch single-chip luck.
    single = run(devices[:1], best)
    host_cpus = os.cpu_count() or 1
    # ISSUE 19 acceptance: the MULTI-CHIP windowed run (simulated mesh
    # OK) serving one federated scrape with sum-exact phase attribution,
    # zero steady-state compiles, and a replayable snapshot ring.
    telemetry = _train_window_telemetry_drill(
        loss_fn, lambda r, b: model.init(r, b)["params"], batches,
        batch, steps=3 * log_window, window_steps=log_window,
        mesh=make_mesh(MeshConfig(), devices=devices),
        dp_kwargs={
            "dp_collective": "psum_bucketed", "collective_buckets": 2,
        },
    )
    return {
        "examples_per_sec_per_chip": sweep[str(best)],
        "window_sweep": sweep,
        "window_steps_swept": windows,
        "best_window_steps": best,
        "mesh_devices": n_dev,
        "mesh_window_speedup": (
            round(sweep[str(best)] / base, 4) if base else None
        ),
        "single_device_eps": single,
        "scaling_efficiency": (
            round(sweep[str(best)] / single, 4) if single else None
        ),
        "dp_collective": "psum_bucketed",
        "collective_buckets": 2,
        "batch_size": batch,
        "steps_per_run": steps,
        "train_telemetry": telemetry,
        "host_cpus": host_cpus,
        # The 1-core-parity caveat, recorded not implied: n virtual
        # devices on fewer host cores time-slice the same silicon, so
        # scaling_efficiency there measures scheduler overhead, not chips.
        "virtual_devices_share_cores": host_cpus < n_dev,
        "method": "train_loop_mesh_window_sweep_vs_single_device",
    }


def bench_bert_parallelism(smoke: bool) -> dict:
    """The bert window sweep's parallelism axis (ISSUE 18): the SAME
    windowed fine-tune step under dp | fsdp | fsdp+accum | ring-attention
    long-context, recording MFU and peak device memory per config.

    ``fsdp`` must hold throughput against pure DP for a chip-sized
    control model (the acceptance bar is within 10%; ``fsdp_mfu_vs_dp``
    records the measured ratio), while its per-device parameter bytes
    read params/N — the memory headroom that buys models bigger than a
    chip.  ``ring_long`` runs the long-context config on a (data x seq)
    mesh with sequence-sharded infeed.  Same honest-box caveats as the
    taxi mesh leg: on a one-device backend it records ``skipped: needs >1
    device`` (no child on virtual CPU devices), and the result names the
    ``platform`` it ran on.
    """
    import jax

    if len(jax.devices()) <= 1:
        return {"skipped": "needs >1 device"}
    result = _bert_parallelism_measure(smoke)
    result["platform"] = jax.devices()[0].platform
    return result


def _bert_parallelism_measure(smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from tpu_pipelines.models.bert import DEFAULT_HPARAMS, build_bert_model
    from tpu_pipelines.parallel.mesh import MeshConfig, make_mesh
    from tpu_pipelines.parallel.ring_attention import (
        long_context_batch_partition,
    )
    from tpu_pipelines.trainer import TrainLoopConfig, train_loop

    devices = jax.devices()
    n_dev = len(devices)
    seq = 128
    long_seq = 256 if smoke else 2048
    batch = 16 if smoke else BERT_BENCH_BATCH
    if batch % n_dev:
        batch = ((batch + n_dev - 1) // n_dev) * n_dev
    steps = 4 if smoke else 48
    window = 2 if smoke else 8
    hp = {
        **DEFAULT_HPARAMS,
        "max_len": seq,
        "attn_impl": "auto",
        "num_classes": 2,
    }
    if smoke:
        hp.update({"d_model": 64, "n_layers": 2, "n_heads": 4, "d_ff": 128,
                   "vocab_size": 512})
    peak = chip_info()["peak_bf16_flops"]
    data_mesh = make_mesh(MeshConfig(), devices=devices)
    seq_axis = 4 if n_dev % 4 == 0 else n_dev
    ring_mesh = Mesh(
        np.array(devices).reshape(n_dev // seq_axis, 1, seq_axis, 1, 1),
        ("data", "model", "seq", "expert", "pipe"),
    )
    # Smoke's short sequences sit under the default ring floor; pin the
    # gate to the leg's long-context length.
    os.environ.setdefault("TPP_RING_MIN_SEQ", str(long_seq))

    def run_cfg(*, seq_len, mesh, model_mesh=None, dp=None, accum=1,
                long_context=False):
        hp_c = {**hp, "max_len": seq_len}
        model = build_bert_model(hp_c, mesh=model_mesh)
        rng = np.random.default_rng(0)
        ids = rng.integers(
            4, hp_c["vocab_size"], size=(batch, seq_len), dtype=np.int64
        )
        data = {
            "input_ids": ids.astype(np.int32),
            "attention_mask": np.ones((batch, seq_len), np.int32),
            "label": (ids[:, 0] % 2).astype(np.int32),
        }
        bp = long_context_batch_partition(data, mesh) if long_context else {}

        def features(b):
            return {k: v for k, v in b.items() if k != "label"}

        def loss_fn(params, b, step_rng):
            logits = model.apply(
                {"params": params}, features(b),
                deterministic=False, rngs={"dropout": step_rng},
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.asarray(b["label"], jnp.int32)
            ).mean()
            return loss, {}

        def batches():
            while True:
                yield data

        params, result = train_loop(
            loss_fn=loss_fn,
            init_params_fn=lambda r, b: model.init(r, features(b))["params"],
            optimizer=optax.adamw(2e-5),
            train_iter=batches(),
            config=TrainLoopConfig(
                train_steps=steps, batch_size=batch, log_every=0,
                window_steps=window, dp_collective=dp,
                grad_accum_steps=accum, batch_partition=bp,
            ),
            mesh=mesh,
        )
        eps = result.examples_per_sec_per_chip
        counts = _count_params(params)
        flops_per_step = (
            6 * counts["matmul"] * batch * seq_len
            + 12 * int(hp_c["n_layers"]) * batch * seq_len * seq_len
            * int(hp_c["d_model"])
        )
        # Per-chip MFU at per-chip throughput: flops/step spread over the
        # mesh against one chip's peak.
        mfu = flops_per_step * (eps / batch) / peak if batch else 0.0
        leaves = jax.tree_util.tree_leaves(params)
        stats = (getattr(jax.local_devices()[0], "memory_stats",
                         lambda: None)() or {})
        return {
            "examples_per_sec_per_chip": eps,
            "mfu": round(mfu, 6),
            "param_bytes_total": sum(v.nbytes for v in leaves),
            # The fsdp memory story, measured: resident parameter bytes on
            # ONE device (params/N sharded, == total when replicated).
            "param_bytes_per_device": sum(
                v.addressable_shards[0].data.nbytes for v in leaves
            ),
            # Populated on backends that expose an allocator (TPU/GPU);
            # None on the CPU smoke box — param_bytes_per_device carries
            # the structural evidence there.
            "device_memory_peak_bytes": stats.get("peak_bytes_in_use"),
            "seq_len": seq_len,
            "grad_accum_steps": accum,
            "dp_collective": dp or "implicit",
        }

    sweep = {
        "dp": run_cfg(seq_len=seq, mesh=data_mesh, dp="psum_bucketed"),
        "fsdp": run_cfg(seq_len=seq, mesh=data_mesh, dp="fsdp"),
        "fsdp_accum": run_cfg(
            seq_len=seq, mesh=data_mesh, dp="fsdp", accum=2
        ),
        "ring_long": run_cfg(
            seq_len=long_seq, mesh=ring_mesh, model_mesh=ring_mesh,
            long_context=True,
        ),
    }
    dp_mfu = sweep["dp"]["mfu"]
    return {
        "examples_per_sec_per_chip": sweep["dp"]["examples_per_sec_per_chip"],
        "parallelism": sweep,
        "fsdp_mfu_vs_dp": (
            round(sweep["fsdp"]["mfu"] / dp_mfu, 4) if dp_mfu else None
        ),
        "fsdp_param_shard_ratio": (
            round(
                sweep["fsdp"]["param_bytes_per_device"]
                / sweep["fsdp"]["param_bytes_total"], 4,
            )
            if sweep["fsdp"]["param_bytes_total"] else None
        ),
        "mesh_devices": n_dev,
        "window_steps": window,
        "batch_size": batch,
        "steps_per_run": steps,
        "host_cpus": os.cpu_count() or 1,
        "virtual_devices_share_cores": (os.cpu_count() or 1) < n_dev,
        "method": "train_loop_bert_window_parallelism_sweep",
    }


def _device_resident_eps(
    *, loss, init_params, batch_data, batch, optimizer, n1, n2, repeats
) -> dict:
    """Chip-bound examples/sec: device-resident input, loop on device.

    N optimizer steps run inside ONE jitted ``lax.fori_loop`` dispatch and
    the per-step time comes from the DIFFERENCE between an n2-step and an
    n1-step call — the per-call dispatch constant cancels exactly, so the
    number measures the chip, not the host.  Dynamic ``n`` lowers to one
    while_loop executable: both loop lengths share a single compile.
    """
    import jax
    import optax

    @jax.jit
    def run_n(params, opt_state, b, n):
        def body(_, carry):
            p, o = carry
            g = jax.grad(loss)(p, b)
            up, o = optimizer.update(g, o, p)
            return (optax.apply_updates(p, up), o)

        return jax.lax.fori_loop(0, n, body, (params, opt_state))

    dbatch = jax.device_put(batch_data)
    params = init_params(jax.random.key(0), dbatch)
    opt_state = optimizer.init(params)

    def timed(n):
        t0 = time.perf_counter()
        p, _ = run_n(params, opt_state, dbatch, n)
        # Device-to-host read of the result proves all n steps executed
        # (block_until_ready can return early on this platform).
        np.asarray(jax.tree_util.tree_leaves(p)[0]).ravel()[0]
        return time.perf_counter() - t0

    # Compile + warm BOTH loop lengths: the first call at each n pays
    # one-time costs (executable finalization, allocator growth) that
    # otherwise depress the first measured repeat (r5 observed a first
    # repeat ~30% low with only the n1 path warmed).
    timed(n1)
    timed(n2)
    eps_runs = []
    for _ in range(repeats):
        t1, t2 = timed(n1), timed(n2)
        if t2 > t1:
            eps_runs.append(batch * (n2 - n1) / (t2 - t1))
    eps_runs.sort()
    k = len(eps_runs)
    # True median: even-length lists average the middle pair (picking
    # eps_runs[k//2] would report the optimistic max of a 2-run list
    # exactly when the t2>t1 guard dropped a noisy repeat).
    med = (
        0.0 if not eps_runs
        else eps_runs[k // 2] if k % 2
        else 0.5 * (eps_runs[k // 2 - 1] + eps_runs[k // 2])
    )
    spread = (
        round((eps_runs[-1] - eps_runs[0]) / med, 4)
        if med and len(eps_runs) > 1 else None
    )
    return {
        "examples_per_sec_per_chip": round(med, 2),
        "repeats": [round(e, 2) for e in eps_runs],
        "relative_spread": spread,
        "batch_size": batch,
        "loop_steps": [n1, n2],
        "method": "device_resident_fori_loop_difference",
    }


def bench_mnist(smoke: bool) -> dict:
    """Measured TPU number for BASELINE configs[1] (MNIST CNN via Trainer).

    The config's reference status is functional-green only; this leg adds
    a throughput datapoint (VERDICT r4 missing#2).  Chip-bound method:
    the whole MNIST train set fits on device many times over, so
    host-feeding would only measure the host.
    """
    import jax.numpy as jnp
    import optax

    from tpu_pipelines.models.mnist import build_mnist_model

    batch = 64 if smoke else 1024
    rng = np.random.default_rng(0)
    data = {
        "image": rng.random((batch, 28, 28, 1)).astype(np.float32),
        "label": rng.integers(0, 10, size=batch).astype(np.int32),
    }
    model = build_mnist_model({})

    def loss(params, b):
        logits = model.apply({"params": params}, b["image"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(b["label"], jnp.int32)
        ).mean()

    return _device_resident_eps(
        loss=loss,
        init_params=lambda rng, b: model.init(rng, b["image"])["params"],
        batch_data=data,
        batch=batch,
        optimizer=optax.adam(1e-3),
        # Same long-loop reasoning as taxi_device: ~0.9 ms steps need a
        # multi-hundred-ms n2-n1 difference to shrug off dispatch jitter.
        n1=3 if smoke else 300,
        n2=9 if smoke else 1200,
        repeats=2 if smoke else 5,
    )


def bench_resnet(smoke: bool) -> dict:
    """Measured TPU number for BASELINE configs[2] (ResNet-50 ImageNet).

    Functional-green in tests since round 2; this leg adds the measured
    examples/sec/chip (VERDICT r4 missing#2) at ImageNet geometry
    (224x224x3, ResNet-50).  Batch 256 rather than the config's 1024:
    single-chip HBM headroom — the per-example rate is what transfers.
    """
    import jax.numpy as jnp
    import optax

    from tpu_pipelines.models.resnet import build_resnet_model

    if smoke:
        batch, size, depth = 4, 32, 18
    else:
        batch, size, depth = 256, 224, 50
    rng = np.random.default_rng(0)
    data = {
        "image": rng.random((batch, size, size, 3)).astype(np.float32),
        "label": rng.integers(0, 1000, size=batch).astype(np.int32),
    }
    model = build_resnet_model({"depth": depth})
    # BatchNorm in train mode normalizes with THIS batch's statistics (the
    # real training compute); the running-average update is dropped from
    # the carry — it feeds nothing downstream here, and its cost is a
    # per-channel running mean, noise next to the convs.
    init_vars = {}

    def loss(params, b):
        logits, _ = model.apply(
            {"params": params, "batch_stats": init_vars["batch_stats"]},
            b["image"], train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(b["label"], jnp.int32)
        ).mean()

    def init_params(rng, b):
        variables = model.init(rng, b["image"], train=False)
        init_vars["batch_stats"] = variables["batch_stats"]
        return variables["params"]

    return _device_resident_eps(
        loss=loss,
        init_params=init_params,
        batch_data=data,
        batch=batch,
        optimizer=optax.sgd(0.1, momentum=0.9),
        n1=2 if smoke else 5,
        n2=6 if smoke else 15,
        repeats=2 if smoke else 5,
    )


def bench_t5_decode(smoke: bool) -> dict:
    """Autoregressive decode throughput: T5-small greedy + beam-4 on chip.

    Evidence that the KV-cache decode path (models/t5.py) runs on TPU as one
    jitted scan: new tokens/sec at t5-small geometry (the BASELINE configs[4]
    model), batch 32, encoder length 64.  Greedy feeds per-step cache updates;
    beam-4 adds the topk + cache-reorder machinery.
    """
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models.t5 import (
        build_t5_model, make_beam_generate, make_greedy_generate,
    )

    if smoke:
        hp = {"vocab_size": 64, "d_model": 16, "n_layers": 1, "n_heads": 2,
              "head_dim": 8, "d_ff": 32, "dropout_rate": 0.0}
        batch, enc_len, dec_len, iters = 2, 8, 8, 1
    else:
        hp = {"dropout_rate": 0.0}      # t5-small geometry from defaults
        batch, enc_len, dec_len, iters = 32, 64, 64, 3

    model = build_t5_model(hp)
    rng = np.random.default_rng(0)
    hi = min(100, int(hp.get("vocab_size", 32128)))
    inputs = rng.integers(2, hi, size=(batch, enc_len)).astype(np.int32)
    params = model.init(
        jax.random.key(0),
        {"inputs": inputs, "targets": np.ones((batch, 4), np.int32)},
    )["params"]

    out = {"batch": batch, "enc_len": enc_len, "max_decode_len": dec_len}
    for name, fn in (
        # The decode scan has no early exit (EOS is masking, not control
        # flow), so every run executes exactly dec_len steps — fixed work
        # per timing regardless of what the random-init model emits.
        ("greedy", make_greedy_generate(
            model, max_decode_len=dec_len, eos_id=0)),
        ("beam4", make_beam_generate(
            model, beam_size=4, max_decode_len=dec_len, eos_id=0)),
    ):
        tokens = fn(params, inputs)[0]
        np.asarray(tokens[0, 0])        # force compile + execution
        t0 = time.perf_counter()
        for _ in range(iters):
            tokens = fn(params, inputs)[0]
        np.asarray(tokens[0, 0])
        dt = (time.perf_counter() - t0) / iters
        out[name] = {
            "tokens_per_sec": round(batch * dec_len / dt, 1),
            "ms_per_token": round(dt / dec_len * 1e3, 3),
        }

    # Flash-decode datapoint (ISSUE 11): the generative engine's per-step
    # kernel — single-query attention against the KV cache — tuned by the
    # autotuner's 1-D block_k sweep and measured against dense cache
    # attention per cache length.  The first length where tuned flash
    # wins is persisted as the DECODE crossover
    # (autotune.record_decode_crossover) that attn_impl="auto" consults
    # in the decode regime (models/transformer.py choose_decode_impl).
    from tpu_pipelines.models.transformer import (
        choose_decode_impl, dense_attention,
    )
    from tpu_pipelines.ops import autotune
    from tpu_pipelines.ops.flash_attention import flash_decode_attention

    interpret = jax.default_backend() != "tpu"
    if smoke:
        db, heads, hd, kv_lens, fd_iters = 2, 2, 8, [128, 256], 1
    else:
        db, heads, hd, kv_lens, fd_iters = 32, 8, 64, [512, 2048, 8192], 20
    fd: dict = {"per_len": {}, "interpret": interpret}
    crossover = None
    for kv_len in kv_lens:
        kq, kk, kv = jax.random.split(jax.random.key(kv_len), 3)
        q = jax.random.normal(kq, (db, 1, heads, hd), jnp.float32)
        k = jax.random.normal(kk, (db, kv_len, heads, hd), jnp.float32)
        v = jax.random.normal(kv, (db, kv_len, heads, hd), jnp.float32)
        sw = autotune.sweep_decode(
            db, heads, kv_len, hd, jnp.float32, interpret, iters=fd_iters,
        )["flash_decode"]
        best = sw["best"]
        dense_c = jax.jit(
            lambda q, k, v: dense_attention(q, k, v, causal=False)
        ).lower(q, k, v).compile()
        dense_ms = round(
            autotune.time_compiled(dense_c, (q, k, v), fd_iters), 4
        )
        row = {
            "dense_ms": dense_ms,
            "flash_ms": best["ms"] if best else None,
            "block_k": best["block_k"] if best else None,
            "candidates_timed": sum(1 for r in sw["swept"] if "ms" in r),
        }
        fd["per_len"][str(kv_len)] = row
        if (
            crossover is None and best is not None
            and best["ms"] <= dense_ms
        ):
            crossover = kv_len
    kind = autotune.current_device_kind()
    autotune.record_decode_crossover(
        kind, crossover,
        geometry={"batch": db, "heads": heads, "head_dim": hd,
                  "kv_lens": kv_lens},
        source="bench-smoke" if smoke else "bench",
    )
    fd["crossover_kv_len"] = crossover
    fd["device_kind"] = kind
    # What "auto" now resolves to at each measured length (reads the
    # crossover just recorded).
    fd["auto_choice"] = {
        str(l): choose_decode_impl(db, heads, l, hd) for l in kv_lens
    }
    out["flash_decode"] = fd
    return out


def _canonical_lineage(
    metadata_path: str,
    pipeline_root: str,
    states: tuple = (),
    strip_exec_ids: bool = False,
) -> list:
    """Id-free canonical form of a run's published lineage: per execution,
    (node, state, sorted input events, sorted output events) with artifact
    URIs relativized to the pipeline root — two runs publishing the same
    artifacts/lineage compare equal regardless of store row ids, publish
    interleaving, or pipeline home.

    ``states`` filters to those execution states (e.g. COMPLETE/CACHED only,
    so a stitched resume — which legitimately carries extra ABANDONED
    fencing records — compares against a cold run's decisive set).
    ``strip_exec_ids`` drops the trailing execution-id path component from
    artifact URIs (``Trainer/model/7`` -> ``Trainer/model``): a resumed
    run's re-dispatched nodes get later execution ids than a cold run's, so
    the embedded id is the one legitimate difference."""
    from tpu_pipelines.metadata import open_store
    from tpu_pipelines.metadata.types import EventType

    store = open_store(metadata_path)
    root = os.path.abspath(pipeline_root)

    def rel(uri: str) -> str:
        a = os.path.abspath(uri)
        out = os.path.relpath(a, root) if a.startswith(root) else uri
        if strip_exec_ids and os.path.basename(out).isdigit():
            out = os.path.dirname(out)
        return out

    entries = []
    for ex in store.get_executions():
        if states and ex.state.value not in states:
            continue
        ins, outs = [], []
        for ev in store.get_events_by_execution(ex.id):
            art = store.get_artifact(ev.artifact_id)
            row = (ev.path, ev.index, rel(art.uri), art.type_name,
                   art.state.value)
            (ins if ev.type == EventType.INPUT else outs).append(row)
        entries.append(
            (ex.node_id, ex.state.value, tuple(sorted(ins)),
             tuple(sorted(outs)))
        )
    store.close()
    return sorted(entries)


def _critical_path(ir, node_walls: dict) -> tuple:
    """(path node ids, total seconds): the longest dependency chain through
    the DAG by measured per-node wall-clock — the lower bound no scheduler
    can beat, and the denominator of the achievable concurrency win."""
    best: dict = {}
    prev: dict = {}
    for node in ir.nodes:  # ir.nodes is topologically ordered
        up = [u for u in node.upstream if u in best]
        base = max((best[u] for u in up), default=0.0)
        if up:
            prev[node.id] = max(up, key=lambda u: best[u])
        best[node.id] = base + node_walls.get(node.id, 0.0)
    if not best:
        return [], 0.0
    end = max(best, key=lambda n: best[n])
    path = [end]
    while path[-1] in prev:
        path.append(prev[path[-1]])
    return list(reversed(path)), round(best[end], 2)


def bench_lint(smoke: bool) -> dict:
    """Static-analyzer health over the six shipped examples (ISSUE 6).

    Compiles every example and runs BOTH analyzer layers (TPP1xx graph
    rules on the IR, TPP2xx code rules over executors + module files)
    without executing anything.  ``findings_total`` must stay 0: a shipped
    example that lints dirty means either a seeded regression in an
    example or an over-eager rule — both block.  Also records the
    graph-layer latency to keep the "milliseconds before a chip is
    touched" claim measured, not asserted.
    """
    import tempfile

    from tpu_pipelines.analysis import analyze_ir, analyze_pipeline
    from tpu_pipelines.dsl.compiler import Compiler
    from tpu_pipelines.utils.module_loader import load_fn

    names = ("taxi", "mnist", "resnet", "bert", "t5", "staged")
    env = {"BERT_TINY": "1", "T5_TINY": "1", "RESNET_IMAGE_SIZE": "8",
           "RESNET_DEPTH": "18"}
    saved = {k: os.environ.get(k) for k in list(env) + ["TPP_PIPELINE_HOME"]}
    os.environ.update(env)
    per_example = {}
    graph_ms = {}
    total = 0
    try:
        with tempfile.TemporaryDirectory() as td:
            os.environ["TPP_PIPELINE_HOME"] = td
            for name in names:
                module = os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "examples", name, "pipeline.py",
                )
                pipeline = load_fn(module, "create_pipeline")()
                ir = Compiler().compile(pipeline)
                t0 = time.perf_counter()
                graph_findings = analyze_ir(ir)
                graph_ms[name] = round(
                    (time.perf_counter() - t0) * 1000, 2
                )
                findings = analyze_pipeline(pipeline, ir=ir)
                del graph_findings  # subset of `findings`; timed only
                total += len(findings)
                per_example[name] = {
                    "findings": len(findings),
                    "rules": sorted({f.rule for f in findings}),
                }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {
        "green": total == 0,
        "findings_total": total,
        "per_example": per_example,
        "graph_layer_ms": graph_ms,
        "graph_layer_ms_max": max(graph_ms.values()) if graph_ms else None,
    }


def _run_example_pipeline(
    name: str,
    env: dict,
    max_parallel_nodes=None,
    capture_lineage: bool = False,
) -> dict:
    """One example pipeline end-to-end in a fresh home (no cache hits);
    returns total wall-clock + the per-component breakdown.  The effective
    scheduler pool size is always recorded so BENCH_*.json files stay
    comparable across concurrency configs."""
    import tempfile

    from tpu_pipelines.dsl.compiler import Compiler
    from tpu_pipelines.orchestration import LocalDagRunner
    from tpu_pipelines.utils.module_loader import load_fn

    module = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", name, "pipeline.py",
    )
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        with tempfile.TemporaryDirectory() as td:
            pipeline = load_fn(module, "create_pipeline")(td)
            t0 = time.perf_counter()
            result = LocalDagRunner(
                max_parallel_nodes=max_parallel_nodes
            ).run(pipeline)
            total = time.perf_counter() - t0
            lineage = (
                _canonical_lineage(
                    pipeline.metadata_path, pipeline.pipeline_root
                )
                if capture_lineage else None
            )
            ir = Compiler().compile(pipeline) if capture_lineage else None
            # RunTrace metrics (observability/): the MEASURED time
            # decomposition, read before the tempdir (and the run's
            # events.jsonl with it) is reclaimed.  None when tracing was
            # disabled via env (the overhead-comparison leg's off run).
            trace_summary = _trace_summary(
                pipeline.pipeline_root, result.run_id
            )
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    out = {
        "green": result.succeeded,
        "wall_clock_s": round(total, 2),
        "max_parallel_nodes": result.max_parallel_nodes,
        "env": env,
        "nodes": {
            nid: {"status": nr.status, "wall_s": round(nr.wall_clock_s, 2)}
            for nid, nr in result.nodes.items()
        },
        "trace": trace_summary,
    }
    if capture_lineage:
        out["lineage"] = lineage
        walls = {nid: nr.wall_clock_s for nid, nr in result.nodes.items()}
        path, path_s = _critical_path(ir, walls)
        out["critical_path"] = path
        out["critical_path_s"] = path_s
    return out


def _trace_summary(pipeline_root: str, run_id: str):
    """Headline trace-derived metrics for one run, or None without a trace
    (TPP_TRACE=0), or {"error": ...} if the log exists but won't digest —
    a bench leg must degrade, never crash, on an observability bug."""
    try:
        from tpu_pipelines.observability import (
            compute_metrics,
            events_path,
            read_events,
        )

        path = events_path(pipeline_root, run_id)
        if not os.path.exists(path):
            return None
        events = read_events(path)
        m = compute_metrics(events)
        return {
            "events": len(events),
            # Full per-node profile: what `trace diff` (and the bench's
            # own previous-run regression self-report) consumes.
            "per_node": m["per_node"],
            "critical_path_measured_s": m["critical_path_measured_s"],
            "critical_path_nodes": m["critical_path_nodes"],
            "span_duration_total_s": m["span_duration_total_s"],
            "longest_node_s": m["longest_node_s"],
            "longest_node": m["longest_node"],
            "queue_wait_total_s": m["queue_wait_total_s"],
            "gate_wait_total_s": m["gate_wait_total_s"],
            "cache_hit_ratio": m["cache_hit_ratio"],
            "phase_totals_s": m["phase_totals_s"],
            "shard_pools": m["shard_pools"],
            "run_wall_s": m["run_wall_s"],
        }
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def bench_e2e_taxi(smoke: bool) -> dict:
    """End-to-end taxi pipeline wall-clock (BASELINE: "Chicago-Taxi ...
    green on v5e"): the canonical 9-node DAG in a fresh pipeline home under
    LocalDagRunner, with per-node wall-clock, the run's trace-derived
    metrics (measured critical path, queue waits, cache-hit ratio), and
    the tracing-overhead comparison — the same DAG re-run with TPP_TRACE=0
    (the ISSUE-4 acceptance bound is <2% end-to-end overhead)."""
    env = {
        "TAXI_TRAIN_STEPS": "4" if smoke else "200",
        "TPP_DISABLE_MID_CHECKPOINT": "1",
    }
    # Cold first: the headline wall_clock_s keeps its round-over-round
    # semantics (includes one-time compiles).  The overhead pair then
    # compares two WARM runs — the cold run doubles as their warm-up, so
    # neither side of the on/off comparison eats compile time (same
    # discipline as the scheduler-comparison leg).
    on = _run_example_pipeline("taxi", env)
    warm_on = _run_example_pipeline("taxi", env)
    warm_off = _run_example_pipeline("taxi", {**env, "TPP_TRACE": "0"})
    on["green"] = on["green"] and warm_on["green"] and warm_off["green"]
    on["trace_overhead"] = {
        "wall_trace_on_s": warm_on["wall_clock_s"],
        "wall_trace_off_s": warm_off["wall_clock_s"],
        # >0 = tracing cost; single-run walls carry normal run-to-run
        # noise, so small negatives just mean "within noise".
        "overhead_frac": (
            round(
                warm_on["wall_clock_s"] / warm_off["wall_clock_s"] - 1.0, 4
            )
            if warm_off["wall_clock_s"] else None
        ),
        "trace_off_wrote_no_events": warm_off["trace"] is None,
    }
    return on


# Worker-pool size for the concurrent leg of the scheduler comparison: wide
# enough for every independent-branch pair in the taxi DAG
# (ExampleValidator ∥ Transform chain, Evaluator ∥ InfraValidator).
E2E_SCHED_WORKERS = 4


def bench_e2e_taxi_sched(smoke: bool) -> dict:
    """Sequential vs concurrent wall-clock on the branching taxi DAG — the
    wall-clock head of the two-headed BASELINE metric.  Runs the identical
    9-node pipeline twice in fresh homes: max_parallel_nodes=1 (the classic
    topo loop) and the ready-set scheduler with E2E_SCHED_WORKERS.  Reports
    both wall-clocks, the per-node critical-path breakdown (the
    no-scheduler-can-beat lower bound), and whether the two runs published
    identical artifacts/lineage (id-free canonical comparison)."""
    env = {
        "TAXI_TRAIN_STEPS": "4" if smoke else "200",
        "TPP_DISABLE_MID_CHECKPOINT": "1",
    }
    # Discarded warm-up first: one cheap pass (4 steps — jit caches are
    # shape-keyed, so step count doesn't matter) absorbs the in-process
    # one-time costs (module loads, XLA compiles).  Without it, whichever
    # measured leg runs first eats ~seconds of compile and the comparison
    # measures warm-up order, not the scheduler.
    _run_example_pipeline(
        "taxi", {**env, "TAXI_TRAIN_STEPS": "4"}, max_parallel_nodes=1
    )
    conc = _run_example_pipeline(
        "taxi", env, max_parallel_nodes=E2E_SCHED_WORKERS,
        capture_lineage=True,
    )
    seq = _run_example_pipeline(
        "taxi", env, max_parallel_nodes=1, capture_lineage=True
    )
    seq_wall, conc_wall = seq["wall_clock_s"], conc["wall_clock_s"]
    return {
        "green": seq["green"] and conc["green"],
        "sequential_wall_s": seq_wall,
        "concurrent_wall_s": conc_wall,
        "speedup": round(seq_wall / conc_wall, 3) if conc_wall else None,
        "concurrent_strictly_faster": conc_wall < seq_wall,
        # Branch overlap needs a spare core to land on: a 1-cpu host can
        # only show parity (the scheduler still must not LOSE there); the
        # win materializes on multicore/TPU hosts.
        "host_cpus": os.cpu_count(),
        "max_parallel_nodes": {
            "sequential": seq["max_parallel_nodes"],
            "concurrent": conc["max_parallel_nodes"],
        },
        # Same artifacts, same lineage, both modes — the single-writer
        # discipline evidence (ids/fingerprints excluded: row ids depend on
        # publish interleaving, checkpoint payloads embed timestamps).
        "lineage_identical": seq["lineage"] == conc["lineage"],
        "lineage_executions": len(conc["lineage"]),
        "critical_path": conc["critical_path"],
        "critical_path_s": conc["critical_path_s"],
        # Trace-derived (measured, not per-node-wall-summed) profiles for
        # both modes: the concurrent leg's measured critical path is the
        # number the wall-clock speedup is judged against.
        "trace_concurrent": conc.get("trace"),
        "trace_sequential": seq.get("trace"),
        "nodes_sequential": seq["nodes"],
        "nodes_concurrent": conc["nodes"],
        "env": env,
    }


def bench_e2e_bert(smoke: bool) -> dict:
    """End-to-end BERT-base fine-tune pipeline (BASELINE configs[3]:
    tokenizing Transform -> Trainer -> Evaluator -> Pusher) — the
    north-star workload's green/per-node-wall-clock evidence."""
    env = {
        "BERT_TRAIN_STEPS": "4" if smoke else "30",
        "TPP_DISABLE_MID_CHECKPOINT": "1",
    }
    if smoke:
        env["BERT_TINY"] = "1"
    return _run_example_pipeline("bert", env)


def _parse_prom_histogram(text: str, name: str, label_filter: str = ""):
    """Parse one histogram family out of a Prometheus text scrape:
    returns {"bounds": [...], "buckets": [per-bucket counts + overflow],
    "count": n, "sum": s} or None.  Deliberately reads the EXPOSITION,
    not the in-process registry — the bench certifies what a real
    Prometheus would ingest."""
    import re

    pairs = []  # (le, cumulative)
    count = total = None
    for line in text.splitlines():
        if not line.startswith(name) or (
            label_filter and label_filter not in line
        ):
            continue
        m = re.match(
            rf'{re.escape(name)}_bucket{{.*le="([^"]+)".*}} (\S+)', line
        )
        if m:
            le = float("inf") if m.group(1) == "+Inf" else float(m.group(1))
            pairs.append((le, float(m.group(2))))
            continue
        m = re.match(rf"{re.escape(name)}_count(?:{{.*}})? (\S+)", line)
        if m:
            count = float(m.group(1))
            continue
        m = re.match(rf"{re.escape(name)}_sum(?:{{.*}})? (\S+)", line)
        if m:
            total = float(m.group(1))
    if not pairs or count is None:
        return None
    pairs.sort(key=lambda p: p[0])
    bounds = [le for le, _ in pairs if le != float("inf")]
    cum = [c for _, c in pairs]
    buckets = [cum[0]] + [b - a for a, b in zip(cum, cum[1:])]
    return {
        "bounds": bounds,
        "buckets": buckets,
        "count": int(count),
        "sum": total or 0.0,
    }


def bench_serving(smoke: bool) -> dict:
    """Live-serving telemetry leg: a ModelServer (micro-batching on) over
    a toy exported payload, hammered with concurrent REST predicts, then
    judged from its OWN ``/metrics`` scrape — p50/p99 request latency
    come out of the Prometheus histogram a real scraper would ingest,
    and ``/healthz`` must report healthy under load.  The model is a
    3x2 matmul on purpose: the leg measures the serving pipeline
    (HTTP + JSON + micro-batcher + dispatch), not the network."""
    import tempfile
    import threading
    import urllib.request

    from tpu_pipelines.observability.metrics import histogram_quantile
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.trainer.export import export_model

    n_threads = 4
    n_requests = 80 if smoke else 800
    with tempfile.TemporaryDirectory() as td:
        module = os.path.join(td, "toy_model.py")
        with open(module, "w") as f:
            f.write(
                "import jax.numpy as jnp\n"
                "def build_model(hp):\n"
                "    return None\n"
                "def apply_fn(model, params, batch):\n"
                "    return jnp.asarray(batch['x'], jnp.float32) "
                "@ params['w']\n"
            )
        export_model(
            serving_model_dir=os.path.join(td, "m", "1"),
            params={"w": np.eye(3, 2).astype(np.float32)},
            module_file=module,
        )
        server = ModelServer(
            "bench", os.path.join(td, "m"), batching=True,
            max_batch_size=16, batch_timeout_s=0.002,
        )
        port = server.start()
        url = f"http://127.0.0.1:{port}/v1/models/bench:predict"
        body = json.dumps(
            {"instances": [{"x": [1.0, 2.0, 3.0]}]}
        ).encode()
        errors = [0]

        def fire(n: int) -> None:
            for _ in range(n):
                try:
                    req = urllib.request.Request(url, data=body)
                    with urllib.request.urlopen(req, timeout=30) as r:
                        r.read()
                except Exception:  # noqa: BLE001 — counted, not raised
                    errors[0] += 1

        try:
            fire(3)  # warm-up: first-bucket XLA compile out of the tail
            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=fire, args=(n_requests // n_threads,))
                for _ in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as r:
                scrape = r.read().decode()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ) as r:
                health = json.loads(r.read())
        finally:
            server.stop()
    hist = _parse_prom_histogram(
        scrape, "serving_request_latency_seconds", 'endpoint="predict"'
    )
    p50 = p99 = None
    if hist:
        series = {"buckets": hist["buckets"], "count": hist["count"],
                  "sum": hist["sum"]}
        p50 = histogram_quantile(series, 0.50, hist["bounds"])
        p99 = histogram_quantile(series, 0.99, hist["bounds"])
    served = int(hist["count"]) if hist else 0
    return {
        "green": (
            errors[0] == 0 and bool(health.get("healthy"))
            and served >= n_requests and p99 is not None
        ),
        "requests": n_requests + 3,
        "request_errors": errors[0],
        "scraped_requests": served,
        "qps": round(n_requests / wall, 1) if wall else None,
        "p50_ms": round(p50 * 1e3, 3) if p50 is not None else None,
        "p99_ms": round(p99 * 1e3, 3) if p99 is not None else None,
        "mean_ms": (
            round(hist["sum"] / hist["count"] * 1e3, 3)
            if hist and hist["count"] else None
        ),
        "healthz": health,
        "concurrency": n_threads,
    }


def _fleet_hammer(url: str, body: bytes, n_threads: int, per_thread: int):
    """Fire ``n_threads x per_thread`` POSTs; returns (errors, codes)."""
    import threading
    import urllib.error
    import urllib.request

    errors = [0]
    codes: dict = {}
    lock = threading.Lock()

    def fire(n: int) -> None:
        for _ in range(n):
            code = None
            try:
                req = urllib.request.Request(url, data=body)
                with urllib.request.urlopen(req, timeout=60) as r:
                    r.read()
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception:  # noqa: BLE001 — dropped connection
                errors[0] += 1
            with lock:
                codes[code] = codes.get(code, 0) + 1

    threads = [
        threading.Thread(target=fire, args=(per_thread,))
        for _ in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errors[0], codes


def _fleet_traced_pass(
    model_dir: str,
    n_threads: int,
    n_requests: int,
    slo_p99_ms: float,
    max_queue_depth: int,
) -> dict:
    """ISSUE 12 pass C: the pass-A hammer shape with request tracing
    sampled on (``sample:4``); mean request latency from the scrape is
    the traced side of ``trace_overhead_pct`` (pass A's untraced mean is
    the baseline — same model dir, same box, back to back)."""
    import urllib.request

    from tpu_pipelines.serving import ModelServer

    server = ModelServer(
        "fleet", model_dir,
        replicas=2, max_versions=2, slo_p99_ms=slo_p99_ms,
        max_batch_size=8, batch_timeout_s=0.002,
        max_queue_depth=max_queue_depth,
        request_trace_mode="sample:4",
    )
    port = server.start()
    url = f"http://127.0.0.1:{port}/v1/models/fleet:predict"
    body = json.dumps({"instances": [{"x": [1.0, 2.0, 3.0]}]}).encode()
    try:
        # Same warm-up budget as pass A (XLA compiles, canary capture).
        _fleet_hammer(url, body, 1, 3)
        t0 = time.perf_counter()
        errors, codes = _fleet_hammer(
            url, body, n_threads, n_requests // n_threads
        )
        wall = time.perf_counter() - t0
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape = r.read().decode()
        ring_events = len(
            server.request_tracer.events()
        ) if server.request_tracer else 0
    finally:
        server.stop()
    hist = _parse_prom_histogram(
        scrape, "serving_request_latency_seconds", 'endpoint="predict"'
    )
    traced_total = int(_parse_prom_counter(
        scrape, "serving_traced_requests_total"
    ))
    mean_s = (hist["sum"] / hist["count"]) if hist and hist["count"] else None
    return {
        "requests": n_requests,
        "errors": errors,
        "codes": {str(k): v for k, v in sorted(codes.items(),
                                               key=lambda kv: str(kv[0]))},
        "qps": round(n_requests / wall, 1) if wall else None,
        "mean_latency_ms": (
            round(mean_s * 1e3, 3) if mean_s is not None else None
        ),
        "sample_mode": "sample:4",
        "traced_requests": traced_total,
        "ring_events": ring_events,
    }


def _fleet_rollback_drill(td: str, module: str, smoke: bool) -> dict:
    """ISSUE 12 pass D: inject a post-swap latency regression via a slow
    stub payload and prove the burn-rate monitor + probation rollback
    close the loop: breach detected, prior version re-activated, interval
    p99 recovered under the SLO, the bad version's re-push answers 409,
    zero 5xx throughout."""
    import urllib.error
    import urllib.request

    from tpu_pipelines.observability.metrics import histogram_quantile
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.trainer.export import export_model

    batch_slo_p99_ms = 250.0        # the batcher's gather-window budget
    n_threads = 4
    per_phase = 3 if smoke else 12
    drill_dir = os.path.join(td, "drill")
    slow_module = os.path.join(td, "slow_model.py")
    with open(slow_module, "w") as f:
        # A genuinely slow payload: the per-call fori_loop matmul chain
        # costs real device time EVERY call (a sleep would vanish into
        # the jit trace), so the post-swap regression is the kind a bad
        # quantization/compile actually produces.  ~1-8 GFLOP per call
        # keeps it decisively over the drill SLO on any host class.
        f.write(
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def build_model(hp):\n"
            "    return None\n"
            "def apply_fn(model, params, batch):\n"
            "    x = jnp.asarray(batch['x'], jnp.float32) @ params['w']\n"
            "    h = jnp.tile(x[:, :1], (1, 256))\n"
            "    h = jax.lax.fori_loop(\n"
            "        0, 30000, lambda i, a: jnp.tanh(a @ params['m']), h)\n"
            "    return h[:, :2]\n"
        )
    rng = np.random.default_rng(0)
    export_model(
        serving_model_dir=os.path.join(drill_dir, "1"),
        params={"w": np.eye(3, 2).astype(np.float32)},
        module_file=module,
    )
    export_model(
        serving_model_dir=os.path.join(drill_dir, "2"),
        params={
            "w": np.eye(3, 2).astype(np.float32),
            "m": (rng.standard_normal((256, 256)) * 0.05).astype(
                np.float32
            ),
        },
        module_file=slow_module,
    )
    v2 = os.path.join(drill_dir, "2")
    v2_staged = os.path.join(td, "drill-v2-staged")
    os.rename(v2, v2_staged)
    server = ModelServer(
        "drill", drill_dir,
        replicas=2, max_versions=2, slo_p99_ms=batch_slo_p99_ms,
        max_batch_size=8, batch_timeout_s=0.002,
        swap_probation_s=600.0,
    )
    port = server.start()
    url = f"http://127.0.0.1:{port}/v1/models/drill:predict"
    body = json.dumps({"instances": [{"x": [1.0, 2.0, 3.0]}]}).encode()
    fleet = server._fleet
    reload_url = f"http://127.0.0.1:{port}/v1/models/drill:reload"
    try:
        # Phase 1 — healthy v1 traffic.  The drill SLO is calibrated to
        # THIS box (4x the healthy p99, floored/capped): on a loaded
        # 1-core CI host the healthy tail is tens of ms of scheduler
        # jitter, on a real serving host single-digit ms — a fixed
        # target would misfire on one of them.  The slow payload's step
        # is decisively over the cap on any host class.
        _fleet_hammer(url, body, 1, 3)
        err1, _ = _fleet_hammer(url, body, n_threads, per_phase)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape_fast = r.read().decode()
        fast = _parse_prom_histogram(
            scrape_fast, "serving_request_latency_seconds",
            'endpoint="predict"',
        )
        p99_fast = histogram_quantile(
            {"buckets": fast["buckets"], "count": fast["count"],
             "sum": fast["sum"]},
            0.99, fast["bounds"],
        ) if fast else None
        slo_s = min(0.25, max(0.05, 4.0 * (p99_fast or 0.0125)))
        from tpu_pipelines.observability.slo import SLOMonitor

        monitor = SLOMonitor(
            server.metrics, slo_p99_s=slo_s,
            on_breach=fleet.on_slo_breach,
            min_events=min(8, n_threads * per_phase),
        )
        # Baseline snapshot for the burn windows (synthetic clock: the
        # drill must not wait real minutes between evaluations).
        monitor.evaluate(now=0.0)
        pre_breaches = int(_registry_drill_breaches(server))
        # Phase 2 — the bad push lands and swaps in mid-traffic.
        os.rename(v2_staged, v2)
        with urllib.request.urlopen(
            urllib.request.Request(reload_url, data=b"{}"), timeout=120
        ) as r:
            assert json.loads(r.read())["version"] == "2"
        err2, _ = _fleet_hammer(url, body, n_threads, per_phase)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape_bad = r.read().decode()
        # Phase 3 — the monitor sees the burn and the fleet rolls back.
        result = monitor.evaluate(now=60.0)
        breached = [b["slo"] for b in result["breaches"]]
        rolled_back = fleet.active_version == "1"
        rollbacks = int(_parse_prom_counter(
            scrape_bad, "serving_auto_rollbacks_total"
        ))
        # Phase 4 — recovered traffic; interval p99 from bucket deltas
        # (the cumulative histogram still holds the slow phase).
        err3, _ = _fleet_hammer(url, body, n_threads, per_phase)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape_end = r.read().decode()
        rollbacks = max(rollbacks, int(_parse_prom_counter(
            scrape_end, "serving_auto_rollbacks_total"
        )))
        # Phase 5 — the quarantined version's re-push answers 409.
        try:
            with urllib.request.urlopen(
                urllib.request.Request(reload_url, data=b"{}"), timeout=60
            ) as r:
                reload_code = r.status
        except urllib.error.HTTPError as e:
            reload_code = e.code
    finally:
        server.stop()
    bad = _parse_prom_histogram(
        scrape_bad, "serving_request_latency_seconds", 'endpoint="predict"'
    )
    end = _parse_prom_histogram(
        scrape_end, "serving_request_latency_seconds", 'endpoint="predict"'
    )
    recovered_p99_ms = None
    if bad and end and end["count"] > bad["count"]:
        delta = {
            "buckets": [
                b - a for a, b in zip(bad["buckets"], end["buckets"])
            ],
            "count": end["count"] - bad["count"],
            "sum": end["sum"] - bad["sum"],
        }
        q = histogram_quantile(delta, 0.99, end["bounds"])
        recovered_p99_ms = round(q * 1e3, 3) if q is not None else None
    drill_5xx = int(_parse_prom_counter(
        scrape_end, "serving_requests_total", 'code="5'
    ))
    slo_ms = round(slo_s * 1e3, 3)
    green = bool(
        err1 == 0 and err2 == 0 and err3 == 0
        and "latency_p99" in breached
        and int(_registry_drill_breaches_text(scrape_end)) > pre_breaches
        and rolled_back
        and rollbacks >= 1
        and reload_code == 409
        and drill_5xx == 0
        and recovered_p99_ms is not None
        and recovered_p99_ms < slo_ms
    )
    return {
        "green": green,
        "slo_p99_ms": slo_ms,
        "healthy_p99_ms": (
            round(p99_fast * 1e3, 3) if p99_fast is not None else None
        ),
        "breached_slos": breached,
        "rolled_back_to": "1" if rolled_back else None,
        "auto_rollbacks": rollbacks,
        "quarantined_reload_code": reload_code,
        "recovered_p99_ms": recovered_p99_ms,
        "drill_5xx": drill_5xx,
        "requests_per_phase": n_threads * per_phase,
    }


def _registry_drill_breaches(server) -> float:
    m = server.metrics.get("serving_slo_breaches_total")
    if m is None:
        return 0.0
    try:
        return m.labels("latency_p99").get()
    except Exception:  # noqa: BLE001 — no such series yet
        return 0.0


def _registry_drill_breaches_text(scrape: str) -> float:
    return _parse_prom_counter(
        scrape, "serving_slo_breaches_total", 'slo="latency_p99"'
    )


def bench_serving_fleet(smoke: bool) -> dict:
    """Serving-fleet leg (ISSUE 10), judged entirely from the fleet's OWN
    ``/metrics`` scrape, in two passes:

      A. **Steady state**: a sustained multi-thread REST hammer against
         the 2-replica fleet with SLO-driven batching; the scraped p99
         must land under the configured SLO target at the measured QPS.
      B. **Reload under load**: the hammer continues while a freshly
         pushed version hot-swaps via the ``:reload`` surface (the
         Pusher push-URL hook's path); the cumulative scrape must record
         zero 5xx across the whole leg and the swap must complete.

      C. **Traced pass** (ISSUE 12): the same hammer at matched request
         counts against a fleet with request-scoped tracing sampled on
         (``sample:4``, in-memory ring); ``trace_overhead_pct`` compares
         mean request latency traced vs untraced — the cost of the span
         plumbing at the bench QPS.

      D. **Rollback drill** (ISSUE 12): a slow payload hot-swaps in
         mid-traffic, the SLO burn-rate monitor detects the post-swap
         latency regression, the fleet auto-rolls back to the prior
         version, interval p99 recovers under the SLO, the bad version's
         re-``:reload`` answers 409, and the whole drill records zero
         5xx — ``slo_rollback_green``.

    Judging p99 from pass A keeps the verdict about the SLO batcher, not
    about CPU contention with the new version's (off-request-path) canary
    compile on small smoke boxes; pass B's zero-5xx is the drop-free
    contract the swap actually promises."""
    import re
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from tpu_pipelines.observability.metrics import histogram_quantile
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.trainer.export import export_model

    n_threads = 8
    n_requests = 160 if smoke else 960
    # The SLO window spends 0.35 x budget - 2 x step (batching.py), so
    # with the toy model's ~2-5 ms step the gather tops out ~85 ms and
    # the scraped p99 sits at least one log-bucket under the target.
    # The target itself budgets for a 1-core CI host (recorded as
    # host_cpus): 8 hammer threads + 2 batcher workers on one core add
    # tens of ms of pure scheduling jitter to the tail — on a multi-core
    # serving host the same leg reads several x lower.
    slo_p99_ms = 250.0
    max_queue_depth = 64
    with tempfile.TemporaryDirectory() as td:
        module = os.path.join(td, "toy_model.py")
        with open(module, "w") as f:
            f.write(
                "import jax.numpy as jnp\n"
                "def build_model(hp):\n"
                "    return None\n"
                "def apply_fn(model, params, batch):\n"
                "    return jnp.asarray(batch['x'], jnp.float32) "
                "@ params['w']\n"
            )
        for version in ("1", "2"):
            export_model(
                serving_model_dir=os.path.join(td, "m", version),
                params={"w": np.eye(3, 2).astype(np.float32)
                        * float(version)},
                module_file=module,
            )
        # v2 stays staged until mid-hammer (the server starts on v1).
        v2 = os.path.join(td, "m", "2")
        v2_hidden = os.path.join(td, "v2-staged")
        os.rename(v2, v2_hidden)
        server = ModelServer(
            "fleet", os.path.join(td, "m"),
            replicas=2, max_versions=2, slo_p99_ms=slo_p99_ms,
            max_batch_size=8, batch_timeout_s=0.002,
            max_queue_depth=max_queue_depth,
        )
        port = server.start()
        url = f"http://127.0.0.1:{port}/v1/models/fleet:predict"
        body = json.dumps({"instances": [{"x": [1.0, 2.0, 3.0]}]}).encode()
        errors = [0]
        codes: dict = {}
        codes_lock = threading.Lock()

        def fire(n: int) -> None:
            for _ in range(n):
                code = None
                try:
                    req = urllib.request.Request(url, data=body)
                    with urllib.request.urlopen(req, timeout=30) as r:
                        r.read()
                        code = r.status
                except urllib.error.HTTPError as e:
                    code = e.code  # shed 429s: counted, not errors
                except Exception:  # noqa: BLE001 — dropped connection
                    errors[0] += 1
                with codes_lock:
                    codes[code] = codes.get(code, 0) + 1

        def hammer(per_thread: int):
            threads = [
                threading.Thread(target=fire, args=(per_thread,))
                for _ in range(n_threads)
            ]
            for t in threads:
                t.start()
            return threads

        try:
            fire(3)  # warm-up: XLA compile + canary-batch capture
            # Pass A — steady state: p99 at the bench QPS, scraped before
            # any reload work shares the box.
            t0 = time.perf_counter()
            for t in hammer(n_requests // n_threads):
                t.join()
            wall = time.perf_counter() - t0
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as r:
                steady_scrape = r.read().decode()
            # Pass B — reload under load: blessed push lands mid-storm;
            # the :reload POST is exactly what the Pusher
            # TPP_SERVING_PUSH_URL hook sends.
            threads = hammer(max(1, n_requests // (2 * n_threads)))
            time.sleep(0.1)
            os.rename(v2_hidden, v2)
            reload_req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/models/fleet:reload",
                data=b"{}",
            )
            with urllib.request.urlopen(reload_req, timeout=60) as r:
                reloaded_to = json.loads(r.read())["version"]
            for t in threads:
                t.join()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as r:
                scrape = r.read().decode()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ) as r:
                health = json.loads(r.read())
        finally:
            server.stop()

        # Pass C — traced at matched counts: same model dir, same hammer
        # shape, request tracing sampled on (ring only: the flush-to-
        # file path is the CLI's, not the hot path's).
        traced = _fleet_traced_pass(
            os.path.join(td, "m"), n_threads, n_requests, slo_p99_ms,
            max_queue_depth,
        )

        # Pass D — SLO burn-rate rollback drill (own model dir).
        drill = _fleet_rollback_drill(td, module, smoke)

    hist = _parse_prom_histogram(
        steady_scrape, "serving_request_latency_seconds",
        'endpoint="predict"'
    )
    p99 = None
    if hist:
        series = {"buckets": hist["buckets"], "count": hist["count"],
                  "sum": hist["sum"]}
        p99 = histogram_quantile(series, 0.99, hist["bounds"])
    p99_ms = round(p99 * 1e3, 3) if p99 is not None else None
    served = int(hist["count"]) if hist else 0
    # Zero-5xx is judged over the WHOLE leg (steady + reload storm).
    reload_5xx = int(_parse_prom_counter(
        scrape, "serving_requests_total", 'code="5'
    ))
    shed = int(_parse_prom_counter(scrape, "serving_load_shed_total"))
    per_replica = {}
    for line in scrape.splitlines():
        m = re.match(
            r'serving_replica_requests_total\{replica="(\d+)"\} (\S+)', line
        )
        if m:
            per_replica[m.group(1)] = int(float(m.group(2)))
    swaps = int(_parse_prom_counter(scrape, "serving_version_swaps_total"))
    # Trace overhead: traced mean vs the pass-A untraced mean at matched
    # request counts (mean, not p99 — tails on a loaded 1-core CI box are
    # scheduler noise; the span plumbing's cost is a per-request constant).
    untraced_mean_ms = (
        round(hist["sum"] / hist["count"] * 1e3, 3)
        if hist and hist["count"] else None
    )
    trace_overhead_pct = None
    if untraced_mean_ms and traced.get("mean_latency_ms"):
        trace_overhead_pct = round(
            max(
                0.0,
                (traced["mean_latency_ms"] - untraced_mean_ms)
                / untraced_mean_ms * 100.0,
            ),
            2,
        )
    green = bool(
        errors[0] == 0
        and reload_5xx == 0
        and reloaded_to == "2"
        and bool(health.get("healthy"))
        and p99_ms is not None and p99_ms < slo_p99_ms
        and served + shed >= n_requests
        and swaps >= 2
    )
    return {
        "green": green,
        "requests": n_requests + n_threads * max(
            1, n_requests // (2 * n_threads)
        ) + 3,
        "request_errors": errors[0],
        "scraped_requests": served,
        "qps": round(n_requests / wall, 1) if wall else None,
        "p99_ms": p99_ms,
        "slo_p99_ms": slo_p99_ms,
        "slo_met": bool(p99_ms is not None and p99_ms < slo_p99_ms),
        "reload_5xx": reload_5xx,
        "reloaded_to": reloaded_to,
        "version_swaps": swaps,
        "shed_requests": shed,
        "codes": {str(k): v for k, v in sorted(codes.items(),
                                               key=lambda kv: str(kv[0]))},
        "replicas": 2,
        "per_replica_requests": per_replica,
        "max_queue_depth": max_queue_depth,
        "concurrency": n_threads,
        "host_cpus": os.cpu_count(),
        "healthz": health,
        "traced": traced,
        "untraced_mean_latency_ms": untraced_mean_ms,
        "trace_overhead_pct": trace_overhead_pct,
        "rollback_drill": drill,
        "slo_rollback_green": bool(drill.get("green")),
    }


def bench_serving_quantized(smoke: bool) -> dict:
    """Quantized + AOT serving leg (ISSUE 14), judged from the fleet's
    OWN ``/metrics`` scrape:

      1. **Rewrite.**  An embedding-retrieval payload (the weight-bytes-
         bound serving shape where int8 genuinely wins on any host: each
         request gathers K rows from a table far bigger than cache, so
         reading int8 rows moves a quarter of the bytes) runs through the
         Rewriter component: float32/bfloat16/aqt_int8 variants, quality
         gated on the Evaluator metric surface, int8 selected, AOT
         bucket executables pre-compiled into the serialized cache at
         export time.
      2. **Float pass.**  The fleet serves the float payload to a
         steady-state hammer (fresh random ids per request — no gather
         caching); per-request latency read as the scrape-delta mean.
      3. **Deploy.**  The Pusher (variant="aqt_int8") pushes the
         quantized payload and its push-URL hook fires the ``:reload``
         — canary, then AOT warmup that LOADS the export-time
         executables (cache hits, no compiles).
      4. **Int8 pass.**  The identical hammer against the quantized
         version; ``quantized_speedup`` = float mean / int8 mean, and
         the post-swap scrape must show
         ``serving_aot_compiles_after_warm_total == 0`` — the PR 12
         compiles-after-warm contract holding by construction.
    """
    import shutil
    import tempfile
    import threading
    import urllib.request

    from tpu_pipelines.components.pusher import Pusher
    from tpu_pipelines.components.rewriter import Rewriter
    from tpu_pipelines.data.examples_io import (
        table_from_columns,
        write_split,
    )
    from tpu_pipelines.dsl.component import ExecutorContext
    from tpu_pipelines.metadata.types import Artifact
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.trainer.export import export_model

    if smoke:
        vocab, dim, k_ids = 50_000, 384, 192
        n_requests = 120
    else:
        vocab, dim, k_ids = 100_000, 512, 256
        n_requests = 480
    n_threads = 4
    quality_tolerance = 0.05
    max_batch = 8
    rng = np.random.default_rng(14)

    prior_cache = os.environ.get("TPP_AOT_CACHE")
    with tempfile.TemporaryDirectory() as td:
        # Leg-scoped AOT cache: the cache-hit accounting below must see
        # exactly the Rewriter's export-time prewarm, not a prior run's.
        os.environ["TPP_AOT_CACHE"] = os.path.join(td, "aot-cache")
        module = os.path.join(td, "emb_model.py")
        with open(module, "w") as f:
            f.write(
                "import jax.numpy as jnp\n"
                "def build_model(hp):\n"
                "    return None\n"
                "def apply_fn(model, params, batch):\n"
                "    ids = jnp.asarray(batch['ids'], jnp.int32)\n"
                "    rows = params['emb'][ids]\n"
                "    return (rows.mean(axis=1) @ params['w'])"
                ".squeeze(-1)\n"
            )
        emb = rng.standard_normal((vocab, dim)).astype(np.float32)
        w = rng.standard_normal((dim, 1)).astype(np.float32) / np.sqrt(dim)
        model_dir = os.path.join(td, "model")
        export_model(
            serving_model_dir=model_dir,
            params={"emb": emb, "w": w}, module_file=module,
        )
        # Eval slice: labels = the float model + noise (regression).
        n_eval = 512
        eval_ids = rng.integers(
            0, vocab, size=(n_eval, k_ids)
        ).astype(np.int32)
        labels = (
            emb[eval_ids].mean(axis=1) @ w
        ).squeeze(-1) + 0.01 * rng.standard_normal(n_eval)
        examples_dir = os.path.join(td, "examples")
        write_split(examples_dir, "eval", table_from_columns({
            "ids": eval_ids, "label": labels.astype(np.float32),
        }))

        rewritten = Artifact(
            type_name="Model", uri=os.path.join(td, "rewritten")
        )
        rw_report = Rewriter.EXECUTOR(ExecutorContext(
            node_id="Rewriter",
            inputs={
                "model": [Artifact(type_name="Model", uri=model_dir)],
                "examples": [
                    Artifact(type_name="Examples", uri=examples_dir)
                ],
            },
            outputs={"model": [rewritten]},
            exec_properties={
                "variants": ["bfloat16", "aqt_int8"],
                "quality_tolerance": quality_tolerance,
                "quality_metrics": ["mae", "r2"],
                "label_key": "label", "problem": "regression",
                "eval_split": "eval", "batch_size": 128,
                "max_eval_examples": n_eval,
                "selection": "aqt_int8", "min_quant_size": 4096,
                "latency_batch_size": max_batch, "latency_iters": 30,
                "aot_warm_buckets": max_batch,
            },
        ))
        int8_info = rw_report["variants"]["aqt_int8"]
        assert int8_info["blessed"], int8_info

        base = os.path.join(td, "serving")
        os.makedirs(base)
        shutil.copytree(model_dir, os.path.join(base, "1"))
        server = ModelServer(
            "quant", base, replicas=1, max_versions=2,
            max_batch_size=max_batch, batch_timeout_s=0.002,
        )
        port = server.start()
        url = f"http://127.0.0.1:{port}/v1/models/quant:predict"
        id_pool = [
            json.dumps({"instances": [{
                "ids": rng.integers(0, vocab, size=k_ids).tolist()
            }]}).encode()
            for _ in range(64)
        ]
        errors = [0]
        fired = [0]
        fired_lock = threading.Lock()

        def fire(n: int) -> None:
            for _ in range(n):
                with fired_lock:
                    i = fired[0]
                    fired[0] += 1
                try:
                    req = urllib.request.Request(
                        url, data=id_pool[i % len(id_pool)]
                    )
                    with urllib.request.urlopen(req, timeout=60) as r:
                        r.read()
                except Exception:  # noqa: BLE001
                    errors[0] += 1

        def hammer() -> None:
            threads = [
                threading.Thread(
                    target=fire, args=(n_requests // n_threads,)
                )
                for _ in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        def scrape() -> str:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as r:
                return r.read().decode()

        def hist_state(text: str):
            h = _parse_prom_histogram(
                text, "serving_request_latency_seconds",
                'endpoint="predict"',
            )
            return h or {"count": 0, "sum": 0.0}

        def pass_mean_ms():
            """Warm the buckets, then measure one hammer pass as the
            scrape-delta mean latency (compiles excluded by the warm)."""
            fire(2 * max_batch)
            before = hist_state(scrape())
            t0 = time.perf_counter()
            hammer()
            wall = time.perf_counter() - t0
            after = hist_state(scrape())
            n = after["count"] - before["count"]
            s = after["sum"] - before["sum"]
            return (
                (s / n * 1e3) if n else None,
                round(n / wall, 1) if wall else None,
            )

        try:
            float_mean_ms, float_qps = pass_mean_ms()

            # Deploy the quantized variant through the Pusher's variant
            # selection + push-URL hook — the production path.
            pushed = Artifact(
                type_name="PushedModel", uri=os.path.join(td, "pushed")
            )
            push_result = Pusher.EXECUTOR(ExecutorContext(
                node_id="Pusher",
                inputs={"model": [
                    Artifact(type_name="Model", uri=rewritten.uri)
                ]},
                outputs={"pushed_model": [pushed]},
                exec_properties={
                    "push_destination": base,
                    "serving_push_url":
                        f"http://127.0.0.1:{port}/v1/models/quant",
                    "variant": "aqt_int8",
                },
            ))
            int8_mean_ms, int8_qps = pass_mean_ms()
            final_scrape = scrape()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ) as r:
                health = json.loads(r.read())
        finally:
            server.stop()
            if prior_cache is None:
                os.environ.pop("TPP_AOT_CACHE", None)
            else:
                os.environ["TPP_AOT_CACHE"] = prior_cache

    warmup_s = _parse_prom_gauge_value(
        final_scrape, "serving_swap_warmup_seconds"
    )
    aot_hits = int(_parse_prom_counter(
        final_scrape, "serving_aot_cache_hits_total"
    ))
    aot_compiles = int(_parse_prom_counter(
        final_scrape, "serving_aot_compiles_total"
    ))
    compiles_after_warm = int(_parse_prom_counter(
        final_scrape, "serving_aot_compiles_after_warm_total"
    ))
    speedup = (
        round(float_mean_ms / int8_mean_ms, 3)
        if float_mean_ms and int8_mean_ms else None
    )
    quality_delta = int8_info.get("max_quality_delta")
    green = bool(
        errors[0] == 0
        and push_result.get("pushed") is True
        and push_result.get("reload_notified") is True
        and str(health.get("version")) == "2"
        and speedup is not None and speedup > 1.0
        and quality_delta is not None
        and quality_delta <= quality_tolerance
        and compiles_after_warm == 0
        and aot_hits >= 1
    )
    return {
        "green": green,
        "model": {
            "vocab": vocab, "dim": dim, "ids_per_request": k_ids,
            "table_mb": round(emb.nbytes / 2**20, 1),
        },
        "requests_per_pass": n_requests,
        "request_errors": errors[0],
        "variants": rw_report["variants"],
        "selected_variant": rw_report["selected_variant"],
        "rewriter_speedup_vs_float": rw_report.get("speedup_vs_float"),
        "float_mean_ms": (
            round(float_mean_ms, 3) if float_mean_ms else None
        ),
        "int8_mean_ms": round(int8_mean_ms, 3) if int8_mean_ms else None,
        "float_qps": float_qps,
        "int8_qps": int8_qps,
        "quantized_speedup": speedup,
        "quantized_quality_delta": quality_delta,
        "quality_tolerance": quality_tolerance,
        "pushed_version": push_result.get("pushed_version"),
        "reload_notified": push_result.get("reload_notified"),
        "swap_warmup_seconds": warmup_s,
        "aot_cache_hits": aot_hits,
        "aot_compiles": aot_compiles,
        "aot_compiles_after_warm": compiles_after_warm,
        "memory_bytes": {
            "float32": rw_report["variants"]["float32"]["params_bytes"],
            "aqt_int8": int8_info["params_bytes"],
        },
        "host_cpus": os.cpu_count(),
        "healthz": health,
    }


def _parse_prom_gauge_value(text: str, name: str):
    """Value of an unlabeled gauge in a Prometheus text scrape."""
    for line in text.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[0] == name:
            try:
                return float(parts[1])
            except ValueError:
                return None
    return None


def bench_generative_serving(smoke: bool) -> dict:
    """Continuous-batching decode leg (ISSUE 11), judged from the fleet's
    OWN ``/metrics`` scrape, as an A/B on identical traffic:

      A. **Continuous** (``model_type="generative"``): mixed-length
         requests with Poisson-jittered arrivals hammer the REST
         ``:generate`` surface of a generative fleet — sequences join the
         running decode batch per step and leave at EOS / their own
         ``max_new_tokens``.  Headline tokens/s and p99-per-token come
         from the fleet's scrape (``serving_decode_*``); a second pass
         hot-swaps a freshly pushed version MID-HAMMER and the cumulative
         scrape must show zero 5xx (in-flight generations finish on the
         version they started on).
      B. **Whole-request**: the SAME requests (same inputs, same wanted
         budgets) against the same payload served the PR-10 way — each
         request decodes alone to the exported ``max_decode_len``
         regardless of how few tokens it wants.

    Useful tokens are counted identically on both sides (the stream up to
    EOS, capped at the requested budget — greedy math is identical, so
    per-request counts agree); the speedup is useful-tokens/s A over B.

    A third pass (ISSUE 16) measures the decode-path optimisations on the
    traffic shape they exist for — **long-shared-prefix**: every request
    carries the same long prompt (the shared-system-prompt regime) with a
    short reply budget, served twice on separate fleets from the same
    payload — optimisations ON (refcounted prefix caching + chunked
    prefill) vs the plain PR-11 engine.
    Green requires >= 1.3x useful tokens/s at no-worse client
    p99-per-token, and the fleet's own scrape supplies the prefix-cache
    hit rate for the report.
    """
    import queue as queue_mod
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import jax

    from tpu_pipelines.models.t5 import build_t5_model
    from tpu_pipelines.observability.metrics import histogram_quantile
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.trainer.export import export_model

    # Geometry note: the exported max_decode_len is the whole-request
    # pass's fixed cost (its scan always runs the full exported budget,
    # EOS is masking not control flow) while the continuous pass pays
    # only each request's OWN ``max_new_tokens`` — exactly the asymmetry
    # the engine exists to exploit, and the realistic serving shape: one
    # exported ceiling, mostly-short replies.  The model is sized so
    # decode compute (not HTTP framing) dominates both passes even on a
    # 1-core smoke host; two rows per request halve the framing share.
    if smoke:
        hp = {"vocab_size": 64, "d_model": 128, "n_layers": 2,
              "n_heads": 4, "head_dim": 16, "d_ff": 384,
              "dropout_rate": 0.0, "max_decode_len": 128, "eos_id": 1,
              "max_input_len": 8}
        n_requests, n_threads = 40, 8
    else:
        hp = {"vocab_size": 256, "d_model": 128, "n_layers": 2,
              "n_heads": 4, "head_dim": 16, "d_ff": 384,
              "dropout_rate": 0.0, "max_decode_len": 128, "eos_id": 1,
              "max_input_len": 8}
        n_requests, n_threads = 200, 8
    dec_len = hp["max_decode_len"]
    in_len = hp["max_input_len"]
    rows_per_request = 2
    long_budget = 48  # the 15% "long reply" tail; shorts want 3-7

    module_src = (
        "import jax.numpy as jnp\n"
        "from tpu_pipelines.models.t5 import (\n"
        "    build_t5_model, make_continuous_decode_fns,\n"
        "    make_greedy_generate,\n"
        ")\n"
        "def build_model(hp):\n"
        "    return build_t5_model(hp)\n"
        "def make_generate_step(model, hp):\n"
        "    gen = make_greedy_generate(\n"
        "        model, max_decode_len=int(hp['max_decode_len']),\n"
        "        eos_id=int(hp['eos_id']))\n"
        "    def fn(params, batch):\n"
        "        mask = (jnp.asarray(batch['input_mask'], jnp.int32)\n"
        "                if 'input_mask' in batch else None)\n"
        "        tokens, _ = gen(\n"
        "            params, jnp.asarray(batch['inputs'], jnp.int32), mask)\n"
        "        return tokens\n"
        "    return fn\n"
        "def make_decode_fns(model, hp):\n"
        "    return make_continuous_decode_fns(\n"
        "        model, max_decode_len=int(hp['max_decode_len']),\n"
        "        eos_id=int(hp['eos_id']),\n"
        "        max_input_len=int(hp['max_input_len']))\n"
    )

    # Identical traffic for both passes: mixed true lengths padded to one
    # wire shape (no per-shape recompiles on either side), mixed decode
    # budgets — mostly short replies plus a 15% tail wanting the full
    # budget, the mix whole-request batching is worst at.
    rng = np.random.default_rng(0)
    requests = []
    for _ in range(n_requests):
        rows = []
        for _ in range(rows_per_request):
            true_len = int(rng.integers(2, in_len + 1))
            row = rng.integers(2, min(60, hp["vocab_size"]), size=(in_len,))
            rows.append({
                "inputs": [int(x) for x in row],
                "input_mask": [1] * true_len + [0] * (in_len - true_len),
            })
        m = long_budget if rng.random() < 0.15 else int(rng.integers(3, 8))
        requests.append({"rows": rows, "max_new_tokens": m})
    wanted_total = sum(
        r["max_new_tokens"] * rows_per_request for r in requests
    )

    def useful_tokens(stream, m):
        n = 0
        for t in stream[:m]:
            n += 1
            if t == hp["eos_id"]:
                break
        return n

    def hammer(url, with_params: bool, reqs) -> dict:
        """Closed-loop n_threads workers with exponential (Poisson)
        arrival jitter; returns per-request latency + useful tokens."""
        work: "queue_mod.Queue" = queue_mod.Queue()
        for r in reqs:
            work.put(r)
        out_lock = threading.Lock()
        lat, tok, errors, codes = [], [], [0], {}
        jit_rng = np.random.default_rng(1)

        def worker():
            while True:
                try:
                    r = work.get_nowait()
                except queue_mod.Empty:
                    return
                payload = {"instances": r["rows"]}
                if with_params:
                    payload["params"] = {
                        "max_new_tokens": r["max_new_tokens"]
                    }
                body = json.dumps(payload).encode()
                with out_lock:
                    delay = float(jit_rng.exponential(0.002))
                time.sleep(delay)
                t0 = time.perf_counter()
                code = None
                try:
                    req = urllib.request.Request(url, data=body)
                    with urllib.request.urlopen(req, timeout=120) as resp:
                        streams = json.loads(resp.read())["outputs"]
                        code = resp.status
                except urllib.error.HTTPError as e:
                    code = e.code
                    streams = []
                except Exception:  # noqa: BLE001 — dropped connection
                    errors[0] += 1
                    streams = []
                dt = time.perf_counter() - t0
                with out_lock:
                    codes[code] = codes.get(code, 0) + 1
                    if code == 200:
                        u = sum(
                            useful_tokens(s, r["max_new_tokens"])
                            for s in streams
                        )
                        lat.append(dt)
                        tok.append(u)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=worker) for _ in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        per_tok_ms = sorted(
            d / max(1, u) * 1e3 for d, u in zip(lat, tok)
        )
        return {
            "wall_s": wall,
            "useful_tokens": sum(tok),
            "tok_s": round(sum(tok) / wall, 1) if wall else None,
            "p99_ms_per_token": (
                round(per_tok_ms[int(0.99 * (len(per_tok_ms) - 1))], 3)
                if per_tok_ms else None
            ),
            "errors": errors[0],
            "codes": {str(k): v for k, v in sorted(
                codes.items(), key=lambda kv: str(kv[0])
            )},
        }

    with tempfile.TemporaryDirectory() as td:
        module = os.path.join(td, "gen_model.py")
        with open(module, "w") as f:
            f.write(module_src)
        model = build_t5_model(hp)
        sample = {"inputs": np.ones((1, in_len), np.int32),
                  "targets": np.ones((1, 4), np.int32)}
        for version, seed in (("1", 0), ("2", 1)):
            params = model.init(jax.random.key(seed), sample)["params"]
            export_model(
                serving_model_dir=os.path.join(td, "a", version),
                params=params, module_file=module, hyperparameters=hp,
            )
        # B serves the SAME v1 payload from its own dir (no v2 in sight).
        import shutil

        shutil.copytree(os.path.join(td, "a", "1"), os.path.join(td, "b", "1"))
        v2 = os.path.join(td, "a", "2")
        v2_hidden = os.path.join(td, "v2-staged")
        os.rename(v2, v2_hidden)

        # ---- Pass A: continuous batching (generative fleet). ----------
        server_a = ModelServer(
            "gen", os.path.join(td, "a"),
            model_type="generative", max_batch_size=8, max_versions=2,
        )
        port = server_a.start()
        url_a = f"http://127.0.0.1:{port}/v1/models/gen:generate"
        try:
            a_warm = hammer(url_a, True, requests[:2])  # HTTP-path warmup
            a = hammer(url_a, True, requests)
            # Reload under load: stage v2, swap mid-hammer; generations
            # in flight finish on v1 (version leases), new ones decode
            # on v2 — zero 5xx over the cumulative scrape.
            threads = threading.Thread(
                target=lambda: hammer(
                    url_a, True, requests[: max(6, n_requests // 3)]
                )
            )
            threads.start()
            time.sleep(0.05)
            os.rename(v2_hidden, v2)
            reload_req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/models/gen:reload", data=b"{}",
            )
            with urllib.request.urlopen(reload_req, timeout=300) as r:
                reloaded_to = json.loads(r.read())["version"]
            threads.join()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as r:
                scrape = r.read().decode()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ) as r:
                health = json.loads(r.read())
        finally:
            server_a.stop()

        # ---- Pass B: whole-request decode on the same payload. --------
        server_b = ModelServer("req", os.path.join(td, "b"))
        port_b = server_b.start()
        url_b = f"http://127.0.0.1:{port_b}/v1/models/req:generate"
        try:
            hammer(url_b, False, requests[:2])          # compile + warmup
            b = hammer(url_b, False, requests)
        finally:
            server_b.stop()

        # ---- Pass C: long-shared-prefix, optimised vs plain engine. ---
        # The shared-system-prompt regime: a LONG prompt (prefill is the
        # dominant per-request cost) identical across every request, short
        # reply budgets.  With the prefix cache on, only the first
        # admission pays the encoder+prefill; every later one rescatters
        # the cached pages.  Chunked prefill keeps the (rare) misses from
        # stalling live decoders.
        hp_c = {**hp, "max_input_len": 48, "max_decode_len": 32}
        in_c = hp_c["max_input_len"]
        n_c = 24 if smoke else 80
        shared_row = {
            "inputs": [int(x) for x in rng.integers(
                2, min(60, hp_c["vocab_size"]), size=(in_c,)
            )],
            "input_mask": [1] * in_c,
        }
        reqs_c = [
            {"rows": [shared_row, shared_row],
             "max_new_tokens": int(rng.integers(4, 9))}
            for _ in range(n_c)
        ]
        module_c = os.path.join(td, "gen_model_c.py")
        with open(module_c, "w") as f:
            f.write(module_src)
        model_c = build_t5_model(hp_c)
        sample_c = {"inputs": np.ones((1, in_c), np.int32),
                    "targets": np.ones((1, 4), np.int32)}
        params_c = model_c.init(jax.random.key(0), sample_c)["params"]
        export_model(
            serving_model_dir=os.path.join(td, "c", "1"),
            params=params_c, module_file=module_c, hyperparameters=hp_c,
        )

        def prefix_pass(name: str, **engine_knobs) -> tuple:
            server = ModelServer(
                name, os.path.join(td, "c"),
                model_type="generative", max_batch_size=8,
                **engine_knobs,
            )
            p = server.start()
            url = f"http://127.0.0.1:{p}/v1/models/{name}:generate"
            try:
                hammer(url, True, reqs_c[:2])           # compile + warmup
                res = hammer(url, True, reqs_c)
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{p}/metrics", timeout=10
                ) as r:
                    sc = r.read().decode()
            finally:
                server.stop()
            return res, sc

        c_on, scrape_c = prefix_pass(
            "pfx", prefix_cache_entries=8, prefill_chunk_pages=4,
        )
        c_off, _ = prefix_pass("plain")

    decode_5xx = int(_parse_prom_counter(
        scrape, "serving_requests_total", 'code="5'
    ))
    hist = _parse_prom_histogram(
        scrape, "serving_decode_per_token_latency_seconds", 'replica="0"'
    )
    scraped_p99_tok_ms = None
    if hist:
        series = {"buckets": hist["buckets"], "count": hist["count"],
                  "sum": hist["sum"]}
        q = histogram_quantile(series, 0.99, hist["bounds"])
        scraped_p99_tok_ms = round(q * 1e3, 3) if q is not None else None
    scraped_tokens = int(_parse_prom_counter(
        scrape, "serving_decode_tokens_total"
    ))
    scraped_steps = int(_parse_prom_counter(
        scrape, "serving_decode_steps_total"
    ))
    speedup = (
        round(a["tok_s"] / b["tok_s"], 2)
        if a["tok_s"] and b["tok_s"] else None
    )
    # Pass C verdict off the optimised fleet's own scrape: hit rate over
    # admissions.
    pfx_hits = _parse_prom_counter(scrape_c, "serving_decode_prefix_hit_total")
    pfx_miss = _parse_prom_counter(scrape_c, "serving_decode_prefix_miss_total")
    prefix_hit_rate = (
        round(pfx_hits / (pfx_hits + pfx_miss), 3)
        if (pfx_hits + pfx_miss) else None
    )
    prefix_speedup = (
        round(c_on["tok_s"] / c_off["tok_s"], 2)
        if c_on["tok_s"] and c_off["tok_s"] else None
    )
    green = bool(
        a["errors"] == 0 and b["errors"] == 0
        and decode_5xx == 0
        and reloaded_to == "2"
        and bool(health.get("healthy"))
        and speedup is not None and speedup >= 2.0
        and a["p99_ms_per_token"] is not None
        and b["p99_ms_per_token"] is not None
        and a["p99_ms_per_token"] <= b["p99_ms_per_token"]
        # ISSUE 16: the decode-path optimisations must EARN their keep on
        # long-shared-prefix traffic — throughput up, tail not worse.
        and c_on["errors"] == 0 and c_off["errors"] == 0
        and prefix_speedup is not None and prefix_speedup >= 1.3
        and c_on["p99_ms_per_token"] is not None
        and c_off["p99_ms_per_token"] is not None
        and c_on["p99_ms_per_token"] <= c_off["p99_ms_per_token"]
    )
    return {
        "green": green,
        "continuous": a,
        "whole_request": b,
        "shared_prefix": {
            "optimized": c_on,
            "plain_engine": c_off,
            "speedup": prefix_speedup,
            "prefix_hit_rate": prefix_hit_rate,
            "prefix_hits": int(pfx_hits),
            "prefix_misses": int(pfx_miss),
        },
        "warmup": a_warm["codes"],
        "decode_tok_s": a["tok_s"],
        "decode_p99_ms_per_token": scraped_p99_tok_ms,
        "client_p99_ms_per_token": {
            "continuous": a["p99_ms_per_token"],
            "whole_request": b["p99_ms_per_token"],
        },
        "continuous_vs_request_speedup": speedup,
        "decode_5xx": decode_5xx,
        "reloaded_to": reloaded_to,
        "scraped_decode_tokens": scraped_tokens,
        "scraped_decode_steps": scraped_steps,
        "requests_per_pass": n_requests,
        "wanted_tokens_per_pass": wanted_total,
        "max_decode_len": dec_len,
        "concurrency": n_threads,
        "host_cpus": os.cpu_count(),
        "healthz": health,
    }


def _trace_regression_report(prev_report, report: dict, smoke: bool) -> dict:
    """Self-report regressions vs the PREVIOUS bench run: diff the taxi
    e2e leg's trace-derived per-node profile against the one the prior
    run left in BENCH_PARTIAL.json (same smoke mode only — 4-step smoke
    walls are not comparable to 200-step full walls).  Advisory, not a
    gate: the flags land in the report and the compact line."""
    from tpu_pipelines.observability import diff_metrics

    def taxi_trace(rep):
        if not isinstance(rep, dict):
            return None
        tr = ((rep.get("pipeline_e2e") or {}).get("taxi") or {}).get("trace")
        return tr if isinstance(tr, dict) and tr.get("per_node") else None

    cur = taxi_trace(report)
    out: dict = {
        "baseline": None,
        "regression_flags": [],
        "threshold": 0.25,
    }
    if cur is None:
        out["note"] = "no current taxi trace to diff"
        return out
    prev = taxi_trace(prev_report)
    if prev is None:
        out["note"] = "no prior bench trace (first run, or crashed prior)"
        return out
    if bool(prev_report.get("smoke")) != smoke:
        out["note"] = "prior bench ran in a different smoke mode"
        return out
    diff = diff_metrics(prev, cur, threshold=out["threshold"])
    out["baseline"] = "BENCH_PARTIAL.json (previous run)"
    out["regression_flags"] = diff["regression_flags"]
    out["regressed"] = diff["regressed"]
    out["critical_path_delta_frac"] = diff["critical_path_delta_frac"]
    out["diff"] = diff
    return out


def _parse_prom_counter(text: str, name: str, label_filter: str = "") -> float:
    """Sum a counter family's samples from a Prometheus text scrape,
    optionally filtered by a label substring (e.g. ``code="5``)."""
    total = 0.0
    for line in text.splitlines():
        if not (line.startswith(name + "{") or line.startswith(name + " ")):
            continue
        if label_filter and label_filter not in line:
            continue
        try:
            total += float(line.rsplit(None, 1)[1])
        except (ValueError, IndexError):
            pass
    return total


def _registry_total(name: str, site_prefix: str = "") -> float:
    """Sum one counter family from the process metrics registry (optionally
    filtered by the first label value's prefix) — how the chaos leg
    quantifies retries/quarantines without private bookkeeping."""
    from tpu_pipelines.observability.metrics import default_registry

    metric = default_registry().get(name)
    if metric is None:
        return 0.0
    return sum(
        float(v) for key, v in metric._snapshot_series().items()
        if not site_prefix or (key and str(key[0]).startswith(site_prefix))
    )


def _bench_taxi_chaos(smoke: bool) -> dict:
    """The ``robustness.taxi_chaos`` leg (ISSUE 7): the taxi pipeline runs
    to completion under an injected fault schedule — transient executor
    errors at the Trainer, one killed StatisticsGen shard worker, store
    contention on publishes — and its decisive lineage must be identical
    (id-free) to a fault-free run's, with merged statistics exact.  A
    serving hammer with admission control then takes a hot reload
    mid-storm and must record zero 5xx (shed 429s are counted, never
    dropped).  Retries/quarantines come off the process metrics registry
    — the same counters an operator's scrape would show.
    """
    import shutil
    import tempfile
    import threading
    import urllib.request

    from tpu_pipelines.data.shard_plan import map_shards_resilient
    from tpu_pipelines.data.statistics import load_statistics
    from tpu_pipelines.orchestration import LocalDagRunner
    from tpu_pipelines.robustness import RetryPolicy
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.testing.faults import (
        KILL_SHARD_WORKER,
        RELOAD_DURING_HAMMER,
        SERVING_KEY,
        SHARD_KEY,
        STORE_CONTENTION,
        STORE_KEY,
        TRANSIENT_EXECUTOR_ERROR,
        FaultPlan,
        NodeFault,
    )
    from tpu_pipelines.trainer.export import export_model
    from tpu_pipelines.utils.module_loader import load_fn

    module = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", "taxi", "pipeline.py",
    )
    env = {
        "TAXI_TRAIN_STEPS": "4" if smoke else "100",
        "TPP_DISABLE_MID_CHECKPOINT": "1",
        # Both runs ingest 2-shard Examples so StatisticsGen fans out
        # (the kill-shard-worker fault needs a pool) and the layouts —
        # and so the lineage — stay comparable.
        "TPP_DATA_SHARDS": "2",
    }
    # Armed for the CHAOS run only: the fleet-default retry rung
    # (docs/RECOVERY.md precedence) covers every layer the schedule hits,
    # including per-shard retries on 1-core hosts where the pool runs
    # sequentially.
    chaos_env = {
        "TPP_RETRY_MAX_ATTEMPTS": "3",
        "TPP_RETRY_BASE_DELAY_S": "0.05",
        "TPP_RETRY_MAX_DELAY_S": "0.5",
    }
    saved = {
        k: os.environ.get(k) for k in {**env, **chaos_env}
    }
    homes = [tempfile.mkdtemp(prefix=f"tpp-chaos-{t}-")
             for t in ("clean", "chaos")]
    counters_before = {
        "retries": _registry_total("retry_attempts_total"),
        "quarantined": _registry_total("shards_quarantined_total"),
        "deaths": _registry_total("shard_worker_deaths_total"),
        "store_retries": _registry_total(
            "retry_attempts_total", "metadata."
        ),
    }
    try:
        os.environ.update(env)
        clean_pipeline = load_fn(module, "create_pipeline")(homes[0])
        clean_result = LocalDagRunner().run(clean_pipeline)

        os.environ.update(chaos_env)
        chaos_pipeline = load_fn(module, "create_pipeline")(homes[1])
        # Component-level policy rung on the node the schedule hits
        # hardest (overrides the env default above).
        trainer = chaos_pipeline.get("Trainer")
        if trainer is not None:
            trainer.with_retry_policy(
                RetryPolicy(max_attempts=3, base_delay_s=0.05,
                            max_delay_s=0.5)
            )
        plan = FaultPlan({
            "Trainer": NodeFault(TRANSIENT_EXECUTOR_ERROR, times=2),
            SHARD_KEY: NodeFault(KILL_SHARD_WORKER, shard=1),
            STORE_KEY: NodeFault(STORE_CONTENTION, times=2),
        })
        with plan.activate():
            chaos_result = LocalDagRunner().run(chaos_pipeline)
        fault_log = sorted({e for _, e in plan.log})
        # The shard kill fires inside a fork child (its log entry dies
        # with the worker); the replacement-worker counter is the proof
        # it happened during the TAXI run, before the salvage demo below
        # adds its own deaths.
        taxi_worker_deaths = round(
            _registry_total("shard_worker_deaths_total")
            - counters_before["deaths"], 1
        )

        decisive = ("COMPLETE", "CACHED")
        lineage_identical = _canonical_lineage(
            clean_pipeline.metadata_path, clean_pipeline.pipeline_root,
            states=decisive, strip_exec_ids=True,
        ) == _canonical_lineage(
            chaos_pipeline.metadata_path, chaos_pipeline.pipeline_root,
            states=decisive, strip_exec_ids=True,
        )

        def stats_of(result):
            arts = result.outputs_of("StatisticsGen", "statistics")
            return load_statistics(arts[0].uri) if arts else None

        clean_stats = stats_of(clean_result)
        chaos_stats = stats_of(chaos_result)
        stats_identical = bool(
            clean_stats and chaos_stats
            and set(clean_stats) == set(chaos_stats)
            and all(
                _stats_close(clean_stats[s], chaos_stats[s])
                for s in clean_stats
            )
        )

        # Partial-salvage quantification: a poison shard that kills its
        # worker on every attempt is quarantined and the survivors'
        # merged statistics stay exact — proven here on a direct
        # resilient fan-out (the pipeline runs above must NOT quarantine:
        # identical lineage requires every shard's rows).
        salvage = map_shards_resilient(
            _chaos_poison_shard, [0, 1, 2, 3], workers=2,
            retry_policy=RetryPolicy(
                max_attempts=2, base_delay_s=0.01, max_delay_s=0.1
            ),
            label="chaos_salvage",
        )
        survivors = [r for r in salvage.results if r is not None]
        salvage_ok = (
            salvage.quarantined == [2]
            and sorted(survivors) == [0, 10, 30]
        )

        # Serving: admission-controlled hammer + reload mid-storm.
        sv = _chaos_serving_hammer(
            smoke, export_model, ModelServer, FaultPlan, NodeFault,
            SERVING_KEY, RELOAD_DURING_HAMMER, threading, urllib.request,
        )

        counters = {
            "retries_total": round(
                _registry_total("retry_attempts_total")
                - counters_before["retries"], 1),
            "store_retries": round(
                _registry_total("retry_attempts_total", "metadata.")
                - counters_before["store_retries"], 1),
            "shards_quarantined": round(
                _registry_total("shards_quarantined_total")
                - counters_before["quarantined"], 1),
            "worker_deaths": round(
                _registry_total("shard_worker_deaths_total")
                - counters_before["deaths"], 1),
        }
        fired_all = {
            "transient_executor_error", "store_contention:publish_execution",
        } <= set(fault_log)
        green = bool(
            chaos_result.succeeded and lineage_identical and stats_identical
            and salvage_ok and sv["reload_5xx"] == 0 and sv["reload_ok"]
            and sv["request_errors"] == 0 and fired_all
            and counters["retries_total"] >= 2
            and taxi_worker_deaths >= 1
        )
        return {"taxi_chaos": {
            "green": green,
            "lineage_identical": lineage_identical,
            "stats_identical": stats_identical,
            "faults_fired": fault_log,
            "taxi_worker_deaths": taxi_worker_deaths,
            "trainer_retries": chaos_result.nodes["Trainer"].retries,
            **counters,
            "salvage": {
                "ok": salvage_ok,
                "quarantined": salvage.quarantined,
                "retries": salvage.retries,
            },
            "serving": sv,
            "shed_requests": sv["shed_requests"],
            "reload_5xx": sv["reload_5xx"],
            "env": {**env, **chaos_env},
        }}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for home in homes:
            shutil.rmtree(home, ignore_errors=True)


def _chaos_poison_shard(x):
    """Module-level (picklable) poison worker for the salvage demo: shard
    2 dies on every attempt; everyone else returns x*10."""
    if x == 2:
        os._exit(11)
    return x * 10


def _chaos_serving_hammer(
    smoke, export_model, ModelServer, FaultPlan, NodeFault,
    SERVING_KEY, RELOAD_DURING_HAMMER, threading, urlreq,
) -> dict:
    """Admission-controlled REST hammer across a fault-injected hot
    reload: model v1 serves, v2 lands on disk, the RELOAD_DURING_HAMMER
    fault swaps mid-storm.  Zero-drop contract: every request answers
    200 (served) or 429 + Retry-After (shed, counted) — never a 5xx,
    never a dropped connection."""
    import tempfile

    n_threads = 4
    n_requests = 120 if smoke else 600
    with tempfile.TemporaryDirectory() as td:
        module = os.path.join(td, "toy_model.py")
        with open(module, "w") as f:
            f.write(
                "import jax.numpy as jnp\n"
                "def build_model(hp):\n"
                "    return None\n"
                "def apply_fn(model, params, batch):\n"
                "    return jnp.asarray(batch['x'], jnp.float32) "
                "@ params['w']\n"
            )
        for version in ("1", "2"):
            export_model(
                serving_model_dir=os.path.join(td, "m", version),
                params={"w": np.eye(3, 2).astype(np.float32)
                        * float(version)},
                module_file=module,
            )
        # v2 exists on disk but the server loads the highest version at
        # start — remove/rename dance is avoided by exporting v2 AFTER
        # start instead.
        v2 = os.path.join(td, "m", "2")
        v2_hidden = os.path.join(td, "v2-staged")
        os.rename(v2, v2_hidden)
        server = ModelServer(
            "chaos", os.path.join(td, "m"), batching=True,
            max_batch_size=8, batch_timeout_s=0.001, max_queue_depth=6,
        )
        port = server.start()
        url = f"http://127.0.0.1:{port}/v1/models/chaos:predict"
        body = json.dumps({"instances": [{"x": [1.0, 2.0, 3.0]}]}).encode()
        errors = [0]
        codes: dict = {}
        codes_lock = threading.Lock()

        import urllib.error

        def fire(n: int) -> None:
            for _ in range(n):
                code = None
                try:
                    req = urlreq.Request(url, data=body)
                    with urlreq.urlopen(req, timeout=30) as r:
                        r.read()
                        code = r.status
                except urllib.error.HTTPError as e:
                    code = e.code  # shed 429s / verdict codes: counted
                except Exception:  # noqa: BLE001 — dropped connection
                    errors[0] += 1
                with codes_lock:
                    codes[code] = codes.get(code, 0) + 1

        plan = FaultPlan({
            SERVING_KEY: NodeFault(
                RELOAD_DURING_HAMMER, after=n_requests // 4
            ),
        })
        try:
            fire(3)  # warm-up compile out of the storm
            os.rename(v2_hidden, v2)  # v2 is now the newest version
            with plan.activate():
                threads = [
                    threading.Thread(
                        target=fire, args=(n_requests // n_threads,)
                    )
                    for _ in range(n_threads)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                # The fault's reload thread may still be swapping.
                deadline = time.time() + 30
                while server.version != "2" and time.time() < deadline:
                    time.sleep(0.05)
            with urlreq.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as r:
                scrape = r.read().decode()
            reloaded_to = server.version
        finally:
            server.stop()
    reload_5xx = int(_parse_prom_counter(
        scrape, "serving_requests_total", 'code="5'
    ))
    shed = int(_parse_prom_counter(scrape, "serving_load_shed_total"))
    served_200 = int(_parse_prom_counter(
        scrape, "serving_requests_total", 'code="200'
    ))
    fault_fired = any(
        e.startswith("reload_during_hammer") for _, e in plan.log
    )
    return {
        "requests": n_requests + 3,
        "served_200": served_200,
        "shed_requests": shed,
        "reload_5xx": reload_5xx,
        "request_errors": errors[0],
        "codes": {str(k): v for k, v in sorted(codes.items(),
                                               key=lambda kv: str(kv[0]))},
        "reload_ok": reloaded_to == "2" and fault_fired,
        "reloaded_to": reloaded_to,
        "max_queue_depth": 6,
        "concurrency": n_threads,
    }


def _bench_serving_chaos(smoke: bool) -> dict:
    """The ``robustness.serving_chaos`` leg (ISSUE 17): kill 1-of-2
    replicas mid-hammer and judge the self-healing fleet from its OWN
    scrape.

    Two phases against real ModelServers with supervisor knobs on:

      - **predict chaos** — 8-thread REST hammer against a 2-replica
        fleet; KILL_REPLICA latches one replica dead mid-storm.  The
        contract: ``lost_requests == 0`` (every request answers 200 —
        failed attempts fail over to the survivor), the victim's breaker
        opens and closes again (``serving_breaker_transitions_total``),
        the fleet returns to full capacity (``serving_replica_state``
        all 0 after the in-place rebuild), and the incident-window p99
        stays bounded — nobody waits out a dead replica.
      - **decode chaos** — a 2-replica generative (tiny T5) fleet; the
        serving replica is killed mid-decode.  The lost sessions are
        re-prefilled onto the survivor and the recovered token streams
        must be IDENTICAL to the undisturbed reference (greedy
        determinism), counted in
        ``serving_decode_sessions_recovered_total``.

    Honesty caveat: the incident p99 budget (5 s) is sized for a 1-core
    CI host where 8 hammer threads + 2 batcher workers + the supervisor
    all share one core — ``host_cpus`` is recorded so the figure is
    interpretable; on a real serving host the same leg reads far lower.
    """
    import tempfile
    import threading
    import urllib.error
    import urllib.request as urlreq

    import jax

    from tpu_pipelines.models.t5 import build_t5_model
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.testing.faults import (
        KILL_REPLICA,
        REPLICA_KEY,
        FaultPlan,
        NodeFault,
    )
    from tpu_pipelines.trainer.export import export_model

    n_threads = 8
    per_thread = 20 if smoke else 60

    # ---- Phase 1: predict fleet, kill 1-of-2 mid-hammer. --------------
    with tempfile.TemporaryDirectory() as td:
        module = os.path.join(td, "toy_model.py")
        with open(module, "w") as f:
            f.write(
                "import jax.numpy as jnp\n"
                "def build_model(hp):\n"
                "    return None\n"
                "def apply_fn(model, params, batch):\n"
                "    return jnp.asarray(batch['x'], jnp.float32) "
                "@ params['w']\n"
            )
        export_model(
            serving_model_dir=os.path.join(td, "m", "1"),
            params={"w": np.eye(3, 2).astype(np.float32)},
            module_file=module,
        )
        server = ModelServer(
            "chaos", os.path.join(td, "m"), replicas=2,
            max_batch_size=8, batch_timeout_s=0.001,
            supervisor_interval_s=0.05,
        )
        port = server.start()
        url = f"http://127.0.0.1:{port}/v1/models/chaos:predict"
        body = json.dumps({"instances": [{"x": [1.0, 2.0, 3.0]}]}).encode()
        dropped = [0]
        codes: dict = {}
        lat: list = []
        lock = threading.Lock()

        def fire(n: int) -> None:
            for _ in range(n):
                code = None
                t0 = time.perf_counter()
                try:
                    req = urlreq.Request(url, data=body)
                    with urlreq.urlopen(req, timeout=60) as r:
                        r.read()
                        code = r.status
                except urllib.error.HTTPError as e:
                    code = e.code
                except Exception:  # noqa: BLE001 — dropped connection
                    dropped[0] += 1
                with lock:
                    lat.append(time.perf_counter() - t0)
                    codes[code] = codes.get(code, 0) + 1

        # The kill lands on the ``after``-th replica predict/heartbeat
        # call fleet-wide — deep enough into the storm that the victim
        # has live traffic to fail over.
        plan = FaultPlan({
            REPLICA_KEY: NodeFault(KILL_REPLICA, after=12),
        })
        try:
            fire(3)  # warm the compile out of the storm
            with lock:
                lat.clear()
                codes.clear()
            with plan.activate():
                threads = [
                    threading.Thread(target=fire, args=(per_thread,))
                    for _ in range(n_threads)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                # Full-capacity recovery, judged from the scrape: the
                # supervisor ejects, rebuilds in place, re-admits.
                deadline = time.time() + 20
                recovered = False
                while time.time() < deadline and not recovered:
                    with urlreq.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=10
                    ) as r:
                        scrape = r.read().decode()
                    recovered = (
                        _parse_prom_counter(
                            scrape, "serving_replica_state"
                        ) == 0.0
                        and "serving_replica_state" in scrape
                    )
                    if not recovered:
                        time.sleep(0.1)
            # Post-incident traffic on the healed fleet (plan retired:
            # the rebuilt incarnation runs clean).
            post_before = len(lat)
            fire(8)
            with urlreq.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as r:
                scrape = r.read().decode()
        finally:
            server.stop()
        incident_lat = sorted(lat[:post_before])
        incident_p99_ms = (
            round(incident_lat[int(0.99 * (len(incident_lat) - 1))] * 1e3, 3)
            if incident_lat else None
        )
        failovers = int(_parse_prom_counter(scrape, "serving_failovers_total"))
        unavailable = int(_parse_prom_counter(
            scrape, "serving_fleet_unavailable_total"
        ))
        breaker_transitions = int(_parse_prom_counter(
            scrape, "serving_breaker_transitions_total"
        ))
        served_5xx = int(_parse_prom_counter(
            scrape, "serving_requests_total", 'code="5'
        ))
        lost = dropped[0] + sum(
            n for code, n in codes.items() if code != 200
        )
        killed = [v for _, v in plan.log if v.startswith("kill_replica:")]

    # ---- Phase 2: generative fleet, kill the decoding replica. --------
    hp = {"vocab_size": 64, "d_model": 32, "n_layers": 2, "n_heads": 2,
          "head_dim": 8, "d_ff": 64, "dropout_rate": 0.0,
          "max_decode_len": 32, "eos_id": 1, "max_input_len": 6}
    module_src = (
        "from tpu_pipelines.models.t5 import (\n"
        "    build_t5_model, make_continuous_decode_fns,\n"
        ")\n"
        "def build_model(hp):\n"
        "    return build_t5_model(hp)\n"
        "def make_decode_fns(model, hp):\n"
        "    return make_continuous_decode_fns(\n"
        "        model, max_decode_len=int(hp['max_decode_len']),\n"
        "        eos_id=int(hp['eos_id']),\n"
        "        max_input_len=int(hp['max_input_len']))\n"
    )
    with tempfile.TemporaryDirectory() as td:
        module = os.path.join(td, "gen_model.py")
        with open(module, "w") as f:
            f.write(module_src)
        model = build_t5_model(hp)
        sample = {"inputs": np.ones((1, 6), np.int32),
                  "targets": np.ones((1, 4), np.int32)}
        params = model.init(jax.random.key(0), sample)["params"]
        export_model(
            serving_model_dir=os.path.join(td, "g", "1"),
            params=params, module_file=module, hyperparameters=hp,
        )
        server = ModelServer(
            "gen", os.path.join(td, "g"), model_type="generative",
            replicas=2, max_batch_size=4, supervisor_interval_s=0.05,
        )
        port = server.start()
        url = f"http://127.0.0.1:{port}/v1/models/gen:generate"
        gen_body = json.dumps({
            "instances": [
                {"inputs": [3, 5, 7, 2, 0, 0],
                 "input_mask": [1, 1, 1, 1, 0, 0]},
                {"inputs": [9, 4, 2, 0, 0, 0],
                 "input_mask": [1, 1, 1, 0, 0, 0]},
            ],
            "params": {"max_new_tokens": 24},
        }).encode()

        def generate():
            req = urlreq.Request(url, data=gen_body)
            with urlreq.urlopen(req, timeout=300) as r:
                return json.loads(r.read())["outputs"]

        fleet = server._fleet
        try:
            reference = generate()
            # Probes off during the kill so the FIRST replica_predict
            # call is the decode worker's fault hook — the kill lands
            # mid-stream on the serving replica, deterministically.
            fleet.supervisor.stop()
            plan = FaultPlan({REPLICA_KEY: NodeFault(KILL_REPLICA)})
            with plan.activate():
                recovered_streams = generate()
                for _ in range(3):  # eject + rebuild the dead replica
                    fleet.supervisor.probe_once()
                healed_streams = generate()
            fleet.supervisor.start()
            with urlreq.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as r:
                gen_scrape = r.read().decode()
        finally:
            server.stop()
        sessions_recovered = int(_parse_prom_counter(
            gen_scrape, "serving_decode_sessions_recovered_total"
        ))
        streams_identical = (
            recovered_streams == reference and healed_streams == reference
        )

    green = bool(
        lost == 0
        and served_5xx == 0
        and len(killed) == 1
        and failovers >= 1
        and breaker_transitions >= 2
        and recovered
        and incident_p99_ms is not None and incident_p99_ms < 5000.0
        and sessions_recovered >= 1
        and streams_identical
    )
    return {"serving_chaos": {
        "green": green,
        "requests": n_threads * per_thread,
        "lost_requests": lost,
        "served_5xx": served_5xx,
        "codes": {str(k): v for k, v in sorted(
            codes.items(), key=lambda kv: str(kv[0])
        )},
        "killed": killed,
        "failovers": failovers,
        "fleet_unavailable": unavailable,
        "breaker_transitions": breaker_transitions,
        "recovered_full_capacity": recovered,
        "incident_p99_ms": incident_p99_ms,
        "sessions_recovered": sessions_recovered,
        "recovered_streams_identical": streams_identical,
        "concurrency": n_threads,
        # 1-core honesty: the p99 above includes pure scheduling jitter
        # when hammer threads, batchers and the supervisor share a core.
        "host_cpus": os.cpu_count(),
    }}


def bench_robustness(smoke: bool) -> dict:
    """Crash-safe resume on the taxi DAG: work saved vs a cold re-run.

    The ``taxi_faults`` leg is the on-hardware evidence for the resume
    layer's contract (docs/RECOVERY.md): kill the orchestrator at the
    Trainer dispatch (the most expensive node), then ``resume_from=
    "latest"`` — the five upstream data-plane nodes must be ADOPTED (same
    execution ids/URIs, zero recompute) and only Trainer + its three
    descendants re-run.  Reported:

      - ``resume_wall_s`` vs ``cold_wall_s`` (an identical full run in a
        fresh home) and the ``work_saved_ratio`` = 1 - resume/cold;
      - ``lineage_identical``: the stitched run's decisive
        (COMPLETE/CACHED) lineage equals the cold run's, id-free and with
        embedded execution ids normalized out — adoption preserved the
        original artifacts and the re-runs published the same graph shape.

    A throwaway warm-up run absorbs in-process one-time costs (module
    loads, XLA compiles) first, so neither measured leg pays them — the
    same discipline as the scheduler-comparison leg.
    """
    import shutil
    import tempfile

    from tpu_pipelines.orchestration import LocalDagRunner
    from tpu_pipelines.testing.faults import (
        KILL_ORCHESTRATOR,
        FaultPlan,
        NodeFault,
        SimulatedCrash,
    )
    from tpu_pipelines.utils.module_loader import load_fn

    module = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "examples", "taxi", "pipeline.py",
    )
    env = {
        "TAXI_TRAIN_STEPS": "4" if smoke else "200",
        "TPP_DISABLE_MID_CHECKPOINT": "1",
    }
    kill_node = "Trainer"
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    homes = [tempfile.mkdtemp(prefix=f"tpp-robust-{tag}-")
             for tag in ("warm", "stitched", "cold")]
    try:
        # Warm-up (throwaway home, 4 steps): jit caches are shape-keyed, so
        # the step count doesn't matter for cache warmth.
        os.environ["TAXI_TRAIN_STEPS"] = "4"
        LocalDagRunner().run(load_fn(module, "create_pipeline")(homes[0]))
        os.environ["TAXI_TRAIN_STEPS"] = env["TAXI_TRAIN_STEPS"]

        plan = FaultPlan({kill_node: NodeFault(KILL_ORCHESTRATOR)})
        crashed = False
        t0 = time.perf_counter()
        try:
            with plan.activate():
                LocalDagRunner().run(
                    load_fn(module, "create_pipeline")(homes[1])
                )
        except SimulatedCrash:
            crashed = True
        partial_wall = time.perf_counter() - t0

        stitched = load_fn(module, "create_pipeline")(homes[1])
        t0 = time.perf_counter()
        resumed = LocalDagRunner().run(stitched, resume_from="latest")
        resume_wall = time.perf_counter() - t0

        cold_pipeline = load_fn(module, "create_pipeline")(homes[2])
        t0 = time.perf_counter()
        cold = LocalDagRunner().run(cold_pipeline)
        cold_wall = time.perf_counter() - t0

        decisive = ("COMPLETE", "CACHED")
        lineage_identical = _canonical_lineage(
            stitched.metadata_path, stitched.pipeline_root,
            states=decisive, strip_exec_ids=True,
        ) == _canonical_lineage(
            cold_pipeline.metadata_path, cold_pipeline.pipeline_root,
            states=decisive, strip_exec_ids=True,
        )
        # Chaos sub-leg in its own guard: a chaos-schedule failure must
        # never erase the resume evidence above (and vice versa — the
        # leg-level retry re-runs both).
        try:
            chaos = _bench_taxi_chaos(smoke)
        except Exception as e:  # noqa: BLE001 — recorded, not raised
            chaos = {"taxi_chaos": {
                "green": False,
                "error": "".join(traceback.format_exception_only(
                    type(e), e)).strip(),
            }}
        # Self-healing serving fleet under chaos (ISSUE 17), same guard
        # discipline: its verdict must not erase the resume evidence.
        try:
            serving_chaos = _bench_serving_chaos(smoke)
        except Exception as e:  # noqa: BLE001 — recorded, not raised
            serving_chaos = {"serving_chaos": {
                "green": False,
                "error": "".join(traceback.format_exception_only(
                    type(e), e)).strip(),
            }}
        return {**chaos, **serving_chaos, "taxi_faults": {
            "green": crashed and resumed.succeeded and cold.succeeded,
            "killed_at": kill_node,
            "partial_wall_s": round(partial_wall, 2),
            "resume_wall_s": round(resume_wall, 2),
            "cold_wall_s": round(cold_wall, 2),
            "work_saved_ratio": (
                round(1.0 - resume_wall / cold_wall, 3) if cold_wall else None
            ),
            "adopted": sorted(
                n for n, r in resumed.nodes.items() if r.adopted
            ),
            "rerun": sorted(
                n for n, r in resumed.nodes.items() if not r.adopted
            ),
            "lineage_identical": lineage_identical,
            "env": env,
        }}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for home in homes:
            shutil.rmtree(home, ignore_errors=True)


# Shard count for the sharded leg of the data-plane comparison: the ISSUE-3
# acceptance floor (>= 4 shards shows >= 1.3x ingest+stats on a >= 4-core
# host; 1-core hosts can only show parity — host_cpus is recorded).
DATA_PLANE_SHARDS = 4


def _row_multiset(uri: str, split: str):
    """Sorted row tuples of a split — the layout-independent content view
    (sharded and single-file writes of the same rows compare equal)."""
    from tpu_pipelines.data import examples_io

    table = examples_io.read_split_table(uri, split)
    cols = [table.column(n).to_pylist() for n in sorted(table.column_names)]
    return sorted(
        tuple(
            tuple(v) if isinstance(v, list) else v
            for v in row
        )
        for row in zip(*cols)
    ) if cols else []


def _stats_close(a, b, rtol: float = 1e-6) -> bool:
    """Sharded-merged stats == single-pass stats: exact for counts/min/max/
    top-k/missing, float-tolerance for mean/std (summation order) and the
    reservoir order statistics (exact while the split fits the reservoir,
    tolerance-bounded beyond)."""
    import math

    if a.num_examples != b.num_examples or set(a.features) != set(b.features):
        return False
    for name, fa in a.features.items():
        fb = b.features[name]
        if (fa.type, fa.num_missing) != (fb.type, fb.num_missing):
            return False
        if (fa.numeric is None) != (fb.numeric is None):
            return False
        if fa.numeric:
            na, nb = fa.numeric, fb.numeric
            if (na.min, na.max, na.num_zeros) != (nb.min, nb.max, nb.num_zeros):
                return False
            for x, y in [(na.mean, nb.mean), (na.std_dev, nb.std_dev),
                         (na.median, nb.median)]:
                if not math.isclose(x, y, rel_tol=rtol, abs_tol=1e-9):
                    return False
        if (fa.string is None) != (fb.string is None):
            return False
        if fa.string and (
            fa.string.unique != fb.string.unique
            or fa.string.top_values != fb.string.top_values
        ):
            return False
    return True


def bench_data_plane(smoke: bool) -> dict:
    """Sharded vs single-file data plane on a scaled taxi CSV.

    The ``taxi_shards`` leg is the on-hardware evidence for the sharded
    Examples layout (ISSUE 3): the same
    CsvExampleGen -> StatisticsGen -> SchemaGen -> Transform chain runs
    twice in fresh homes — ``num_shards=1`` (the legacy single-writer data
    plane) and ``num_shards=DATA_PLANE_SHARDS`` (parallel ingest workers,
    process-pool stats, per-shard transform writers) — and reports the
    per-stage wall-clocks plus two identity checks: per-split row multisets
    match (hash-bucket split membership is shard-count-invariant) and
    sharded-merged statistics equal the single-pass statistics.
    """
    import shutil
    import tempfile

    import pyarrow.csv as pacsv

    from tpu_pipelines.components import (
        CsvExampleGen,
        SchemaGen,
        StatisticsGen,
        Transform,
    )
    from tpu_pipelines.data import examples_io
    from tpu_pipelines.data.statistics import load_statistics
    from tpu_pipelines.dsl.pipeline import Pipeline
    from tpu_pipelines.orchestration import LocalDagRunner

    here = os.path.dirname(os.path.abspath(__file__))
    sample = os.path.join(here, "tests", "testdata", "taxi_sample.csv")
    preprocessing = os.path.join(here, "examples", "taxi",
                                 "taxi_preprocessing.py")
    # 120-row sample scaled by replication with a per-replica fare
    # perturbation (diversifies row hashes and the numeric distributions;
    # train split stays under the stats reservoir so the identity check is
    # exact, not tolerance-bounded).
    reps = 50 if smoke else 1250
    base = examples_io.columns_from_table(pacsv.read_csv(sample))
    n0 = len(base["fare"])
    cols = {k: np.tile(v, reps) for k, v in base.items()}
    cols["fare"] = cols["fare"] + np.repeat(
        np.arange(reps, dtype=np.float64) * 1e-3, n0
    )
    work = tempfile.mkdtemp(prefix="tpp-data-plane-")
    csv_path = os.path.join(work, "taxi_scaled.csv")
    pacsv.write_csv(examples_io.table_from_columns(cols), csv_path)

    def run_chain(home: str, shards: int):
        gen = CsvExampleGen(input_path=csv_path, num_shards=shards)
        stats = StatisticsGen(examples=gen.outputs["examples"])
        schema = SchemaGen(statistics=stats.outputs["statistics"])
        transform = Transform(
            examples=gen.outputs["examples"],
            schema=schema.outputs["schema"],
            module_file=preprocessing,
        )
        p = Pipeline(
            "data-plane", [gen, stats, schema, transform],
            pipeline_root=os.path.join(home, "root"),
            metadata_path=os.path.join(home, "metadata.sqlite"),
        )
        result = LocalDagRunner().run(p)
        walls = {
            nid: round(nr.wall_clock_s, 3)
            for nid, nr in result.nodes.items()
        }
        return {
            "green": result.succeeded,
            "walls": walls,
            "ingest_stats_s": round(
                walls.get("CsvExampleGen", 0.0)
                + walls.get("StatisticsGen", 0.0), 3
            ),
            "transform_s": walls.get("Transform", 0.0),
            "examples_uri": result.outputs_of("CsvExampleGen", "examples")[0].uri,
            "stats_uri": result.outputs_of("StatisticsGen", "statistics")[0].uri,
            "transformed_uri": result.outputs_of(
                "Transform", "transformed_examples"
            )[0].uri,
        }

    homes = {
        tag: tempfile.mkdtemp(prefix=f"tpp-data-plane-{tag}-")
        for tag in ("warm", "single", "sharded")
    }
    try:
        # Warm-up in a throwaway home: absorbs module loads / first-call
        # overheads so neither measured leg pays them (the same discipline
        # as the scheduler and robustness legs).
        run_chain(homes["warm"], 1)
        sharded = run_chain(homes["sharded"], DATA_PLANE_SHARDS)
        single = run_chain(homes["single"], 1)

        splits = examples_io.split_names(single["examples_uri"])
        rows_identical = all(
            _row_multiset(single["examples_uri"], s)
            == _row_multiset(sharded["examples_uri"], s)
            for s in splits
        )
        transform_rows_identical = all(
            _row_multiset(single["transformed_uri"], s)
            == _row_multiset(sharded["transformed_uri"], s)
            for s in examples_io.split_names(single["transformed_uri"])
        )
        stats_single = load_statistics(single["stats_uri"])
        stats_sharded = load_statistics(sharded["stats_uri"])
        stats_identical = set(stats_single) == set(stats_sharded) and all(
            _stats_close(stats_single[s], stats_sharded[s])
            for s in stats_single
        )
        shard_layout = {
            s: examples_io.num_split_shards(sharded["examples_uri"], s)
            for s in splits
        }
        speedup = (
            round(single["ingest_stats_s"] / sharded["ingest_stats_s"], 3)
            if sharded["ingest_stats_s"] else None
        )
        return {
            "config": {
                "default_shard_policy": "param > TPP_DATA_SHARDS > "
                                        "min(host_cpus, 8)",
                "env_shards": os.environ.get("TPP_DATA_SHARDS") or None,
                "env_pool": os.environ.get("TPP_DATA_POOL") or None,
                "bench_leg_shards": DATA_PLANE_SHARDS,
            },
            "taxi_shards": {
                "green": (
                    single["green"] and sharded["green"]
                    and rows_identical and stats_identical
                    and transform_rows_identical
                ),
                "rows": int(n0 * reps),
                "shards": DATA_PLANE_SHARDS,
                "shard_layout": shard_layout,
                # A 1-core host can only show parity (the shard fan-out
                # still must not LOSE); the >= 1.3x acceptance claim is for
                # >= 4-core hosts.
                "host_cpus": os.cpu_count(),
                "single_ingest_stats_s": single["ingest_stats_s"],
                "sharded_ingest_stats_s": sharded["ingest_stats_s"],
                "speedup_ingest_stats": speedup,
                "single_transform_s": single["transform_s"],
                "sharded_transform_s": sharded["transform_s"],
                "speedup_transform": (
                    round(single["transform_s"] / sharded["transform_s"], 3)
                    if sharded["transform_s"] else None
                ),
                "rows_identical": rows_identical,
                "stats_identical": stats_identical,
                "transform_rows_identical": transform_rows_identical,
                "walls_single": single["walls"],
                "walls_sharded": sharded["walls"],
            },
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)
        for home in homes.values():
            shutil.rmtree(home, ignore_errors=True)


def bench_continuous(smoke: bool) -> dict:
    """The ``continuous.taxi_spans`` leg (ISSUE 13): three synthetic
    spans fed to a RUNNING ContinuousController.

    Evidence recorded:
      - the controller ingests spans 1+2, retrains over the rolling
        window, and the blessed model deploys through the serving
        fleet's canary-gated hot-swap (real export, real loader);
      - span 3 arrives while the loop runs: ONLY the new span's
        ingest+stats execute (``work_saved_ratio`` = (K-1)/K), and the
        window's merged statistics are BYTE-IDENTICAL to a cold
        StatisticsGen full run over the assembled window artifact — the
        id-free lineage-identity analog for incremental stats;
      - ``deploy_to_serving_s``: span-3 file landing -> the fleet
        serving the retrained version (watch poll + ingest + retrain +
        push + canary + swap), plus the controller's own in-iteration
        deploy latency.
    """
    import shutil
    import tempfile
    import threading

    from tpu_pipelines.components import (
        CsvExampleGen,
        Importer,
        Pusher,
        RollingWindowResolver,
        StatisticsGen,
    )
    from tpu_pipelines.continuous import (
        ContinuousConfig,
        ContinuousController,
        SpanWindow,
        WindowStatisticsMerger,
    )
    from tpu_pipelines.dsl.component import component
    from tpu_pipelines.dsl.pipeline import Pipeline
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.orchestration import LocalDagRunner
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.trainer.export import export_model

    td = tempfile.mkdtemp(prefix="tpp-continuous-")
    base_rows = 60 if smoke else 2000
    server = None
    stop = threading.Event()
    thread = None
    try:
        data = os.path.join(td, "data")
        pattern = os.path.join(data, "span-{SPAN}", "v-{VERSION}")
        md = os.path.join(td, "md.sqlite")
        dest = os.path.join(td, "serving")

        def write_span(span, rows):
            d = os.path.join(data, f"span-{span}", "v-1")
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, "data.csv"), "w") as f:
                f.write("x,y\n")
                for i in range(rows):
                    f.write(f"{i + 1000 * span},{(i * 3 + span) % 7}\n")

        # Toy-but-real serving payload (the bench_serving idiom): the
        # trainer exports a loadable model, so the fleet's canary LOADS
        # what the pipeline pushed.
        module = os.path.join(td, "toy_module.py")
        with open(module, "w") as f:
            f.write(
                "import jax.numpy as jnp\n"
                "def build_model(hp):\n"
                "    return None\n"
                "def apply_fn(model, params, batch):\n"
                "    return jnp.asarray(batch['x'], jnp.float32) "
                "* params['w']\n"
            )

        @component(inputs={"examples": "Examples"},
                   outputs={"model": "Model"}, name="ToyTrainer")
        def ToyTrainer(ctx):
            n = sum(ctx.input("examples").properties.get(
                "split_counts", {}).values())
            export_model(
                serving_model_dir=ctx.output("model").uri,
                params={"w": np.array([float(n)], np.float32)},
                module_file=module,
            )
            return {"rows_trained": n}

        @component(inputs={"model": "Model",
                           "statistics": "ExampleStatistics"},
                   outputs={"blessing": "ModelBlessing"}, is_sink=True,
                   name="ToyBless")
        def ToyBless(ctx):
            with open(os.path.join(
                    ctx.output("blessing").uri, "BLESSED"), "w") as f:
                f.write("{}")
            ctx.output("blessing").properties["blessed"] = True
            return {"blessed": True}

        # Bootstrap version so the fleet can start before the first push.
        export_model(
            serving_model_dir=os.path.join(dest, "1"),
            params={"w": np.array([1.0], np.float32)},
            module_file=module,
        )
        server = ModelServer("taxi", dest, replicas=2, max_versions=2)
        port = server.start()
        serving_url = f"http://127.0.0.1:{port}/v1/models/taxi"

        def make_span_pipeline(span, version):
            gen = CsvExampleGen(
                input_path=pattern, span=span, num_shards=2
            )
            stats = StatisticsGen(
                examples=gen.outputs["examples"], save_accumulators=True
            )
            return Pipeline(
                "spans-ingest", [gen, stats],
                pipeline_root=os.path.join(td, "ingest-root"),
                metadata_path=md, node_timeout_s=600,
            )

        def make_window_pipeline():
            win = RollingWindowResolver(
                window_spans=3, source_pipeline="spans-ingest",
                examples_producer="CsvExampleGen",
                statistics_producer="StatisticsGen",
            )
            spanwin = SpanWindow(examples=win.outputs["examples"])
            merged = WindowStatisticsMerger(
                statistics=win.outputs["statistics"]
            )
            trainer = ToyTrainer(examples=spanwin.outputs["window"])
            bless = ToyBless(
                model=trainer.outputs["model"],
                statistics=merged.outputs["statistics"],
            )
            pusher = Pusher(
                model=trainer.outputs["model"],
                blessing=bless.outputs["blessing"],
                push_destination=dest,
                serving_push_url=serving_url,
            ).with_lint_suppressions("TPP109")
            return Pipeline(
                "window-train",
                [win, spanwin, merged, trainer, bless, pusher],
                pipeline_root=os.path.join(td, "window-root"),
                metadata_path=md, node_timeout_s=600,
            )

        registry = MetricsRegistry()
        controller = ContinuousController(ContinuousConfig(
            input_pattern=pattern,
            make_span_pipeline=make_span_pipeline,
            make_window_pipeline=make_window_pipeline,
            poll_interval_s=0.1,
            serving_url=serving_url,
            probation_watch_s=0.0,   # rollback drill lives in tier-1 tests
            state_dir=os.path.join(td, "state"),
            registry=registry,
        ))

        # Feed spans 1+2 to the RUNNING controller: bootstrap deploy.
        write_span(1, base_rows)
        write_span(2, base_rows + base_rows // 2)
        thread = threading.Thread(
            target=controller.run, kwargs={"stop_event": stop},
        )
        thread.start()

        def wait_for(predicate, timeout_s=120.0):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                if predicate():
                    return True
                time.sleep(0.05)
            return False

        deploys = registry.get("continuous_deploys_total")
        boot_ok = wait_for(lambda: deploys.get() >= 1)

        # Span 3 lands mid-loop: measure landing -> serving the retrain.
        t_land = time.monotonic()
        write_span(3, base_rows * 2)
        incr_ok = wait_for(
            lambda: deploys.get() >= 2 and server.version == "3"
        )
        deploy_to_serving_s = time.monotonic() - t_land
        stop.set()
        thread.join(timeout=60)
        it = dict(controller.last_iteration)

        # Identity: merged window stats == a cold full run over the
        # assembled window artifact.
        from tpu_pipelines.metadata import open_store

        store = open_store(md)
        try:
            merged_art = max(
                (a for a in store.get_artifacts(
                    type_name="ExampleStatistics")
                 if a.properties.get("window_spans") == [1, 2, 3]),
                key=lambda a: a.id, default=None,
            )
            window_art = max(
                (a for a in store.get_artifacts(type_name="Examples")
                 if a.properties.get("window_spans") == [1, 2, 3]),
                key=lambda a: a.id, default=None,
            )
        finally:
            store.close()
        stats_identical = False
        if merged_art is not None and window_art is not None:
            imp = Importer(
                source_uri=window_art.uri, artifact_type="Examples"
            )
            cold_sg = StatisticsGen(examples=imp.outputs["result"])
            rc = LocalDagRunner().run(Pipeline(
                "cold", [imp, cold_sg],
                pipeline_root=os.path.join(td, "cold-root"),
                metadata_path=os.path.join(td, "cold.sqlite"),
            ))
            cold_art = rc.outputs_of("StatisticsGen", "statistics")[0]
            with open(os.path.join(cold_art.uri, "stats.json")) as f:
                cold = json.load(f)
            with open(os.path.join(merged_art.uri, "stats.json")) as f:
                inc = json.load(f)
            stats_identical = inc == cold

        work_saved = it.get("work_saved_ratio")
        green = bool(
            boot_ok and incr_ok and stats_identical
            and server.version == "3"
            and work_saved is not None and abs(work_saved - 2 / 3) < 1e-3
        )
        return {"taxi_spans": {
            "green": green,
            "spans": 3,
            "rows_per_span": [base_rows, base_rows + base_rows // 2,
                              base_rows * 2],
            "bootstrap_deploy_ok": boot_ok,
            "incremental_deploy_ok": incr_ok,
            "stats_identical": stats_identical,
            "work_saved_ratio": work_saved,
            "deploy_to_serving_s": round(deploy_to_serving_s, 3),
            "controller_deploy_latency_s": (
                (it.get("deployed") or {}).get("deploy_latency_s")
            ),
            "deploys": deploys.get(),
            "spans_seen": registry.get("continuous_spans_seen").get(),
            "serving_version": server.version,
            "last_iteration": it,
        }}
    finally:
        stop.set()
        if thread is not None and thread.is_alive():
            thread.join(timeout=30)
        if server is not None:
            server.stop()
        shutil.rmtree(td, ignore_errors=True)


def bench_monitoring(smoke: bool) -> dict:
    """The ``monitoring.drift_drill`` leg (ISSUE 20): the live drift &
    skew plane exercised end to end against a RUNNING controller.

    Evidence recorded:
      - a monitored fleet (``monitor_sample_rate=1.0``) under control
        traffic drawn from the training distribution stays quiet —
        ``drift_false_alarms`` must read 0 across >= 3 scored windows;
      - covariate-shifted traffic (loc 0 -> 5) breaches the payload-
        stamped training baseline within ``drift_detect_windows`` <= 3
        tumbling windows, read from the fleet's own /metrics scrape;
      - the controller's scrape poll consumes the breach and answers
        with EXACTLY ONE out-of-cadence window retrain
        (``continuous_drift_triggered_runs_total == 1``), evidence
        recorded as a drift_evidence context in the metadata store;
      - ``drift_sampler_overhead_pct``: matched sequential predict
        latency, monitored fleet vs an unmonitored fleet on the same
        payloads — the sampler must stay off the critical path.
    """
    import shutil
    import tempfile
    import threading
    import urllib.request

    import pyarrow as pa

    from tpu_pipelines.components import (
        CsvExampleGen,
        Pusher,
        RollingWindowResolver,
        StatisticsGen,
    )
    from tpu_pipelines.continuous import (
        ContinuousConfig,
        ContinuousController,
        SpanWindow,
        WindowStatisticsMerger,
    )
    from tpu_pipelines.data.statistics import (
        compute_split_statistics,
        save_statistics,
    )
    from tpu_pipelines.dsl.component import component
    from tpu_pipelines.dsl.pipeline import Pipeline
    from tpu_pipelines.observability.drift import parse_drift_scrape
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.trainer.export import export_model

    td = tempfile.mkdtemp(prefix="tpp-monitoring-")
    rng = np.random.default_rng(20)
    span_rows = 60 if smoke else 400
    baseline_rows = 2000 if smoke else 8000
    window_s = 0.8 if smoke else 1.5
    lat_n = 80 if smoke else 300
    server = None
    server_plain = None
    stop = threading.Event()
    thread = None
    try:
        data = os.path.join(td, "data")
        pattern = os.path.join(data, "span-{SPAN}", "v-{VERSION}")
        md = os.path.join(td, "md.sqlite")
        dest = os.path.join(td, "serving")

        # The training baseline the live plane scores against: real
        # accumulator statistics over the feature the fleet will see,
        # stamped onto every exported payload below.
        stats_uri = os.path.join(td, "baseline-stats")
        base_stats = compute_split_statistics(
            "train", pa.table({"x": rng.normal(size=baseline_rows)})
        )
        save_statistics(stats_uri, {"train": base_stats})

        def write_span(span, rows):
            d = os.path.join(data, f"span-{span}", "v-1")
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, "data.csv"), "w") as f:
                f.write("x,y\n")
                for i in range(rows):
                    f.write(f"{i + 1000 * span},{(i * 3 + span) % 7}\n")

        module = os.path.join(td, "toy_module.py")
        with open(module, "w") as f:
            f.write(
                "import jax.numpy as jnp\n"
                "def build_model(hp):\n"
                "    return None\n"
                "def apply_fn(model, params, batch):\n"
                "    return jnp.asarray(batch['x'], jnp.float32) "
                "* params['w']\n"
            )

        @component(inputs={"examples": "Examples"},
                   outputs={"model": "Model"}, name="ToyTrainer")
        def ToyTrainer(ctx):
            n = sum(ctx.input("examples").properties.get(
                "split_counts", {}).values())
            export_model(
                serving_model_dir=ctx.output("model").uri,
                params={"w": np.array([float(n)], np.float32)},
                module_file=module,
                training_statistics_uri=stats_uri,
            )
            return {"rows_trained": n}

        @component(inputs={"model": "Model",
                           "statistics": "ExampleStatistics"},
                   outputs={"blessing": "ModelBlessing"}, is_sink=True,
                   name="ToyBless")
        def ToyBless(ctx):
            with open(os.path.join(
                    ctx.output("blessing").uri, "BLESSED"), "w") as f:
                f.write("{}")
            ctx.output("blessing").properties["blessed"] = True
            return {"blessed": True}

        export_model(
            serving_model_dir=os.path.join(dest, "1"),
            params={"w": np.array([1.0], np.float32)},
            module_file=module,
            training_statistics_uri=stats_uri,
        )
        server = ModelServer(
            "taxi", dest, replicas=2, max_versions=2,
            monitor_sample_rate=1.0, monitor_window_s=window_s,
        )
        port = server.start()
        serving_url = f"http://127.0.0.1:{port}/v1/models/taxi"
        predict_url = serving_url + ":predict"
        metrics_url = f"http://127.0.0.1:{port}/metrics"

        def make_span_pipeline(span, version):
            gen = CsvExampleGen(
                input_path=pattern, span=span, num_shards=2
            )
            stats = StatisticsGen(
                examples=gen.outputs["examples"], save_accumulators=True
            )
            return Pipeline(
                "drift-ingest", [gen, stats],
                pipeline_root=os.path.join(td, "ingest-root"),
                metadata_path=md, node_timeout_s=600,
            )

        def make_window_pipeline():
            win = RollingWindowResolver(
                window_spans=1, source_pipeline="drift-ingest",
                examples_producer="CsvExampleGen",
                statistics_producer="StatisticsGen",
            )
            spanwin = SpanWindow(examples=win.outputs["examples"])
            merged = WindowStatisticsMerger(
                statistics=win.outputs["statistics"]
            )
            trainer = ToyTrainer(examples=spanwin.outputs["window"])
            bless = ToyBless(
                model=trainer.outputs["model"],
                statistics=merged.outputs["statistics"],
            )
            pusher = Pusher(
                model=trainer.outputs["model"],
                blessing=bless.outputs["blessing"],
                push_destination=dest,
                serving_push_url=serving_url,
            ).with_lint_suppressions("TPP109")
            return Pipeline(
                "drift-window",
                [win, spanwin, merged, trainer, bless, pusher],
                pipeline_root=os.path.join(td, "window-root"),
                metadata_path=md, node_timeout_s=600,
            )

        registry = MetricsRegistry()
        controller = ContinuousController(ContinuousConfig(
            input_pattern=pattern,
            make_span_pipeline=make_span_pipeline,
            make_window_pipeline=make_window_pipeline,
            poll_interval_s=0.1,
            serving_url=serving_url,
            probation_watch_s=0.0,
            state_dir=os.path.join(td, "state"),
            registry=registry,
        ))

        write_span(1, span_rows)
        thread = threading.Thread(
            target=controller.run, kwargs={"stop_event": stop},
        )
        thread.start()

        def wait_for(predicate, timeout_s=120.0):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                if predicate():
                    return True
                time.sleep(0.05)
            return False

        deploys = registry.get("continuous_deploys_total")
        boot_ok = wait_for(lambda: deploys.get() >= 1, timeout_s=180.0)

        def predict(x_rows):
            body = json.dumps({"instances": [
                {"x": float(v)} for v in x_rows
            ]}).encode()
            req = urllib.request.Request(
                predict_url, data=body,
                headers={"Content-Type": "application/json"},
            )
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=30) as r:
                r.read()
            return time.perf_counter() - t0

        def scrape():
            with urllib.request.urlopen(metrics_url, timeout=5) as r:
                return parse_drift_scrape(
                    r.read().decode("utf-8", "replace")
                )

        # Phase A — control traffic drawn from the training distribution
        # for >= 3 scored windows: the plane must stay quiet.
        t_end = time.monotonic() + 3.5 * window_s
        control_requests = 0
        while time.monotonic() < t_end:
            predict(rng.normal(size=32))
            control_requests += 1
            time.sleep(0.01)
        time.sleep(1.5 * window_s)  # let the last control window close
        rep = scrape()
        false_alarms = rep.get("alerts_total", 0.0)
        control_windows = rep.get("windows_total", 0.0)
        w0 = control_windows

        # Phase B — covariate shift (loc 0 -> 5): the skew comparator
        # against the payload-stamped baseline must fire within 3
        # windows of the shift landing.
        detect_windows = None
        t_shift_end = time.monotonic() + 8 * window_s
        while time.monotonic() < t_shift_end:
            for _ in range(4):
                predict(rng.normal(loc=5.0, size=32))
            r2 = scrape()
            if r2.get("alerts_total", 0.0) > false_alarms:
                detect_windows = max(
                    1.0, r2.get("windows_total", 0.0) - w0
                )
                break
            time.sleep(0.05)

        # Loop closure: the controller's scrape poll consumes the alert
        # delta and runs ONE out-of-cadence retrain.  Stop the loop the
        # moment the counter lands so residual shifted windows (the tail
        # of the burst draining through the sampler) cannot double-fire.
        drift_runs = registry.get("continuous_drift_triggered_runs_total")
        retrain_ok = wait_for(lambda: drift_runs.get() >= 1)
        stop.set()
        thread.join(timeout=120)

        evidence = 0
        from tpu_pipelines.metadata import open_store

        store = open_store(md)
        try:
            evidence = len(store.get_contexts(type_name="drift_evidence"))
        finally:
            store.close()

        # Phase C — sampler overhead: matched sequential predict latency
        # against an unmonitored fleet over the same payload directory.
        server_plain = ModelServer("taxi", dest, replicas=2,
                                   max_versions=2)
        port2 = server_plain.start()
        plain_url = f"http://127.0.0.1:{port2}/v1/models/taxi:predict"

        def hammer(url, n):
            body = json.dumps({"instances": [
                {"x": float(v)} for v in rng.normal(size=32)
            ]}).encode()
            lats = []
            for _ in range(n):
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"},
                )
                t0 = time.perf_counter()
                with urllib.request.urlopen(req, timeout=30) as r:
                    r.read()
                lats.append(time.perf_counter() - t0)
            return lats

        hammer(plain_url, 10)  # warm-up (XLA compile, canary capture)
        plain = sorted(hammer(plain_url, lat_n))
        hammer(predict_url, 10)
        mon = sorted(hammer(predict_url, lat_n))
        p50_plain = plain[len(plain) // 2]
        p50_mon = mon[len(mon) // 2]
        overhead_pct = (
            (p50_mon / p50_plain - 1.0) * 100.0 if p50_plain > 0 else None
        )

        runs = drift_runs.get()
        green = bool(
            boot_ok
            and false_alarms == 0
            and control_windows >= 3
            and detect_windows is not None and detect_windows <= 3
            and retrain_ok and runs == 1
            and evidence >= 1
        )
        return {"drift_drill": {
            "green": green,
            "bootstrap_deploy_ok": boot_ok,
            "control_requests": control_requests,
            "control_windows": control_windows,
            "false_alarms": false_alarms,
            "detect_windows": detect_windows,
            "drift_triggered_runs": runs,
            "drift_evidence_contexts": evidence,
            "deploys": deploys.get(),
            "serving_version": server.version,
            "sampler_overhead_pct": (
                round(overhead_pct, 2) if overhead_pct is not None
                else None
            ),
            "p50_monitored_ms": round(p50_mon * 1000, 3),
            "p50_plain_ms": round(p50_plain * 1000, 3),
            "window_s": window_s,
            "sampled_total": rep.get("sampled_total"),
            "dropped_total": rep.get("dropped_total"),
        }}
    finally:
        stop.set()
        if thread is not None and thread.is_alive():
            thread.join(timeout=30)
        if server is not None:
            server.stop()
        if server_plain is not None:
            server_plain.stop()
        shutil.rmtree(td, ignore_errors=True)


def bench_flash_probe(smoke: bool) -> dict:
    """Flash vs dense attention across a seq-length sweep (ISSUE 9).

    Evidence for the autotuner (ops/autotune.py): at every swept sequence
    length this times a grad step of sum(attn(q,k,v)) for the DEFAULT
    flash blocks (128/128), every tuned candidate block config, and dense
    — with an expected-temp-bytes precheck that skips dense cleanly where
    its O(L^2) temporaries cannot fit (``dense_skipped_oom_precheck``,
    instead of leaning on a backend compile error as r5 did).  The leg
    records the measured flash-vs-dense crossover, persists winners +
    crossover into the autotune cache (real user cache on chip; a throw-
    away dir in smoke), and first proves an EMPTY-cache cache-only run
    completes on defaults without sweeping — the jit-trace-time contract.
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models.transformer import (
        choose_attn_impl,
        dense_attn_expected_temp_bytes,
        dense_attn_fits,
    )
    from tpu_pipelines.ops import autotune
    from tpu_pipelines.ops.flash_attention import flash_attention
    from tpu_pipelines.parallel.ring_attention import dense_attention

    if smoke:
        b, h, d, iters = 1, 2, 32, 2
        seqs, workhorse = (128, 256), 256
    else:
        b, h, d, iters = 8, 12, 64, 10
        seqs, workhorse = (512, 2048, 8192), 2048

    def qkv(l, seed=0):
        kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
        return (
            jax.random.normal(kq, (b, l, h, d), jnp.bfloat16),
            jax.random.normal(kk, (b, l, h, d), jnp.bfloat16),
            jax.random.normal(kv, (b, l, h, d), jnp.bfloat16),
        )

    def measure(attn_fn, mq, mk, mv, n_iters):
        def loss(q, k, v):
            return attn_fn(q, k, v).astype(jnp.float32).sum()

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        compiled = step.lower(mq, mk, mv).compile()
        mem = {}
        try:
            ma = compiled.memory_analysis()
            for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes", "generated_code_size_in_bytes"):
                val = getattr(ma, attr, None)
                if val is not None:
                    mem[attr] = int(val)
        except Exception:  # memory_analysis is best-effort per backend
            pass
        out = compiled(mq, mk, mv)
        np.asarray(out[0][0, 0, 0, 0])  # warm-up + force execution
        # Feed dq back in as q: iteration N consumes N-1's output, so the
        # final device-to-host read proves EVERY iteration executed (same
        # shapes/dtypes, so the compiled executable is reused as-is).
        cur_q = out[0]
        t0 = time.perf_counter()
        for _ in range(n_iters):
            out = compiled(cur_q, mk, mv)
            cur_q = out[0]
        np.asarray(cur_q[0, 0, 0, 0])
        ms = (time.perf_counter() - t0) / n_iters * 1e3
        return {"ms_per_step": round(ms, 3), **mem}

    def flash_fn(bq, bk):
        # Explicit blocks: the measurement must bypass the table so every
        # candidate is timed as requested (clamped to valid tilings).
        return lambda q, k, v: flash_attention(
            q, k, v, block_q=bq, block_k=bk, bwd_block_q=bq, bwd_block_k=bk
        )

    tmp_cache = tempfile.mkdtemp(prefix="tpp-autotune-bench-") if smoke else None
    saved_env = {
        k: os.environ.get(k) for k in ("TPP_AUTOTUNE", "TPP_AUTOTUNE_CACHE")
    }
    try:
        if tmp_cache:
            os.environ["TPP_AUTOTUNE_CACHE"] = tmp_cache
        os.environ["TPP_AUTOTUNE"] = "cache-only"
        autotune.clear_memo()

        from tpu_pipelines.observability.metrics import default_registry

        reg = default_registry()

        def counter(name):
            m = reg.get(name)
            total = 0.0
            if m is not None:
                for key, val in m._snapshot_series().items():  # noqa: SLF001
                    total += float(val)
            return total

        # --- cold cache-only run: empty user cache, default-block flash
        # through the TABLE-CONSULTING path (no explicit blocks) must
        # complete without sweeping — what jit tracing relies on.
        lw = workhorse
        qw, kw, vw = qkv(lw)
        hits0, miss0, sweeps0 = (
            counter("autotune_cache_hits_total"),
            counter("autotune_cache_misses_total"),
            counter("autotune_sweeps_total"),
        )
        cold = measure(
            lambda q, k, v: flash_attention(q, k, v), qw, kw, vw, max(2, iters // 2)
        )
        autotune_info = {
            "mode_cold": "cache-only",
            "cold_cache_completed": bool(cold.get("ms_per_step")),
            "sweeps_during_cold_run": int(
                counter("autotune_sweeps_total") - sweeps0
            ),
            "cache_dir": autotune.cache_dir(),
        }

        # --- seq-length sweep: default vs tuned candidates vs dense
        # (candidates pass explicit blocks, which bypass the table — the
        # hit/miss deltas below therefore count the TABLE-consulting cold
        # run plus any tuned-path retraces).
        sweep: dict = {}
        crossover = None
        device_kind = autotune.current_device_kind()
        for l in seqs:
            ql, kl, vl = qkv(l, seed=l)
            n_iters = iters if l <= workhorse else max(2, iters // 2)
            if smoke:
                cand_blocks = [c for c in (64, 128) if c <= l]
            else:
                cand_blocks = autotune.valid_blocks(l, 2)[:4]
            row: dict = {"candidates": []}
            default_bq = autotune.clamp_block(l, autotune.DEFAULT_BLOCK_Q, 2)
            times = {}
            for c in sorted(set(cand_blocks) | {default_bq}):
                entry = {"block_q": c, "block_k": c}
                try:
                    m = measure(flash_fn(c, c), ql, kl, vl, n_iters)
                    entry.update(m)
                    times[c] = m["ms_per_step"]
                except Exception as e:  # noqa: BLE001
                    entry["error"] = _clean_err(str(e))
                row["candidates"].append(entry)
            if times:
                best = min(times, key=times.get)
                row["default_blocks"] = default_bq
                row["default_ms"] = times.get(default_bq)
                row["tuned_blocks"] = [best, best]
                row["tuned_ms"] = times[best]
                # Structural: the default config is IN the candidate grid,
                # so the winner can never be slower than it.
                row["tuned_not_worse"] = (
                    row["default_ms"] is None
                    or row["tuned_ms"] <= row["default_ms"]
                )
                flash_ms = row["tuned_ms"]
                for op in ("flash_fwd", "flash_bwd"):
                    autotune.record_entry(
                        autotune.make_key(
                            op, b, h, l, d, "bfloat16", False, device_kind
                        ),
                        best, best, times[best],
                        swept=row["candidates"], source="bench_step_sweep",
                    )
            else:
                flash_ms = None
            # Dense: expected-temp-bytes precheck instead of compiling into
            # a backend OOM/HTTP-500 (the r5 long_seq failure mode).
            row["dense_expected_temp_bytes"] = dense_attn_expected_temp_bytes(
                b, h, l, l, 2
            )
            if not dense_attn_fits(b, h, l, l, 2):
                row["dense_skipped_oom_precheck"] = True
                if flash_ms is not None and crossover is None:
                    crossover = l  # flash is the only implementation that runs
            else:
                row["dense_skipped_oom_precheck"] = False
                try:
                    row["dense"] = measure(dense_attention, ql, kl, vl, n_iters)
                    if (
                        flash_ms is not None and crossover is None
                        and flash_ms <= row["dense"]["ms_per_step"]
                    ):
                        crossover = l
                except Exception as e:  # noqa: BLE001
                    row["dense_error"] = _clean_err(str(e))
            sweep[str(l)] = row
        autotune_info.update(
            cache_hits=int(counter("autotune_cache_hits_total") - hits0),
            cache_misses=int(counter("autotune_cache_misses_total") - miss0),
            sweeps=int(counter("autotune_sweeps_total") - sweeps0),
        )

        # Persist the measured crossover (None = dense won everywhere it
        # fits at every swept length — recorded explicitly so `auto` can
        # tell measured-no-crossover from never-measured).
        autotune.record_crossover(
            device_kind, crossover,
            geometry={"batch": b, "heads": h, "head_dim": d,
                      "dtype": "bfloat16", "seqs": list(seqs)},
            source="bench_flash_probe",
        )
        autotune.clear_memo()

        # What attn_impl="auto" now decides per swept length: dense below
        # the measured crossover, flash at/above it, flash where dense's
        # temporaries cannot fit (the OOM guard).
        auto_choice = {
            str(l): choose_attn_impl(b, h, l, l, 2) for l in seqs
        }

        wh = sweep[str(workhorse)]
        out = {
            "shape": {"batch": b, "heads": h, "head_dim": d,
                      "seq_len": workhorse},
            "seqs_swept": list(seqs),
            "autotune": autotune_info,
            "sweep": sweep,
            "flash": next(
                (c for c in wh["candidates"]
                 if c["block_q"] == wh.get("default_blocks")), {}
            ),
            "dense": wh.get("dense", {}),
            "auto_choice": auto_choice,
            "crossover_seq_len": crossover,
            "device_kind": device_kind,
        }
        if wh.get("tuned_ms") and wh.get("default_ms"):
            out["flash_tuned_speedup"] = round(
                wh["default_ms"] / wh["tuned_ms"], 3
            )
        flash_m, dense_m = out["flash"], out["dense"]
        if flash_m.get("ms_per_step") and dense_m.get("ms_per_step"):
            out["dense_over_flash_time"] = round(
                dense_m["ms_per_step"] / flash_m["ms_per_step"], 3
            )
        if flash_m.get("temp_size_in_bytes") and dense_m.get("temp_size_in_bytes"):
            out["dense_over_flash_temp_mem"] = round(
                dense_m["temp_size_in_bytes"] / flash_m["temp_size_in_bytes"], 3
            )
        return out
    finally:
        for key, val in saved_env.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        autotune.clear_memo()
        if tmp_cache:
            shutil.rmtree(tmp_cache, ignore_errors=True)


_ANSI = None


def _clean_err(msg: str, limit: int = 200) -> str:
    """First line, ANSI escapes stripped — committed evidence, not a log."""
    global _ANSI
    if _ANSI is None:
        import re

        _ANSI = re.compile(r"\x1b\[[0-9;]*m")
    return (_ANSI.sub("", msg).splitlines() or [""])[0][:limit]


def _is_transient(err: str) -> bool:
    """Platform flakes worth retrying (transport resets and friends) —
    NOT deterministic failures like
    ImportError/shape errors/OOM, which would just burn chip time twice.
    Shared classifier: utils/transient.py (same list the Evaluator uses)."""
    from tpu_pipelines.utils.transient import is_transient_error

    return is_transient_error(err)


def run_workload(name: str, fn, smoke: bool, retries: int = 2):
    """Run one workload in isolation; returns (result_or_None, error_or_None).

    Retries cover transient runtime flakes; the last traceback is returned
    so one workload cannot take out the others' evidence — main() records
    it under ``errors`` and exits non-zero once every leg has flushed.
    """
    last_err = None
    for attempt in range(retries + 1):
        try:
            return fn(smoke), None
        except Exception as e:
            last_err = "".join(
                traceback.format_exception_only(type(e), e)
            ).strip()
        if attempt < retries and _is_transient(last_err):
            print(
                f"# bench: {name} attempt {attempt + 1} failed, retrying: "
                f"{last_err[:200]}",
                file=sys.stderr,
            )
            time.sleep(2.0)
        else:
            break
    return None, last_err


def _finalize_headline(report: dict) -> None:
    """(Re)compute the headline fields from whatever workloads have landed —
    called before every flush so each partial line is self-describing."""
    def measured(w):
        w = report.get(w)
        return w if w and "examples_per_sec_per_chip" in w else None

    bert = measured("bert")
    taxi = measured("taxi")
    if bert:
        report["metric"] = "bert_base_finetune_examples_per_sec_per_chip"
        report["value"] = round(bert["examples_per_sec_per_chip"], 2)
        report["vs_baseline"] = round(
            bert["examples_per_sec_per_chip"] / A100_BERT_BASE_EX_PER_SEC, 4
        )
        report["mfu"] = bert["mfu"]
    elif taxi:
        # vs_baseline is ONLY the A100 north-star ratio; with no BERT number
        # it must read as absent, not as taxi's (self-relative) ratio —
        # a >=0.9 check must not pass in a round the flagship never ran.
        report["metric"] = "taxi_trainer_examples_per_sec_per_chip"
        report["value"] = round(taxi["examples_per_sec_per_chip"], 2)
        report["vs_baseline"] = None
        report["mfu"] = None
    else:
        report["metric"] = "bench_failed"
        report["value"] = 0.0
        report["vs_baseline"] = None
        report["mfu"] = None


def _compact(report: dict) -> dict:
    """Headline-only view of the cumulative report, guaranteed to fit the
    driver's 2,000-byte stdout tail.

    Rounds 1-4 all ended with ``parsed: null`` in the driver artifact: the
    full cumulative report grew past 3.7 KB, the tail buffer kept only the
    last 2,000 bytes, and the captured line started mid-JSON.  The fix is a
    contract split: stdout carries ONLY this compact line (~1.5 KB with
    every leg's headline keys, budget-checked in test_bench_smoke); the
    full report lives in BENCH_PARTIAL.json and the committed
    BENCH_R{N}_LOCAL.json artifact.
    """
    e2e = report.get("pipeline_e2e") or {}

    def green(name):
        w = e2e.get(name)
        return bool(w and w.get("green"))

    def skip_reason(name, w):
        # A bare leg name in the skip list read as "forgot to run it";
        # carry the WHY (budget arithmetic) so the compact line is
        # self-explanatory: bert_goodput(need 160s, had 42s).
        est = w.get("est_cost_s")
        rem = w.get("remaining_s")
        if est is None or rem is None:
            return name
        return f"{name}(need {est:g}s, had {rem:g}s)"

    skipped = sorted(
        {
            skip_reason(name, w) for name, w in report.items()
            if isinstance(w, dict) and w.get("skipped_budget")
        }
        | {
            skip_reason(f"e2e_{name}", w) for name, w in e2e.items()
            if isinstance(w, dict) and w.get("skipped_budget")
        }
    )
    compact = {
        "metric": report.get("metric"),
        "value": report.get("value"),
        "unit": report.get("unit"),
        "vs_baseline": report.get("vs_baseline"),
        "mfu": report.get("mfu"),
        "mfu_xla": (report.get("bert") or {}).get("mfu_xla"),
        "bert_e2e_green": green("bert"),
        "taxi_e2e_green": green("taxi"),
        "elapsed_s": report.get("elapsed_s"),
        "skipped": skipped,
        "error_legs": sorted(report.get("errors", {})),
        "full_report": "BENCH_PARTIAL.json",
    }
    robust = (report.get("robustness") or {}).get("taxi_faults")
    if isinstance(robust, dict) and "green" in robust:
        compact["robust_green"] = bool(robust.get("green"))
        compact["work_saved"] = robust.get("work_saved_ratio")
    chaos = (report.get("robustness") or {}).get("taxi_chaos")
    if isinstance(chaos, dict) and "green" in chaos:
        # Unified fault-tolerance headline (ISSUE 7): completion under the
        # injected fault schedule, quantified from the metrics registry.
        compact["chaos_green"] = bool(chaos.get("green"))
        compact["retries_total"] = chaos.get("retries_total")
        compact["shards_quarantined"] = chaos.get("shards_quarantined")
        compact["shed_requests"] = chaos.get("shed_requests")
        compact["reload_5xx"] = chaos.get("reload_5xx")
    schaos = (report.get("robustness") or {}).get("serving_chaos")
    if isinstance(schaos, dict) and "green" in schaos:
        # Self-healing fleet headline (ISSUE 17): kill 1-of-2 replicas
        # mid-hammer — zero lost requests, failovers + recovered decode
        # sessions counted from the fleet's own scrape, bounded p99.
        compact["chaos_serving_green"] = bool(schaos.get("green"))
        compact["failovers"] = schaos.get("failovers")
        compact["sessions_recovered"] = schaos.get("sessions_recovered")
        compact["incident_p99_ms"] = schaos.get("incident_p99_ms")
        compact["lost_requests"] = schaos.get("lost_requests")
    dp = (report.get("data_plane") or {}).get("taxi_shards")
    if isinstance(dp, dict) and "green" in dp:
        compact["data_plane_green"] = bool(dp.get("green"))
        compact["shard_speedup"] = dp.get("speedup_ingest_stats")
    # Live-telemetry headline: serving tail latency off the scraped
    # /metrics histogram, and the previous-run trace-diff verdict.
    sv = report.get("serving")
    if isinstance(sv, dict) and "green" in sv:
        compact["serving_green"] = bool(sv.get("green"))
        compact["serving_p99_ms"] = sv.get("p99_ms")
    # Serving-fleet headline (ISSUE 10): p99-under-SLO at the bench QPS
    # and the zero-5xx hot-swap, both off the fleet's own scrape.
    fl = report.get("serving_fleet")
    if isinstance(fl, dict) and "green" in fl:
        compact["fleet_green"] = bool(fl.get("green"))
        compact["fleet_p99_ms"] = fl.get("p99_ms")
        compact["fleet_reload_5xx"] = fl.get("reload_5xx")
        compact["fleet_shed_requests"] = fl.get("shed_requests")
        compact["trace_overhead_pct"] = fl.get("trace_overhead_pct")
        compact["slo_rollback_green"] = fl.get("slo_rollback_green")
    # Quantized-serving headline (ISSUE 14): int8-over-float request
    # latency at matched QPS, the Evaluator-surface quality delta the
    # gate recorded, and the post-swap compiles-after-warm audit.
    sq = report.get("serving_quantized")
    if isinstance(sq, dict) and "green" in sq:
        compact["quantized_green"] = bool(sq.get("green"))
        compact["quantized_speedup"] = sq.get("quantized_speedup")
        compact["quantized_quality_delta"] = sq.get(
            "quantized_quality_delta"
        )
        compact["aot_compiles_after_warm"] = sq.get(
            "aot_compiles_after_warm"
        )
    # Continuous-batching decode headline (ISSUE 11): tokens/s and
    # p99-per-token off the fleet's own scrape, the A/B speedup over
    # whole-request decode, and the zero-5xx-across-hot-swap count.
    gs = report.get("generative_serving")
    if isinstance(gs, dict) and "green" in gs:
        compact["generative_green"] = bool(gs.get("green"))
        compact["decode_tok_s"] = gs.get("decode_tok_s")
        compact["decode_p99_ms_per_token"] = gs.get(
            "decode_p99_ms_per_token"
        )
        compact["continuous_vs_request_speedup"] = gs.get(
            "continuous_vs_request_speedup"
        )
        compact["decode_5xx"] = gs.get("decode_5xx")
        # ISSUE 16 headline: long-shared-prefix speedup from the decode-
        # path optimisations, plus the rate that explains it.
        sp = gs.get("shared_prefix")
        if isinstance(sp, dict):
            compact["prefix_speedup"] = sp.get("speedup")
            compact["prefix_hit_rate"] = sp.get("prefix_hit_rate")
    cont = (report.get("continuous") or {}).get("taxi_spans")
    if isinstance(cont, dict) and "green" in cont:
        compact["continuous_green"] = bool(cont.get("green"))
        compact["incremental_work_saved"] = cont.get("work_saved_ratio")
    # Live drift-plane headline (ISSUE 20): quiet under control traffic,
    # shift caught within 3 windows, one retrain, sampler off the path.
    mon = (report.get("monitoring") or {}).get("drift_drill")
    if isinstance(mon, dict) and "green" in mon:
        compact["drift_green"] = bool(mon.get("green"))
        compact["drift_detect_windows"] = mon.get("detect_windows")
        compact["drift_false_alarms"] = mon.get("false_alarms")
        compact["drift_sampler_overhead_pct"] = mon.get(
            "sampler_overhead_pct"
        )
    td = report.get("trace_diff")
    if isinstance(td, dict):
        # Capped: the compact line must stay under the driver-tail budget
        # even if every node regressed.
        compact["regression_flags"] = td.get("regression_flags", [])[:8]
    # Host-loop-tax headline (ISSUE 8): windowed-vs-per-step speedup on
    # the real pipeline path, and the remaining gap to the device-resident
    # ceiling (taxi_device).
    tw = report.get("taxi_window")
    if isinstance(tw, dict) and "window_speedup" in tw:
        compact["window_speedup"] = tw["window_speedup"]
        compact["gap_to_ceiling"] = tw.get("gap_to_device_ceiling")
    # Multi-chip window headline (ISSUE 15): windowing win on the full
    # mesh plus measured DP scaling efficiency vs one device.  Only from
    # an accelerator mesh: figures from virtual CPU devices are counts of
    # scheduler overhead, never headline numbers.
    def on_chips(leg) -> bool:
        return isinstance(leg, dict) and leg.get("platform") not in (
            None, "cpu"
        )

    twm = report.get("taxi_window_mesh")
    if on_chips(twm) and "mesh_window_speedup" in twm:
        compact["mesh_window_speedup"] = twm["mesh_window_speedup"]
        compact["scaling_efficiency"] = twm.get("scaling_efficiency")
    # Training-telemetry headline (ISSUE 19): where the window went
    # (infeed-wait share of the attributed window wall-clock) and the
    # steady-state recompile count, which must read 0.
    tt = (tw if isinstance(tw, dict) else {}).get("train_telemetry")
    if not isinstance(tt, dict):
        tt = (twm if isinstance(twm, dict) else {}).get("train_telemetry")
    if isinstance(tt, dict):
        compact["train_infeed_wait_pct"] = tt.get("infeed_wait_pct")
        compact["train_compiles_after_warm"] = tt.get("compiles_after_warm")
    bpar = report.get("bert_parallelism")
    if on_chips(bpar) and "fsdp_mfu_vs_dp" in bpar:
        compact["fsdp_mfu_vs_dp"] = bpar["fsdp_mfu_vs_dp"]
        compact["fsdp_param_shard_ratio"] = bpar.get(
            "fsdp_param_shard_ratio"
        )
    # Kernel-autotune headline (ISSUE 9): tuned-over-default flash speedup
    # at the workhorse shape and the measured flash/dense crossover.
    fp = report.get("flash_probe")
    if isinstance(fp, dict) and "sweep" in fp:
        compact["flash_tuned_speedup"] = fp.get("flash_tuned_speedup")
        compact["crossover_seq_len"] = fp.get("crossover_seq_len")
    # Analyzer health: total `tpp lint` findings over the six shipped
    # examples (must be 0 — see bench_lint).
    lint = report.get("lint")
    if isinstance(lint, dict) and "findings_total" in lint:
        compact["lint_findings"] = lint["findings_total"]
    if "terminated" in report:
        compact["terminated"] = report["terminated"]
    return compact


def _flush(report: dict) -> None:
    _finalize_headline(report)
    # stdout: compact headline line only (driver tail keeps 2,000 bytes and
    # JSON-parses the LAST line — it must never see the multi-KB report).
    print(json.dumps(_compact(report)), flush=True)
    try:
        # Atomic replace: a kill mid-write must corrupt the temp file, not
        # the last good snapshot the survivability contract promises.
        with open(PARTIAL_FILE + ".tmp", "w") as f:
            f.write(json.dumps(report) + "\n")
        os.replace(PARTIAL_FILE + ".tmp", PARTIAL_FILE)
    except OSError:
        pass


def main() -> None:
    import signal

    smoke = bool(int(os.environ.get("BENCH_SMOKE", "0")))
    # The PREVIOUS bench run's full report, read before the first flush
    # overwrites it: the baseline for the trace-diff regression
    # self-report (see _trace_regression_report).
    prev_report = None
    try:
        with open(PARTIAL_FILE) as f:
            prev_report = json.load(f)
    except (OSError, ValueError):
        prev_report = None
    # 1300 s fits the full round-5 leg set (measured 964 s end to end);
    # overrunning an external timeout is survivable anyway — flagship legs
    # run first, every flush prints a compact parseable stdout line, and
    # SIGTERM triggers a final flush — whereas a budget below the leg-set
    # cost guarantees the tail legs are skipped.
    budget = float(os.environ.get("BENCH_BUDGET_S", "1300"))
    t0 = time.monotonic()

    def remaining() -> float:
        return budget - (time.monotonic() - t0)

    report: dict = {
        "metric": "bench_failed", "value": 0.0,
        "unit": "examples/sec/chip",
        # North star: >=90% of A100 (vs_baseline >= 0.9 hits the target).
        "vs_baseline": None,
        "a100_reference": A100_REFERENCE,
        "mfu": None,
        "budget_s": budget,
        "errors": {},
        "smoke": smoke,
        # Scheduler concurrency config, recorded so BENCH_*.json files from
        # different rounds/configs stay comparable (each e2e leg also
        # records its own effective max_parallel_nodes).
        "concurrency": {
            "scheduler": "ready_set",
            "default_policy": "n_dag_roots",
            "env_max_parallel_nodes": (
                os.environ.get("TPP_MAX_PARALLEL_NODES") or None
            ),
            "e2e_sched_leg_workers": E2E_SCHED_WORKERS,
        },
    }

    def on_term(signum, frame):  # noqa: ARG001
        report["terminated"] = f"signal {signum}"
        report["elapsed_s"] = round(time.monotonic() - t0, 1)
        _flush(report)
        os._exit(128 + signum)

    signal.signal(signal.SIGTERM, on_term)

    try:
        report["chip"] = chip_info()
    except Exception as e:
        report["chip"] = {"error": str(e)}

    def leg(name: str, fn, est_cost_s: float, retries: int = 2,
            post=None) -> None:
        """One budget-checked workload: skip when it doesn't fit, record its
        result or error, flush the cumulative report either way."""
        if remaining() < est_cost_s:
            report[name] = {
                "skipped_budget": True,
                "est_cost_s": est_cost_s,
                "remaining_s": round(remaining(), 1),
            }
        else:
            result, err = run_workload(name, fn, smoke, retries=retries)
            if post is not None and result is not None:
                result = post(result)
            if result is not None:
                report[name] = result
            if err:
                report["errors"][name] = err
        report["elapsed_s"] = round(time.monotonic() - t0, 1)
        _flush(report)

    def taxi_best_of_2(first: dict) -> dict:
        # Best-of-2: taxi's µs-scale steps are host-transfer-bound, so its
        # throughput swings run to run with the host's load; the better
        # run is the less-noise-polluted measurement.
        # (BERT is device-bound and stable; one run suffices.)
        if not smoke and remaining() > 120:
            second, _ = run_workload("taxi", bench_taxi, smoke, retries=0)
            if second is not None and (
                second["examples_per_sec_per_chip_wholerun"]
                > first["examples_per_sec_per_chip_wholerun"]
            ):
                first = second
            first["best_of"] = 2
        return first

    # Order: cheapest evidence first, flagship second, e2e-BERT (the
    # north-star green target) before e2e-taxi, probes last.
    # Analyzer health first: compile-and-lint all six examples costs
    # seconds (module imports dominate) and its findings_total==0 verdict
    # is the cheapest whole-repo sanity signal in the round.
    leg("lint", bench_lint, est_cost_s=30, retries=1)
    leg("taxi", bench_taxi, est_cost_s=90, post=taxi_best_of_2)
    leg("taxi_device", bench_taxi_device, est_cost_s=60, retries=1)

    def taxi_window_post(result: dict) -> dict:
        # taxi_device is the published ceiling: the ratio of the windowed
        # pipeline-path throughput to the device-resident fori_loop figure
        # is the remaining host-orchestration gap (1.0 = fully closed).
        ceiling = (report.get("taxi_device") or {}).get(
            "examples_per_sec_per_chip"
        )
        if ceiling:
            result["taxi_device_ceiling"] = ceiling
            result["gap_to_device_ceiling"] = round(
                result["examples_per_sec_per_chip"] / ceiling, 4
            )
        return result

    # Host-loop-tax evidence (ISSUE 8): windowed train_loop sweep, right
    # after its ceiling so the gap ratio can land in the same flush.
    leg("taxi_window", bench_taxi_window, est_cost_s=110, retries=1,
        post=taxi_window_post)

    def taxi_window_mesh_post(result: dict) -> dict:
        # Same ceiling as taxi_window: the windowed MESH throughput per
        # chip over the device-resident fori_loop figure — the remaining
        # host+collective gap on the multi-chip path (ISSUE 15).
        ceiling = (report.get("taxi_device") or {}).get(
            "examples_per_sec_per_chip"
        )
        if ceiling and "examples_per_sec_per_chip" in result:
            result["taxi_device_ceiling"] = ceiling
            result["gap_to_ceiling"] = round(
                result["examples_per_sec_per_chip"] / ceiling, 4
            )
        return result

    # Multi-chip window evidence (ISSUE 15): the same window sweep on the
    # full mesh with the bucketed in-scan collective, vs one device
    # (skipped on a one-device backend).
    leg("taxi_window_mesh", bench_taxi_window_mesh, est_cost_s=180,
        retries=1, post=taxi_window_mesh_post)
    # +80 s vs r5: the windowed BERT datapoint is one extra compile + run.
    leg("bert", bench_bert, est_cost_s=200)
    # The bert window sweep's parallelism axis (ISSUE 18): dp | fsdp |
    # fsdp+accum | ring-attention long-context, MFU + memory per config.
    leg("bert_parallelism", bench_bert_parallelism, est_cost_s=180,
        retries=1)
    e2e: dict = {}
    report["pipeline_e2e"] = e2e

    def e2e_leg(name: str, fn, est_cost_s: float) -> None:
        if remaining() < est_cost_s:
            e2e[name] = {
                "green": False, "skipped_budget": True,
                "est_cost_s": est_cost_s,
                "remaining_s": round(remaining(), 1),
            }
        else:
            result, err = run_workload(f"e2e_{name}", fn, smoke, retries=1)
            e2e[name] = (
                result if result is not None
                else {"green": False, "error": err}
            )
        report["elapsed_s"] = round(time.monotonic() - t0, 1)
        _flush(report)

    e2e_leg("bert", bench_e2e_bert, est_cost_s=200)
    # Runs the DAG three times (cold headline + warm trace-on/off pair
    # for the tracing-overhead bound).
    e2e_leg("taxi", bench_e2e_taxi, est_cost_s=260)
    # Cross-run regression self-report: diff this run's taxi trace
    # profile against the previous bench run's (advisory flags on the
    # compact line; `trace diff` is the operator-facing equivalent).
    report["trace_diff"] = _trace_regression_report(
        prev_report, report, smoke
    )
    _flush(report)
    # Live serving telemetry: tail latency from the server's own
    # /metrics scrape + /healthz under concurrent load.
    leg("serving", bench_serving, est_cost_s=60, retries=1)
    # Serving fleet (ISSUE 10): multi-replica + SLO batching + reload-
    # under-load hammer, judged from the fleet's own scrape.
    leg("serving_fleet", bench_serving_fleet, est_cost_s=150, retries=1)
    # Quantized + AOT serving payloads (ISSUE 14): Rewriter variants,
    # quality gate, Pusher variant deploy, int8-vs-float hammer A/B and
    # the compiles-after-warm == 0 contract, off the fleet's own scrape.
    leg(
        "serving_quantized", bench_serving_quantized,
        est_cost_s=120, retries=1,
    )
    # Continuous-batching decode (ISSUE 11): generative fleet vs
    # whole-request A/B on identical mixed-length traffic + zero-5xx
    # hot-swap with generations in flight, off the fleet's own scrape.
    # +60 s vs r5 (ISSUE 16): the long-shared-prefix pass runs the same
    # traffic on an optimised (prefix cache + chunked prefill) fleet and a
    # plain one.
    leg(
        "generative_serving", bench_generative_serving,
        est_cost_s=180, retries=1,
    )
    # Wall-clock head of the BASELINE metric: the same taxi DAG sequential
    # vs concurrent, identical-lineage checked (see bench_e2e_taxi_sched).
    e2e_leg("taxi_sched", bench_e2e_taxi_sched, est_cost_s=240)
    # Crash-safety evidence: kill-at-Trainer + resume vs cold re-run
    # (work-saved ratio + stitched-lineage identity) PLUS the taxi_chaos
    # fault-schedule leg (classified retries, shard-worker kill, store
    # contention, zero-5xx reload hammer — see _bench_taxi_chaos) PLUS
    # the serving_chaos self-healing-fleet leg (kill 1-of-2 replicas
    # mid-hammer, decode-session recovery — see _bench_serving_chaos).
    leg("robustness", bench_robustness, est_cost_s=480, retries=1)
    # Sharded data plane: sharded-vs-single ingest+stats+transform
    # wall-clock + identity checks (see bench_data_plane).
    leg("data_plane", bench_data_plane, est_cost_s=120, retries=1)
    # Continuous pipelines (ISSUE 13): three synthetic spans fed to a
    # RUNNING controller — incremental stats identity, work-saved ratio,
    # and span-landing -> fleet-serving deploy latency.
    leg("continuous", bench_continuous, est_cost_s=90, retries=1)
    # Live drift & skew plane (ISSUE 20): a monitored fleet under control
    # then covariate-shifted traffic — zero false alarms, detection
    # within 3 windows of the shift, and exactly one drift-triggered
    # retrain through the RUNNING controller's scrape poll.
    leg("monitoring", bench_monitoring, est_cost_s=90, retries=1)
    leg("mnist", bench_mnist, est_cost_s=60, retries=1)
    leg("resnet", bench_resnet, est_cost_s=150, retries=1)
    # +50 s vs r5: the seq sweep times ~4 candidate block configs per
    # length instead of one fixed config.
    leg("flash_probe", bench_flash_probe, est_cost_s=150, retries=1)
    leg("t5_decode", bench_t5_decode, est_cost_s=90, retries=1)
    # Least critical, so last: the converged-goodput evidence leg — sized
    # from whatever budget is actually left (~90 s compile/init reserve
    # plus the computed step time must fit under remaining()).
    leg(
        "bert_goodput",
        lambda s: bench_bert_goodput(
            s,
            budget_s=remaining(),
            eps_hint=(report.get("bert") or {}).get(
                "examples_per_sec_per_chip"
            ) or 0.0,
        ),
        est_cost_s=160,
        retries=1,
    )

    report["elapsed_s"] = round(time.monotonic() - t0, 1)
    _flush(report)
    failed = sorted(report["errors"]) + sorted(
        f"e2e_{name}" for name, row in e2e.items() if row.get("error")
    )
    if failed:
        # Every leg's evidence is flushed above; a failed leg still fails
        # the run (skipped-for-budget legs do not).
        print(f"# bench: failed legs: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
