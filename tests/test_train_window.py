"""Device-resident multi-step training window (ISSUE 8).

The `log_every` window runs as ONE compiled ``lax.scan`` over a
device-staged batch stack instead of a Python loop dispatching one jitted
step at a time.  The contracts pinned here:

  * numerical identity — the windowed path is bit-identical to
    ``window_steps=1`` (same param trajectory, same per-step loss series)
    on the CPU mesh: it is the same ``step_fn``, scanned;
  * boundary semantics — eval/checkpoint land on their exact steps
    (windows shrink to the boundary), watchdogs see every per-step loss
    reconstructed from the windowed accumulator (a NaN injected
    mid-window fires at the boundary), and telemetry gauges publish at
    window cadence;
  * async checkpoint fence — a run interrupted between windows leaves a
    durable, resumable checkpoint (the background save is fenced before
    every subsequent save and at loop exit);
  * config resolution — explicit ``window_steps`` > ``TPP_WINDOW_STEPS``
    env > ``log_every`` default; ``window_steps=1`` keeps the per-step
    loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_pipelines.trainer import TrainLoopConfig, train_loop

pytestmark = pytest.mark.trainer

BATCH = 32


def _batches(n, batch=BATCH, seed=0):
    """A finite, deterministic batch list (replayable across runs)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.normal(size=(batch, 2)).astype(np.float32)
        y = (x @ np.array([3.0, -2.0], np.float32) + 1.0).astype(np.float32)
        out.append({"x": x, "y": y})
    return out


def _loss_fn(params, b, rng):
    pred = b["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - b["y"]) ** 2), {"w_norm": jnp.sum(params["w"] ** 2)}


def _init_fn(rng, b):
    return {"w": jnp.zeros((2,)), "b": jnp.zeros(())}


def _run(window_steps, steps=24, log_every=4, **kw):
    hist = []
    params, result = train_loop(
        loss_fn=_loss_fn,
        init_params_fn=_init_fn,
        optimizer=optax.adam(0.05),
        train_iter=iter(_batches(steps)),
        config=TrainLoopConfig(
            train_steps=steps, batch_size=BATCH, log_every=log_every,
            window_steps=window_steps, prng_impl=None,
        ),
        metrics_cb=lambda s, m: hist.append((s, m["loss"], m["w_norm"])),
        **kw,
    )
    return params, result, hist


def test_windowed_matches_per_step_bitwise():
    p1, r1, h1 = _run(1)
    pw, rw, hw = _run(8)
    assert r1.window_steps == 1 and rw.window_steps == 8
    for a, b in zip(
        jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(pw)
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # Same loss series at the same steps: per-step values are reconstructed
    # from the windowed accumulator, so the log cadence is unchanged.
    assert h1 == hw
    assert len(h1) == 24 // 4
    assert r1.final_metrics == rw.final_metrics
    assert rw.steps_completed == 24


def test_window_defaults_to_log_every_and_env_overrides(monkeypatch):
    _, r_default, _ = _run(None, steps=12, log_every=4)
    assert r_default.window_steps == 4
    monkeypatch.setenv("TPP_WINDOW_STEPS", "6")
    _, r_env, _ = _run(None, steps=12, log_every=4)
    assert r_env.window_steps == 6
    # Explicit config wins over the env.
    _, r_explicit, _ = _run(3, steps=12, log_every=4)
    assert r_explicit.window_steps == 3
    # log_every=0 stays per-step unless asked otherwise.
    monkeypatch.delenv("TPP_WINDOW_STEPS")
    _, r_bench, _ = _run(None, steps=6, log_every=0)
    assert r_bench.window_steps == 1


def test_partial_tail_and_iterator_exhaustion():
    # 10 steps at window 4 -> windows of 4, 4, 2; and an iterator that dies
    # mid-window (6 batches for 8 scheduled steps) still yields a clean stop.
    _, r, _ = _run(4, steps=10, log_every=0)
    assert r.steps_completed == 10
    params, result = train_loop(
        loss_fn=_loss_fn,
        init_params_fn=_init_fn,
        optimizer=optax.adam(0.05),
        train_iter=iter(_batches(6)),
        config=TrainLoopConfig(
            train_steps=8, batch_size=BATCH, log_every=0, window_steps=4,
            prng_impl=None,
        ),
    )
    assert result.steps_completed == 6


def test_nan_mid_window_fires_watchdog_at_boundary():
    fired = []

    def nan_batches():
        for i, b in enumerate(_batches(16, batch=8)):
            if i == 10:  # mid-window for window_steps=8 (steps 9..16)
                b = {**b, "y": b["y"] * np.nan}
            yield b

    train_loop(
        loss_fn=_loss_fn,
        init_params_fn=_init_fn,
        optimizer=optax.sgd(0.01),
        train_iter=nan_batches(),
        config=TrainLoopConfig(
            train_steps=16, batch_size=8, log_every=0, window_steps=8,
            prng_impl=None,
            health_alert_cb=lambda kind, detail: fired.append((kind, detail)),
        ),
    )
    nan_alerts = [d for k, d in fired if k == "nan"]
    assert nan_alerts, fired
    # The reconstructed per-step series attributes the alert to the exact
    # in-window step (batch 10 -> step 11), not just "the window".
    assert "step 11" in nan_alerts[0]


def test_telemetry_gauges_publish_at_window_cadence():
    from tpu_pipelines.observability.metrics import default_registry

    _, r, _ = _run(6, steps=18, log_every=6)
    reg = default_registry()
    assert reg.gauge("train_steps_total").get() == 18
    assert reg.gauge("train_examples_per_sec").get() > 0
    assert reg.gauge("train_step_seconds").get() > 0
    assert reg.gauge("train_host_input_wait_seconds_total").get() >= 0
    # Window boundaries are sync anchors (a forced device read per window):
    # 3 windows -> first absorbs compile, the rest form anchored spans.
    assert r.anchor_windows >= 1


_PHASES = ("infeed_wait", "device_compute", "device_collective", "host")


@pytest.mark.profiling
def test_window_phase_attribution_sums_exactly(tmp_path):
    """Step-time attribution (ISSUE 19): each post-warmup window's four
    phases sum EXACTLY to that window's wall-clock (host is measured as
    the remainder, so the shares are trustworthy), the breakdown lands
    in the RunTrace for `trace`/`trace diff`, the registry counters
    advance by the same totals, and a fixed-shape run has zero
    compiles after warmup."""
    from tpu_pipelines.observability import TraceRecorder, activate, read_events
    from tpu_pipelines.observability.metrics import default_registry

    reg = default_registry()
    c_phase = reg.counter("train_window_time_seconds", labels=("phase",))
    base = {ph: c_phase.labels(ph).get() for ph in _PHASES}
    base_compiles = reg.counter("train_compiles_after_warm_total").get()

    rec = TraceRecorder(str(tmp_path / "run"), "telemetry")
    with activate(rec):
        _, r, _ = _run(6, steps=24, log_every=6)
    rec.close()

    # Steady state: every window compiles the same scan -> zero
    # post-warmup compiles, in the result AND on the registry.
    assert r.compiles_after_warm == 0
    assert (
        reg.counter("train_compiles_after_warm_total").get()
        == base_compiles
    )

    # Per-window sum-exact invariant, from the recorded instants: 4
    # windows, the first absorbs compile (warmup) and is not attributed.
    events = read_events(rec.events_path)
    windows = [e for e in events if e["name"] == "window_breakdown"]
    assert len(windows) == 24 // 6 - 1
    for e in windows:
        phase_sum = sum(e["args"][ph] for ph in _PHASES)
        assert phase_sum == pytest.approx(e["args"]["window_s"], rel=1e-6)
        assert all(e["args"][ph] >= 0 for ph in _PHASES)

    # The run summary instant and TrainResult agree with the registry.
    summary, = [e for e in events if e["name"] == "train_telemetry_summary"]
    assert summary["args"]["compiles_after_warm"] == 0
    assert set(r.window_phase_seconds) == set(_PHASES)
    total = sum(r.window_phase_seconds.values())
    assert total > 0
    assert total == pytest.approx(
        sum(e["args"]["window_s"] for e in windows), abs=1e-4
    )
    for ph in _PHASES:
        assert c_phase.labels(ph).get() - base[ph] == pytest.approx(
            r.window_phase_seconds[ph], abs=1e-4
        )

    # HBM watermark gauge: at least as high as the live bytes gauge
    # whenever this backend reports memory stats at all.
    peak = reg.gauge("device_memory_peak_bytes", labels=("device",))
    live = reg.gauge("train_device_memory_bytes").get()
    peak_total = sum(peak.labels(str(d)).get() for d in range(8))
    assert peak_total >= 0
    if live > 0:
        assert peak_total >= live

    # MFU: unmeasurable (no cost analysis / unknown peak) or a sane
    # fraction.
    assert r.mfu is None or 0.0 <= r.mfu <= 1.5


@pytest.mark.profiling
def test_compiles_after_warm_excludes_administrative_compiles(tmp_path):
    """A healthy run with checkpointing, eval, AND a checkpoint cadence
    misaligned with the window must still read compiles_after_warm == 0
    (found live: the first CLI drive read 10 on a healthy taxi run).
    The checkpoint snapshot copy and the eval program's first build are
    admin-booked under train_compile_seconds_total{when="admin"}; the
    cadence-split short window's scan is a NEW program whose one compile
    is its own warmup — only a re-compile of a seen length is a stall."""
    from tpu_pipelines.observability.metrics import default_registry

    reg = default_registry()
    c_when = reg.counter("train_compile_seconds_total", labels=("when",))
    base_admin = c_when.labels("admin").get()
    base_warm = reg.counter("train_compiles_after_warm_total").get()

    steps = 30
    params, result = train_loop(
        loss_fn=_loss_fn,
        init_params_fn=_init_fn,
        optimizer=optax.adam(0.05),
        train_iter=iter(_batches(steps)),
        eval_iter_fn=lambda: iter(_batches(2, seed=1)),
        config=TrainLoopConfig(
            train_steps=steps, batch_size=BATCH, log_every=5,
            # 7 does not divide the 10-step window: the loop dispatches
            # cadence-split windows (new scan lengths) mid-run.
            window_steps=10, checkpoint_every=7, eval_steps=2,
            prng_impl=None,
        ),
        checkpoint_dir=str(tmp_path / "ckpts"),
    )
    assert result.steps_completed == steps
    assert result.compiles_after_warm == 0
    assert reg.counter("train_compiles_after_warm_total").get() == base_warm
    # The administrative compiles really happened and were really booked
    # — the counter moved, it didn't just skip the events.
    assert c_when.labels("admin").get() > base_admin


def test_async_checkpoint_fence_interrupt_and_resume(tmp_path):
    ckpt = str(tmp_path / "ckpts")

    # "Kill" between windows: the iterator exhausts at step 16 of 32.  The
    # async save at the step-16 boundary must be fenced to durability
    # before train_loop returns.
    _, r1 = train_loop(
        loss_fn=_loss_fn,
        init_params_fn=_init_fn,
        optimizer=optax.adam(0.05),
        train_iter=iter(_batches(16)),
        config=TrainLoopConfig(
            train_steps=32, batch_size=BATCH, log_every=8, window_steps=8,
            checkpoint_every=8, prng_impl=None,
        ),
        checkpoint_dir=ckpt,
    )
    assert r1.steps_completed == 16

    import orbax.checkpoint as ocp

    assert ocp.CheckpointManager(ckpt).latest_step() == 16

    # Resume completes the run from the fenced checkpoint.
    params, r2 = train_loop(
        loss_fn=_loss_fn,
        init_params_fn=_init_fn,
        optimizer=optax.adam(0.05),
        train_iter=iter(_batches(16, seed=1)),
        config=TrainLoopConfig(
            train_steps=32, batch_size=BATCH, log_every=8, window_steps=8,
            checkpoint_every=8, prng_impl=None,
        ),
        checkpoint_dir=ckpt,
    )
    assert r2.resumed_from_step == 16
    assert r2.steps_completed == 32
    assert ocp.CheckpointManager(ckpt).latest_step() == 32


def test_eval_and_checkpoint_land_on_exact_boundaries(tmp_path):
    # window 8 with eval_every=6: windows shrink (6, 2, 4, ...) so eval
    # sees the state at exactly steps 6 and 12.
    eval_at = []
    train_loop(
        loss_fn=_loss_fn,
        init_params_fn=_init_fn,
        optimizer=optax.adam(0.05),
        train_iter=iter(_batches(12)),
        config=TrainLoopConfig(
            train_steps=12, batch_size=BATCH, log_every=0, window_steps=8,
            eval_every=6, eval_steps=1, prng_impl=None,
        ),
        eval_iter_fn=lambda: iter(_batches(2, seed=9)),
        metrics_cb=lambda s, m: eval_at.append(s) if any(
            k.startswith("eval_") for k in m
        ) else None,
    )
    assert eval_at == [6, 12]


def test_model_state_threads_through_windowed_scan():
    # has_model_state=True: the mutable collection round-trips the scan
    # carry identically to the per-step path.
    def loss_fn(params, mstate, b, rng):
        pred = b["x"] @ params["w"] + params["b"]
        new_state = {"seen": mstate["seen"] + 1.0}
        return jnp.mean((pred - b["y"]) ** 2), ({}, new_state)

    def init_fn(rng, b):
        return {"w": jnp.zeros((2,)), "b": jnp.zeros(())}, {"seen": jnp.zeros(())}

    outs = {}
    for w in (1, 4):
        (params, mstate), result = train_loop(
            loss_fn=loss_fn,
            init_params_fn=init_fn,
            optimizer=optax.adam(0.05),
            train_iter=iter(_batches(8)),
            config=TrainLoopConfig(
                train_steps=8, batch_size=BATCH, log_every=0, window_steps=w,
                prng_impl=None,
            ),
            has_model_state=True,
        )
        outs[w] = (params, mstate)
    assert float(outs[4][1]["seen"]) == 8.0
    for a, b in zip(
        jax.tree_util.tree_leaves(outs[1]), jax.tree_util.tree_leaves(outs[4])
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))
