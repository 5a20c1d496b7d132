"""Driver for cells that train through ``trainer.train_loop``.

One call of ``train_loop`` is one object: the compiled window with its
state.  Its first window (which compiles) runs the first steps from the
seed through the window's own call and feed; the same object then runs the
measured window.  Three things of the benchmark's ride along so that the
first steps can be compared with the plain reference afterwards: per-step
losses come back through ``metrics_cb``; the optimizer handed to the loop
is ``optax.adamw`` inside ``with_probes``, which writes the norm and one
fixed projection of every leaf of the first gradient (as the optimizer
gets it) and the norm of the parameters' change after the third step into
a small extra parameter leaf that the loop returns with the final
parameters.
"""

from __future__ import annotations

import importlib
import math
import os
import shutil
import time
from typing import Any, Dict, Iterator, List

import numpy as np

from benchmark import harness, peaks, traffic as traffic_mod
from benchmark import weights, window_events, work
from benchmark.harness import say

PROBE = "benchmark_probe"
CHECKED_STEPS = 3


# ------------------------------------------------------------- the probes

def _real(tree: Dict) -> Dict:
    return {k: v for k, v in tree.items() if k != PROBE}


def with_probes(inner):
    """``inner`` (an optax transformation) over the real leaves, plus the
    probe leaf's update: row 0 the norms of the first gradient's leaves,
    row 1 their projections on the reference's fixed sign pattern, row 2
    the norms of the leaves of ``params - initial params`` after update
    ``CHECKED_STEPS``.  Each is computed once, under ``lax.cond``."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.reference.common import project

    def projections(tree):
        return jnp.stack(
            [project(x) for x in jax.tree_util.tree_leaves(tree)])

    def norms(tree):
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)
        ])

    def init(params):
        real = _real(params)
        return {
            "inner": inner.init(real),
            "count": jnp.zeros((), jnp.int32),
            "start": jax.tree_util.tree_map(jnp.copy, real),
        }

    def update(grads, state, params=None):
        g, p = _real(grads), _real(params)
        updates, inner_state = inner.update(g, state["inner"], p)
        count = state["count"]
        n = len(jax.tree_util.tree_leaves(g))
        zero = jnp.zeros((n,), jnp.float32)
        first_grad = jax.lax.cond(
            count == 0, lambda: norms(g), lambda: zero)
        first_proj = jax.lax.cond(
            count == 0, lambda: projections(g), lambda: zero)
        change = jax.lax.cond(
            count == CHECKED_STEPS - 1,
            lambda: norms(jax.tree_util.tree_map(
                lambda a, u, s: a + u - s, p, updates, state["start"])),
            lambda: zero,
        )
        out = dict(updates)
        out[PROBE] = jnp.stack([first_grad, first_proj, change])
        return out, {
            "inner": inner_state, "count": count + 1,
            "start": state["start"],
        }

    return optax.GradientTransformation(init, update)


# ------------------------------------------------------- the event sink

class WindowRecorder:
    """In-memory stand-in for the run trace's recorder: keeps the
    trainer's ``window_breakdown`` events with the host clock at receipt,
    which is the end of the window the event closes."""

    run_id = "benchmark"
    run_dir = ""

    def __init__(self, on_window=None):
        self.windows: List[Dict] = []
        self.on_window = on_window

    def instant(self, name, cat="", node="", args=None):
        at = time.perf_counter()
        if name == "window_breakdown":
            event = dict(args or {}, at=at)
            self.windows.append(event)
            if self.on_window is not None:
                self.on_window(event, len(self.windows) - 1)

    def complete(self, *a, **kw):
        pass

    def span(self, *a, **kw):
        import contextlib

        return contextlib.nullcontext({})

    def close(self):
        pass


def window_host_spans(windows: List[Dict]):
    """What the trainer's host thread was doing, from the events."""
    spans = []
    for w in windows:
        start = w["at"] - w["window_s"]
        a = start + w["host"]
        b = a + w["infeed_wait"]
        spans.append(("trainer: host work after the last window", start, a))
        spans.append(("data plane: waiting for the staged window", a, b))
        spans.append(("trainer: window dispatched, waiting for its metrics",
                      b, w["at"]))
    return spans


# ------------------------------------------------------------ the driver

def _feed(pool: List[Dict], window_steps: int, stop,
          on_fetch=None) -> Iterator[Dict]:
    """Cycle the pool; end on a window boundary once ``stop()`` says so,
    which ends ``train_loop`` by its own exhausted-iterator path."""
    i = 0
    while True:
        if i % window_steps == 0 and stop():
            return
        if on_fetch is not None:
            on_fetch(i)
        yield pool[i % len(pool)]
        i += 1


# A leaf whose gradient is zero by the mathematics (the bias of an
# attention key projection: adding a constant to every key's score leaves
# the softmax unchanged) has a first gradient of rounding noise alone, and
# Adam scales that noise up to whole steps of the learning rate.  Its
# change says nothing about the step, so the change-norm check leaves out
# leaves whose reference gradient norm is below this share of the median.
ZERO_GRADIENT_SHARE = 1e-4


def _worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                    leaves=None, what: str = "") -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    leaves = list(ref) if leaves is None else leaves
    floor = float(np.median([ref[name] for name in leaves]))
    gaps = {
        name: abs(prog[name] - ref[name]) / max(ref[name], floor)
        for name in leaves
    }
    worst = max(gaps, key=gaps.get)
    say(f"  {what} worst leaf {worst}: program {prog[worst]!r}, "
        f"reference {ref[worst]!r}, median leaf {floor!r}")
    return gaps[worst]


def _projection_error(prog: Dict, ref: Dict) -> float:
    """Root mean square over the leaves of the gap between the program's
    and the reference's projection of a leaf of the first gradient,
    against the reference's norm of that leaf or of the median leaf.  A
    projection on a fixed sign pattern carries the error of the whole
    leaf, so this estimates the relative error of the first gradient."""
    norms = ref["first_grad_norms"]
    floor = float(np.median(list(norms.values())))
    sq = [
        ((prog["first_grad_projections"][name]
          - ref["first_grad_projections"][name])
         / max(norms[name], floor)) ** 2
        for name in norms
    ]
    return float(np.sqrt(np.mean(sq)))


def build(ctx) -> Dict[str, Any]:
    """Model, loss, seeded weights and feed of a cell, from its files."""
    import jax
    import jax.numpy as jnp
    import optax

    config, mix = ctx.config, ctx.traffic
    builder = importlib.import_module(config["program"]["module"])
    model = getattr(builder, config["program"]["build"])(config["hparams"])
    hp = config["hparams"]
    pool = traffic_mod.train_pool(mix, ctx.seed, int(hp["vocab_size"]))

    def features(b):
        return {k: v for k, v in b.items() if k != "label"}

    dropout = bool(float(hp["dropout_rate"]))

    def loss_fn(params, b, step_rng):
        logits = model.apply(
            {"params": _real(params)}, features(b),
            deterministic=not dropout, rngs={"dropout": step_rng})
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(b["label"], jnp.int32)).mean()
        return loss, {}

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), features(pool[0]))["params"])
    n_leaves = len(jax.tree_util.tree_leaves(shapes))

    def make_params():
        return weights.make_weights(shapes, config["weights"], ctx.seed)

    def init_params_fn(_rng, _batch):
        params = dict(make_params())
        params[PROBE] = jnp.zeros((3, n_leaves), jnp.float32)
        return params

    return {
        "model": model, "pool": pool, "loss_fn": loss_fn, "shapes": shapes,
        "init_params_fn": init_params_fn, "make_params": make_params,
    }


# What the driver hands ``TrainLoopConfig`` itself; a file may not set them.
DRIVERS_OWN = ("train_steps", "batch_size", "seed", "log_every")


def loop_fields(mix: Dict, config_cls) -> Dict[str, Any]:
    """The traffic file's ``train_loop`` block: fields of
    ``TrainLoopConfig`` as data.  A key that the dataclass lacks, or that
    the driver sets itself, is an error."""
    import dataclasses

    block = dict(mix.get("train_loop", {}))
    harness.check_option_keys(
        "train_loop", block,
        {f.name for f in dataclasses.fields(config_cls)}, DRIVERS_OWN)
    return block


def train_seed(ctx) -> int:
    """The seed handed to ``TrainLoopConfig``, which takes 31 bits."""
    return int(ctx.seed) % (2 ** 31)


def reference_numbers(ctx, built, prng_impl, mode: str = "f32") -> Dict:
    """The plain reference's numbers for the first steps of the feed."""
    config = ctx.config
    ref = importlib.import_module(
        "benchmark.reference." + config["reference"])
    n_layers = int(config["hparams"]["n_layers"])
    ref_params = ref.from_served_tree(
        weights.flat_leaves(built["make_params"]()), n_layers)
    rate = float(config["hparams"]["dropout_rate"])
    out = ref.follow_steps(
        ref_params, built["pool"][:CHECKED_STEPS],
        lr=float(ctx.traffic["learning_rate"]), mode=mode,
        rows_per_block=int(config["check"]["reference_rows_per_block"]),
        dropout={"rate": rate, "train_seed": train_seed(ctx),
                 "prng_impl": prng_impl} if rate else None,
    )
    out["leaf_names"] = ref.leaf_names(n_layers)
    return out


def compare(checks, limits: Dict, prog: Dict, ref: Dict, label="") -> None:
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        checks.at_most(f"{label}loss_gap.step{i + 1}",
                       abs(a - b) / abs(b), limits["loss_gap"])
    grad_norms = ref["first_grad_norms"]
    checks.at_most(f"{label}first_grad_norm_gap.worst_leaf",
                   _worst_leaf_gap(prog["first_grad_norms"], grad_norms,
                                   what="first gradient norm,"),
                   limits["first_grad_norm_gap"])
    checks.at_most(f"{label}first_grad_error.rms_leaf",
                   _projection_error(prog, ref),
                   limits["first_grad_error"])
    floor = ZERO_GRADIENT_SHARE * float(np.median(list(grad_norms.values())))
    moved = [name for name, g in grad_norms.items() if g >= floor]
    say(f"  change norm: {len(grad_norms) - len(moved)} leaves with a zero "
        f"gradient left out: {sorted(set(grad_norms) - set(moved))[:4]} ...")
    checks.at_most(f"{label}change_norm_gap.worst_leaf",
                   _worst_leaf_gap(prog["change_norms"], ref["change_norms"],
                                   moved, what="change norm,"),
                   limits["change_norm_gap"])


def run(ctx) -> Dict[str, Any]:
    import jax
    import optax

    from tpu_pipelines.observability import trace as program_trace
    from tpu_pipelines.parallel.mesh import MeshConfig, make_mesh
    from tpu_pipelines.trainer import TrainLoopConfig, train_loop

    config, mix = ctx.config, ctx.traffic
    stage = lambda what: harness.stage(ctx, what)
    stage("imports done")
    built = build(ctx)
    stage("model, pool and shapes built")
    pool = built["pool"]
    batch_size = int(mix["batch_size"])
    chips = len(ctx.devices)
    fields = loop_fields(mix, TrainLoopConfig)
    mesh = make_mesh(
        MeshConfig(**fields.pop("mesh_config", {"data": chips})),
        devices=ctx.devices)
    loop_config = TrainLoopConfig(
        train_steps=10 ** 9, batch_size=batch_size, log_every=1,
        seed=train_seed(ctx), **fields)
    if not loop_config.window_steps:
        raise KeyError(
            f"traffic {ctx.cell['traffic']!r} states no window_steps in "
            "its train_loop block")
    window_steps = int(loop_config.window_steps)
    checkpoint_dir = ""
    if loop_config.checkpoint_every:
        checkpoint_dir = os.path.join(ctx.out_dir, "checkpoints")
        shutil.rmtree(checkpoint_dir, ignore_errors=True)

    device_trace = harness.DeviceTrace(ctx.out_dir) if ctx.trace else None
    trace_windows = int(mix["trace_windows"])
    state = {"t0": None, "trace_from": None, "trace_to": None}

    def on_window(event, index):
        if index == 0:
            state["t0"] = event["at"]
            stage("second window closed, measured window opens")
        if device_trace is None:
            return
        if index == 1:
            device_trace.start()
            state["trace_from"] = time.perf_counter()
        elif index == 1 + trace_windows:
            device_trace.stop()
            state["trace_to"] = time.perf_counter()

    recorder = WindowRecorder(on_window)

    def stop() -> bool:
        t0 = state["t0"]
        if t0 is None or time.perf_counter() < t0 + ctx.seconds:
            return False
        return device_trace is None or device_trace.stopped

    losses: Dict[int, float] = {}

    def metrics_cb(step, metrics):
        if step == 1:
            stage("first window closed (compile and first steps)")
        if "loss" in metrics:
            losses[step] = float(metrics["loss"])

    def on_fetch(i):
        if i == 0:
            stage("train_loop asked for its first batch")

    with program_trace.activate(recorder):
        final, result = train_loop(
            loss_fn=built["loss_fn"],
            init_params_fn=built["init_params_fn"],
            optimizer=with_probes(
                optax.adamw(float(mix["learning_rate"]))),
            train_iter=_feed(pool, window_steps, stop, on_fetch),
            config=loop_config, mesh=mesh, metrics_cb=metrics_cb,
            checkpoint_dir=checkpoint_dir,
        )
    t_end = time.perf_counter()
    stage("train_loop returned")
    probe = np.asarray(jax.device_get(final[PROBE]))
    leaf_paths = list(weights.flat_leaves(built["shapes"]))
    peak_bytes = harness.memory_peak_bytes(ctx.devices)
    del final

    t0 = state["t0"]
    if t0 is None:
        raise RuntimeError("train_loop closed no window after its first")
    inside = window_events.windows_inside(
        recorder.windows, t0, t0 + ctx.seconds)
    bad_steps = [s for s, l in losses.items() if not math.isfinite(l)]
    failed = len({(s - 1) // window_steps for s in bad_steps})
    failed += int(result.compiles_after_warm)
    say(f"train_loop: {result.steps_completed} steps, "
        f"{len(recorder.windows)} windows after the first, "
        f"{len(inside)} wholly inside {ctx.seconds}s; compiles after "
        f"warm-up {result.compiles_after_warm}; ran {t_end - t0:.1f}s "
        f"past the first event")

    clean = inside
    if device_trace is not None and state["trace_to"] is not None:
        # Windows that the profiler's start, its overhead or its stop
        # touched say nothing about the untraced loop.
        lo, hi = state["trace_from"], state["trace_to"]
        clean = [
            w for w in inside
            if w["at"] < lo - 0.5 or w["at"] - w["window_s"] > hi + 0.5
        ] or inside
    reduced = window_events.reduce_windows(
        clean, batch_size=batch_size, chips=chips)
    say("windows:", reduced)

    # ---- correctness: the first steps against the plain reference
    checks = harness.Checks()
    ref = reference_numbers(ctx, built, loop_config.prng_impl)
    names = ref["leaf_names"]
    prog = {
        "losses": [losses[s] for s in range(1, CHECKED_STEPS + 1)],
        "first_grad_norms": {
            names[p]: float(probe[0, i]) for i, p in enumerate(leaf_paths)},
        "first_grad_projections": {
            names[p]: float(probe[1, i]) for i, p in enumerate(leaf_paths)},
        "change_norms": {
            names[p]: float(probe[2, i]) for i, p in enumerate(leaf_paths)},
    }
    compare(checks, config["check"]["limits"], prog, ref)
    if ctx.control:
        for mode in config["check"]["control_modes"]:
            low = reference_numbers(
                ctx, built, loop_config.prng_impl, mode)
            control = harness.Checks()
            compare(control, config["check"]["limits"], low, ref,
                    label=f"control[{mode}].")
            say(f"control[{mode}] correct: {control.ok}")

    counts = work.count_params(built["shapes"])
    hp = config["hparams"]
    flops = work.encoder_train_flops_per_step(
        matmul_params=counts["matmul"], batch=batch_size,
        seq_len=int(mix["seq_len"]), n_layers=int(hp["n_layers"]),
        d_model=int(hp["d_model"]),
    )
    facts: Dict[str, Any] = {
        "train_windows": reduced,
        "train_flops_per_step": flops,
        "chips": chips,
        "peaks": (None if ctx.rehearse
                  else peaks.peaks_for(ctx.devices[0].device_kind)),
    }
    out: Dict[str, Any] = {
        "correct": checks.ok and failed == 0,
        "attempted": len(inside), "failed": failed,
        "end_to_end": {
            "train_examples_per_s": reduced["examples_per_s_per_chip"],
            "setup_s": t0 - ctx.t_process_start,
        },
        "facts": facts, "memory_peak_bytes": peak_bytes,
        "checks": checks.rows,
    }
    if device_trace is not None:
        traced = [
            w for w in recorder.windows
            if state["trace_from"] <= w["at"] <= state["trace_to"] + 0.5
        ]
        facts["trace"] = device_trace.reduce(
            window_host_spans(traced), ctx.rehearse)
    return out
