"""Generic jitted training loop: the framework-owned hot path.

SURVEY.md §3.3 maps the reference's per-step path (tf.function graph →
CollectiveAllReduce over NCCL) to: one ``jax.jit``-compiled train step with
params replicated and the batch sharded over the mesh ``data`` axis; XLA
emits the gradient all-reduce over ICI.  The host loop only feeds batches
(``device_put`` at the infeed boundary) and drains metrics every
``log_every`` steps — no per-step host sync.

Also here: the measurement harness (examples/sec/chip — the BASELINE metric),
orbax checkpoint/resume (the BackupAndRestore equivalent), and optional
per-parameter sharding rules for model parallelism.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_pipelines.parallel.mesh import (
    MeshConfig,
    data_parallel_sharding,
    make_mesh,
    replicate,
)
from tpu_pipelines.parallel.partition import (
    foreign_axis_paths,
    fsdp_param_partition,
    validate_partition,
)
from tpu_pipelines.trainer.fn_args import TrainResult
from tpu_pipelines.trainer.goodput import GoodputTracker

log = logging.getLogger("tpu_pipelines.trainer")


# ---- XLA compile-event tracking (the training twin of the serving
# fleet's aot-compiles-after-warm audit).  jax.monitoring fires
# '/jax/core/compile/backend_compile_duration' for every backend
# compile; listeners cannot be unregistered, so ONE process-wide
# listener is installed lazily and dispatches to the hook of whichever
# train loop is currently running — the indirection is what scopes
# attribution to the live loop and makes repeated train_loop calls in
# one process (tests, tuner trials) not leak listeners.
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"
_COMPILE_HOOK: Optional[Callable[[float], None]] = None
_COMPILE_LISTENER_INSTALLED = False


def _on_xla_compile_event(event: str, duration_s: float, **_kw: Any) -> None:
    hook = _COMPILE_HOOK
    if hook is not None and event.endswith(_COMPILE_EVENT_SUFFIX):
        hook(float(duration_s))


# Marked administrative regions: compiles inside one (same thread) are
# real XLA work but never a step stall — the hook books them under the
# "admin" label instead of the after-warm counter.  threading.local so a
# region opened on the loop thread cannot mask a concurrent thread.
_COMPILE_ADMIN = threading.local()


def _compile_admin_depth() -> int:
    return getattr(_COMPILE_ADMIN, "depth", 0)


@contextlib.contextmanager
def _compile_admin_region():
    _COMPILE_ADMIN.depth = _compile_admin_depth() + 1
    try:
        yield
    finally:
        _COMPILE_ADMIN.depth -= 1


def _set_compile_hook(hook: Optional[Callable[[float], None]]) -> None:
    global _COMPILE_HOOK, _COMPILE_LISTENER_INSTALLED
    _COMPILE_HOOK = hook
    if hook is not None and not _COMPILE_LISTENER_INSTALLED:
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(
                _on_xla_compile_event
            )
            _COMPILE_LISTENER_INSTALLED = True
        except Exception as e:  # noqa: BLE001 — telemetry must not fail a run
            log.debug("compile-event listener unavailable: %s", e)


# Peak per-chip bf16 FLOPs for the live train_mfu gauge.  Precedence:
# TrainLoopConfig.peak_flops_per_chip > TPP_PEAK_FLOPS env > device-kind
# table > 0.0 (MFU not computed — an
# assumed denominator would publish a made-up utilization).
ENV_PEAK_FLOPS = "TPP_PEAK_FLOPS"
_PEAK_BF16_FLOPS = [
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]


def _peak_flops_per_chip(config: "TrainLoopConfig") -> float:
    if config.peak_flops_per_chip:
        return float(config.peak_flops_per_chip)
    env = os.environ.get(ENV_PEAK_FLOPS, "").strip()
    if env:
        try:
            return float(env)
        except ValueError:
            log.warning("ignoring non-numeric %s=%r", ENV_PEAK_FLOPS, env)
    kind = jax.local_devices()[0].device_kind.lower()
    for key, peak in _PEAK_BF16_FLOPS:
        if key in kind:
            return peak
    return 0.0


def _tree_bytes(tree: Any) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        try:
            total += int(np.prod(shape)) * int(np.dtype(dtype).itemsize)
        except (TypeError, ValueError):
            pass
    return total


def _collective_fraction(params: Any, first_batch: Any, mesh: Mesh,
                         dp_mode: str) -> float:
    """Estimated share of a window's device span spent in the gradient
    exchange, splitting the measured device phase into device_compute /
    device_collective.  Bandwidth proxy over the same byte counts the
    PR 18 memory_analysis checks reason about: per step the exchange
    moves ~factor x (N-1)/N x param_bytes over the interconnect
    (factor 2 for an all-reduce — reduce-scatter + all-gather — and 3
    for fsdp's JIT gathers + reduce-scatter), against an HBM-traffic
    proxy of 3 x param_bytes (read params + read grads + write params)
    plus the per-device batch.  An estimate, not a measurement — but the
    published phases still sum exactly to wall-clock because only the
    measured device span is being split."""
    try:
        n = int(mesh.shape["data"])
    except (KeyError, TypeError):
        n = 1
    if n <= 1:
        return 0.0
    param_bytes = _tree_bytes(params)
    if param_bytes <= 0:
        return 0.0
    batch_bytes = _tree_bytes(first_batch) / n
    factor = 3.0 if dp_mode == "fsdp" else 2.0
    coll = factor * (n - 1) / n * param_bytes
    hbm = 3.0 * param_bytes + batch_bytes
    return coll / max(coll + hbm, 1.0)


class TrainState(struct.PyTreeNode):
    """Step counter + params + optimizer state + rng, all on device.

    ``model_state`` carries non-trained mutable collections (BatchNorm
    running statistics — flax's ``batch_stats``); None for stateless models.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array
    model_state: Any = None

    @classmethod
    def create(cls, params, optimizer, rng, model_state=None) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
            rng=rng,
            model_state=model_state,
        )


@dataclasses.dataclass
class TrainLoopConfig:
    train_steps: int
    batch_size: int = 128
    eval_every: int = 0            # 0 = eval only at the end
    eval_steps: int = 0            # 0 = full eval split pass per eval
    checkpoint_every: int = 0      # 0 = no mid-training checkpoints
    keep_checkpoints: int = 3
    log_every: int = 100
    # Device-resident multi-step window: dispatch this many optimizer steps
    # as ONE compiled ``lax.scan`` over a device-staged batch stack (leading
    # axis = step-in-window), with a single device->host metric fetch per
    # window — the per-step host round-trip (device_put + dispatch + drain)
    # is the gap between the real train_loop path and the device-resident
    # fori_loop ceiling on µs-scale steps.
    # None = read env TPP_WINDOW_STEPS, else default to ``log_every``
    # (window cadence == metric cadence); <=1 = the per-step loop,
    # bit-for-bit in metric semantics.  Windows shrink to land exactly on
    # eval/checkpoint/train_steps boundaries; per-step metric values are
    # reconstructed host-side from the windowed accumulator, so log_every
    # emission and the NaN/stall/loss-spike watchdogs keep their per-step
    # semantics, sampled at window boundaries.  Forced to 1 while
    # profile_dir is set (profiling needs per-step dispatch granularity).
    window_steps: Optional[int] = None
    seed: int = 0
    mesh_config: Optional[MeshConfig] = None
    # Optional pytree-of-PartitionSpec matching params, for model parallelism;
    # None = fully replicated params (pure DP, the reference's strategy).
    param_partition: Optional[Any] = None
    # Optional {batch_key: PartitionSpec} for input sharding beyond plain
    # batch-dim DP — e.g. P("data", "seq") on token ids for ring-attention
    # sequence parallelism.  Keys not listed shard dim 0 over "data".
    batch_partition: Optional[Dict[str, Any]] = None
    donate_state: bool = True
    # Gradient accumulation: the per-step batch splits into this many
    # microbatches, scanned inside ONE jitted step (grads averaged, one
    # optimizer update) — the large-effective-batch story when the full
    # batch's activations exceed HBM.  Microbatches interleave rows
    # (every a-th row) so each stays evenly sharded over the mesh ``data``
    # axis.  batch_size must divide evenly.
    grad_accum_steps: int = 1
    # ---- explicit data-parallel collective modes (multi-chip window) ----
    # How the scan body's gradient all-reduce is expressed on a >1 'data'
    # axis.  None/"auto" (default): implicit GSPMD — XLA inserts one fused
    # all-reduce wherever it likes, which on µs-scale steps lands exactly
    # at the window boundary and serializes against the next step.
    # "psum_bucketed": grads are computed per device under shard_map and
    # all-reduced as ``collective_buckets`` chunked psums INSIDE the scan
    # body, so the scheduler can overlap bucket k's collective with the
    # remaining backward compute (verified from compiled HLO in
    # tests/test_multichip_window.py).  "ordered": grads are computed per
    # fixed global block (``dp_grad_blocks`` blocks, a count chosen
    # independently of the mesh), all-gathered, and summed in block order —
    # the param trajectory is bitwise-invariant to the data-axis size, so
    # an elastic resume onto a survivor mesh continues the exact same
    # trajectory; costs all-gather bandwidth (block grads move whole), and
    # blocks run one at a time on a device that holds several.  Bitwise on
    # the CPU backend always; on TPU it held on 4/2/1 chips at MXU-sized
    # matmuls and not for sub-tile toy shapes (PERF.md, PR 21).
    # "fsdp": ZeRO-3 — params (and Adam moments) live SHARDED over the
    # data axis per ``param_partition`` (or a derived default: first dim
    # divisible by the axis), each leaf is all-gathered just-in-time
    # inside the scan body (a distinct collective per leaf, overlappable
    # like the bucketed psums; the backward re-gathers under a remat
    # policy instead of saving full params), and the gradient exchange is
    # the reduce-scatter AD transpose of those gathers — per-device
    # resident bytes ≈ params/N + one layer's gather.  Capability table:
    # param_partition requires "fsdp" (data-axis specs) or None/"auto"
    # (arbitrary GSPMD axes); batch_partition (ring-attention sequence
    # sharding) requires None/"auto"; grad_accum_steps and model_state
    # compose with every mode.
    dp_collective: Optional[str] = None
    # Chunked-psum bucket count for "psum_bucketed" (>=1; grad leaves are
    # round-robined into buckets, one psum each).
    collective_buckets: int = 2
    # Fixed global gradient-block count for "ordered".  None = the mesh
    # data-axis size (cheapest).  Pin it to the LARGEST mesh you intend to
    # resume across — trajectories are bitwise-comparable only between
    # runs sharing the same block count.
    dp_grad_blocks: Optional[int] = None
    # Sync-anchored throughput windows: every ``anchor_every`` post-compile
    # steps, force a device-to-host read of that step's loss (the same
    # cannot-lie transfer used for t_start below) and time the span since the
    # previous anchor.  The median windowed examples/sec over these spans is
    # the defensible throughput figure wherever async dispatch lets host
    # clocks run ahead of device progress.
    # 0 = whole-run timing only.
    anchor_every: int = 0
    # PRNG implementation for the training rng (dropout masks etc.).
    # "rbg" is the TPU-fast generator — measured ~1.5x step throughput on
    # BERT-base fine-tune vs the default threefry, whose counter math
    # dominates dropout cost on the MXU-light path.  Set "threefry2x32" for
    # jax-default stream reproducibility, or None for the jax default.
    prng_impl: Optional[str] = "rbg"
    # Device profiling (the TensorBoard-profile equivalent, SURVEY.md §5):
    # capture a jax.profiler trace for steps [profile_from, profile_to).
    profile_dir: str = ""
    profile_from: int = 2
    profile_to: int = 5
    # TensorBoard scalar sink (SURVEY.md §5 observability, the Keras
    # TensorBoard-callback equivalent): when set, train metrics at log_every
    # cadence + eval metrics land there as tf.summary scalars via clu.
    tensorboard_dir: str = ""
    # Record XLA's own FLOP count for the compiled train step
    # (TrainResult.cost_analysis_flops_per_step) — the falsifiability
    # cross-check for analytic MFU numerators (VERDICT r4 weak#3).  Runs
    # AFTER the timed loop (an extra trace, and possibly an extra backend
    # compile) so throughput is unaffected; costs wall-clock, so off by
    # default.
    collect_cost_analysis: bool = False
    # Live telemetry (observability/metrics.py + health.py): the loop
    # always publishes step-time / examples-per-sec / input-wait / device
    # -memory gauges into the process metrics registry (in-memory — zero
    # file/socket footprint) and heartbeats a HealthMonitor whose NaN and
    # loss-spike checks ride the log_every host transfer.  The stall
    # watchdog THREAD starts only when a timeout is configured:
    # None = read env TPP_STALL_TIMEOUT_S, 0 = no watchdog thread.
    stall_timeout_s: Optional[float] = None
    # Called as cb(kind, detail) when a watchdog fires ("stall", "nan",
    # "loss_spike") — wire pagers, or sys.exit for fail-fast jobs.
    health_alert_cb: Optional[Callable[[str, str], None]] = None
    # ---- telemetry plane (observability/federation + metrics_history) --
    # Pipeline root the durable metrics-history ring lives under
    # (<pipeline_root>/.runs/_metrics/<run_id>/).  "" = derive both from
    # the active RunTrace recorder when one is installed.  Snapshots are
    # written only when TPP_METRICS_HISTORY is set — zero files
    # otherwise.  Federation publishing needs no config: it keys off
    # TPP_FEDERATION_DIR alone.
    pipeline_root: str = ""
    run_id: str = ""
    # Peak per-chip FLOPs for the live train_mfu gauge; None = env
    # TPP_PEAK_FLOPS, else the device-kind table, else no MFU (a made-up
    # denominator would publish a made-up utilization).
    peak_flops_per_chip: Optional[float] = None


LossFn = Callable[[Any, Dict[str, jax.Array], jax.Array], Tuple[jax.Array, Dict[str, jax.Array]]]


# The windowed loop's device program as a profile's "XLA Modules" line
# names it: ``jit_`` + the ``__name__`` of the function handed to
# jax.jit.  Readers of traces find the program by this name (the
# benchmark's ``*_share.train`` readers): renaming the inner function is
# a change to this constant and to those readers.
WINDOW_PROGRAM_NAME = "jit_train_window"


def _param_sharding(mesh: Mesh, config: TrainLoopConfig, params):
    if config.param_partition is None:
        return jax.tree_util.tree_map(lambda _: replicate(mesh), params)
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), config.param_partition,
        is_leaf=lambda x: isinstance(x, P),
    )


def _key_name(entry) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _opt_state_sharding(opt_state, params, p_shard, mesh: Mesh):
    """Shard optimizer state like its matching params, replicate the rest.

    Optax states (e.g. Adam's mu/nu) embed copies of the params pytree, so an
    opt_state leaf whose tree-path *suffix* and shape match a param leaf gets
    that param's sharding — Adam moments stay sharded alongside
    model-parallel params instead of being replicated onto every chip.
    """
    param_entries = [
        (tuple(_key_name(k) for k in path), leaf.shape, shard)
        for (path, leaf), (_, shard) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(p_shard)[0],
        )
    ]

    def match(path, leaf):
        tail = tuple(_key_name(k) for k in path)
        for ptail, pshape, pshard in param_entries:
            if (
                len(tail) >= len(ptail)
                and tail[-len(ptail):] == ptail
                and getattr(leaf, "shape", None) == pshape
            ):
                return pshard
        return replicate(mesh)

    flat, treedef = jax.tree_util.tree_flatten_with_path(opt_state)
    return jax.tree_util.tree_unflatten(
        treedef, [match(path, leaf) for path, leaf in flat]
    )


ENV_DP_COLLECTIVE = "TPP_DP_COLLECTIVE"
_DP_MODES = ("auto", "psum_bucketed", "ordered", "fsdp")


def _effective_dp_collective(config: TrainLoopConfig) -> str:
    """Resolve the explicit-collective mode: config > TPP_DP_COLLECTIVE
    env > '' (implicit GSPMD).  'auto' normalizes to ''."""
    mode = config.dp_collective
    if mode is None:
        mode = os.environ.get(ENV_DP_COLLECTIVE, "").strip() or None
    if mode in (None, "", "auto"):
        return ""
    if mode not in _DP_MODES:
        raise ValueError(
            f"dp_collective {mode!r}: expected one of {_DP_MODES}"
        )
    return mode


_FSDP_GATHER_NAME = "fsdp_allgather"


def _make_dp_forward_backward(
    loss_fn: LossFn,
    mesh: Mesh,
    mode: str,
    *,
    buckets: int,
    grad_blocks: int,
    accum: int = 1,
    has_model_state: bool = False,
    fsdp_specs: Optional[Any] = None,
):
    """Mesh-explicit DP forward/backward: (params, model_state, batch, rng)
    -> (loss, metrics, grads, new_model_state), loss/metrics replicated.

    The gradient exchange is expressed INSIDE the function (and therefore
    inside the windowed scan body) instead of being left to GSPMD:

      * ``psum_bucketed`` — per-device grads, leaves round-robined into
        ``buckets`` chunks, one ``psum`` per chunk.  Distinct all-reduce
        ops in the compiled HLO let the scheduler start bucket k's
        collective while the rest of the backward still computes, instead
        of one fused all-reduce serialized at the window boundary.
      * ``ordered`` — grads per fixed global block (``grad_blocks`` blocks
        of the global batch, a count independent of the mesh), block grads
        all-gathered to every device and summed in block order by one
        ``jnp.sum`` over the stacked [G, ...] axis.  Because every mesh
        size computes the same per-block grads and reduces them with the
        same op, the result is bitwise-invariant to the data-axis size —
        the contract elastic resume onto a survivor mesh relies on.
      * ``fsdp`` — ZeRO-3: params arrive SHARDED per ``fsdp_specs`` (data
        axis only).  Each leaf is all-gathered just-in-time (tiled, one
        distinct op per leaf — the overlappable analogue of the psum
        buckets) under a ``jax.checkpoint`` policy that refuses to save
        the gathered values, so the backward re-gathers instead of
        holding full params as residuals; differentiating w.r.t. the
        SHARDS makes the AD transpose of each tiled all-gather a
        ``psum_scatter`` — the reduce-scatter gradient exchange falls out
        of autodiff, and grads leave sharded exactly like the params the
        optimizer then updates shard-wise.

    ``accum > 1`` composes with every mode as an inner ``lax.scan`` over
    interleaved micro-batches of the LOCAL batch.  For ``psum_bucketed``
    the scan accumulates per-device grads and the bucketed psums run once
    per OUTER step (exchange volume independent of accum).  For
    ``ordered`` the block-ordered exchange IS the summation-order
    contract, so it runs per micro-batch and the replicated micro results
    accumulate in fixed scan order — mesh-size bitwise invariance holds
    through accumulation.  For ``fsdp`` the reduce-scatter is the AD
    transpose inside each micro step (deferring it would need a
    full-size local accumulator, defeating the sharded memory model);
    the accumulator itself stays sharded at params/N bytes.

    ``model_state`` (BatchNorm-style collections) threads micro-batch to
    micro-batch; float leaves of the step's final state are psum-averaged
    over the data axis (the sync-BN convention) for ``psum_bucketed`` /
    ``fsdp``, while ``ordered`` averages the per-block states in block
    order, preserving its mesh-size-invariance contract.

    Loss/metrics follow the same reduction as the grads, so the reported
    series inherits the mode's determinism contract.
    """
    from jax.ad_checkpoint import checkpoint_name

    from tpu_pipelines.parallel.partition import gather_leaf

    data_axis = mesh.shape["data"]

    def call_loss(params, ms, mb, rng):
        """Either loss contract -> (loss, (metrics, new_model_state)).
        Under the head's scope, as ``forward_backward`` of the implicit
        path has it."""
        with jax.named_scope("embed_head"):
            if has_model_state:
                return loss_fn(params, ms, mb, rng)
            loss, metrics = loss_fn(params, mb, rng)
            return loss, (metrics, ms)

    def plain_micro(params, ms, mb, rng):
        (loss, (metrics, new_ms)), grads = jax.value_and_grad(
            lambda p: call_loss(p, ms, mb, rng), has_aux=True
        )(params)
        return loss, metrics, grads, new_ms

    def fsdp_micro(p_shards, ms, mb, rng):
        def from_shards(shards):
            full = jax.tree_util.tree_map(
                lambda x, s: checkpoint_name(
                    gather_leaf(x, s), _FSDP_GATHER_NAME
                ),
                shards, fsdp_specs,
            )
            return call_loss(full, ms, mb, rng)

        f = jax.checkpoint(
            from_shards,
            policy=jax.checkpoint_policies.save_anything_except_these_names(
                _FSDP_GATHER_NAME
            ),
        )
        (loss, (metrics, new_ms)), g_shards = jax.value_and_grad(
            f, has_aux=True
        )(p_shards)
        # g_shards left psum_scatter as the SUM over devices of the local
        # grads' shard slice; the caller scales to the global mean.
        return loss, metrics, g_shards, new_ms

    def ordered_micro(params, ms, mb, rng):
        blocks = grad_blocks // data_axis

        def block_fb(bmb):
            (loss, (metrics, new_ms)), grads = jax.value_and_grad(
                lambda p: call_loss(p, ms, bmb, rng), has_aux=True
            )(params)
            return loss, metrics, grads, new_ms

        bmb = jax.tree_util.tree_map(
            lambda x: x.reshape(
                blocks, x.shape[0] // blocks, *x.shape[1:]
            ),
            mb,
        )
        # One block at a time (lax.map), not vmap: every mesh size then runs
        # the SAME per-block program on the same shapes, however many blocks
        # a device holds.  A batched (vmap) block dimension changes the
        # matmul shapes with the mesh, and the TPU compiler tiles — and so
        # rounds — differently shaped matmuls differently: with vmap the
        # bitwise invariance held on the CPU and broke on four chips at
        # every size tried; with lax.map it holds there at MXU-sized
        # matmuls (PERF.md, PR 21).
        l_b, m_b, g_b, s_b = jax.lax.map(block_fb, bmb)
        gather = lambda t: jax.lax.all_gather(t, "data", tiled=True)
        inv = 1.0 / grad_blocks
        ordered_sum = lambda v: jnp.sum(gather(v), axis=0) * inv
        # Float collections average in block order (the mode's contract);
        # integer leaves (counters) advance identically in every block and
        # must keep their dtype — take block 0's value.
        new_ms = (
            jax.tree_util.tree_map(
                lambda v: (
                    ordered_sum(v)
                    if jnp.issubdtype(v.dtype, jnp.inexact) else v[0]
                ),
                s_b,
            )
            if has_model_state else ms
        )
        return (
            ordered_sum(l_b),
            jax.tree_util.tree_map(ordered_sum, m_b),
            jax.tree_util.tree_map(ordered_sum, g_b),
            new_ms,
        )

    micro_fb = {
        "psum_bucketed": plain_micro,
        "ordered": ordered_micro,
        "fsdp": fsdp_micro,
    }[mode]

    def fb(params, mstate, batch, rng):
        # Loss/metrics shapes for the accumulator carry, traced OUTSIDE the
        # shard_map (mean reductions make them batch-size independent).
        out_sd = (
            jax.eval_shape(call_loss, params, mstate, batch, rng)
            if accum > 1 else None
        )

        def local(params, ms, lb, rng):
            if accum == 1:
                loss, metrics, grads, new_ms = micro_fb(params, ms, lb, rng)
            else:
                # Micro-batch i takes every accum-th LOCAL row (interleaved
                # split, same as the implicit path) so each micro stays
                # evenly spread over the data axis.
                def split(x):
                    return jnp.moveaxis(
                        x.reshape(
                            x.shape[0] // accum, accum, *x.shape[1:]
                        ), 1, 0,
                    )

                micro = jax.tree_util.tree_map(split, lb)
                loss_sd, (metrics_sd, _) = out_sd
                zeros = lambda sd: jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), sd
                )

                def mb_step(carry, idx_mb):
                    g_acc, l_acc, m_acc, ms_c = carry
                    i, mb = idx_mb
                    l, m, g, ms_c = micro_fb(
                        params, ms_c, mb, jax.random.fold_in(rng, i)
                    )
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    m_acc = jax.tree_util.tree_map(jnp.add, m_acc, m)
                    return (g_acc, l_acc + l, m_acc, ms_c), None

                # Grad accumulator: zeros shaped like the LOCAL param view —
                # full params for psum/ordered, the shard for fsdp, so the
                # donated carry never exceeds the mode's resident budget.
                (g_sum, l_sum, m_sum, new_ms), _ = jax.lax.scan(
                    mb_step,
                    (
                        jax.tree_util.tree_map(jnp.zeros_like, params),
                        zeros(loss_sd), zeros(metrics_sd), ms,
                    ),
                    (jnp.arange(accum), micro),
                )
                inv_a = 1.0 / accum
                grads = jax.tree_util.tree_map(lambda v: v * inv_a, g_sum)
                loss = l_sum * inv_a
                metrics = jax.tree_util.tree_map(
                    lambda v: v * inv_a, m_sum
                )

            # The per-outer-step exchange.  "ordered" already exchanged
            # inside each micro step (the block order IS the contract) and
            # returned replicated means; "fsdp" grads left the AD transpose
            # as reduce-scattered sums — only scaling remains.
            inv = 1.0 / data_axis
            if mode == "psum_bucketed":
                leaves, treedef = jax.tree_util.tree_flatten(grads)
                k = max(1, min(buckets, len(leaves)))
                reduced: list = [None] * len(leaves)
                for i in range(k):
                    chunk = tuple(leaves[i::k])
                    out = jax.lax.psum(chunk, "data")
                    for j, v in enumerate(out):
                        reduced[i + j * k] = v
                grads = jax.tree_util.tree_unflatten(
                    treedef, [v * inv for v in reduced]
                )
                loss = jax.lax.psum(loss, "data") * inv
                metrics = jax.tree_util.tree_map(
                    lambda v: jax.lax.psum(v, "data") * inv, metrics
                )
            elif mode == "fsdp":
                grads = jax.tree_util.tree_map(lambda v: v * inv, grads)
                loss = jax.lax.psum(loss, "data") * inv
                metrics = jax.tree_util.tree_map(
                    lambda v: jax.lax.psum(v, "data") * inv, metrics
                )
            if has_model_state and mode != "ordered":
                # Sync-BN convention: float collections average over the
                # data axis (replicated out); integer leaves (counters)
                # advance identically on every device and pass through.
                new_ms = jax.tree_util.tree_map(
                    lambda v: (
                        jax.lax.psum(v, "data") * inv
                        if jnp.issubdtype(v.dtype, jnp.inexact) else v
                    ),
                    new_ms,
                )
            return loss, metrics, grads, new_ms

        pspec = fsdp_specs if mode == "fsdp" else P()
        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(pspec, P(), P("data"), P()),
            out_specs=(P(), P(), pspec, P()),
            check_vma=False,
        )(params, mstate, batch, rng)

    return fb


def train_loop(
    *,
    loss_fn: LossFn,
    init_params_fn: Callable[[jax.Array, Dict[str, np.ndarray]], Any],
    optimizer: optax.GradientTransformation,
    train_iter: Iterable[Dict[str, np.ndarray]],
    config: TrainLoopConfig,
    eval_iter_fn: Optional[Callable[[], Iterable[Dict[str, np.ndarray]]]] = None,
    checkpoint_dir: str = "",
    mesh: Optional[Mesh] = None,
    metrics_cb: Optional[Callable[[int, Dict[str, float]], None]] = None,
    has_model_state: bool = False,
) -> Tuple[Any, TrainResult]:
    """Run the jitted train loop; returns (final_params, TrainResult).

    Enables the persistent XLA compile cache (utils/compile_cache.py)
    before compiling, so a re-run of an unchanged program — another
    trial, a retry, a resumed job — skips the multi-10-second compile.

    ``loss_fn(params, batch, rng) -> (loss, metrics)`` must be jax-traceable.
    ``init_params_fn(rng, sample_batch)`` builds the params pytree.
    ``train_iter`` yields host batches (dict of numpy, fixed shapes).

    ``has_model_state=True`` switches both contracts to thread mutable
    non-trained collections (flax ``batch_stats`` for BatchNorm models):
      - ``init_params_fn(rng, batch) -> (params, model_state)``
      - ``loss_fn(params, model_state, batch, rng)
           -> (loss, (metrics, new_model_state))``
    and the returned "final params" is ``(params, model_state)``.
    """
    from tpu_pipelines.utils.compile_cache import maybe_enable_compile_cache

    maybe_enable_compile_cache()
    # Badput accounting (SURVEY.md §5): the real ml_goodput_measurement
    # algebra over a local logger; falls back to the host-input-wait proxy
    # when the library is absent (tracker no-ops, summary() == {}).
    tracker = GoodputTracker(
        job_name="train_loop",
        jsonl_path=(
            os.path.join(checkpoint_dir, "goodput_log.jsonl")
            if checkpoint_dir else ""
        ),
    )
    tracker.job_start()
    tracker.tpu_init_start()
    if mesh is None:
        mesh = make_mesh(config.mesh_config)
    n_devices = mesh.devices.size
    tracker.tpu_init_end()

    train_it = iter(train_iter)
    tracker.data_loading_start()
    first_batch = next(train_it)
    tracker.data_loading_end()

    tracker.training_prep_start()
    rng = (
        jax.random.key(config.seed, impl=config.prng_impl)
        if config.prng_impl else jax.random.key(config.seed)
    )
    rng, init_rng = jax.random.split(rng)
    model_state = None
    if has_model_state:
        params, model_state = init_params_fn(init_rng, first_batch)
    else:
        params = init_params_fn(init_rng, first_batch)
    bp = config.batch_partition or {}
    accum = max(1, int(config.grad_accum_steps))
    if accum > 1 and config.batch_size % accum:
        raise ValueError(
            f"batch_size {config.batch_size} not divisible by "
            f"grad_accum_steps {accum}"
        )

    # Explicit DP collective modes (multi-chip window): replace the
    # implicit GSPMD gradient exchange with a shard_map-expressed one.
    # Capability table — each refusal below routes to the mode that
    # supports the ask instead of just blocking:
    #   psum_bucketed / ordered  params replicated (pure DP exchange);
    #   fsdp                     params sharded over 'data' (per-leaf JIT
    #                            all-gather + reduce-scatter grads);
    #   None/'auto' (implicit)   arbitrary param_partition axes and
    #                            batch_partition (ring-attention sequence
    #                            sharding) live here.
    # grad_accum_steps and model_state compose with EVERY mode.
    dp_mode = _effective_dp_collective(config)
    data_axis = mesh.shape["data"]
    fsdp_partition = None
    if dp_mode:
        if bp:
            raise ValueError(
                f"dp_collective={dp_mode!r}: batch_partition (sequence-"
                "sharded inputs for ring attention) rides the implicit-"
                "GSPMD window — use dp_collective=None/'auto' for "
                "long-context configs; explicit collective modes shard "
                "the batch over 'data' only"
            )
        if dp_mode == "fsdp":
            fsdp_partition = (
                config.param_partition
                if config.param_partition is not None
                else fsdp_param_partition(params, mesh)
            )
            foreign = foreign_axis_paths(params, fsdp_partition)
            if foreign:
                raise ValueError(
                    "dp_collective='fsdp' shards params over the mesh "
                    "'data' axis only; these param_partition specs name "
                    "other axes — model-parallel specs ride the implicit "
                    "mode (dp_collective=None/'auto'):\n  "
                    + "\n  ".join(foreign)
                )
        elif config.param_partition is not None:
            raise ValueError(
                f"dp_collective={dp_mode!r} keeps params replicated "
                "(pure data parallelism); param_partition requires "
                "dp_collective='fsdp' (params sharded over 'data', "
                "per-layer all-gather inside the scan body) or the "
                "implicit mode (None/'auto') for model-parallel specs"
            )
        if config.batch_size % data_axis:
            raise ValueError(
                f"dp_collective={dp_mode!r}: batch_size "
                f"{config.batch_size} must be divisible by the mesh "
                f"data axis ({data_axis})"
            )
        if accum > 1 and (config.batch_size // data_axis) % accum:
            raise ValueError(
                f"grad_accum_steps {accum} must divide the per-device "
                f"batch ({config.batch_size} over data axis {data_axis} "
                f"= {config.batch_size // data_axis} rows)"
            )

    # Surface bad partitions BEFORE compilation (satellite of ISSUE 18):
    # a spec whose mesh-axis size doesn't divide the param dim otherwise
    # only fails deep inside jit with a GSPMD error naming no parameter.
    partition_in_play = (
        fsdp_partition if dp_mode == "fsdp" else config.param_partition
    )
    if partition_in_play is not None:
        problems = validate_partition(params, partition_in_play, mesh)
        if problems:
            raise ValueError(
                "param_partition does not fit this mesh — fix these "
                "rules before compilation:\n  " + "\n  ".join(problems)
            )

    if fsdp_partition is not None:
        p_shard = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), fsdp_partition,
            is_leaf=lambda x: isinstance(x, P),
        )
    else:
        p_shard = _param_sharding(mesh, config, params)
    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), params, p_shard
    )
    state = TrainState.create(params, optimizer, rng, model_state=model_state)
    # Pin the whole state's sharding explicitly (TrainState.create built
    # opt_state/step on the default device) so jit's donation is stable.
    state_shard = TrainState(
        step=replicate(mesh),
        params=p_shard,
        opt_state=_opt_state_sharding(state.opt_state, params, p_shard, mesh),
        rng=replicate(mesh),
        model_state=(
            jax.tree_util.tree_map(lambda _: replicate(mesh), model_state)
            if model_state is not None else None
        ),
    )
    state = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), state, state_shard
    )
    unknown = sorted(set(bp) - set(first_batch))
    if unknown:
        raise ValueError(
            f"batch_partition keys {unknown} not in batch "
            f"(has {sorted(first_batch)})"
        )
    batch_shard = {
        k: (
            NamedSharding(mesh, bp[k]) if k in bp
            else data_parallel_sharding(mesh, np.asarray(v).ndim)
        )
        for k, v in first_batch.items()
    }

    # Runs even on a data=1 mesh so a single-chip "ordered" run shares the
    # multi-chip run's exact reduction structure.
    dp_fb = None
    if dp_mode:
        grad_blocks = int(config.dp_grad_blocks or data_axis)
        if dp_mode == "ordered" and (
            grad_blocks % data_axis
            or (config.batch_size // accum) % grad_blocks
        ):
            raise ValueError(
                f"dp_grad_blocks {grad_blocks} must be a multiple of the "
                f"mesh data axis ({data_axis}) and divide the "
                f"per-microbatch global batch "
                f"({config.batch_size} / grad_accum_steps {accum} = "
                f"{config.batch_size // accum})"
            )
        dp_fb = _make_dp_forward_backward(
            loss_fn, mesh, dp_mode,
            buckets=max(1, int(config.collective_buckets)),
            grad_blocks=grad_blocks,
            accum=accum,
            has_model_state=has_model_state,
            fsdp_specs=fsdp_partition,
        )

    def forward_backward(params, mstate, mb, rng):
        # What the loss function does outside its model's own scopes (the
        # loss itself, its labels and metrics) is the head's.
        with jax.named_scope("embed_head"):
            if has_model_state:
                (loss, (metrics, new_mstate)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, mstate, mb, rng)
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, mb, rng)
                new_mstate = mstate
        return loss, metrics, grads, new_mstate

    def step_fn(state: TrainState, batch) -> Tuple[TrainState, Dict[str, jax.Array]]:
        with jax.named_scope("dropout"):
            step_rng = jax.random.fold_in(state.rng, state.step)
        if dp_fb is not None:
            # Accumulation and model_state live INSIDE the collective fb
            # (the inner scan accumulates under the same shard_map as the
            # exchange), so every dp mode composes with both.
            loss, metrics, grads, new_mstate = dp_fb(
                state.params, state.model_state, batch, step_rng
            )
        elif accum == 1:
            loss, metrics, grads, new_mstate = forward_backward(
                state.params, state.model_state, batch, step_rng
            )
        else:
            # Microbatch i takes every accum-th row: an interleaved split
            # keeps each microbatch evenly spread across the contiguous
            # per-device blocks of the batch-dim sharding (a blocked split
            # would put whole microbatches on single devices).
            def split(x):
                if x.shape[0] % accum:
                    raise ValueError(
                        f"batch dim {x.shape[0]} not divisible by "
                        f"grad_accum_steps {accum}"
                    )
                return jnp.moveaxis(
                    x.reshape(x.shape[0] // accum, accum, *x.shape[1:]), 1, 0
                )

            micro = jax.tree_util.tree_map(split, batch)

            def mb_step(carry, idx_mb):
                g_acc, l_acc, m_acc, mstate = carry
                i, mb = idx_mb
                loss, metrics, grads, mstate = forward_backward(
                    state.params, mstate, mb,
                    jax.random.fold_in(step_rng, i),
                )
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
                m_acc = {k: m_acc[k] + v for k, v in metrics.items()}
                return (g_acc, l_acc + loss, m_acc, mstate), None

            # Zero-seeded carry via eval_shape: tracing the forward once for
            # shapes only, so the fwd+bwd graph compiles ONCE (as the scan
            # body) instead of once unrolled + once scanned.
            out_shape = jax.eval_shape(
                lambda: forward_backward(
                    state.params, state.model_state,
                    jax.tree_util.tree_map(lambda x: x[0], micro),
                    jax.random.fold_in(step_rng, 0),
                )
            )
            loss_s, metrics_s, grads_s, _ = out_shape
            zeros = lambda tree: jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), tree
            )
            (g_sum, l_sum, m_sum, new_mstate), _ = jax.lax.scan(
                mb_step,
                (zeros(grads_s), zeros(loss_s), zeros(metrics_s),
                 state.model_state),
                (jnp.arange(accum), micro),
            )
            inv = 1.0 / accum
            grads = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
            loss = l_sum * inv
            metrics = {k: v * inv for k, v in m_sum.items()}
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, **metrics}
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                rng=state.rng,
                model_state=new_mstate,
            ),
            metrics,
        )

    train_step = jax.jit(
        step_fn,
        in_shardings=(state_shard, batch_shard),
        out_shardings=(state_shard, None),
        donate_argnums=(0,) if config.donate_state else (),
    )

    eval_step = None
    if eval_iter_fn is not None:
        # Same input shardings as the train step: without them, eval batches
        # and (on a TP mesh) params would take default placement — a silent
        # per-batch replication/transfer cost on multi-chip meshes.
        if has_model_state:
            def eval_fn(params, mstate, batch):
                loss, (metrics, _) = loss_fn(
                    params, mstate, batch, jax.random.key(0)
                )
                return {"loss": loss, **metrics}

            eval_step = jax.jit(
                eval_fn,
                in_shardings=(p_shard, state_shard.model_state, batch_shard),
            )
        else:
            def eval_fn(params, batch):
                loss, metrics = loss_fn(
                    params, batch, jax.random.key(0)
                )
                return {"loss": loss, **metrics}

            eval_step = jax.jit(eval_fn, in_shardings=(p_shard, batch_shard))

    # ---- checkpoint manager (resume support)
    checkpoint_every = config.checkpoint_every
    mngr = None
    start_step = 0
    if checkpoint_dir:
        import orbax.checkpoint as ocp

        mngr = ocp.CheckpointManager(
            os.path.abspath(checkpoint_dir),
            options=ocp.CheckpointManagerOptions(
                max_to_keep=config.keep_checkpoints,
                save_interval_steps=max(1, checkpoint_every),
            ),
        )
        latest = mngr.latest_step()
        if latest is not None:
            # rng (a typed PRNG key) is rebuilt from the seed, not restored.
            saveable = {"step": state.step, "params": state.params,
                        "opt_state": state.opt_state}
            if has_model_state:
                saveable["model_state"] = state.model_state
            abstract = jax.tree_util.tree_map(
                ocp.utils.to_shape_dtype_struct, saveable
            )
            restored = mngr.restore(
                latest, args=ocp.args.StandardRestore(abstract)
            )
            state = TrainState(
                step=restored["step"],
                params=restored["params"],
                opt_state=restored["opt_state"],
                rng=state.rng,
                model_state=restored.get("model_state", state.model_state),
            )
            state = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s), state, state_shard
            )
            start_step = int(latest)
            log.info("resumed from checkpoint step %d", start_step)
    # Replayed-span accounting: the progress marker records the furthest
    # EXECUTED step; resuming from an earlier durable checkpoint means the
    # gap re-executes.  Reported (never double-counted as fresh progress)
    # so an elastic restart can prove exactly how much work the lost host
    # cost — see tests/test_multichip_window.py.
    replayed_steps = 0
    if checkpoint_dir:
        executed = _read_progress_step(checkpoint_dir)
        if executed > start_step:
            replayed_steps = executed - start_step
            log.info(
                "resume replays steps %d..%d (executed before the "
                "interruption, lost with the non-durable window)",
                start_step + 1, executed,
            )
    tracker.training_prep_end()

    # ---- the loop
    from tpu_pipelines.data.input_pipeline import stage_global

    def put_batch(b):
        return stage_global(b, batch_shard)

    tb_writer = None
    if config.tensorboard_dir and jax.process_index() == 0:
        # Process 0 only (multi-host peers would write N duplicate points per
        # tag into the shared logdir).  Lazy import — clu pulls TensorFlow —
        # and optional: a missing clu degrades to no sink, not a dead loop.
        try:
            from clu import metric_writers

            tb_writer = metric_writers.SummaryWriter(config.tensorboard_dir)
        except ImportError as e:
            log.warning("tensorboard_dir set but clu unavailable (%s)", e)

    last_tb = {"train": -1, "eval": -1}

    def tb_write(kind: str, at_step: int, scalars: Dict[str, float]) -> None:
        if tb_writer is None or not scalars:
            return
        tb_writer.write_scalars(at_step, scalars)
        # Flush per write (log_every cadence, so amortized): a crash mid-run
        # must not lose the tail of the curve to tf.summary buffering.
        tb_writer.flush()
        last_tb[kind] = at_step

    # ---- live telemetry: gauges + health watchdog (observability/)
    from tpu_pipelines.observability.health import HealthMonitor
    from tpu_pipelines.observability.metrics import default_registry

    reg = default_registry()
    g_step_s = reg.gauge(
        "train_step_seconds", "Mean wall time per step over the last "
        "log_every window.",
    )
    g_eps = reg.gauge(
        "train_examples_per_sec", "Window throughput at log_every cadence.",
    )
    g_tps = reg.gauge(
        "train_tokens_per_sec", "Window token throughput (0 when the "
        "batch carries no token-shaped integer feature).",
    )
    g_input_wait = reg.gauge(
        "train_host_input_wait_seconds_total",
        "Cumulative post-compile host time spent feeding batches "
        "(the goodput proxy's numerator).",
    )
    g_device_mem = reg.gauge(
        "train_device_memory_bytes",
        "bytes_in_use on device 0 (0 where the backend reports none).",
    )
    g_steps = reg.gauge("train_steps_total", "Steps completed so far.")
    # ---- step-time attribution + compile/HBM tracking (telemetry plane)
    c_phase = reg.counter(
        "train_window_time_seconds",
        "Post-warmup windowed-loop wall-clock attributed per phase "
        "(infeed_wait | device_compute | device_collective | host); the "
        "phases of each window sum to its wall-clock.",
        labels=("phase",),
    )
    c_compiles_warm = reg.counter(
        "train_compiles_after_warm_total",
        "XLA backend compiles of the TRAINING STEP path observed after "
        "the first window retired — each one is a mid-run recompile "
        "stall; steady state is 0.  Administrative compiles (checkpoint "
        "snapshot copy, the eval program's own first build, background "
        "threads) land under train_compile_seconds_total{when=\"admin\"} "
        "instead.",
    )
    c_compile_s = reg.counter(
        "train_compile_seconds_total",
        "Cumulative XLA backend compile wall-clock, split by when it "
        "happened (warmup = before the first window retired, steady = "
        "after, admin = checkpoint-copy / eval-first-build / "
        "background-thread compiles that are not step stalls).",
        labels=("when",),
    )
    g_mfu = reg.gauge(
        "train_mfu",
        "Model-FLOPs utilization: cost-analysis FLOPs/step x post-warmup "
        "steps / device-compute seconds / (peak chip FLOPs x chips); 0 "
        "until measured (needs collect_cost_analysis and a known peak).",
    )
    g_dev_peak = reg.gauge(
        "device_memory_peak_bytes",
        "Per-device HBM high-water mark (memory_stats peak_bytes_in_use)"
        ", live at window cadence.",
        labels=("device",),
    )
    c_compiles_warm.inc(0)  # materialize the zero: absence is not proof

    compile_stats = {
        "warm": False, "after_warm": 0, "seconds": 0.0,
        # True while dispatching the FIRST window of a given length: a
        # cadence-split short window (checkpoint_every not a multiple of
        # window_steps) compiles a new scan once, which is that
        # program's warmup — only a re-compile of a length already seen
        # is a genuine steady-state stall.
        "first_of_len": False,
    }
    loop_thread = threading.get_ident()

    def _on_compile(duration_s: float) -> None:
        # Only the dispatch thread's un-suppressed compiles can be step
        # stalls: the async checkpointer's orbax thread and the marked
        # admin regions (snapshot copy, eval first build) compile real
        # XLA programs too, but none of them block a training step — a
        # healthy checkpointing run must still read after_warm == 0.
        if (threading.get_ident() != loop_thread
                or _compile_admin_depth() > 0):
            compile_stats["seconds"] += duration_s
            c_compile_s.labels("admin").inc(duration_s)
            return
        steady = compile_stats["warm"] and not compile_stats["first_of_len"]
        compile_stats["seconds"] += duration_s
        c_compile_s.labels("steady" if steady else "warmup").inc(duration_s)
        if steady:
            compile_stats["after_warm"] += 1
            c_compiles_warm.inc()

    # ---- federation + durable history publication (both opt-in by env;
    # no knob set => no file, no socket, byte-identical scrape).
    from tpu_pipelines.observability import federation as _fed
    from tpu_pipelines.observability import trace as _obs
    from tpu_pipelines.observability.metrics_history import MetricsHistory

    fed_source = (
        f"trainer-p{jax.process_index()}-{os.getpid()}"
        if _fed.federation_dir() is not None else None
    )
    _active_rec = _obs.active_recorder()
    _pipeline_root = config.pipeline_root
    _hist_run_id = config.run_id
    if _active_rec is not None:
        _hist_run_id = _hist_run_id or getattr(_active_rec, "run_id", "")
        rec_dir = getattr(_active_rec, "run_dir", "")
        if not _pipeline_root and rec_dir:
            # run_dir is <pipeline_root>/.runs/<run_id>
            _pipeline_root = os.path.dirname(os.path.dirname(rec_dir))
    history = (
        MetricsHistory.from_env(_pipeline_root) if _pipeline_root else None
    )
    hist_run_id = _hist_run_id or "train"
    # tokens/example: the widest trailing extent among integer features
    # (token ids); mask-like siblings share the shape, max() dedups them.
    tokens_per_example = max(
        (
            int(np.prod(np.asarray(v).shape[1:]))
            for v in first_batch.values()
            if np.asarray(v).dtype.kind in "iu" and np.asarray(v).ndim >= 2
        ),
        default=0,
    )
    monitor = HealthMonitor(
        "train_loop",
        stall_timeout_s=config.stall_timeout_s,
        on_alert=config.health_alert_cb,
    )

    def _publish_window(at_step: int, window_steps: int, window_s: float,
                        loss: Optional[float]) -> None:
        if window_steps > 0 and window_s > 0:
            step_s = window_s / window_steps
            g_step_s.set(step_s)
            g_eps.set(config.batch_size / step_s)
            g_tps.set(config.batch_size * tokens_per_example / step_s)
        g_input_wait.set(input_wait_s)
        g_steps.set(at_step)
        try:
            stats = jax.local_devices()[0].memory_stats()
            g_device_mem.set(float((stats or {}).get("bytes_in_use", 0)))
        except Exception:  # noqa: BLE001 — not every backend reports
            pass
        try:
            # Per-device HBM watermark as a live labeled gauge (not every
            # backend reports it).
            for d in jax.local_devices():
                peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
                if peak is not None:
                    g_dev_peak.labels(str(d.id)).set(float(peak))
        except Exception:  # noqa: BLE001
            pass
        monitor.heartbeat(at_step, loss=loss)
        if fed_source is not None:
            try:
                _fed.publish_registry(reg, source=fed_source)
            except OSError as e:
                log.warning("federation publish failed: %s", e)
        if history is not None:
            try:
                history.append(reg, hist_run_id, step=at_step)
            except OSError as e:
                log.warning("metrics-history append failed: %s", e)

    metrics_hist: list = []
    metrics = None   # stays None when resume starts at/past train_steps
    t_start = None
    anchors: list = []   # (step, host time) at each forced device read
    examples_after_t0 = 0
    input_wait_s = 0.0     # host-side time not overlapped with device work
    profiling = False
    device_batch = None
    batch = first_batch
    step = start_step
    eff_window = _effective_window_steps(config)
    window_anchor = (step, time.perf_counter())  # telemetry window start
    # Step-time attribution state (windowed path): measured per-window
    # partition (the infeed wait and device span are clocked; host is
    # the remainder, so the family sums exactly to wall-clock) with the
    # estimated collective fraction splitting the device span.
    phase_totals = {
        "infeed_wait": 0.0, "device_compute": 0.0,
        "device_collective": 0.0, "host": 0.0,
    }
    coll_frac = _collective_fraction(
        state.params, first_batch, mesh, dp_mode
    )

    eval_warmed = {"done": False}

    def emit_eval(at_step: int) -> None:
        # The eval program's FIRST build is its own warmup, not a step
        # stall — admin-book it; a re-compile on a later eval is real.
        region = (
            _compile_admin_region() if not eval_warmed["done"]
            else contextlib.nullcontext()
        )
        eval_warmed["done"] = True
        with region:
            ev = _run_eval(eval_step, state, eval_iter_fn, config,
                           put_batch, has_model_state)
        if metrics_cb:
            metrics_cb(at_step, {f"eval_{k}": v for k, v in ev.items()})
        tb_write("eval", at_step, {f"eval_{k}": v for k, v in ev.items()})
        log.info("step %d eval: %s", at_step, ev)

    _set_compile_hook(_on_compile)
    try:
        if eff_window > 1:
            # ---- device-resident multi-step window (the host-loop-tax fix).
            # The log_every window runs as ONE compiled lax.scan over a batch
            # stack staged on device by the double-buffered infeed; the only
            # per-window host traffic is the fetch of the scan's stacked
            # metrics — a copy-out, never a sync on the (donated) hot state.
            from tpu_pipelines.data.input_pipeline import windowed_infeed

            win_shard = {
                k: NamedSharding(mesh, P(None, *s.spec))
                for k, s in batch_shard.items()
            }

            # WINDOW_PROGRAM_NAME is ``jit_`` + this function's name.
            def train_window(st, bats):
                return jax.lax.scan(step_fn, st, bats)

            train_window = jax.jit(
                train_window,
                in_shardings=(state_shard, win_shard),
                out_shardings=(state_shard, None),
                donate_argnums=(0,) if config.donate_state else (),
            )

            def stage_window(stacked):
                return stage_global(stacked, win_shard)

            def window_lengths(start: int):
                # Windows shrink to land exactly on eval/checkpoint/train_steps
                # boundaries, so boundary consumers still see the state at the
                # exact step they expect.  Scan length is shape-static (each
                # distinct length is one compile); the schedule keeps distinct
                # lengths to O(1): the window itself plus boundary remainders.
                s = start
                while s < config.train_steps:
                    stop = s + eff_window
                    for every in (
                        config.eval_every if eval_step is not None else 0,
                        checkpoint_every if mngr is not None else 0,
                    ):
                        if every:
                            stop = min(stop, ((s // every) + 1) * every)
                    stop = min(stop, config.train_steps)
                    yield stop - s
                    s = stop

            saver = _AsyncCheckpointSaver(mngr) if mngr is not None else None
            seen_window_lens: set = set()
            infeed = windowed_infeed(
                itertools.chain([first_batch], train_it),
                window_lengths(step),
                stage_window,
            )
            while step < config.train_steps:
                t_in = time.perf_counter()
                tracker.data_loading_start()
                try:
                    item = next(infeed, None)
                finally:
                    tracker.data_loading_end()
                if item is None:
                    log.info("train iterator exhausted at step %d", step)
                    break
                t_fetched = time.perf_counter()
                infeed_s = t_fetched - t_in
                if t_start is not None:
                    input_wait_s += infeed_s
                w, dev_window = item
                tracker.step_start(step)
                # Scan programs are keyed by window length; the first
                # dispatch of a NEW length (cadence-split short window)
                # compiles once as that program's warmup.
                compile_stats["first_of_len"] = w not in seen_window_lens
                seen_window_lens.add(w)
                try:
                    state, mstack = train_window(state, dev_window)
                finally:
                    compile_stats["first_of_len"] = False
                step += w
                # ONE device-to-host fetch per window: the stacked metrics are
                # a data dependency of every step in the window, so the
                # transfer proves the whole window executed before the clock
                # is read — the same cannot-lie anchoring as the per-step
                # path, at window granularity.  Per HOST, not per device: the
                # scan's metric outputs land replicated (the loss mean/psum
                # makes them so), so device_get reads one locally-addressable
                # copy — no cross-device gather, and each process in a
                # multi-host run fetches only from its own devices.
                host_stack = jax.device_get(mstack)
                now = time.perf_counter()
                if t_start is None:
                    t_start = now  # the first window absorbs compile
                    # From here on, every backend compile is a mid-run stall
                    # (a shrunk boundary window, a shape change) — counted by
                    # the listener as train_compiles_after_warm_total.
                    compile_stats["warm"] = True
                else:
                    examples_after_t0 += w * config.batch_size
                    # Measured window partition: infeed wait + device span
                    # are clocked, host is the remainder (the previous
                    # window's post-fetch host work: per-step reconstruction,
                    # publishing, checkpoint markers) — so the four phases
                    # sum EXACTLY to this window's wall-clock.  The estimated
                    # collective fraction only splits the device span.
                    device_s = now - t_fetched
                    host_s = max(
                        0.0, (now - window_anchor[1]) - infeed_s - device_s
                    )
                    phases = {
                        "infeed_wait": infeed_s,
                        "device_compute": device_s * (1.0 - coll_frac),
                        "device_collective": device_s * coll_frac,
                        "host": host_s,
                    }
                    for ph, secs in phases.items():
                        phase_totals[ph] += secs
                        c_phase.labels(ph).inc(secs)
                    _obs.instant(
                        "window_breakdown", cat="trainer",
                        args={
                            "step": step, "window_steps": w,
                            "window_s": now - window_anchor[1], **phases,
                        },
                    )
                anchors.append((step, now))
                # Per-step values reconstructed from the windowed accumulator:
                # the watchdog sees every step's loss (a mid-window NaN fires
                # at the boundary) and log_every keeps its exact cadence.
                for i in range(w):
                    s_i = step - w + 1 + i
                    monitor.heartbeat(s_i, loss=float(host_stack["loss"][i]))
                    if config.log_every and s_i % config.log_every == 0:
                        host_metrics = {
                            k: float(v[i]) for k, v in host_stack.items()
                        }
                        metrics_hist.append((s_i, host_metrics))
                        if metrics_cb:
                            metrics_cb(s_i, host_metrics)
                        tb_write("train", s_i, host_metrics)
                        log.info("step %d: %s", s_i, host_metrics)
                metrics = {k: v[-1] for k, v in host_stack.items()}
                _publish_window(
                    step, step - window_anchor[0], now - window_anchor[1],
                    float(host_stack["loss"][-1]),
                )
                window_anchor = (step, now)
                if checkpoint_dir:
                    # The window just proved itself executed (the metric fetch
                    # above is a data dependency of every step in it): advance
                    # the progress marker so a crash before the NEXT durable
                    # checkpoint shows up as a replayed span on resume.
                    _write_progress(checkpoint_dir, step)
                if (
                    saver is not None and checkpoint_every
                    and step % checkpoint_every == 0
                ):
                    saver.save(step, state)
                if (
                    eval_step is not None
                    and config.eval_every
                    and step % config.eval_every == 0
                ):
                    emit_eval(step)
            if saver is not None:
                # Completion fence at loop exit: the in-flight save must be
                # durable before the final synchronous save/export below.
                saver.fence()
        else:
            while step < config.train_steps:
                if config.profile_dir and not profiling and step - start_step == config.profile_from:
                    jax.profiler.start_trace(config.profile_dir)
                    profiling = True
                tracker.step_start(step)
                t_in = time.perf_counter()
                device_batch = put_batch(batch)
                if t_start is not None:  # only measure the post-compile window
                    input_wait_s += time.perf_counter() - t_in
                state, metrics = train_step(state, device_batch)
                step += 1
                monitor.heartbeat(step)  # liveness only; loss rides log cadence
                if profiling and step - start_step >= config.profile_to:
                    # Device-to-host read (not block_until_ready — see t_start
                    # note) so the trace captures the step's full execution.
                    np.asarray(metrics["loss"])
                    jax.profiler.stop_trace()
                    profiling = False
                if t_start is None:
                    # Start timing after step 1 retires (excludes compile time).  A
                    # device-to-host READ of the step's output: the transfer cannot
                    # complete before the step has executed.
                    np.asarray(metrics["loss"])
                    t_start = time.perf_counter()
                    compile_stats["warm"] = True  # later compiles are stalls
                    anchors.append((step, t_start))
                else:
                    examples_after_t0 += config.batch_size
                    if (
                        config.anchor_every
                        and (step - anchors[0][0]) % config.anchor_every == 0
                    ):
                        # Device-to-host read of THIS step's output: the step chain
                        # is a data dependency, so the transfer proves every step up
                        # to here executed on device before the clock is read.
                        np.asarray(metrics["loss"])
                        anchors.append((step, time.perf_counter()))
                if config.log_every and step % config.log_every == 0:
                    host_metrics = {
                        k: float(v) for k, v in metrics.items()
                    }
                    metrics_hist.append((step, host_metrics))
                    if metrics_cb:
                        metrics_cb(step, host_metrics)
                    tb_write("train", step, host_metrics)
                    log.info("step %d: %s", step, host_metrics)
                    # Telemetry window: the host loss just materialized above, so
                    # the NaN/spike checks are free here; gauges cover the span
                    # since the previous log point.
                    now = time.perf_counter()
                    _publish_window(
                        step, step - window_anchor[0], now - window_anchor[1],
                        host_metrics.get("loss"),
                    )
                    window_anchor = (step, now)
                if (
                    mngr is not None and checkpoint_every
                    and step % checkpoint_every == 0
                ):
                    # Gated on the cadence here, not just inside orbax: building
                    # save args and consulting the manager every step is pure
                    # per-step host overhead on the hot path.
                    mngr.save(step, args=_ocp_save_args(state))
                    _write_progress(checkpoint_dir, step)
                if (
                    eval_step is not None
                    and config.eval_every
                    and step % config.eval_every == 0
                ):
                    emit_eval(step)
                if step >= config.train_steps:
                    break
                try:
                    t_in = time.perf_counter()
                    tracker.data_loading_start()
                    try:
                        batch = next(train_it)
                    finally:
                        # On StopIteration too — an open-ended data-loading interval
                        # would misattribute everything through job_end as badput.
                        tracker.data_loading_end()
                    if t_start is not None:
                        input_wait_s += time.perf_counter() - t_in
                except StopIteration:
                    log.info("train iterator exhausted at step %d", step)
                    break

    finally:
        _set_compile_hook(None)

    if profiling:
        jax.profiler.stop_trace()
    if metrics is not None:
        # Host read of the final step's output: the step sequence is a
        # dependency chain, so this proves every timed step executed (see
        # t_start note on why block_until_ready is not sufficient).
        final_loss = float(np.asarray(metrics["loss"]))
        now = time.perf_counter()
        _publish_window(
            step, step - window_anchor[0], now - window_anchor[1],
            final_loss,
        )
    jax.block_until_ready(state.params)
    monitor.close()
    elapsed = max(1e-9, time.perf_counter() - (t_start or time.perf_counter()))
    eps = examples_after_t0 / elapsed if examples_after_t0 else 0.0

    # Median examples/sec over the sync-anchored windows (see anchor_every).
    anchored_eps = 0.0
    window_rates = []
    for (s1, t1), (s2, t2) in zip(anchors, anchors[1:]):
        if t2 > t1:
            window_rates.append((s2 - s1) * config.batch_size / (t2 - t1))
    if window_rates:
        window_rates.sort()
        anchored_eps = window_rates[len(window_rates) // 2]

    # Report the actual final-step metrics (not the last logged snapshot).
    final_metrics: Dict[str, float] = (
        {k: float(v) for k, v in metrics.items()} if metrics is not None else {}
    )
    if eval_step is not None:
        # Post-loop final eval: any compile here (first build when no
        # in-loop eval cadence fired) happens after the last step — by
        # definition not a step stall.
        with _compile_admin_region():
            ev = _run_eval(eval_step, state, eval_iter_fn, config,
                           put_batch, has_model_state)
        final_metrics.update({f"eval_{k}": v for k, v in ev.items()})

    if tb_writer is not None:
        # Only what the in-loop cadence didn't already emit at this step —
        # a same-tag/same-step rewrite doubles points in TensorBoard.
        tail: Dict[str, float] = {}
        if step != last_tb["train"]:
            tail.update({
                k: v for k, v in final_metrics.items()
                if not k.startswith("eval_")
            })
        if step != last_tb["eval"]:
            tail.update({
                k: v for k, v in final_metrics.items()
                if k.startswith("eval_")
            })
        tb_write("train", step, tail)
        tb_writer.close()

    if mngr is not None:
        if mngr.latest_step() != step:
            mngr.save(step, args=_ocp_save_args(state), force=True)
        mngr.wait_until_finished()
        _write_progress(checkpoint_dir, step)

    cost_flops = None
    cost_source = ""
    if config.collect_cost_analysis and metrics is not None:
        # XLA's per-step FLOP count for the SAME step function — after the
        # timed loop, so the extra trace/compile cannot pollute throughput.
        # Preference order: cost analysis of the optimized executable, then
        # HLO cost analysis of the unoptimized lowering (backends without
        # the former).  Both count every op, so a figure BELOW an analytic
        # 6NT-style numerator falsifies that numerator.
        try:
            if device_batch is None:
                # Windowed path: no per-step batch is alive; the analysis
                # only needs shapes/shardings, so re-stage the first batch.
                device_batch = put_batch(first_batch)
            lowered = train_step.lower(state, device_batch)
            ca = None
            try:
                ca = lowered.compile().cost_analysis()
                cost_source = "compiled"
            except Exception:
                ca = lowered.cost_analysis()
                cost_source = "lowered"
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if ca and ca.get("flops"):
                cost_flops = float(ca["flops"])
            else:
                cost_source = ""
        except Exception as e:  # noqa: BLE001 — diagnostics must not fail a run
            log.warning("train-step cost analysis failed: %s", e)

    # MFU over the ATTRIBUTED device-compute seconds when the windowed
    # loop measured them (post-warmup windows only), else post-compile
    # wall-clock (the per-step path cannot separate device from host
    # without a per-step sync — that figure is a lower bound).
    mfu = None
    peak = _peak_flops_per_chip(config)
    steps_measured = (
        examples_after_t0 / config.batch_size if config.batch_size else 0
    )
    if cost_flops and peak and steps_measured > 0:
        device_s = phase_totals["device_compute"] or elapsed
        if device_s > 0:
            mfu = cost_flops * steps_measured / device_s / (
                peak * n_devices
            )
            g_mfu.set(round(mfu, 4))
            # The gauge changed after the loop's last window publish:
            # push one more snapshot so the scrape/ring carry it.
            if fed_source is not None:
                try:
                    _fed.publish_registry(reg, source=fed_source)
                except OSError as e:
                    log.warning("federation publish failed: %s", e)
            if history is not None:
                try:
                    history.append(reg, hist_run_id, step=step)
                except OSError as e:
                    log.warning("metrics-history append failed: %s", e)

    tracker.job_end()
    gsum = tracker.summary()
    # The proxy stays the reported floor when the library is absent; when
    # present, the library's number is the real (stricter) figure — it counts
    # init/prep/compile windows as badput, so short runs read lower.
    proxy_goodput = (
        round(max(0.0, 1.0 - input_wait_s / elapsed), 4)
        if examples_after_t0 else 1.0
    )
    # Bridge the goodput/badput decomposition into the run trace (no-op
    # outside a traced pipeline run): the run-wide profile then carries
    # the same algebra trainer/goodput.py computes for the train loop.
    from tpu_pipelines.observability import trace as _obs

    _obs.instant(
        "goodput_summary", cat="trainer",
        args={
            "goodput": gsum.get("goodput", proxy_goodput),
            "source": (
                "ml_goodput_measurement" if gsum
                else "host_input_wait_proxy"
            ),
            "badput": gsum.get("badput", {}),
            "goodput_post_compile": proxy_goodput,
            "steps_completed": step,
            # Replayed span (elastic resume): steps re-executed because
            # the interruption outran the last durable window.  Counted
            # here as lost work, never as fresh progress.
            "replayed_steps": replayed_steps,
        },
    )
    # Stamp the window-phase breakdown into the RunTrace alongside the
    # per-window instants, so `trace`/`trace diff` compare runs on where
    # their windows went, not just how long they took.
    _obs.instant(
        "train_telemetry_summary", cat="trainer",
        args={
            "window_phase_seconds": {
                k: round(v, 6) for k, v in phase_totals.items()
            },
            "compiles_after_warm": compile_stats["after_warm"],
            "compile_seconds": round(compile_stats["seconds"], 6),
            "collective_fraction_est": round(coll_frac, 6),
            "mfu": mfu,
            "window_steps": eff_window,
        },
    )
    result = TrainResult(
        final_metrics=final_metrics,
        examples_per_sec=round(eps, 2),
        examples_per_sec_per_chip=round(eps / n_devices, 2),
        anchored_examples_per_sec_per_chip=round(anchored_eps / n_devices, 2),
        anchor_windows=len(window_rates),
        steps_completed=step,
        resumed_from_step=start_step,
        goodput=gsum.get("goodput", proxy_goodput),
        goodput_source=(
            "ml_goodput_measurement" if gsum else "host_input_wait_proxy"
        ),
        goodput_post_compile=proxy_goodput,
        badput=gsum.get("badput", {}),
        cost_analysis_flops_per_step=cost_flops,
        cost_analysis_source=cost_source,
        window_steps=eff_window,
        replayed_steps=replayed_steps,
        dp_collective=dp_mode,
        mfu=round(mfu, 4) if mfu is not None else None,
        compiles_after_warm=compile_stats["after_warm"],
        window_phase_seconds={
            k: round(v, 6) for k, v in phase_totals.items()
        },
    )
    final = (
        (state.params, state.model_state) if has_model_state
        else state.params
    )
    return final, result


ENV_WINDOW_STEPS = "TPP_WINDOW_STEPS"


def _effective_window_steps(config: TrainLoopConfig) -> int:
    """Resolve the multi-step window length: explicit config >
    TPP_WINDOW_STEPS env > log_every; floor 1.  Profiling forces 1 —
    a trace of one scan dispatch has no per-step spans to look at."""
    w = config.window_steps
    if w is None:
        raw = os.environ.get(ENV_WINDOW_STEPS, "").strip()
        if raw:
            try:
                w = int(raw)
            except ValueError:
                log.warning("ignoring non-integer %s=%r", ENV_WINDOW_STEPS, raw)
    if w is None:
        w = config.log_every
    w = max(1, int(w or 0))
    if w > 1 and config.profile_dir:
        log.info(
            "window_steps=%d forced to 1: profile_dir is set and the "
            "profiler needs per-step dispatch granularity", w,
        )
        return 1
    return w


def _progress_path(checkpoint_dir: str) -> str:
    return os.path.join(os.path.abspath(checkpoint_dir), "window_progress.json")


def _write_progress(checkpoint_dir: str, step: int) -> None:
    """Record the furthest step the loop has EXECUTED (crash-durable,
    atomic) — intentionally ahead of the last durable checkpoint.  On
    resume the gap between this marker and the restored step is the
    replayed span: work that ran, was lost with the host, and runs again.
    The resumed run reports it (TrainResult.replayed_steps) so goodput
    accounting can prove replayed examples are counted as badput, not as
    fresh progress."""
    from tpu_pipelines.robustness import atomic_write_json

    try:
        atomic_write_json(
            _progress_path(checkpoint_dir), {"step": int(step)}
        )
    except OSError as e:  # progress is accounting, never a run failure
        log.warning("window progress write failed: %s", e)


def _read_progress_step(checkpoint_dir: str) -> int:
    from tpu_pipelines.robustness import load_json_tolerant

    data = load_json_tolerant(_progress_path(checkpoint_dir))
    try:
        return int((data or {}).get("step", 0))
    except (TypeError, ValueError):
        return 0


def _saveable(state):
    out = {"step": state.step, "params": state.params,
           "opt_state": state.opt_state}
    if state.model_state is not None:
        out["model_state"] = state.model_state
    return out


def _ocp_save_args(state):
    import orbax.checkpoint as ocp

    return ocp.args.StandardSave(_saveable(state))


class _AsyncCheckpointSaver:
    """Checkpoint writes off the windowed loop's critical path.

    ``save()`` first snapshots the saveable state with an on-device copy —
    the hot state's buffers are donated into the next dispatched window,
    so a background reader must not touch them — then a daemon thread
    fetches the copy and runs the orbax save to completion.  ``fence()``
    (run before every subsequent save and at loop exit) joins the thread
    and re-raises any save error, so a kill between windows loses at most
    the one in-flight save, never a finished one (orbax step dirs are
    atomic), and the final checkpoint is always durable before
    ``train_loop`` returns."""

    def __init__(self, mngr):
        self._mngr = mngr
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def save(self, step: int, state: "TrainState") -> None:
        self.fence()
        with _compile_admin_region():
            snap = jax.tree_util.tree_map(
                lambda x: jnp.array(x) if isinstance(x, jax.Array) else x,
                _saveable(state),
            )

        def run() -> None:
            import orbax.checkpoint as ocp

            try:
                self._mngr.save(step, args=ocp.args.StandardSave(snap))
                self._mngr.wait_until_finished()
            except BaseException as e:  # noqa: BLE001 — re-raised at fence
                self._error = e

        self._thread = threading.Thread(
            target=run, name="tpp-async-ckpt", daemon=True
        )
        self._thread.start()

    def fence(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err


def _run_eval(eval_step, state, eval_iter_fn, config, put_batch,
              has_model_state) -> Dict[str, float]:
    totals: Dict[str, float] = {}
    n = 0
    for i, batch in enumerate(eval_iter_fn()):
        if config.eval_steps and i >= config.eval_steps:
            break
        if has_model_state:
            m = eval_step(state.params, state.model_state, put_batch(batch))
        else:
            m = eval_step(state.params, put_batch(batch))
        for k, v in m.items():
            totals[k] = totals.get(k, 0.0) + float(v)
        n += 1
    return {k: v / max(1, n) for k, v in totals.items()}
