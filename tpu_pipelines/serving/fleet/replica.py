"""Replica: one micro-batcher + model runner with its own telemetry.

Each replica owns a private :class:`RequestBatcher` (its queue IS the
per-replica queue the router inspects) and, when the host exposes more
than one accelerator, can be pinned to its own device so N replicas feed
N chips from one server process.  The replica publishes the
``serving_replica_*`` family the router and operators read:

  ==============================================  =========================
  serving_replica_queue_depth{replica}            requests queued+in-flight
  serving_replica_p99_seconds{replica}            EWMA p99 request latency
  serving_replica_ewma_latency_seconds{replica}   EWMA mean request latency
  serving_replica_requests_total{replica}         requests routed here
  serving_replica_batch_deadline_seconds{replica} effective gather window
  serving_replica_step_seconds{replica}           EWMA device-call wall
  ==============================================  =========================

(The last two mirror the single-server batcher's unlabeled
``serving_batch_deadline_seconds``/``serving_model_step_seconds`` — per
replica, because each batcher observes its own device's step time.)
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Dict, Optional

import numpy as np

from tpu_pipelines.serving.batching import RequestBatcher
from tpu_pipelines.testing import faults as _faults

# Routing cost for a replica nothing has been observed on yet: small but
# non-zero, so fresh replicas attract traffic without dividing by zero.
DEFAULT_LATENCY_S = 1e-3


def _recoverable_decode_error(exc: BaseException) -> bool:
    """Is this decode failure the *replica's* fault (recover the streams
    elsewhere) rather than the request's (return to caller)?  Overload
    and deliberate eviction keep their 429/503 semantics, validation
    errors stay 4xx, and a still-decoding client timeout is not a dead
    replica; anything else — an engine worker death, a device error —
    means the sequences need a new home."""
    from tpu_pipelines.serving.generative import (
        EngineOverloaded,
        GenerationEvicted,
    )

    if isinstance(exc, (EngineOverloaded, GenerationEvicted)):
        return False
    return not isinstance(exc, (TimeoutError, ValueError, TypeError, KeyError))


class LatencyTracker:
    """Sliding-window p99 + EWMA smoothing over observed request latencies.

    The window (last ``window`` requests) makes p99 a real order statistic
    over recent traffic; the EWMA keeps the routed-on estimate from
    whiplashing on a single outlier while still converging within ~1/alpha
    observations when a replica genuinely degrades."""

    def __init__(self, alpha: float = 0.2, window: int = 128):
        self.alpha = alpha
        self._samples: collections.deque = collections.deque(maxlen=window)
        self._lock = threading.Lock()
        self.ewma_mean_s = 0.0
        self.ewma_p99_s = 0.0
        self.count = 0

    def observe(self, latency_s: float) -> None:
        with self._lock:
            self._samples.append(float(latency_s))
            p99 = float(np.percentile(self._samples, 99))
            mean = float(np.mean(self._samples))
            if self.count == 0:
                self.ewma_p99_s = p99
                self.ewma_mean_s = mean
            else:
                a = self.alpha
                self.ewma_p99_s = (1 - a) * self.ewma_p99_s + a * p99
                self.ewma_mean_s = (1 - a) * self.ewma_mean_s + a * mean
            self.count += 1


class Replica:
    """One worker: batcher + runner + latency telemetry.

    ``predict_fn`` resolves the model at call time (the version manager's
    lease), so hot-swaps apply to queued work without touching the
    replica.  ``device`` (a ``jax.Device``) pins this replica's dispatch;
    None runs on the process default — on a single-device host every
    replica still wins by splitting queue wait across batchers."""

    def __init__(
        self,
        index: int,
        predict_fn: Callable[[Dict[str, Any]], np.ndarray],
        *,
        max_batch_size: int = 64,
        batch_timeout_s: float = 0.005,
        slo_p99_s: float = 0.0,
        device: Any = None,
        registry=None,
        generative_cfg: Optional[Dict[str, Any]] = None,
    ):
        self.index = index
        self.name = str(index)
        self.device = device
        # Rebuild epoch: bumped by rebuild() so anything latched to the
        # OLD incarnation (an injected replica kill, a wedged worker's
        # stale future) stops applying to the new one.
        self.generation = 0
        self.latency = LatencyTracker()
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        # Generative (continuous-batching) side: one GenerativeEngine per
        # RESIDENT version, created + warmed by the fleet's canary gate
        # and drained across hot-swaps (engines for evicted versions are
        # pruned once idle).  ``generative_cfg`` carries the version
        # manager (lease source) and the engine constructor kwargs.
        self._generative_cfg = generative_cfg
        self._engines: Dict[str, Any] = {}
        self._engines_lock = threading.Lock()
        self._decode_telemetry = None
        if generative_cfg is not None:
            from tpu_pipelines.serving.generative import DecodeTelemetry

            self._decode_telemetry = DecodeTelemetry(registry, self.name)
        if device is not None:
            inner = predict_fn

            def predict_fn(batch, _inner=inner, _dev=device):
                import jax

                with jax.default_device(_dev):
                    return _inner(batch)

        def _hooked_predict(batch, _inner=predict_fn):
            # Fault-injection seam (KILL_REPLICA / WEDGE_PREDICT /
            # DEVICE_ERROR): one module-global read when no plan is
            # active, same cost contract as the other hooks.
            _faults.replica_predict(self.name, self.generation)
            return _inner(batch)

        self._predict_fn = _hooked_predict
        # Kept so rebuild() can re-create the private batcher with the
        # exact knobs this replica was born with.
        self._batcher_kwargs = dict(
            max_batch_size=max_batch_size,
            batch_timeout_s=batch_timeout_s,
            slo_p99_s=slo_p99_s,
        )
        self.batcher = RequestBatcher(
            self._predict_fn,
            registry=None,  # per-replica series below; shared batcher
            #               gauges would collide across replicas
            name=self.name,
            **self._batcher_kwargs,
        )
        self._m_depth = self._m_p99 = self._m_ewma = self._m_requests = None
        self._m_deadline = self._m_step = self._m_latency_h = None
        if registry is not None:
            from tpu_pipelines.observability.metrics import (
                fine_latency_buckets,
            )

            # Histogram twin of the p99 gauge, on the sqrt(2) fine
            # ladder: the gauge is an EWMA estimate (smooth, but
            # unmergeable and un-reaggregatable); this series lets a
            # scraper derive replica p99 with ~1.42x worst-case
            # quantization instead of the default ladder's ~2x (the
            # margin SLO_WINDOW_FRAC exists to absorb — batching.py).
            self._m_latency_h = registry.histogram(
                "serving_replica_latency_seconds",
                "Per-request latency observed on this replica "
                "(fine sqrt(2) buckets; gauge twin: "
                "serving_replica_p99_seconds).",
                labels=("replica",),
                buckets=fine_latency_buckets(),
            ).labels(self.name)
            self._m_depth = registry.gauge(
                "serving_replica_queue_depth",
                "Requests queued or in flight on this replica.",
                labels=("replica",),
            ).labels(self.name)
            self._m_p99 = registry.gauge(
                "serving_replica_p99_seconds",
                "EWMA p99 request latency observed on this replica.",
                labels=("replica",),
            ).labels(self.name)
            self._m_ewma = registry.gauge(
                "serving_replica_ewma_latency_seconds",
                "EWMA mean request latency observed on this replica.",
                labels=("replica",),
            ).labels(self.name)
            self._m_requests = registry.counter(
                "serving_replica_requests_total",
                "Requests the router assigned to this replica.",
                labels=("replica",),
            ).labels(self.name)
            self._m_deadline = registry.gauge(
                "serving_replica_batch_deadline_seconds",
                "Effective batch-gather window on this replica "
                "(SLO-derived when slo_p99_ms is configured).",
                labels=("replica",),
            ).labels(self.name)
            self._m_step = registry.gauge(
                "serving_replica_step_seconds",
                "EWMA wall time of one coalesced device call on this "
                "replica.",
                labels=("replica",),
            ).labels(self.name)

    # ------------------------------------------------------------- routing

    def queue_depth(self) -> int:
        """Queued + in-flight work: the router's load signal."""
        with self._inflight_lock:
            inflight = self._inflight
        return self.batcher._queue.qsize() + inflight

    def ewma_p99_s(self) -> float:
        return self.latency.ewma_p99_s or DEFAULT_LATENCY_S

    def routing_cost(self) -> float:
        """Estimated wait for one MORE request routed here: every request
        already queued (plus this one) pays ~the replica's observed
        latency.  Queue depth carries the instantaneous load, EWMA p99 the
        replica's demonstrated speed — a slow replica's cost rises even at
        equal depth, so the router redirects before its queue grows.

        Generative replicas cost in TOKENS x per-step latency instead:
        requests overlap inside the continuous batch, so request-level
        (depth x p99) wildly overestimates an engine mid-generation —
        what a new sequence actually waits on is the outstanding token
        work ahead of it, each token costing ~one observed decode step."""
        if self._generative_cfg is not None:
            tokens = 0
            step = None
            with self._engines_lock:
                engines = list(self._engines.values())
            for eng in engines:
                tokens += eng.outstanding_tokens()
                if eng.step_ewma_s is not None:
                    step = max(step or 0.0, eng.step_ewma_s)
            return (tokens + 1) * (step or DEFAULT_LATENCY_S)
        return (self.queue_depth() + 1) * self.ewma_p99_s()

    # -------------------------------------------------------------- health

    def heartbeat(self) -> None:
        """Supervisor probe: a tiny device-committed no-op on this
        replica's device.  Bypasses the batcher deliberately — a wedged
        batcher would swallow a queued probe, and the queue-age check
        covers that axis; this one answers "is the device itself alive".
        The fault hook fires first so an injected replica kill fails the
        heartbeat exactly like a dead device would."""
        _faults.replica_predict(self.name, self.generation)
        import jax
        import jax.numpy as jnp

        if self.device is not None:
            with jax.default_device(self.device):
                jax.block_until_ready(jnp.zeros((), jnp.float32) + 1.0)
        else:
            jax.block_until_ready(jnp.zeros((), jnp.float32) + 1.0)

    def rebuild(self, timeout_s: float = 2.0) -> None:
        """Rebuild this replica in place after ejection: fail the old
        batcher's wedged work so callers unblock (and fail over), bump
        the generation, then re-create the private batcher and — for
        generative replicas — one engine per RESIDENT version from the
        version manager.  With the AOT executable cache warm, the engine
        re-warm is a deserialize, not a compile storm.  The Replica
        object (and its labeled metric series) survives; only the
        machinery inside is new."""
        old = self.batcher
        old.request_close()
        old.join_close(timeout_s)
        self.generation += 1
        self.batcher = RequestBatcher(
            self._predict_fn,
            registry=None,
            name=self.name,
            **self._batcher_kwargs,
        )
        # Fresh latency window: the dead incarnation's tail latencies
        # must not deter the router from the rebuilt replica.
        self.latency = LatencyTracker()
        cfg = self._generative_cfg
        if cfg is not None:
            final_error = None
            if cfg.get("recover"):
                final_error = RuntimeError(
                    "replica rebuilt while generation was in flight"
                )
            with self._engines_lock:
                engines = list(self._engines.values())
                self._engines.clear()
            for e in engines:
                e.close(timeout_s=timeout_s, final_error=final_error)
            versions = cfg["versions"]
            for version in versions.resident_versions():
                loaded = versions.loaded_for(version)
                if loaded is not None:
                    self.prepare_engine(version, loaded)

    # ------------------------------------------------------------- serving

    def submit(self, batch, n_rows: int, timeout_s: float = 300.0, ctx=None):
        import time

        with self._inflight_lock:
            self._inflight += 1
        if self._m_requests is not None:
            self._m_requests.inc()
        if self._m_depth is not None:
            self._m_depth.set(self.queue_depth())
        t0 = time.perf_counter()
        try:
            return self.batcher.submit(
                batch, n_rows, timeout_s=timeout_s, ctx=ctx
            )
        finally:
            dt = time.perf_counter() - t0
            with self._inflight_lock:
                self._inflight -= 1
            self.latency.observe(dt)
            if self._m_latency_h is not None:
                self._m_latency_h.observe(dt)
            if self._m_p99 is not None:
                self._m_p99.set(self.latency.ewma_p99_s)
                self._m_ewma.set(self.latency.ewma_mean_s)
                self._m_depth.set(self.queue_depth())
                self._m_deadline.set(self.batcher.gather_window_s())
                self._m_step.set(self.batcher._step_ewma_s or 0.0)

    # --------------------------------------------------------- generative

    def prepare_engine(self, version: str, loaded) -> Any:
        """Build (and warm) this replica's continuous-batching engine for
        one model version.  Called by the fleet's canary gate BEFORE the
        version becomes eligible: every (batch_bucket, kv_bucket) decode
        program compiles here, off the request path, so a hot-swap never
        pays an XLA compile mid-traffic.  Raises ``ValueError`` when the
        payload carries no decode contract (``decode_fns``) — the same
        verdict class as a failed canary."""
        if self._generative_cfg is None:
            raise RuntimeError("replica is not generative")
        with self._engines_lock:
            engine = self._engines.get(version)
        if engine is not None:
            return engine
        fns = getattr(loaded, "decode_fns", None)
        if fns is None:
            raise ValueError(
                "payload does not support generative serving (exported "
                "module defines no make_decode_fns)"
            )
        from tpu_pipelines.serving.generative import GenerativeEngine

        kwargs = dict(self._generative_cfg.get("engine_kwargs", {}))

        def _engine_fault_hook(_self=self):
            # Generative traffic never touches the batcher's predict
            # path, so the engine carries its own injection seam — a
            # latched replica kill fails decode rounds here until the
            # rebuild bumps the generation.
            _faults.replica_predict(_self.name, _self.generation)

        # This replica's engine decodes on this replica's device, so it
        # is handed the params copy that lives there (stub payloads in
        # tests carry no per-device copies).
        params_on = getattr(loaded, "params_on", None)
        engine = GenerativeEngine(
            fns,
            loaded.params if params_on is None else params_on(self.device),
            device=self.device,
            telemetry=self._decode_telemetry,
            fault_hook=_engine_fault_hook,
            **kwargs,
        )
        engine.warm()
        with self._engines_lock:
            # Two loads racing the same version: keep the first engine.
            existing = self._engines.setdefault(version, engine)
        if existing is not engine:
            engine.close(timeout_s=1.0)
            return existing
        return engine

    def decode_submit(
        self,
        rows,
        gen_params: Dict[str, Any],
        timeout_s: float = 300.0,
        ctx=None,
    ) -> np.ndarray:
        """Run one request's sequences through this replica's engine.

        The version LEASE is held for the whole generation: sequences
        admitted before a hot-swap finish on the version they started on
        (the engine keyed by that version keeps stepping until it drains),
        while new requests lease — and decode on — the new active
        version.  Rows of one request stream concurrently through the
        iteration-level scheduler; the reply pads them to the longest
        emitted stream."""
        import time as _time

        cfg = self._generative_cfg
        if cfg is None:
            raise RuntimeError("replica is not generative")
        versions = cfg["versions"]
        with self._inflight_lock:
            self._inflight += 1
        if self._m_requests is not None:
            self._m_requests.inc()
        t0 = _time.perf_counter()
        try:
            with versions.lease() as (version, loaded):
                if ctx is not None:
                    # The lease pins this generation to `version` across
                    # any hot-swap; the trace records the pin so a
                    # mid-swap stream is attributable to the version
                    # that actually decoded it.
                    ctx.annotate(version=version, replica=self.name)
                engine = self.prepare_engine(version, loaded)
                # Submit-time validation: a malformed request is ITS
                # caller's 4xx here, before any sequence joins the engine
                # — never a failure inside a decode step shared with
                # other requests.
                from tpu_pipelines.serving.batching import (
                    validate_generation_params,
                )

                gp = validate_generation_params(
                    gen_params, max_decode_len=engine.max_decode_len
                )
                handles = []
                try:
                    for row in rows:
                        handles.append(engine.submit_nowait(
                            row["inputs"],
                            input_mask=row.get("input_mask"),
                            max_new_tokens=gp["max_new_tokens"],
                            ctx=ctx,
                        ))
                    outs = [h.wait(timeout_s) for h in handles]
                except Exception as e:
                    if cfg.get("recover") and _recoverable_decode_error(e):
                        # Supervised fleet: surface the sequences' progress
                        # (prompt is the caller's; accepted tokens are on
                        # the handles) so the fleet can re-prefill onto a
                        # surviving replica and continue the streams.
                        from tpu_pipelines.serving.generative import (
                            DecodeSessionLost,
                        )

                        raise DecodeSessionLost(
                            e,
                            partial_tokens=[
                                [int(t) for t in h.tokens] for h in handles
                            ],
                            unfinished=sum(
                                1 for h in handles if h.result is None
                            ),
                        ) from e
                    raise
        finally:
            with self._inflight_lock:
                self._inflight -= 1
            self.latency.observe(_time.perf_counter() - t0)
            self._prune_engines()
        pad_id = engine.pad_id
        width = max(len(o) for o in outs)
        return np.stack([
            np.pad(o, (0, width - len(o)), constant_values=pad_id)
            for o in outs
        ])

    def _prune_engines(self) -> None:
        """Drop idle engines whose version is no longer resident — the
        engine half of drain-then-evict.  An engine with live sequences
        is left stepping regardless of residency."""
        cfg = self._generative_cfg
        if cfg is None:
            return
        resident = set(cfg["versions"].resident_versions())
        with self._engines_lock:
            stale = [
                v for v, e in self._engines.items()
                if v not in resident and e.idle()
            ]
            engines = [self._engines.pop(v) for v in stale]
        for e in engines:
            e.close(timeout_s=1.0)

    def decode_outstanding_tokens(self) -> int:
        with self._engines_lock:
            engines = list(self._engines.values())
        return sum(e.outstanding_tokens() for e in engines)

    def close_engines(self, timeout_s: float = 5.0) -> None:
        with self._engines_lock:
            engines = list(self._engines.values())
            self._engines.clear()
        for e in engines:
            e.close(timeout_s=timeout_s)
