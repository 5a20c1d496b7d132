"""ServingFleet: the facade ModelServer front-ends route through.

One fleet = one model name served by ``replicas`` workers (each its own
micro-batcher, optionally its own device) over a shared
:class:`ModelVersionManager`.  The REST/gRPC surfaces stay on
``ModelServer``; in fleet mode its ``predict_batch``/``reload`` simply
delegate here, so canaries and tests exercise the
identical request path single-server deployments use.

Canary gating: the fleet remembers the first feature batch it serves and
replays it against every subsequently pushed version via the SAME check
InfraValidator runs (``canary_check``: prediction count + finiteness)
BEFORE the version becomes eligible — a bad push is refused
(:class:`CanaryRefused`) while the prior version keeps serving.  Callers
with a better batch (e.g. a schema-filtered serving request) can install
it with :meth:`set_canary_batch`.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from tpu_pipelines.observability import request_trace
from tpu_pipelines.serving.fleet.pool import ReplicaPool
from tpu_pipelines.serving.fleet.replica import Replica
from tpu_pipelines.serving.fleet.versions import ModelVersionManager

log = logging.getLogger("tpu_pipelines.serving")

# Post-swap probation window (seconds): an SLO burn-rate breach inside
# it is attributed to the swap and auto-rolls back to the prior resident
# version; past it, breaches are the operator's page, not the fleet's
# trigger (a long-running version degrading is not the new push's fault).
ENV_SWAP_PROBATION = "TPP_SWAP_PROBATION_S"
DEFAULT_SWAP_PROBATION_S = 120.0


def _local_devices() -> List[Any]:
    """Accelerators to pin replicas to; [] means run on the default."""
    try:
        import jax

        devices = jax.local_devices()
        return list(devices) if len(devices) > 1 else []
    except Exception:  # noqa: BLE001 — no jax / no backend: default device
        return []


class ServingFleet:
    def __init__(
        self,
        model_name: str,
        base_dir: str,
        *,
        replicas: int = 2,
        raw: bool = True,
        max_batch_size: int = 64,
        batch_timeout_s: float = 0.005,
        slo_p99_s: float = 0.0,
        max_versions: int = 2,
        model_type: str = "predict",
        decode_page_size: int = 0,
        max_queue_tokens: int = 0,
        slo_ms_per_token: float = 0.0,
        prefix_cache_entries: int = 0,
        prefill_chunk_pages: int = 0,
        swap_probation_s: float = -1.0,
        supervisor_interval_s: float = 0.0,
        supervisor_queue_age_s: float = 0.0,
        supervisor_breaker_failures: int = 3,
        supervisor_breaker_open_s: float = 0.0,
        monitor_sample_rate: float = 0.0,
        monitor_window_s: float = 0.0,
        registry=None,
        loader: Optional[Callable[[str], Any]] = None,
    ):
        if model_type not in ("predict", "generative"):
            raise ValueError(
                f"model_type must be 'predict' or 'generative', "
                f"got {model_type!r}"
            )
        self.model_name = model_name
        self.base_dir = base_dir
        self.raw = raw
        self.slo_p99_s = slo_p99_s
        self.model_type = model_type
        if swap_probation_s < 0:
            try:
                swap_probation_s = float(
                    os.environ.get(ENV_SWAP_PROBATION, "").strip()
                    or DEFAULT_SWAP_PROBATION_S
                )
            except ValueError:
                swap_probation_s = DEFAULT_SWAP_PROBATION_S
        self.swap_probation_s = max(0.0, swap_probation_s)
        self._max_batch_size = max_batch_size
        self._canary_batch: Optional[Dict[str, Any]] = None
        self._canary_lock = threading.Lock()
        self._rollback_lock = threading.Lock()
        self._m_rollbacks = None
        self._m_warmup = self._m_aot_compiles = None
        self._m_aot_hits = self._m_aot_after_warm = None
        if registry is not None:
            self._m_rollbacks = registry.counter(
                "serving_auto_rollbacks_total",
                "Automatic activations of the prior resident version "
                "after an SLO burn-rate breach inside the post-swap "
                "probation window.",
            )
            self._m_warmup = registry.gauge(
                "serving_swap_warmup_seconds",
                "Measured wall time of the last swap's bucket warmup "
                "(AOT compile or cache-deserialize of every padded "
                "bucket shape, off the request path).",
            )
            self._m_aot_compiles = registry.counter(
                "serving_aot_compiles_total",
                "Bucket executables compiled at swap gates (AOT cache "
                "misses).",
            )
            self._m_aot_hits = registry.counter(
                "serving_aot_cache_hits_total",
                "Bucket executables deserialized from the AOT cache "
                "instead of compiled.",
            )
            self._m_aot_after_warm = registry.counter(
                "serving_aot_compiles_after_warm_total",
                "Predict-path shapes that missed the AOT table after "
                "warmup — each one paid an XLA trace mid-traffic "
                "(budget: zero; the predict twin of "
                "serving_decode_compiles_after_warm_total).",
            )
        self.versions = ModelVersionManager(
            model_name,
            max_versions=max_versions,
            loader=loader,
            canary_fn=self._canary,
            registry=registry,
        )
        generative_cfg = None
        if model_type == "generative":
            # The engine arena is sized by the same max_batch_size the
            # request batcher uses; page size shapes the KV buckets.
            generative_cfg = {
                "versions": self.versions,
                "engine_kwargs": {
                    "max_batch_size": max_batch_size,
                    "page_size": decode_page_size,
                    "max_queue_tokens": max_queue_tokens,
                    "slo_ms_per_token": slo_ms_per_token,
                    # ISSUE 16 decode levers, both off at 0 (see
                    # serving/generative.py): refcounted prefix caching,
                    # credit-metered chunked prefill.
                    "prefix_cache_entries": prefix_cache_entries,
                    "prefill_chunk_pages": prefill_chunk_pages,
                },
            }
        supervised = supervisor_interval_s > 0
        if generative_cfg is not None and supervised:
            # Supervised fleets recover in-flight generations: a dying
            # replica's decode failures surface as DecodeSessionLost
            # (progress attached) instead of the raw worker-death error.
            generative_cfg["recover"] = True
        devices = _local_devices()
        n = max(1, int(replicas))
        self.pool = ReplicaPool([
            Replica(
                i,
                self._leased_predict,
                max_batch_size=max_batch_size,
                batch_timeout_s=batch_timeout_s,
                slo_p99_s=slo_p99_s,
                device=devices[i % len(devices)] if devices else None,
                registry=registry,
                generative_cfg=generative_cfg,
            )
            for i in range(n)
        ])
        # Self-healing layer (ISSUE 17), opt-in via supervisor_interval_s:
        # OFF (the default) leaves the router ungated, the pool without
        # failover, and none of the serving_replica_state /
        # serving_breaker_transitions_total / serving_failovers_total /
        # serving_fleet_unavailable_total /
        # serving_decode_sessions_recovered_total families registered —
        # the disabled fleet is byte-identical to the pre-supervision one.
        self.supervisor = None
        self._m_failovers = self._m_unavailable = None
        self._m_sessions_recovered = None
        if supervised:
            from tpu_pipelines.serving.fleet.supervisor import (
                ReplicaSupervisor,
            )

            slo_age = 10.0 * slo_p99_s if slo_p99_s > 0 else 0.0
            self.supervisor = ReplicaSupervisor(
                self.pool,
                interval_s=supervisor_interval_s,
                queue_age_s=(
                    supervisor_queue_age_s if supervisor_queue_age_s > 0
                    else max(slo_age, 2.0)
                ),
                breaker_failures=supervisor_breaker_failures,
                breaker_open_s=supervisor_breaker_open_s,
                registry=registry,
            )
            self.pool.router.gate = self.supervisor.allow
            self.pool.supervisor = self.supervisor
            if registry is not None:
                self._m_failovers = registry.counter(
                    "serving_failovers_total",
                    "Requests transparently retried on a healthy replica "
                    "after a transient failure on the routed one.",
                )
                self._m_unavailable = registry.counter(
                    "serving_fleet_unavailable_total",
                    "Requests refused because every replica was ejected "
                    "or breaker-open (HTTP 503 + Retry-After / gRPC "
                    "UNAVAILABLE).",
                )
                self._m_sessions_recovered = registry.counter(
                    "serving_decode_sessions_recovered_total",
                    "In-flight generations re-prefilled onto a surviving "
                    "replica after their replica died, continued with "
                    "bitwise-identical greedy tokens.",
                )
                self.pool.on_failover = self._m_failovers.inc
            self.supervisor.start()
        # Live drift & skew plane (ISSUE 20), opt-in via
        # monitor_sample_rate: OFF (the default) constructs no sampler —
        # no thread, no queue, none of the serving_monitor_* /
        # serving_drift_* families registered, zero bytes added to the
        # predict path — the disabled fleet is byte-identical to the
        # unmonitored one (the same contract the supervisor keeps above).
        self.sampler = None
        if monitor_sample_rate > 0:
            from tpu_pipelines.observability.drift import (
                DEFAULT_WINDOW_S,
                TrafficSampler,
            )
            from tpu_pipelines.observability.metrics_history import (
                MetricsHistory,
            )

            self.sampler = TrafficSampler(
                model_name,
                sample_rate=monitor_sample_rate,
                window_s=(
                    monitor_window_s if monitor_window_s > 0
                    else DEFAULT_WINDOW_S
                ),
                registry=registry,
                baseline_for=self._drift_baseline,
                # None unless TPP_METRICS_HISTORY is on: the drift plane
                # inherits the history ring's zero-footprint contract.
                history=MetricsHistory.from_env(base_dir),
            )
            self.sampler.start()

    @property
    def generative(self) -> bool:
        return self.model_type == "generative"

    # ------------------------------------------------------------- predict

    def _predict_callable(self, loaded):
        return loaded.predict if self.raw else loaded.predict_transformed

    def _leased_predict(self, batch: Dict[str, Any]) -> np.ndarray:
        """Every device call runs under a version lease: a hot-swap during
        the call cannot evict the version mid-predict, and the drain the
        swap contract promises is the lease count hitting zero."""
        with self.versions.lease() as (version, loaded):
            # Runs in the batcher worker thread, below the span emitter:
            # the thread-local note surfaces the leased version onto the
            # model.step span (one global int read when tracing is off).
            request_trace.note("version", version)
            result = np.asarray(self._predict_callable(loaded)(batch))
            if self.sampler is not None:
                # Rate-gated, non-blocking handoff to the drift sampler
                # thread: a full queue drops the sample (counted), never
                # the predict.  Runs while the lease still pins `version`
                # so the sample is attributed to the version that served.
                self.sampler.offer(version, batch, result)
            return result

    def submit(
        self,
        batch: Dict[str, Any],
        n_rows: int,
        timeout_s: float = 300.0,
        ctx=None,
    ) -> np.ndarray:
        if ctx is None:
            ctx = request_trace.current()
        try:
            result = self.pool.submit(
                batch, n_rows, timeout_s=timeout_s, ctx=ctx
            )
        except Exception as e:  # noqa: BLE001 — count + re-raise
            self._note_unavailable(e)
            raise
        if self._canary_batch is None:
            with self._canary_lock:
                if self._canary_batch is None:
                    # First SUCCESSFULLY served request becomes the
                    # canary probe for future pushes: by construction it
                    # is a batch the ACTIVE version answers, i.e. the
                    # live request shape.  Captured only after the
                    # predict returned — a malformed first request
                    # (missing feature, bad dtype) must not become the
                    # probe, or every future push would fail the canary
                    # on the CALLER's mistake.
                    self._canary_batch = {
                        k: np.asarray(v) for k, v in batch.items()
                    }
        return result

    # ---------------------------------------------------------- generative

    def generate_submit(
        self,
        batch: Dict[str, Any],
        gen_params: Optional[Dict[str, Any]] = None,
        timeout_s: float = 300.0,
    ) -> np.ndarray:
        """Continuous-batching generate for one request's rows.

        The router picks ONE replica (token-aware routing cost) and every
        row of the request joins that replica's iteration-level scheduler
        as its own sequence — rows decode concurrently and each leaves the
        batch the moment it finishes.  Requires the ``inputs`` feature
        (token ids); ``input_mask`` optional."""
        if not self.generative:
            raise RuntimeError("fleet is not generative")
        if "inputs" not in batch:
            raise ValueError(
                "generative serving requires an 'inputs' feature "
                "(token ids per row)"
            )
        inputs = np.asarray(batch["inputs"])
        mask = batch.get("input_mask")
        rows = []
        for i in range(inputs.shape[0]):
            row = {"inputs": inputs[i]}
            if mask is not None:
                row["input_mask"] = np.asarray(mask)[i]
            rows.append(row)
        ctx = request_trace.current()
        try:
            if ctx is None:
                replica = self.pool.router.pick(self.pool.replicas)
            else:
                replica, costs = self.pool.router.pick_with_costs(
                    self.pool.replicas
                )
                ctx.instant("route", replica=replica.name, costs=costs)
        except Exception as e:  # noqa: BLE001 — count + re-raise
            self._note_unavailable(e)
            raise
        try:
            return replica.decode_submit(
                rows, dict(gen_params or {}), timeout_s=timeout_s, ctx=ctx
            )
        except Exception as e:  # noqa: BLE001 — classified below
            from tpu_pipelines.serving.generative import DecodeSessionLost

            if not isinstance(e, DecodeSessionLost):
                raise
            return self._recover_decode(
                replica, e, rows, dict(gen_params or {}), timeout_s, ctx
            )

    def _recover_decode(
        self,
        dead,
        lost,
        rows: List[Dict[str, Any]],
        gen_params: Dict[str, Any],
        timeout_s: float,
        ctx,
    ) -> np.ndarray:
        """Decode-session recovery: the routed replica died with this
        request's generations in flight.  Greedy decode is deterministic,
        so re-prefilling prompt (+ the accepted tokens the engine had
        committed, re-derived by replay) onto a surviving replica
        continues every stream bitwise-identically — the caller sees the
        exact token arrays an uninterrupted run would have produced, at
        the cost of one extra prefill (prefix-cache-assisted when
        enabled).  One recovery per request: a second death surfaces."""
        sup = self.supervisor
        if sup is None:
            raise lost.cause
        sup.on_request_error(dead, lost.cause)
        survivors = [
            r for r in self.pool.replicas if r is not dead and sup.allow(r)
        ]
        if not survivors:
            from tpu_pipelines.serving.fleet.supervisor import (
                FleetUnavailable,
            )

            err = FleetUnavailable(
                "decode session lost and no healthy replica remains"
            )
            self._note_unavailable(err)
            raise err from lost.cause
        replica = self.pool.router.pick(survivors)
        if ctx is not None:
            ctx.instant(
                "decode_recover", from_replica=dead.name,
                to_replica=replica.name, unfinished=lost.unfinished,
                error=f"{type(lost.cause).__name__}: {lost.cause}",
            )
        out = replica.decode_submit(
            rows, gen_params, timeout_s=timeout_s, ctx=ctx
        )
        # Soft continuity audit: each recovered stream must extend the
        # tokens the dead engine had already committed (determinism is
        # the recovery contract; a mismatch means the survivor decoded a
        # DIFFERENT stream and the client-visible guarantee broke).
        for i, partial in enumerate(lost.partial_tokens[: len(out)]):
            got = [int(t) for t in out[i][: len(partial)]]
            if partial and got != partial:
                log.warning(
                    "fleet: %s recovered stream %d diverged from the "
                    "accepted prefix (%r -> %r)",
                    self.model_name, i, partial, got,
                )
        if self._m_sessions_recovered is not None:
            self._m_sessions_recovered.inc(max(lost.unfinished, 1))
        sup.on_request_success(replica)
        return out

    def _note_unavailable(self, exc: BaseException) -> None:
        if self._m_unavailable is not None:
            from tpu_pipelines.serving.fleet.supervisor import (
                FleetUnavailable,
            )

            if isinstance(exc, FleetUnavailable):
                self._m_unavailable.inc()

    def outstanding_tokens(self) -> int:
        """Fleet-wide decode work owed (token-level admission input)."""
        return sum(
            r.decode_outstanding_tokens() for r in self.pool.replicas
        )

    # -------------------------------------------------------------- canary

    def set_canary_batch(self, batch: Optional[Dict[str, Any]]) -> None:
        with self._canary_lock:
            self._canary_batch = (
                None if batch is None
                else {k: np.asarray(v) for k, v in batch.items()}
            )

    def _canary(self, loaded, version: str) -> str:
        from tpu_pipelines.components.infra_validator import canary_check

        # Gate 2 of the Rewriter's double-gated deploy: a variant payload
        # the quality gate refused at rewrite time carries
        # spec["rewriter"]["blessed"] = false, and the fleet refuses to
        # serve it no matter how it reached the version directory —
        # CanaryRefused => HTTP 409 / gRPC FAILED_PRECONDITION, the prior
        # version keeps serving.
        spec = getattr(loaded, "spec", None)
        rewrite = spec.get("rewriter") if isinstance(spec, dict) else None
        if isinstance(rewrite, dict) and rewrite.get("blessed") is False:
            return (
                f"rewriter variant {rewrite.get('variant', '?')!r} is "
                f"NOT_BLESSED (quality gate): "
                f"{rewrite.get('reason', 'outside quality_tolerance')}"
            )
        if self.generative:
            # Generative gate: the payload must carry the decode contract,
            # and every replica's engine compiles its full
            # (batch_bucket, kv_bucket) program set HERE — before the
            # version becomes eligible — so post-swap decode steps never
            # pay an XLA compile mid-traffic (engine.warm, the decode
            # analog of the predict bucket warmup below).
            try:
                for replica in self.pool.replicas:
                    replica.prepare_engine(version, loaded)
            except Exception as e:  # noqa: BLE001 — same verdict as canary
                return f"generative warmup failed: {type(e).__name__}: {e}"
        with self._canary_lock:
            batch = self._canary_batch
        if batch is None:
            return ""  # nothing served yet: a loadable payload is eligible
        error = canary_check(self._predict_callable(loaded), batch)
        if error:
            return error
        return self._warm_buckets(loaded, batch)

    def _warm_buckets(self, loaded, batch: Dict[str, Any]) -> str:
        """Ahead-of-time compile the padded bucket shapes the replica
        batchers will pose, BEFORE the swap: one lowered computation per
        bucket on the device step (serving/aot.py), loaded from the
        serialized-executable cache when this payload was compiled by
        any prior process — a warm hot-swap deserializes instead of
        tracing, and post-swap batches never pay an XLA compile
        mid-traffic (``serving_aot_compiles_after_warm_total`` audits
        exactly that).  Runs outside every serving lock (part of
        load-outside-lock); a shape the version cannot answer is a gate
        failure — it WOULD fail in production.  Measured wall time lands
        in ``serving_swap_warmup_seconds``."""
        from tpu_pipelines.serving import aot

        t0 = time.monotonic()
        try:
            stats = aot.warm_loaded(
                loaded, batch, self._max_batch_size, raw=self.raw,
                # One executable set per replica device: each replica
                # computes on its own chip, against its own params copy.
                devices=[
                    r.device for r in self.pool.replicas
                    if r.device is not None
                ] or None,
            )
        except Exception as e:  # noqa: BLE001 — same verdict as the canary
            return f"bucket warmup failed: {type(e).__name__}: {e}"
        if self._m_warmup is not None:
            self._m_warmup.set(time.monotonic() - t0)
            self._m_aot_compiles.inc(stats.get("compiled", 0))
            self._m_aot_hits.inc(stats.get("cache_hits", 0))
        dispatch = getattr(loaded, "aot", None)
        if dispatch is not None and self._m_aot_after_warm is not None:
            dispatch.on_compile_after_warm = self._m_aot_after_warm.inc
        log.info(
            "fleet: %s bucket warmup %.3fs (%d compiled, %d cache hits, "
            "%d cache entries failed to load%s)",
            self.model_name, stats.get("seconds", 0.0),
            stats.get("compiled", 0), stats.get("cache_hits", 0),
            stats.get("load_failed", 0),
            ", legacy trace path" if stats.get("fallback_warm") else "",
        )
        return ""

    # ---------------------------------------------------------- drift plane

    def _drift_baseline(self, version: str):
        """Training-time statistics baseline for one resident version.

        The payload spec carries ``training_statistics_uri`` (stamped at
        export or Pusher time — the no-store-walk lineage contract), so
        the skew baseline is one JSON read per version, cached by the
        sampler.  Returns ``(SplitStatistics, uri)`` or None when the
        payload has no lineage (drift-vs-previous-window still runs)."""
        loaded = self.versions.loaded_for(version)
        uri = str(getattr(loaded, "training_statistics_uri", "") or "")
        if not uri:
            return None
        from tpu_pipelines.data.statistics import load_statistics

        stats = load_statistics(uri)
        baseline = stats.get("train")
        if baseline is None and stats:
            baseline = stats[sorted(stats)[0]]
        if baseline is None:
            return None
        return baseline, uri

    # -------------------------------------------------- SLO auto-rollback

    def on_slo_breach(self, breach: Dict[str, Any]) -> bool:
        """Default breach policy: canary-style probation rollback.

        An SLO burn-rate breach (observability/slo.py) that fires within
        ``swap_probation_s`` of the last hot-swap is attributed to the
        swap: the prior resident version is re-``activate()``\\ d (an
        instant swap — it never left memory), the bad version is
        quarantined so a repeat ``:reload`` of it answers 409 until
        :meth:`clear_quarantine`, and ``serving_auto_rollbacks_total``
        records the event.  Returns True when a rollback happened —
        False when no recent swap, probation expired, the prior version
        is gone, or a rollback already ran (idempotent under the
        monitor's edge-triggered breaches AND a racing double-fire)."""
        if breach.get("slo") == "drift":
            # A drift breach is a property of the DATA, not of the swap:
            # rolling back the model would not un-shift the traffic.  The
            # continuous controller owns the response (retrain), so the
            # probation policy explicitly declines it.
            return False
        with self._rollback_lock:
            swap = self.versions.last_swap()
            if swap is None or self.swap_probation_s <= 0:
                return False
            if swap.get("rollback"):
                return False    # our own rollback opened no probation
            age_s = time.monotonic() - swap["mono"]
            if age_s > self.swap_probation_s:
                return False
            bad, prior = swap["version"], swap["prior"]
            if prior is None or self.versions.active_version != bad:
                return False
            if prior not in self.versions.resident_versions():
                return False
            self.versions.quarantine(
                bad,
                reason=(
                    f"SLO breach ({breach.get('slo', '?')}) "
                    f"{age_s:.1f}s after swap"
                ),
            )
            self.versions.activate(prior, rollback=True)
            if self._m_rollbacks is not None:
                self._m_rollbacks.inc()
            log.warning(
                "fleet: %s auto-rollback %s -> %s (%s burn breach %.1fs "
                "into the %.0fs probation window)",
                self.model_name, bad, prior, breach.get("slo", "?"),
                age_s, self.swap_probation_s,
            )
            return True

    def clear_quarantine(self, version: Optional[str] = None) -> List[str]:
        return self.versions.clear_quarantine(version)

    # ----------------------------------------------------------- lifecycle

    def load_version(self, version_dir: str) -> str:
        return self.versions.load_version(version_dir)

    def reload(self) -> str:
        """Load-and-activate the newest version under ``base_dir``."""
        from tpu_pipelines.serving.server import latest_version_dir

        vdir = latest_version_dir(self.base_dir)
        if vdir is None:
            raise FileNotFoundError(
                f"no model versions under {self.base_dir!r}"
            )
        return self.load_version(vdir)

    @property
    def active_version(self) -> Optional[str]:
        return self.versions.active_version

    def active_loaded(self):
        return self.versions.active_loaded()

    def queue_depth(self) -> int:
        return self.pool.queue_depth()

    @property
    def closed(self) -> bool:
        return self.pool.closed

    def health(self) -> Dict[str, Any]:
        health = {
            "replicas": len(self.pool),
            "versions_resident": self.versions.resident_versions(),
            "active_version": self.active_version,
            "slo_p99_ms": (
                round(self.slo_p99_s * 1e3, 3) if self.slo_p99_s else None
            ),
            "model_type": self.model_type,
        }
        quarantined = self.versions.quarantined()
        if quarantined:
            health["quarantined_versions"] = sorted(quarantined)
        if self.generative:
            health["outstanding_decode_tokens"] = self.outstanding_tokens()
        if self.supervisor is not None:
            health["replica_states"] = {
                r.name: self.supervisor.state(r) for r in self.pool.replicas
            }
        if self.sampler is not None:
            health["drift"] = self.sampler.summary()
        return health

    def close(self, timeout_s: float = 5.0) -> None:
        if self.sampler is not None:
            self.sampler.stop()
        if self.supervisor is not None:
            self.supervisor.stop()
        self.pool.close(timeout_s=timeout_s)
