"""REST model server over exported model payloads.

The TF-Serving-equivalent serving path (SURVEY.md §3.5): Pusher copies a
blessed payload into ``<base>/<version>/``; this server watches that layout,
loads the highest version (preprocessing fused with the forward pass in one
jitted function — trainer/export.py), and answers TF-Serving-style REST:

    GET  /v1/models/<name>            -> version status
    POST /v1/models/<name>:predict    -> {"predictions": [...]}
    POST /v1/models/<name>:generate   -> {"outputs": [[token ids], ...]}
         (seq2seq payloads exported with a make_generate_step hook)
         body: {"instances": [{feature: value, ...}, ...]}
         or    {"inputs": {feature: [values...], ...}}

    POST /v1/models/<name>:reload     -> {"version": "..."} (rescan +
         hot-swap to the newest pushed version; the Pusher push-URL hook
         and ops tooling call this instead of waiting for the poll)

Implementation is stdlib ``ThreadingHTTPServer``; concurrent requests are
safe (jax dispatch is thread-safe) and, with ``batching=True``, coalesce
through a micro-batcher into padded fixed-bucket device calls
(serving/batching.py) — the BatchingSession equivalent.  This server exists
for InfraValidator canaries, e2e tests, and small deployments.  For
high-QPS serving, ``replicas``/``max_versions``/``slo_p99_ms`` switch the
SAME surfaces onto the serving fleet (serving/fleet/, docs/SERVING.md):
N replica workers behind a latency-aware router, N model versions
resident with canary-gated atomic hot-swap, and SLO-driven batch
deadlines.  SavedModel export into TF Serving (serving/saved_model.py)
remains the interop escape hatch.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np

from tpu_pipelines.observability import federation as _fed
from tpu_pipelines.observability import request_trace
from tpu_pipelines.observability.metrics import (
    CONTENT_TYPE_LATEST,
    MetricsRegistry,
)
from tpu_pipelines.observability.request_trace import RequestTracer
from tpu_pipelines.trainer.export import LoadedModel, load_exported_model

log = logging.getLogger("tpu_pipelines.serving")

# Admission-control bound fallback when the constructor leaves it 0
# (deployment knob for `python -m tpu_pipelines.serving`).
ENV_MAX_QUEUE = "TPP_SERVING_MAX_QUEUE"
# Fleet knobs, same constructor-0-falls-back-to-env convention: replica
# worker count, versions kept resident for instant rollback, and the p99
# budget (ms) the SLO-driven batch deadline spends (0 = fixed window).
ENV_REPLICAS = "TPP_SERVING_REPLICAS"
ENV_MAX_VERSIONS = "TPP_SERVING_MAX_VERSIONS"
ENV_SLO_P99_MS = "TPP_SERVING_SLO_P99_MS"
# Generative (continuous-batching) knobs: model type selects the fleet's
# decode engine for :generate, page size shapes the KV-cache buckets, the
# token bound is generate-endpoint admission control (outstanding decode
# TOKENS, not requests), and the per-token SLO prices each generation's
# deadline by its length.
ENV_MODEL_TYPE = "TPP_SERVING_MODEL_TYPE"
ENV_PAGE_SIZE = "TPP_SERVING_PAGE_SIZE"
ENV_MAX_TOKENS = "TPP_SERVING_MAX_TOKENS"
ENV_SLO_MS_PER_TOKEN = "TPP_SERVING_SLO_MS_PER_TOKEN"
# Decode-speed levers (serving/generative.py, both off at 0): resident
# prefix-cache entries (refcounted prefill reuse for shared prompts) and
# prefill pages admitted per decode step (chunked prefill's credit
# meter).
ENV_PREFIX_CACHE = "TPP_SERVING_PREFIX_CACHE"
ENV_PREFILL_CHUNK = "TPP_SERVING_PREFILL_CHUNK"
# Self-healing fleet (ISSUE 17): probe interval > 0 turns the
# ReplicaSupervisor on (heartbeat + queue-age probes, circuit breakers,
# failover, rebuild-in-place); queue-age is the wedge threshold (0 =
# derived from the SLO).  Off by default: the unsupervised fleet is
# byte-identical to the pre-supervision one.
ENV_SUPERVISOR_S = "TPP_SERVING_SUPERVISOR_S"
ENV_SUPERVISOR_QUEUE_AGE_S = "TPP_SERVING_SUPERVISOR_QUEUE_AGE_S"
# Observability knobs (docs/OBSERVABILITY.md "Request tracing & SLO burn
# rates"): request-scoped tracing mode (off | sample:N | all — default
# off: zero files, byte-identical /metrics), where sampled spans flush
# (<dir>/serving/events.jsonl; empty = in-memory ring only), and the SLO
# burn-rate monitor's evaluation cadence in seconds (unset/0 = no
# monitor thread, no burn-rate series).
ENV_REQUEST_TRACE = request_trace.ENV_REQUEST_TRACE
ENV_REQUEST_TRACE_DIR = request_trace.ENV_REQUEST_TRACE_DIR
ENV_SLO_MONITOR = "TPP_SLO_MONITOR"
# Live drift & skew plane (ISSUE 20, observability/drift.py): fraction
# of admitted predicts sampled into tumbling stats windows scored
# against the training baseline (0 < rate <= 1; unset/0 = no sampler
# thread, no serving_monitor_*/serving_drift_* families, byte-identical
# /metrics), and the window length in seconds (0 = 60 s default).
ENV_MONITOR_SAMPLE = "TPP_SERVING_MONITOR_SAMPLE"
ENV_MONITOR_WINDOW = "TPP_SERVING_MONITOR_WINDOW_S"


def _env_number(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "").strip() or default)
    except ValueError:
        return default


class GenerateUnsupported(ValueError):
    """This server/payload cannot serve generate requests (no
    make_generate_step hook, or raw=False with an embedded transform)."""


class ServerOverloaded(RuntimeError):
    """Admission control refused the request: queue depth + in-flight work
    already exceed the configured bound.  Maps to HTTP 429 + Retry-After
    (gRPC: RESOURCE_EXHAUSTED) — load is SHED at the door, so every
    admitted request still meets its latency budget and none is dropped
    mid-flight (the zero-drop half of the contract)."""

    retry_after_s = 1


def latest_version_dir(base_dir: str) -> Optional[str]:
    """Highest numeric subdirectory — the TF Serving version convention."""
    if not os.path.isdir(base_dir):
        return None
    versions = [
        d for d in os.listdir(base_dir)
        if d.isdigit() and os.path.isdir(os.path.join(base_dir, d))
    ]
    if not versions:
        return None
    return os.path.join(base_dir, max(versions, key=int))


class ModelServer:
    """Serves one model name from a version-dir layout (or a flat payload).

    ``raw=True`` (default) serves ``LoadedModel.predict`` (embedded transform
    applied to raw features); ``raw=False`` serves ``predict_transformed``
    for callers sending already-materialized features.
    """

    def __init__(
        self,
        model_name: str,
        base_dir: str,
        *,
        raw: bool = True,
        batching: bool = False,
        max_batch_size: int = 64,
        batch_timeout_s: float = 0.005,
        metrics_registry: Optional[MetricsRegistry] = None,
        max_queue_depth: int = 0,
        replicas: int = 0,
        max_versions: int = 0,
        slo_p99_ms: float = -1.0,
        model_type: str = "",
        decode_page_size: int = 0,
        max_queue_tokens: int = 0,
        slo_ms_per_token: float = -1.0,
        prefix_cache_entries: int = 0,
        prefill_chunk_pages: int = 0,
        request_trace_mode: str = "",
        trace_dir: str = "",
        slo_monitor_interval_s: float = -1.0,
        swap_probation_s: float = -1.0,
        supervisor_interval_s: float = -1.0,
        supervisor_queue_age_s: float = -1.0,
        monitor_sample_rate: float = -1.0,
        monitor_window_s: float = -1.0,
    ):
        self.model_name = model_name
        self.base_dir = base_dir
        self.raw = raw
        # Fleet knobs: constructor wins, 0/-1 falls back to env, then to
        # the single-server defaults (1 replica, 1 resident version,
        # fixed batch window).
        if replicas <= 0:
            replicas = int(_env_number(ENV_REPLICAS, 1))
        if max_versions <= 0:
            max_versions = int(_env_number(ENV_MAX_VERSIONS, 1))
        if slo_p99_ms < 0:
            slo_p99_ms = _env_number(ENV_SLO_P99_MS, 0.0)
        if not model_type:
            model_type = (
                os.environ.get(ENV_MODEL_TYPE, "").strip() or "predict"
            )
        if decode_page_size <= 0:
            decode_page_size = int(_env_number(ENV_PAGE_SIZE, 0))
        if max_queue_tokens <= 0:
            max_queue_tokens = int(_env_number(ENV_MAX_TOKENS, 0))
        if slo_ms_per_token < 0:
            slo_ms_per_token = _env_number(ENV_SLO_MS_PER_TOKEN, 0.0)
        if prefix_cache_entries <= 0:
            prefix_cache_entries = int(_env_number(ENV_PREFIX_CACHE, 0))
        if prefill_chunk_pages <= 0:
            prefill_chunk_pages = int(_env_number(ENV_PREFILL_CHUNK, 0))
        if supervisor_interval_s < 0:
            supervisor_interval_s = _env_number(ENV_SUPERVISOR_S, 0.0)
        if supervisor_queue_age_s < 0:
            supervisor_queue_age_s = _env_number(
                ENV_SUPERVISOR_QUEUE_AGE_S, 0.0
            )
        if monitor_sample_rate < 0:
            monitor_sample_rate = _env_number(ENV_MONITOR_SAMPLE, 0.0)
        if monitor_window_s < 0:
            monitor_window_s = _env_number(ENV_MONITOR_WINDOW, 0.0)
        self.supervisor_interval_s = max(0.0, supervisor_interval_s)
        self.supervisor_queue_age_s = max(0.0, supervisor_queue_age_s)
        self.monitor_sample_rate = max(0.0, monitor_sample_rate)
        self.monitor_window_s = max(0.0, monitor_window_s)
        self.replicas = max(1, replicas)
        self.max_versions = max(1, max_versions)
        self.slo_p99_ms = max(0.0, slo_p99_ms)
        self.model_type = model_type
        self.decode_page_size = max(0, decode_page_size)
        self.max_queue_tokens = max(0, max_queue_tokens)
        self.slo_ms_per_token = max(0.0, slo_ms_per_token)
        self.prefix_cache_entries = max(0, prefix_cache_entries)
        self.prefill_chunk_pages = max(0, prefill_chunk_pages)
        self._lock = threading.Lock()
        # Serializes reload(): concurrent version swaps would race the
        # load-outside-lock / swap-under-lock dance.  Never held while
        # answering requests — predict always reads whichever reference
        # is current, so a reload drains naturally with zero 5xx.
        self._reload_lock = threading.Lock()
        self._loaded: Optional[LoadedModel] = None
        self._loaded_version: Optional[str] = None
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        # Admission control (load shedding): when > 0, a predict/generate
        # arriving while (in-flight + batcher queue) >= bound is refused
        # with 429 + Retry-After instead of queuing into a latency cliff.
        # 0 falls back to env TPP_SERVING_MAX_QUEUE, else unbounded.
        if max_queue_depth <= 0:
            try:
                max_queue_depth = int(
                    os.environ.get(ENV_MAX_QUEUE, "0").strip() or "0"
                )
            except ValueError:
                max_queue_depth = 0
        self.max_queue_depth = max(0, max_queue_depth)
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        # Live telemetry (observability/metrics.py): per-server registry by
        # default so two servers in one process never mix series; callers
        # may inject a shared registry.  In-memory only — the sole exposure
        # is this server's own GET /metrics route.
        self.metrics = metrics_registry or MetricsRegistry()
        self._m_requests = self.metrics.counter(
            "serving_requests_total",
            "HTTP requests handled, by endpoint and status code.",
            labels=("endpoint", "code"),
        )
        self._m_latency = self.metrics.histogram(
            "serving_request_latency_seconds",
            "End-to-end request latency (parse + model + reply), "
            "by endpoint.",
            labels=("endpoint",),
        )
        self._m_model_info = self.metrics.gauge(
            "serving_model_info",
            "1 for the currently served model version, 0 for prior ones.",
            labels=("model", "version"),
        )
        self._m_reloads = self.metrics.counter(
            "serving_model_reloads_total",
            "Successful model version loads (including the initial one).",
        )
        self._m_shed = self.metrics.counter(
            "serving_load_shed_total",
            "Requests refused (429) by admission control, by endpoint.",
            labels=("endpoint",),
        )
        self._m_inflight = self.metrics.gauge(
            "serving_inflight_requests",
            "Predict/generate requests currently being served.",
        )
        self._m_inflight.set_function(lambda: self._inflight)
        # Request-scoped tracing (observability/request_trace.py):
        # constructor wins, else env; default off — no tracer object, no
        # file, no extra metric family, byte-identical /metrics.
        self.request_tracer = RequestTracer.create(
            request_trace_mode or os.environ.get(ENV_REQUEST_TRACE, ""),
            trace_dir or os.environ.get(ENV_REQUEST_TRACE_DIR, ""),
            service=model_name,
            registry=self.metrics,
        )
        # Metric federation (observability/federation.py), opt-in via
        # TPP_FEDERATION_DIR: each scrape first publishes THIS server's
        # registry into the spool (so sibling replicas' endpoints merge
        # it, at most one scrape interval stale), then serves the merged
        # host/replica/tenant-labeled exposition — any replica's
        # /metrics is the fleet-wide endpoint.  The writer stamp keeps
        # merged() from re-counting our own spool file.  Unset: plain
        # local exposition, no files — byte-identical to pre-federation.
        self._federated = None
        self._fed_source = ""
        if _fed.federation_dir() is not None:
            self._fed_source = f"serving-{model_name}-{os.getpid()}"
            self._federated = _fed.FederatedRegistry(self.metrics)
        if slo_monitor_interval_s < 0:
            slo_monitor_interval_s = _env_number(ENV_SLO_MONITOR, 0.0)
        self._slo_interval_s = max(0.0, slo_monitor_interval_s)
        self.slo_monitor = None
        # Micro-batching (serving/batching.py): coalesce concurrent requests
        # into padded fixed-bucket device calls.  The batcher resolves the
        # current model at call time, so hot-swaps apply to queued requests.
        # Fleet mode (replicas/max_versions > 1) moves batching into the
        # per-replica workers behind the latency-aware router; the REST/
        # gRPC surfaces, admission control, and /metrics stay right here.
        self._batcher = None
        self._fleet = None
        if (
            self.replicas > 1
            or self.max_versions > 1
            or self.model_type == "generative"
            # The drift sampler hooks the fleet's leased predict path, so
            # asking for live monitoring promotes a single-server config
            # to a one-replica fleet (identical request semantics).
            or self.monitor_sample_rate > 0
        ):
            # Generative serving is a FLEET model type even at one
            # replica: the continuous-batch engine, per-version drain and
            # decode-bucket warmup all live behind the version manager.
            from tpu_pipelines.serving.fleet import ServingFleet

            self._fleet = ServingFleet(
                model_name,
                base_dir,
                replicas=self.replicas,
                raw=raw,
                max_batch_size=max_batch_size,
                batch_timeout_s=batch_timeout_s,
                slo_p99_s=self.slo_p99_ms / 1e3,
                max_versions=self.max_versions,
                model_type=self.model_type,
                decode_page_size=self.decode_page_size,
                max_queue_tokens=self.max_queue_tokens,
                slo_ms_per_token=self.slo_ms_per_token,
                prefix_cache_entries=self.prefix_cache_entries,
                prefill_chunk_pages=self.prefill_chunk_pages,
                swap_probation_s=swap_probation_s,
                supervisor_interval_s=self.supervisor_interval_s,
                supervisor_queue_age_s=self.supervisor_queue_age_s,
                monitor_sample_rate=self.monitor_sample_rate,
                monitor_window_s=self.monitor_window_s,
                registry=self.metrics,
            )
            if self._fleet.sampler is not None:
                # Drift alerts land in the same trace stream request
                # spans use (a drift/alert instant next to the slo
                # burn_alert ones); no tracer configured = module-level
                # no-op instants, nothing extra recorded.
                self._fleet.sampler.tracer = self.request_tracer
            if self._slo_interval_s > 0:
                # SLO burn-rate monitor (observability/slo.py), wired to
                # the fleet's default breach policy: a breach inside the
                # post-swap probation window auto-rolls back to the
                # prior resident version.  Opt-in (the burn-rate series
                # only exist when someone asked for the monitor).
                from tpu_pipelines.observability.slo import SLOMonitor

                drift_threshold = 0.0
                if self.monitor_sample_rate > 0:
                    from tpu_pipelines.observability.drift import (
                        DEFAULT_DRIFT_THRESHOLD,
                    )

                    drift_threshold = DEFAULT_DRIFT_THRESHOLD
                self.slo_monitor = SLOMonitor(
                    self.metrics,
                    slo_p99_s=self.slo_p99_ms / 1e3,
                    drift_threshold=drift_threshold,
                    on_breach=self._fleet.on_slo_breach,
                    tracer=self.request_tracer,
                )
        elif batching:
            from tpu_pipelines.serving.batching import RequestBatcher

            self._batcher = RequestBatcher(
                lambda b: np.asarray(self._predict_fn()(b)),
                max_batch_size=max_batch_size,
                batch_timeout_s=batch_timeout_s,
                slo_p99_s=self.slo_p99_ms / 1e3,
                registry=self.metrics,
                name="server",
            )
        self.reload()

    # ----------------------------------------------------------- lifecycle

    def reload(self) -> str:
        """(Re)load the newest version; returns the version string.

        Reload-under-load guarantee (docs/RECOVERY.md): the (slow) load
        happens outside the predict lock, the swap is a single reference
        assignment under it, and a failed load leaves the prior version
        serving — so a sustained request hammer sees zero 5xx across a
        hot reload.  In-flight requests (including ones queued in the
        micro-batcher, which resolves the model at call time) drain onto
        whichever reference is current; nothing is cancelled or dropped.
        Concurrent reload() calls serialize on their own lock, never
        blocking the request path.
        """
        with self._reload_lock:
            vdir = latest_version_dir(self.base_dir)
            if vdir is None:
                # flat layout: base_dir IS the payload
                if os.path.exists(
                    os.path.join(self.base_dir, "model_spec.json")
                ):
                    vdir = self.base_dir
                else:
                    raise FileNotFoundError(
                        f"no model versions under {self.base_dir!r}"
                    )
            version = os.path.basename(vdir.rstrip("/"))
            if self._fleet is not None:
                # Fleet path: the version manager owns load-outside-lock,
                # the canary gate, swap, drain and eviction; it also
                # maintains serving_model_info.  A CanaryRefused
                # propagates — the prior version keeps serving.
                if version == self._fleet.active_version:
                    return version
                self._fleet.load_version(vdir)
                self._m_reloads.inc()
                return version
            if version == self._loaded_version:
                return version
            loaded = load_exported_model(vdir)
            with self._lock:
                prior = self._loaded_version
                self._loaded = loaded
                self._loaded_version = version
            if prior is not None:
                self._m_model_info.labels(self.model_name, prior).set(0)
            self._m_model_info.labels(self.model_name, version).set(1)
            self._m_reloads.inc()
            log.info("loaded %s version %s", self.model_name, version)
            return version

    # -------------------------------------------------- admission control

    def _admit(self, endpoint: str) -> None:
        """Admission check + in-flight accounting (pair with _release).

        The bound covers work already admitted (in-flight) plus work
        queued in the micro-batcher: past it, this request would only
        deepen the latency cliff, so it is refused NOW with a 429 the
        client can back off on — shed load is counted, never dropped
        silently."""
        with self._inflight_lock:
            if (
                endpoint == "generate"
                and self.max_queue_tokens > 0
                and self._fleet is not None
                and self._fleet.generative
            ):
                # Generative admission counts outstanding TOKENS, not
                # requests: a queued 500-token generation is 125x the
                # device work of a 4-token one, and the request count
                # hides exactly that.
                owed = self._fleet.outstanding_tokens()
                if owed >= self.max_queue_tokens:
                    self._m_shed.labels(endpoint).inc()
                    raise ServerOverloaded(
                        f"outstanding decode tokens {owed} >= bound "
                        f"{self.max_queue_tokens}"
                    )
            ctx = request_trace.current()
            depth = None
            if self.max_queue_depth > 0 or ctx is not None:
                depth = self._inflight
                if self._fleet is not None:
                    depth += self._fleet.queue_depth()
                elif self._batcher is not None:
                    depth += self._batcher._queue.qsize()
            if self.max_queue_depth > 0 and depth >= self.max_queue_depth:
                self._m_shed.labels(endpoint).inc()
                raise ServerOverloaded(
                    f"queue depth {depth} >= bound "
                    f"{self.max_queue_depth}"
                )
            self._inflight += 1
        if ctx is not None:
            # What admission saw when it let the request in: with a bad
            # p99, depth-at-admit distinguishes "queued behind a storm"
            # from "slow on an idle box" at a glance.
            ctx.instant(
                "admission", depth=depth, bound=self.max_queue_depth
            )

    def _release(self) -> None:
        with self._inflight_lock:
            self._inflight -= 1

    @property
    def version(self) -> Optional[str]:
        if self._fleet is not None:
            return self._fleet.active_version
        return self._loaded_version

    # ------------------------------------------------------------- predict

    def _current_loaded(self):
        if self._fleet is not None:
            return self._fleet.active_loaded()
        with self._lock:
            return self._loaded

    def _predict_fn(self):
        loaded = self._current_loaded()
        if loaded is None:
            raise RuntimeError("no model loaded")
        return loaded.predict if self.raw else loaded.predict_transformed

    def predict_batch(self, batch: Dict[str, Any]) -> np.ndarray:
        """Predict on a columnar feature batch — the shared entry for every
        surface (REST, gRPC, InfraValidator canaries), so all of them ride
        the same micro-batcher (or, in fleet mode, the latency-aware
        router's pick of replica batcher) and see hot-swaps at the same
        instant."""
        n_rows = len(next(iter(batch.values())))
        if self._fleet is not None:
            return self._fleet.submit(batch, n_rows)
        if self._batcher is not None:
            return self._batcher.submit(batch, n_rows)
        return np.asarray(self._predict_fn()(batch))

    @staticmethod
    def _payload_to_batch(payload: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """TF-Serving REST semantics: 'instances' (row) or 'inputs' (column);
        None for an empty instances list."""
        if "instances" in payload:
            rows = payload["instances"]
            if not rows:
                return None
            return {
                k: np.asarray([r[k] for r in rows])
                for k in rows[0]
            }
        if "inputs" in payload:
            return {k: np.asarray(v) for k, v in payload["inputs"].items()}
        raise ValueError("request needs 'instances' or 'inputs'")

    def predict(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        batch = self._payload_to_batch(payload)
        if batch is None:
            return {"predictions": []}
        return {"predictions": self.predict_batch(batch).tolist()}

    def _generate_fn(self):
        """The loaded model's generate callable; raises GenerateUnsupported
        (a ValueError) when this server/payload cannot decode — the typed
        contract the gRPC surface maps to FAILED_PRECONDITION."""
        loaded = self._current_loaded()
        if loaded is None:
            raise RuntimeError("no model loaded")
        if loaded.generate is None:
            raise GenerateUnsupported(
                f"model {self.model_name!r} does not support generate "
                "(exported module has no make_generate_step or legacy make_generate_fn)"
            )
        if not self.raw and loaded.transform is not None:
            # Same hazard bulk_inferrer.py rejects: loaded.generate applies
            # the embedded transform, so a raw=False server (callers send
            # already-materialized features) would double-tokenize.
            raise GenerateUnsupported(
                "generate requires raw features (server is raw=False but "
                "the payload embeds a transform)"
            )
        return loaded.generate

    def generate_batch(
        self,
        batch: Dict[str, Any],
        gen_params: Optional[Dict[str, Any]] = None,
    ) -> np.ndarray:
        """Seq2seq decoding on a columnar feature batch: the shared entry
        for REST :generate and gRPC Generate.

        ``model_type="generative"`` routes through the fleet's continuous-
        batching engine (serving/generative.py): each row joins the
        iteration-level scheduler as its own sequence and leaves at EOS —
        no whole-request batching, no replica pinned for the longest row.
        Otherwise the exported whole-request decode fn (make_generate_step)
        runs as before; ``gen_params`` is only meaningful on the engine
        path and rejected elsewhere (unknown-knob 4xx beats silence)."""
        if self._fleet is not None and self._fleet.generative:
            return self._fleet.generate_submit(batch, gen_params)
        if gen_params:
            raise ValueError(
                "generation params require a generative model type "
                f"(server model_type={self.model_type!r}); "
                f"got {sorted(gen_params)}"
            )
        return np.asarray(self._generate_fn()(batch))

    def generate(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        # Generation params ride next to instances/inputs: top-level
        # "params" dict ({"max_new_tokens": N}) — validated at SUBMIT time
        # (batching.validate_generation_params) so a malformed request is
        # a 400 to its caller, never a failure inside a shared decode step.
        gen_params = payload.get("params")
        if gen_params is not None and not isinstance(gen_params, dict):
            raise ValueError(
                f"'params' must be an object, got {type(gen_params).__name__}"
            )
        if self._fleet is None or not self._fleet.generative:
            # Capability check BEFORE payload parsing: an empty request
            # against a server that cannot generate must error, not 200 [].
            self._generate_fn()
        batch = self._payload_to_batch(payload)
        if batch is None:
            return {"outputs": []}
        return {"outputs": self.generate_batch(batch, gen_params).tolist()}

    # -------------------------------------------------------------- health

    def health(self) -> Dict[str, Any]:
        """The ``GET /healthz`` payload: liveness + which version serves.

        Healthy = a model is loaded and the batcher (when enabled) is
        accepting work; the probe never touches the device, so a slow
        model cannot fail the liveness check."""
        loaded = self._current_loaded() is not None
        version = self.version
        batcher_open = self._batcher is None or not self._batcher._closed
        if self._fleet is not None:
            batcher_open = not self._fleet.closed
        health = {
            "healthy": loaded and batcher_open and not self._stopped,
            "model": self.model_name,
            "version": version,
            "batching": self._batcher is not None or self._fleet is not None,
        }
        if self._fleet is not None:
            health["fleet"] = self._fleet.health()
        return health

    # ---------------------------------------------------------------- HTTP

    def start(self, port: int = 0, host: str = "127.0.0.1") -> int:
        """Serve in a background thread; returns the bound port."""
        if self._httpd is not None:
            raise RuntimeError(
                f"server for {self.model_name!r} already running on port "
                f"{self._httpd.server_address[1]}; call stop() first"
            )
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # route to logging, not stderr
                log.debug("http: " + fmt, *args)

            def _reply(
                self,
                code: int,
                obj: Dict[str, Any],
                endpoint: str = "",
                retry_after_s: int = 0,
            ) -> None:
                body = json.dumps(obj).encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                if retry_after_s > 0:
                    # 429/503 contract: the client is told WHEN to come
                    # back, so shed load decorrelates instead of
                    # instantly re-stampeding.
                    self.send_header("Retry-After", str(retry_after_s))
                ctx = getattr(self, "_trace_ctx", None)
                if ctx is not None:
                    # The caller gets the trace id back (and can hand it
                    # to support / grep the span log); this request's
                    # root span is the downstream parent.
                    self.send_header("traceparent", ctx.traceparent())
                    self._trace_code = code
                self.end_headers()
                self.wfile.write(body)
                if endpoint:
                    server._m_requests.labels(endpoint, code).inc()

            def do_GET(self):
                if self.path == "/metrics":
                    # Prometheus text exposition of this server's
                    # registry (request latencies, batcher depth, model
                    # info) — the scrape endpoint the cluster runner's
                    # prometheus.io annotations point at.  With request
                    # tracing on, exemplar comment lines link the
                    # latency histogram to the slowest request's trace
                    # id per scrape interval (comments are invisible to
                    # scrape parsers; with tracing off nothing is
                    # appended and the exposition is byte-identical).
                    if server._federated is not None:
                        try:
                            _fed.publish_registry(
                                server.metrics, source=server._fed_source
                            )
                        except OSError:
                            pass  # spool unwritable: still serve local
                        text = server._federated.to_prometheus()
                    else:
                        text = server.metrics.to_prometheus()
                    if server.request_tracer is not None:
                        text += server.request_tracer.exemplar_exposition()
                    body = text.encode("utf-8")
                    self.send_response(200)
                    self.send_header("Content-Type", CONTENT_TYPE_LATEST)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    server._m_requests.labels("metrics", 200).inc()
                elif self.path == "/healthz":
                    health = server.health()
                    self._reply(
                        200 if health["healthy"] else 503, health,
                        endpoint="healthz",
                    )
                elif self.path == f"/v1/models/{server.model_name}":
                    t0 = time.perf_counter()
                    self._reply(200, {
                        "model_version_status": [{
                            "version": server.version,
                            "state": "AVAILABLE",
                        }],
                    }, endpoint="status")
                    server._m_latency.labels("status").observe(
                        time.perf_counter() - t0
                    )
                else:
                    self._reply(
                        404, {"error": f"unknown path {self.path}"},
                        endpoint="other",
                    )

            def do_POST(self):
                routes = {
                    f"/v1/models/{server.model_name}:predict":
                        ("predict", server.predict),
                    f"/v1/models/{server.model_name}:generate":
                        ("generate", server.generate),
                    # Management op (Pusher push-URL hook, ops tooling):
                    # rescan base_dir and hot-swap to the newest version.
                    # Never admission-controlled — a full queue is exactly
                    # when an operator may need to roll the model.
                    f"/v1/models/{server.model_name}:reload":
                        ("reload", lambda _payload: {
                            "version": server.reload(),
                            "model": server.model_name,
                        }),
                }
                route = routes.get(self.path)
                if route is None:
                    self._reply(
                        404, {"error": f"unknown path {self.path}"},
                        endpoint="other",
                    )
                    return
                endpoint, handler = route
                t0 = time.perf_counter()
                admitted = False
                # Request trace root: the traceparent header joins an
                # existing distributed trace, absence starts one; the
                # head-sampling verdict is made HERE and inherited by
                # every downstream span.
                ctx = None
                trace_token = None
                if server.request_tracer is not None:
                    ctx = server.request_tracer.start(
                        endpoint, self.headers.get("traceparent")
                    )
                    if ctx is not None:
                        self._trace_ctx = ctx
                        self._trace_code = 0
                        trace_token = request_trace.push(ctx)
                try:
                    if endpoint != "reload":
                        server._admit(endpoint)
                        admitted = True
                    n = int(self.headers.get("Content-Length", "0"))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    self._reply(200, handler(payload), endpoint=endpoint)
                except ServerOverloaded as e:
                    # Load shed at the door: an explicit, retriable
                    # verdict — never a dropped connection or a 5xx.
                    self._reply(
                        429, {"error": f"overloaded: {e}"},
                        endpoint=endpoint,
                        retry_after_s=ServerOverloaded.retry_after_s,
                    )
                except Exception as e:
                    from tpu_pipelines.serving.generative import (
                        EngineOverloaded,
                        GenerationEvicted,
                    )

                    if isinstance(e, EngineOverloaded):
                        # Token-level admission control (the engine counts
                        # outstanding decode TOKENS): same shed contract
                        # as ServerOverloaded — 429 + Retry-After.
                        self._reply(
                            429, {"error": f"overloaded: {e}"},
                            endpoint=endpoint,
                            retry_after_s=EngineOverloaded.retry_after_s,
                        )
                        return
                    if isinstance(e, GenerationEvicted):
                        # The sequence lost its per-token SLO race (or the
                        # engine is shutting down): the server is healthy
                        # and a retry may land inside budget — retriable
                        # 503, never a 5xx-counted server fault.
                        self._reply(
                            503, {"error": f"evicted: {e}"},
                            endpoint=endpoint,
                            retry_after_s=ServerOverloaded.retry_after_s,
                        )
                        return
                    # Classified verdicts (the zero-5xx-under-reload
                    # guarantee depends on 5xx meaning SERVER fault, not
                    # "anything went wrong"): caller mistakes are 4xx,
                    # not-ready is a retriable 503, everything else is an
                    # honest 500.
                    from tpu_pipelines.serving.fleet.supervisor import (
                        FleetUnavailable,
                    )
                    from tpu_pipelines.serving.fleet.versions import (
                        CanaryRefused,
                    )

                    if isinstance(e, FleetUnavailable):
                        # Every replica is ejected or breaker-open:
                        # capacity is being rebuilt, so this is a
                        # structured retriable verdict, not a hang or an
                        # anonymous 500.
                        self._reply(
                            503, {"error": f"fleet unavailable: {e}"},
                            endpoint=endpoint,
                            retry_after_s=FleetUnavailable.retry_after_s,
                        )
                        return
                    if isinstance(e, CanaryRefused):
                        # The pushed payload failed the canary gate; the
                        # prior version keeps serving.  The server is
                        # healthy, so this is a conflict verdict on the
                        # push, not a 5xx.
                        code, retry = 409, 0
                    elif isinstance(
                        e, (ValueError, KeyError, TypeError)
                    ):
                        code, retry = 400, 0
                    elif "no model loaded" in str(e):
                        code, retry = 503, ServerOverloaded.retry_after_s
                    else:
                        code, retry = 500, 0
                        log.exception(
                            "%s: internal error serving %s",
                            server.model_name, endpoint,
                        )
                    self._reply(
                        code, {"error": f"{type(e).__name__}: {e}"},
                        endpoint=endpoint, retry_after_s=retry,
                    )
                finally:
                    if admitted:
                        server._release()
                    server._m_latency.labels(endpoint).observe(
                        time.perf_counter() - t0
                    )
                    if ctx is not None:
                        request_trace.pop(trace_token)
                        self._trace_ctx = None
                        ctx.finish(self._trace_code or 0)

        class Httpd(ThreadingHTTPServer):
            # socketserver's default listen backlog is 5; a concurrent-client
            # burst on a loaded host overflows it into connection resets.
            request_queue_size = 128
            daemon_threads = True

        self._httpd = Httpd((host, port), Handler)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        if self.slo_monitor is not None:
            self.slo_monitor.start(self._slo_interval_s)
        return self._httpd.server_address[1]

    def stop(self) -> None:
        self._stopped = True
        if self.slo_monitor is not None:
            self.slo_monitor.stop()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if self._batcher is not None:
            self._batcher.close()
            self._batcher = None
        if self._fleet is not None:
            # Parallel drain across every replica batcher: shutdown is
            # bounded by one timeout, not replicas x timeout.
            self._fleet.close()
            self._fleet = None
        if self.request_tracer is not None:
            self.request_tracer.close()
            self.request_tracer = None
