"""Live telemetry: metrics registry, Prometheus exposition, health
watchdogs, serving /metrics + /healthz, batcher close semantics,
goodput mirror retry, runner progress gauges + TPP_METRICS_PORT,
cluster scrape annotations, and `trace diff` (ISSUE 5).

Tier-1-safe (CPU-only, stub pipelines + one toy model export); select
alone with ``-m observability``.
"""

import json
import math
import os
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tpu_pipelines.observability.health import HealthMonitor
from tpu_pipelines.observability.metrics import (
    MetricsRegistry,
    default_registry,
    histogram_quantile,
    latency_buckets,
    start_http_server,
)

pytestmark = pytest.mark.observability


# ------------------------------------------------------------- helpers


def _parse_prom(text: str):
    """Minimal Prometheus text-format parser: {"<name>{labels}": value}
    plus a per-family TYPE map — enough to prove the exposition is
    well-formed and scrape-able."""
    samples = {}
    types = {}
    for line in text.splitlines():
        if not line or line.startswith("# HELP"):
            continue
        if line.startswith("# TYPE"):
            _, _, name, typ = line.split()
            types[name] = typ
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$", line)
        assert m, f"malformed exposition line: {line!r}"
        value = float("inf") if m.group(3) == "+Inf" else float(m.group(3))
        samples[f"{m.group(1)}{m.group(2) or ''}"] = value
    return samples, types


def _child_registry_snapshot(i):
    """Module-level (picklable) shard task: builds a PRIVATE registry in
    the (possibly forked) worker and ships its snapshot back."""
    reg = MetricsRegistry()
    reg.counter("shard_rows_total", "rows ingested").inc(10 * (i + 1))
    reg.histogram(
        "shard_seconds", "per-shard wall", buckets=[0.1, 1.0]
    ).observe(0.05 * (i + 1))
    reg.gauge("shard_last_index", "last index seen").set(i)
    return os.getpid(), reg.snapshot()


# ----------------------------------------------------- registry basics


def test_counter_gauge_labels_and_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "reqs", labels=("endpoint",))
    c.labels("predict").inc()
    c.labels(endpoint="predict").inc(2)
    c.labels("status").inc()
    assert c.labels("predict").get() == 3
    assert c.labels("status").get() == 1
    # Same name + same shape => same instrument (modules declare
    # independently); different type or labels => error.
    assert reg.counter("requests_total", labels=("endpoint",)) is c
    with pytest.raises(ValueError):
        reg.gauge("requests_total")
    with pytest.raises(ValueError):
        reg.counter("requests_total", labels=("other",))
    with pytest.raises(ValueError):
        c.labels("predict").inc(-1)  # counters only go up
    with pytest.raises(ValueError):
        c.inc()  # labels declared: must bind them
    g = reg.gauge("depth")
    g.set(5)
    g.inc(2)
    assert g.get() == 7
    g.set_function(lambda: 42)
    assert g.get() == 42


def test_histogram_bucket_correctness():
    bounds = [0.001, 0.01, 0.1, 1.0]
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "lat", buckets=bounds)
    # le is INCLUSIVE (Prometheus contract): a value on a bound lands in
    # that bucket; past the top bound lands only in +Inf.
    for v in (0.0005, 0.001, 0.005, 0.1, 0.5, 2.0, 3.0):
        h.observe(v)
    text = reg.to_prometheus()
    samples, types = _parse_prom(text)
    assert types["lat_seconds"] == "histogram"
    assert samples['lat_seconds_bucket{le="0.001"}'] == 2
    assert samples['lat_seconds_bucket{le="0.01"}'] == 3
    assert samples['lat_seconds_bucket{le="0.1"}'] == 4
    assert samples['lat_seconds_bucket{le="1"}'] == 5
    assert samples['lat_seconds_bucket{le="+Inf"}'] == 7
    assert samples["lat_seconds_count"] == 7
    assert abs(samples["lat_seconds_sum"] - 5.6065) < 1e-9
    # Quantile estimator: p50 of 7 obs lands in the (0.01, 0.1] bucket.
    series = reg.snapshot()["lat_seconds"]["series"][()]
    p50 = histogram_quantile(series, 0.5, bounds)
    assert 0.01 < p50 <= 0.1
    # Default ladder is fixed and log-spaced: constant ratio.
    lb = latency_buckets()
    ratios = {round(b / a, 6) for a, b in zip(lb, lb[1:])}
    assert ratios == {2.0}


def test_exposition_escapes_label_values():
    reg = MetricsRegistry()
    reg.counter("odd_total", "odd", labels=("path",)).labels(
        'a"b\\c\nd'
    ).inc()
    text = reg.to_prometheus()
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    # Still one well-formed sample line.
    samples, _ = _parse_prom(text)
    assert any(k.startswith("odd_total{") for k in samples)


def test_fork_pool_child_metrics_merge():
    """Shard-pool contract: children (forked processes when available)
    build private registries and return snapshots; the parent merges —
    counters/histograms add, gauges last-write-wins."""
    from tpu_pipelines.data.shard_plan import map_shards

    results = map_shards(_child_registry_snapshot, [0, 1, 2, 3], workers=2)
    merged = MetricsRegistry()
    for _, snap in results:
        merged.merge(snap)
    assert merged.counter("shard_rows_total").get() == 10 + 20 + 30 + 40
    hist = merged.snapshot()["shard_seconds"]["series"][()]
    assert hist["count"] == 4
    assert abs(hist["sum"] - 0.5) < 1e-9
    assert hist["buckets"] == [2, 2, 0]  # 0.05,0.10 <= 0.1 < 0.15,0.20
    assert merged.gauge("shard_last_index").get() in (0, 1, 2, 3)
    # Snapshots crossed a pickle boundary; under a real fork pool they
    # also crossed a process boundary.
    assert all(isinstance(pid, int) for pid, _ in results)


def test_start_http_server_scrape_and_health():
    reg = MetricsRegistry()
    reg.counter("pings_total").inc(3)
    state = {"healthy": True}
    srv = start_http_server(reg, health_fn=lambda: dict(state))
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics"
        ) as r:
            assert "version=0.0.4" in r.headers["Content-Type"]
            body = r.read().decode()
        samples, _ = _parse_prom(body)
        assert samples["pings_total"] == 3
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz"
        ) as r:
            assert json.load(r)["healthy"] is True
        state["healthy"] = False
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/healthz")
        assert e.value.code == 503
    finally:
        srv.close()
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=0.5
        )


# ------------------------------------------------------------ watchdogs


def test_watchdog_fires_on_synthetic_stall_and_rearms():
    fired = []
    reg = MetricsRegistry()
    mon = HealthMonitor(
        "t", stall_timeout_s=0.08,
        on_alert=lambda kind, detail: fired.append(kind),
        registry=reg,
    )
    try:
        mon.heartbeat(step=1)
        deadline = time.monotonic() + 5.0
        while "stall" not in fired and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fired.count("stall") == 1
        assert mon.status()["healthy"] is False
        assert mon.status()["stalled"] is True
        # Progress resumes -> re-armed and healthy again.
        mon.heartbeat(step=2)
        assert mon.status()["healthy"] is True
        c = reg.counter(
            "watchdog_alerts_total", labels=("monitor", "kind")
        )
        assert c.labels("t", "stall").get() == 1
    finally:
        mon.close()


def test_watchdog_fires_on_nan_and_loss_spike():
    fired = []
    mon = HealthMonitor(
        "t2", stall_timeout_s=0,
        on_alert=lambda kind, detail: fired.append((kind, detail)),
        loss_spike_factor=5.0, loss_window=4,
    )
    for step in range(4):
        mon.heartbeat(step=step, loss=1.0)
    assert fired == []
    mon.heartbeat(step=4, loss=50.0)  # > 5x trailing mean of 1.0
    assert [k for k, _ in fired] == ["loss_spike"]
    mon.heartbeat(step=5, loss=float("nan"))
    assert [k for k, _ in fired] == ["loss_spike", "nan"]
    st = mon.status()
    assert st["nan_seen"] is True and st["healthy"] is False
    assert len(st["alerts"]) == 2
    mon.close()  # no thread was ever started (stall_timeout_s=0)


def test_watchdog_alert_lands_in_run_trace(tmp_path):
    from tpu_pipelines.observability import (
        TraceRecorder,
        activate,
        read_events,
    )

    rec = TraceRecorder(str(tmp_path / "run"), "healthtest")
    mon = HealthMonitor("tr", stall_timeout_s=0)
    with activate(rec):
        mon.heartbeat(step=1, loss=float("nan"))
    rec.close()
    mon.close()
    events = read_events(rec.events_path)
    alert, = [e for e in events if e["name"] == "watchdog_alert"]
    assert alert["cat"] == "health"
    assert alert["args"]["kind"] == "nan"
    assert alert["args"]["monitor"] == "tr"


# ----------------------------------------------- train loop integration


def _tiny_iter(n=10_000, batch=8):
    rng = np.random.RandomState(0)
    while True:
        x = rng.randn(batch, 3).astype(np.float32)
        yield {"x": x, "y": (x @ np.ones((3, 1))).astype(np.float32)}


def test_train_loop_publishes_gauges_and_nan_watchdog():
    import jax.numpy as jnp
    import optax

    from tpu_pipelines.trainer.train_loop import (
        TrainLoopConfig,
        train_loop,
    )

    def loss_fn(params, b, rng):
        pred = b["x"] @ params["w"]
        return jnp.mean((pred - b["y"]) ** 2), {}

    def init_fn(rng, b):
        return {"w": jnp.zeros((3, 1), jnp.float32)}

    train_loop(
        loss_fn=loss_fn,
        init_params_fn=init_fn,
        optimizer=optax.sgd(0.01),
        train_iter=_tiny_iter(),
        config=TrainLoopConfig(
            train_steps=6, batch_size=8, log_every=2, stall_timeout_s=0,
        ),
    )
    reg = default_registry()
    assert reg.gauge("train_steps_total").get() == 6
    assert reg.gauge("train_examples_per_sec").get() > 0
    assert reg.gauge("train_step_seconds").get() > 0
    assert reg.gauge("train_host_input_wait_seconds_total").get() >= 0

    # NaN loss -> the watchdog fires through the configured callback.
    fired = []

    def nan_loss(params, b, rng):
        return (
            jnp.float32(float("nan")) + 0.0 * jnp.sum(params["w"]), {}
        )

    train_loop(
        loss_fn=nan_loss,
        init_params_fn=init_fn,
        optimizer=optax.sgd(0.01),
        train_iter=_tiny_iter(),
        config=TrainLoopConfig(
            train_steps=3, batch_size=8, log_every=1, stall_timeout_s=0,
            health_alert_cb=lambda kind, detail: fired.append(kind),
        ),
    )
    assert "nan" in fired


# -------------------------------------------------- goodput mirror retry


def test_goodput_tracker_drives_real_library_end_to_end():
    """Regression for constructor drift: the recorder and calculator are
    constructed against the REAL installed ml_goodput_measurement (keyword
    ``cloud_logger=``; reads return ``(entries, cursor)``), events are
    recorded, and ``summary()`` must come back non-empty.  Only a missing
    library may downgrade a run to the host-input-wait proxy — a
    mismatched constructor raises."""
    goodput_lib = pytest.importorskip("ml_goodput_measurement")
    del goodput_lib

    from tpu_pipelines.trainer.goodput import GoodputTracker

    t = GoodputTracker("goodput-regression-probe")
    # The whole point: construction against the real library succeeded.
    assert t.enabled

    t.job_start()
    t.tpu_init_start()
    time.sleep(0.02)
    t.tpu_init_end()
    t.training_prep_start()
    time.sleep(0.01)
    t.training_prep_end()
    t.step_start(0)
    time.sleep(0.02)
    t.step_start(1)
    time.sleep(0.02)
    t.job_end()

    s = t.summary()
    assert s, "summary() fell back to {} against the real library"
    assert 0.0 < s["goodput"] <= 1.0
    assert s["last_step"] == 1
    # The badput algebra ran: init + prep windows were attributed.
    assert "tpu_initialization" in s["badput"]
    assert "training_prep" in s["badput"]


def test_goodput_mirror_counts_failures_and_retries_once(tmp_path):
    import builtins

    from tpu_pipelines.trainer import goodput as goodput_mod

    counter = default_registry().counter("goodput_mirror_failures_total")
    base = counter.get()
    path = tmp_path / "g.jsonl"
    logger = goodput_mod.LocalEntryLogger(
        "job", jsonl_path=str(path), mirror_retry_backoff_s=0.05
    )
    entry = {"job_name": "job", "step": 1}

    calls = {"n": 0}
    real_open = builtins.open

    def failing_open(*args, **kwargs):
        calls["n"] += 1
        raise OSError("disk full")

    goodput_mod.open = failing_open
    try:
        logger.write_cloud_logging_entry(dict(entry))   # strike 1
        assert counter.get() == base + 1
        logger.write_cloud_logging_entry(dict(entry))   # backing off
        assert calls["n"] == 1  # no write attempted during backoff
        time.sleep(0.06)
        # Disk "recovers": the single post-backoff retry succeeds and the
        # mirror keeps mirroring (no permanent latch).
        goodput_mod.open = real_open
        logger.write_cloud_logging_entry(dict(entry))
        logger.write_cloud_logging_entry(dict(entry))
        assert len(path.read_text().splitlines()) == 2
        # A NEW failure episode gets its own backoff + single retry; a
        # second strike after the backoff latches the mirror off.
        goodput_mod.open = failing_open
        logger.write_cloud_logging_entry(dict(entry))   # strike 1 (ep. 2)
        time.sleep(0.06)
        logger.write_cloud_logging_entry(dict(entry))   # strike 2: dead
        assert counter.get() == base + 3
        goodput_mod.open = real_open
        logger.write_cloud_logging_entry(dict(entry))   # dead: no write
        assert len(path.read_text().splitlines()) == 2
        # Every entry stayed in memory regardless of mirror state.
        entries, last = logger.read_cloud_logging_entries()
        assert len(entries) == 7
        # Incremental read from the returned cursor: nothing new.
        assert logger.read_cloud_logging_entries(
            last_entry_info=last
        ) == ([], (None, None))
    finally:
        if hasattr(goodput_mod, "open"):
            del goodput_mod.open


# -------------------------------------------------------------- serving


def _toy_module(tmp_path):
    mod = tmp_path / "toy_model.py"
    mod.write_text(
        "import jax.numpy as jnp\n"
        "def build_model(hp):\n"
        "    return None\n"
        "def apply_fn(model, params, batch):\n"
        "    return jnp.asarray(batch['x'], jnp.float32) @ params['w']\n"
    )
    return str(mod)


def test_server_metrics_healthz_under_concurrent_load(tmp_path):
    """The acceptance hammer: concurrent predicts + concurrent /metrics
    and /healthz scrapes; the final scrape parses as Prometheus text and
    its request-latency histogram accounts for every predict."""
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.trainer.export import export_model

    export_model(
        serving_model_dir=str(tmp_path / "m" / "1"),
        params={"w": np.eye(3, 2).astype(np.float32)},
        module_file=_toy_module(tmp_path),
    )
    server = ModelServer(
        "toy", str(tmp_path / "m"), batching=True, max_batch_size=8,
        batch_timeout_s=0.001,
    )
    port = server.start()
    url = f"http://127.0.0.1:{port}"
    body = json.dumps({"instances": [{"x": [1.0, 0.0, 0.0]}]}).encode()
    n_per_thread, n_threads = 10, 4
    errors = []

    def predict_loop():
        for _ in range(n_per_thread):
            try:
                req = urllib.request.Request(
                    f"{url}/v1/models/toy:predict", data=body
                )
                with urllib.request.urlopen(req, timeout=30) as r:
                    assert json.load(r)["predictions"] == [[1.0, 0.0]]
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

    def scrape_loop():
        for _ in range(n_per_thread):
            try:
                with urllib.request.urlopen(
                    f"{url}/metrics", timeout=30
                ) as r:
                    _parse_prom(r.read().decode())  # must always parse
                with urllib.request.urlopen(
                    f"{url}/healthz", timeout=30
                ) as r:
                    assert json.load(r)["healthy"] is True
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

    try:
        threads = [
            threading.Thread(target=predict_loop) for _ in range(n_threads)
        ] + [threading.Thread(target=scrape_loop) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        with urllib.request.urlopen(f"{url}/metrics") as r:
            assert "version=0.0.4" in r.headers["Content-Type"]
            text = r.read().decode()
        samples, types = _parse_prom(text)
        total = n_per_thread * n_threads
        # Request-latency histogram: scraped, parsed, and complete.
        assert types["serving_request_latency_seconds"] == "histogram"
        key = 'serving_request_latency_seconds_count{endpoint="predict"}'
        assert samples[key] == total
        assert (
            samples[
                'serving_request_latency_seconds_bucket'
                '{endpoint="predict",le="+Inf"}'
            ]
            == total
        )
        assert (
            samples['serving_request_latency_seconds_sum'
                    '{endpoint="predict"}'] > 0
        )
        assert (
            samples['serving_requests_total'
                    '{endpoint="predict",code="200"}'] == total
        )
        # Batcher telemetry: every request went through the micro-batcher.
        assert samples["serving_batched_requests_total"] == total
        assert 1 <= samples["serving_batches_total"] <= total
        assert samples["serving_batcher_queue_depth"] >= 0
        # Model info metric marks the served version.
        assert samples[
            'serving_model_info{model="toy",version="1"}'
        ] == 1
        assert samples["serving_model_reloads_total"] == 1
    finally:
        server.stop()
    # Stopped server: healthz reports unhealthy via the in-process view.
    assert server.health()["healthy"] is False


def test_batcher_close_rejects_and_unblocks_inflight():
    """The close()/submit() race regression test: a wedged predict_fn
    must not leave submit() callers hanging, and late submits fail with
    a clear error instead of landing in a dead queue."""
    from tpu_pipelines.serving.batching import RequestBatcher

    release = threading.Event()
    entered = threading.Event()

    def wedged_predict(batch):
        entered.set()
        release.wait(timeout=30)
        return np.zeros((len(next(iter(batch.values()))), 1))

    b = RequestBatcher(wedged_predict, max_batch_size=4,
                       batch_timeout_s=0.001)
    out = {}

    def submit_one(key):
        try:
            b.submit({"x": np.zeros((1, 2))}, 1, timeout_s=30)
            out[key] = "ok"
        except RuntimeError as e:
            out[key] = f"error: {e}"

    t1 = threading.Thread(target=submit_one, args=("inflight",))
    t1.start()
    assert entered.wait(timeout=5)  # the request is inside predict_fn
    # A second request is parked in the queue behind the wedged batch.
    t2 = threading.Thread(target=submit_one, args=("queued",))
    t2.start()
    time.sleep(0.05)
    t_close0 = time.monotonic()
    b.close(timeout_s=0.2)
    close_s = time.monotonic() - t_close0
    t1.join(timeout=5)
    t2.join(timeout=5)
    assert not t1.is_alive() and not t2.is_alive()
    # Both callers got errors promptly — nobody waited out the 30s
    # submit timeout.
    assert out["inflight"].startswith("error:"), out
    assert out["queued"].startswith("error:"), out
    assert close_s < 5
    # Late submit: clear, immediate rejection.
    with pytest.raises(RuntimeError, match="closed"):
        b.submit({"x": np.zeros((1, 2))}, 1)
    release.set()  # the wedged worker drains without raising


def test_batcher_close_serves_prior_submits():
    """Requests enqueued before close() (with a responsive predict_fn)
    complete normally: close drains, it does not drop."""
    from tpu_pipelines.serving.batching import RequestBatcher

    b = RequestBatcher(
        lambda batch: np.asarray(batch["x"]).sum(axis=1, keepdims=True),
        max_batch_size=8, batch_timeout_s=0.001,
    )
    results = []
    threads = [
        threading.Thread(
            target=lambda i=i: results.append(
                float(b.submit({"x": np.full((1, 2), i)}, 1)[0, 0])
            )
        )
        for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    b.close()
    assert sorted(results) == [0.0, 2.0, 4.0, 6.0]
    assert b.requests_served == 4


# ------------------------------------------------- runner telemetry


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_runner_progress_gauges_and_metrics_port(tmp_path):
    """TPP_METRICS_PORT: the runner serves /metrics + /healthz for the
    duration of the run (proved by a component scraping it mid-run),
    updates run-progress gauges, and tears the listener down at run
    end."""
    from tpu_pipelines.dsl.component import component
    from tpu_pipelines.dsl.pipeline import Pipeline
    from tpu_pipelines.orchestration import LocalDagRunner

    port = _free_port()

    @component(inputs={}, outputs={"examples": "Examples"}, name="Scraper")
    def Scraper(ctx):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            text = r.read().decode()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ) as r:
            health = json.load(r)
        with open(os.path.join(ctx.output("examples").uri, "scrape.txt"),
                  "w") as f:
            f.write(text)
        assert health["healthy"] is True
        assert health["run_id"]

    p = Pipeline(
        "scrapeme", [Scraper()],
        pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    os.environ["TPP_METRICS_PORT"] = str(port)
    try:
        result = LocalDagRunner().run(p)
    finally:
        os.environ.pop("TPP_METRICS_PORT", None)
    assert result.succeeded
    scrape = open(
        os.path.join(
            result.nodes["Scraper"].outputs["examples"][0].uri,
            "scrape.txt",
        )
    ).read()
    samples, _ = _parse_prom(scrape)
    # Mid-run view: this node was running, nothing settled yet.
    assert samples["pipeline_nodes_running"] == 1
    assert samples["pipeline_nodes_pending"] == 0
    assert any(
        k.startswith("pipeline_run_info{") and "scrapeme" in k
        for k in samples
    )
    # Post-run: gauges settled, heartbeat + dispatch recorded.
    reg = default_registry()
    assert reg.gauge("pipeline_nodes_done").get() == 1
    assert reg.gauge("pipeline_nodes_failed").get() == 0
    assert reg.gauge("pipeline_nodes_running").get() == 0
    assert (
        reg.counter(
            "pipeline_node_dispatch_total", labels=("node",)
        ).labels("Scraper").get()
        >= 1
    )
    # The listener died with the run.
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=0.5
        )


def test_runner_failed_nodes_gauge(tmp_path):
    from tpu_pipelines.dsl.component import component
    from tpu_pipelines.dsl.pipeline import Pipeline
    from tpu_pipelines.orchestration import LocalDagRunner

    @component(inputs={}, outputs={"examples": "Examples"}, name="Boom")
    def Boom(ctx):
        raise RuntimeError("kaboom")

    p = Pipeline(
        "boomp", [Boom()],
        pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    result = LocalDagRunner().run(p, raise_on_failure=False)
    assert not result.succeeded
    assert default_registry().gauge("pipeline_nodes_failed").get() == 1


# ---------------------------------------------- cluster scrape config


def test_cluster_runner_prometheus_scrape_annotations(tmp_path):
    import yaml

    from tpu_pipelines.orchestration.cluster_runner import (
        TPUJobRunner,
        TPUJobRunnerConfig,
    )
    from examples.taxi.pipeline import create_pipeline

    pipeline = create_pipeline(str(tmp_path / "home"))
    out = TPUJobRunner(TPUJobRunnerConfig(
        image="img:1", pipeline_module="examples/taxi/pipeline.py",
        output_dir=str(tmp_path / "manifests"), metrics_port=9090,
    )).run(pipeline)
    with open(out["workflow"]) as f:
        wf = yaml.safe_load(f)
    container_tpls = [
        t for t in wf["spec"]["templates"] if "container" in t
    ]
    assert container_tpls
    for tpl in container_tpls:
        ann = tpl["metadata"]["annotations"]
        assert ann["prometheus.io/scrape"] == "true"
        assert ann["prometheus.io/port"] == "9090"
        assert ann["prometheus.io/path"] == "/metrics"
        env = {e["name"]: e["value"] for e in tpl["container"]["env"]}
        assert env["TPP_METRICS_PORT"] == "9090"
    # Default (metrics_port=0): no annotations, no env — manifests
    # unchanged for operators who didn't opt in.
    out2 = TPUJobRunner(TPUJobRunnerConfig(
        image="img:1", pipeline_module="examples/taxi/pipeline.py",
        output_dir=str(tmp_path / "manifests0"),
    )).run(pipeline)
    with open(out2["workflow"]) as f:
        wf0 = yaml.safe_load(f)
    for tpl in wf0["spec"]["templates"]:
        ann = (tpl.get("metadata") or {}).get("annotations") or {}
        assert "prometheus.io/scrape" not in ann
        for e in (tpl.get("container") or {}).get("env") or []:
            assert e["name"] != "TPP_METRICS_PORT"


# ------------------------------------------------------- trace diff


def _sleep_pipeline(tmp_path, sleep_s):
    from tpu_pipelines.dsl.component import component
    from tpu_pipelines.dsl.pipeline import Pipeline

    @component(inputs={}, outputs={"examples": "Examples"}, name="Gen")
    def Gen(ctx):
        time.sleep(sleep_s)
        with open(os.path.join(ctx.output("examples").uri, "d.txt"),
                  "w") as f:
            f.write("x")

    @component(
        inputs={"examples": "Examples"}, outputs={"model": "Model"},
        name="Train",
    )
    def Train(ctx):
        time.sleep(sleep_s)
        with open(os.path.join(ctx.output("model").uri, "m.txt"),
                  "w") as f:
            f.write("m")

    gen = Gen()
    return Pipeline(
        "diffp", [gen, Train(examples=gen.outputs["examples"])],
        pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
        enable_cache=False,
    )


def test_trace_diff_cli_on_two_recorded_runs(tmp_path, capsys):
    from tpu_pipelines.__main__ import main
    from tpu_pipelines.orchestration import LocalDagRunner

    fast = LocalDagRunner().run(_sleep_pipeline(tmp_path, 0.01))
    slow = LocalDagRunner().run(_sleep_pipeline(tmp_path, 0.35))
    root = str(tmp_path / "root")

    # Regression direction: fast -> slow trips the threshold, exit 3.
    rc = main(["trace", "diff", fast.run_id, slow.run_id,
               "--pipeline-root", root])
    assert rc == 3
    text = capsys.readouterr().out
    assert "REGRESSED" in text and "Train" in text

    # Self-diff: clean, exit 0.
    assert main(["trace", "diff", fast.run_id, fast.run_id,
                 "--pipeline-root", root]) == 0
    assert "no regressions" in capsys.readouterr().out

    # Improvement direction (slow -> fast) is not a regression.
    assert main(["trace", "diff", slow.run_id, fast.run_id,
                 "--pipeline-root", root]) == 0
    capsys.readouterr()

    # --json: machine-readable, same verdict, per-node deltas present.
    rc = main(["trace", "diff", fast.run_id, slow.run_id,
               "--pipeline-root", root, "--json"])
    assert rc == 3
    diff = json.loads(capsys.readouterr().out)
    assert diff["run_a"] == fast.run_id and diff["run_b"] == slow.run_id
    assert "Gen.wall_s" in diff["regression_flags"]
    assert "Train.wall_s" in diff["regression_flags"]
    assert diff["per_node"]["Train"]["regressed"] is True
    assert diff["critical_path_delta_s"] > 0

    # A huge threshold silences the flags (and the exit code).
    assert main(["trace", "diff", fast.run_id, slow.run_id,
                 "--pipeline-root", root, "--threshold", "1000"]) == 0
    capsys.readouterr()

    # Unknown run id: error exit, stderr message.
    assert main(["trace", "diff", fast.run_id, "nope",
                 "--pipeline-root", root]) == 1
    assert "no trace event log" in capsys.readouterr().err


def test_trace_diff_formats_zero_baseline_regression(tmp_path):
    """compiles_after_warm 0 -> N has no defined fraction (rel to a zero
    baseline); format_diff must render the absolute move, not crash on
    ``None.__format__`` — found live on the first real 0 -> 10 diff."""
    from tpu_pipelines.observability.export import diff_metrics, format_diff

    base = {
        "per_node": {}, "critical_path_measured_s": 1.0,
        "train_telemetry": {
            "window_phase_seconds": {"infeed_wait": 0.1, "host": 0.9},
            "compiles_after_warm": 0,
        },
    }
    cand = {
        "per_node": {}, "critical_path_measured_s": 1.0,
        "train_telemetry": {
            "window_phase_seconds": {"infeed_wait": 0.1, "host": 0.9},
            "compiles_after_warm": 10,
        },
    }
    diff = diff_metrics(base, cand)
    assert "train_telemetry.compiles_after_warm" in diff["regression_flags"]
    text = format_diff(diff)
    assert "compiles_after_warm 0 -> 10" in text
    assert "(0.0 -> 10.0)" in text


def test_trace_latest_skips_cross_run_metrics_dir(tmp_path, capsys):
    """`.runs/_metrics` (the durable snapshot ring) is newer than every
    run dir the moment a ring snapshot lands — `trace latest` must never
    resolve it as a run (found live: the very first post-ring scrape)."""
    from tpu_pipelines.__main__ import main
    from tpu_pipelines.orchestration import LocalDagRunner

    result = LocalDagRunner().run(_sleep_pipeline(tmp_path, 0.01))
    root = str(tmp_path / "root")
    ring = os.path.join(root, ".runs", "_metrics", result.run_id)
    os.makedirs(ring)
    with open(os.path.join(ring, "snap-00000000.json"), "w") as f:
        f.write("{}")

    assert main(["trace", "latest", "--pipeline-root", root]) == 0
    out = capsys.readouterr().out
    assert result.run_id in out
    assert "_metrics" not in out


def test_trace_and_inspect_runs_json_flags(tmp_path, capsys):
    from tpu_pipelines.__main__ import main
    from tpu_pipelines.orchestration import LocalDagRunner

    result = LocalDagRunner().run(_sleep_pipeline(tmp_path, 0.01))
    root = str(tmp_path / "root")

    assert main(["trace", result.run_id, "--pipeline-root", root,
                 "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["run_id"] == result.run_id
    assert payload["per_node"]["Train"]["status"] == "COMPLETE"
    assert payload["critical_path_nodes"] == ["Gen", "Train"]

    assert main([
        "inspect", "runs", "diffp",
        "--metadata", str(tmp_path / "md.sqlite"),
        "--pipeline-root", root, "--json",
    ]) == 0
    listing = json.loads(capsys.readouterr().out)
    assert listing["pipeline"] == "diffp"
    run, = listing["runs"]
    assert run["run_id"] == result.run_id
    nodes = {n["node"]: n for n in run["nodes"]}
    assert nodes["Gen"]["state"] == "COMPLETE"
    # Trace-derived queue-wait column rides along in JSON mode too.
    assert "trace" in nodes["Gen"]
    assert math.isfinite(nodes["Gen"]["trace"]["queue_wait_s"])
