"""Serving fleet (ISSUE 10): versioned hot-swap, routing, SLO batching.

Tier-1-safe: every test runs on a stub "loaded model" (the version
manager's ``loader`` seam / a monkeypatched default loader), so the suite
exercises the real fleet machinery — version leases, canary gate, router,
per-replica batchers, the full REST surface — without exporting or
jit-compiling a model.  The heavyweight exported-payload paths stay in
tests/test_serving.py (slow).
"""

import json
import os
import re
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

pytestmark = pytest.mark.observability


class FakeLoaded:
    """Stands in for trainer.export.LoadedModel: predict scales the 'x'
    feature by the payload's recorded scale (NaN payloads model a broken
    export the canary must catch)."""

    def __init__(self, scale, delay_s=0.0):
        self.scale = scale
        self.delay_s = delay_s
        self.generate = None
        self.transform = None

    def predict(self, batch):
        if self.delay_s:
            time.sleep(self.delay_s)
        return np.asarray(batch["x"], np.float64) * self.scale

    predict_transformed = predict


def _fake_payload(base, version, scale):
    vdir = base / str(version)
    vdir.mkdir(parents=True)
    (vdir / "scale.txt").write_text(str(scale))
    return str(vdir)


def _fake_loader(version_dir):
    with open(os.path.join(version_dir, "scale.txt")) as f:
        return FakeLoaded(float(f.read()))


@pytest.fixture
def fake_loader(monkeypatch):
    monkeypatch.setattr(
        "tpu_pipelines.serving.fleet.versions._default_loader", _fake_loader
    )
    # Single-server fallback path (server.py binds the name at import).
    monkeypatch.setattr(
        "tpu_pipelines.serving.server.load_exported_model", _fake_loader
    )
    return _fake_loader


# ----------------------------------------------------- ModelVersionManager


def test_version_manager_swap_resident_and_rollback(tmp_path):
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.fleet import ModelVersionManager

    reg = MetricsRegistry()
    mgr = ModelVersionManager(
        "m", max_versions=2, loader=_fake_loader, registry=reg
    )
    d1 = _fake_payload(tmp_path, 1, 1.0)
    d2 = _fake_payload(tmp_path, 2, 2.0)
    d3 = _fake_payload(tmp_path, 3, 3.0)

    assert mgr.load_version(d1) == "1"
    assert mgr.active_version == "1"
    assert mgr.load_version(d2) == "2"
    # Both versions resident: instant rollback without a disk read.
    assert mgr.resident_versions() == ["1", "2"]
    assert mgr.active_loaded().scale == 2.0
    loads_before = []
    mgr2_loader_calls = loads_before  # rollback must not call the loader
    assert mgr.activate("1") == "1"
    assert mgr.active_loaded().scale == 1.0
    assert mgr2_loader_calls == []

    # Beyond max_versions the oldest non-active drains out immediately
    # (no leases held).
    mgr.activate("2")
    assert mgr.load_version(d3) == "3"
    assert mgr.resident_versions() == ["2", "3"]
    assert reg.get("serving_version_evictions_total").get() == 1
    assert reg.get("serving_versions_resident").get() == 2
    # Swaps: 1, 2, rollback 1, 2 again, 3.
    assert reg.get("serving_version_swaps_total").get() == 5
    # An evicted version cannot be activated (it is gone).
    with pytest.raises(KeyError):
        mgr.activate("1")


def test_version_manager_drains_before_evicting(tmp_path):
    from tpu_pipelines.serving.fleet import ModelVersionManager

    mgr = ModelVersionManager("m", max_versions=1, loader=_fake_loader)
    d1 = _fake_payload(tmp_path, 1, 1.0)
    d2 = _fake_payload(tmp_path, 2, 2.0)
    mgr.load_version(d1)

    with mgr.lease() as (version, loaded):
        assert (version, loaded.scale) == ("1", 1.0)
        # Hot-swap WHILE a request is in flight on v1: the lease pins it.
        mgr.load_version(d2)
        assert mgr.active_version == "2"
        assert mgr.lease_count("1") == 1
        assert "1" in mgr._versions  # still resident: draining, not dead
        assert mgr.resident_versions() == ["2"]  # but no longer offered
        # New leases land on the new active version immediately.
        with mgr.lease() as (v2, l2):
            assert (v2, l2.scale) == ("2", 2.0)
    # Last lease released -> the drained version is evicted.
    assert "1" not in mgr._versions
    assert mgr.lease_count("1") == 0


def test_version_manager_canary_gate(tmp_path):
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.fleet import CanaryRefused, ModelVersionManager

    def canary(loaded, version):
        from tpu_pipelines.components.infra_validator import canary_check

        return canary_check(
            loaded.predict, {"x": np.asarray([1.0, 2.0])}
        )

    reg = MetricsRegistry()
    mgr = ModelVersionManager(
        "m", max_versions=2, loader=_fake_loader, canary_fn=canary,
        registry=reg,
    )
    mgr.load_version(_fake_payload(tmp_path, 1, 1.0))
    bad = _fake_payload(tmp_path, 2, float("nan"))
    with pytest.raises(CanaryRefused, match="non-finite"):
        mgr.load_version(bad)
    # The refused version changed NOTHING about the serving state.
    assert mgr.active_version == "1"
    assert mgr.resident_versions() == ["1"]
    assert reg.get("serving_canary_failures_total").get() == 1


# ------------------------------------------------------- SLO batch window


def test_slo_gather_window_math():
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.batching import RequestBatcher

    reg = MetricsRegistry()
    b = RequestBatcher(
        lambda batch: np.asarray(batch["x"]),
        max_batch_size=8, batch_timeout_s=0.005, slo_p99_s=0.2,
        registry=reg,
    )
    frac = b.SLO_WINDOW_FRAC       # spendable share of the p99 budget
    steps = b.SLO_STEP_BUDGET      # step times reserved (own + in-flight)
    try:
        # Before any observed step the fixed window applies (fallback).
        assert b.gather_window_s() == pytest.approx(0.005)
        # First observation seeds the EWMA exactly:
        # window = slo*frac - steps*step.
        b._observe_step(0.02)
        assert b.gather_window_s() == pytest.approx(
            0.2 * frac - steps * 0.02
        )
        # The window tracks the EWMA as the step drifts.
        for _ in range(50):
            b._observe_step(0.03)
        assert b._step_ewma_s == pytest.approx(0.03, abs=1e-3)
        assert b.gather_window_s() == pytest.approx(
            0.2 * frac - steps * 0.03, abs=3e-3
        )
        # Steps consume the whole spendable budget -> immediate dispatch,
        # never negative.
        for _ in range(50):
            b._observe_step(0.15)
        assert b.gather_window_s() == 0.0
        # Telemetry: the effective deadline and step EWMA are scrapeable.
        assert reg.get("serving_batch_deadline_seconds").get() == 0.0
        assert reg.get("serving_model_step_seconds").get() == pytest.approx(
            0.15, abs=5e-3
        )
    finally:
        b.close()

    # Unconfigured SLO: fixed window regardless of observed steps.
    b2 = RequestBatcher(
        lambda batch: np.asarray(batch["x"]),
        max_batch_size=8, batch_timeout_s=0.004,
    )
    try:
        b2._observe_step(0.05)
        assert b2.gather_window_s() == pytest.approx(0.004)
    finally:
        b2.close()


def test_slo_batcher_serves_correctly_end_to_end():
    """Functional: results stay row-correct when the SLO window governs
    the gather loop (the deadline changes WHEN batches close, never what
    they return)."""
    from tpu_pipelines.serving.batching import RequestBatcher

    b = RequestBatcher(
        lambda batch: np.asarray(batch["x"]) * 2.0,
        max_batch_size=8, batch_timeout_s=0.005, slo_p99_s=0.05,
    )
    try:
        with ThreadPoolExecutor(max_workers=6) as pool:
            futs = [
                pool.submit(
                    b.submit, {"x": np.full((2, 3), float(i))}, 2
                )
                for i in range(12)
            ]
            for i, f in enumerate(futs):
                np.testing.assert_allclose(
                    f.result(timeout=30), np.full((2, 3), 2.0 * i)
                )
        assert b._step_ewma_s is not None  # SLO mode engaged
    finally:
        b.close()


# ------------------------------------------------------ parallel shutdown


def test_replica_pool_close_drains_in_parallel():
    """Fleet shutdown is bounded by ONE close timeout, not replicas x
    timeout: every batcher gets the close sentinel before any join."""
    from tpu_pipelines.serving.fleet import Replica, ReplicaPool

    release = threading.Event()

    def wedged(batch):
        release.wait(10)
        return np.asarray(batch["x"])

    replicas = [
        Replica(i, wedged, max_batch_size=2, batch_timeout_s=0.001)
        for i in range(3)
    ]
    pool = ReplicaPool(replicas)
    with ThreadPoolExecutor(max_workers=3) as tp:
        futs = [
            tp.submit(r.submit, {"x": np.ones((1, 2))}, 1, 30.0)
            for r in replicas
        ]
        time.sleep(0.2)  # let every replica wedge inside predict_fn
        t0 = time.monotonic()
        pool.close(timeout_s=1.0)
        wall = time.monotonic() - t0
        # Serial joins would cost ~3 x 1.0 s; the shared deadline keeps
        # the whole drain within ~one timeout (+ margin for CI noise).
        assert wall < 2.0, f"close took {wall:.2f}s — drained serially?"
        # The wedged in-flight futures were failed, not left hanging.
        for f in futs:
            with pytest.raises(RuntimeError, match="closed"):
                f.result(timeout=10)
        release.set()
    assert pool.closed


# ------------------------------------------------------ latency-aware routing


def test_router_redirects_around_slow_replica():
    """One artificially slow replica must not absorb new traffic: the
    router's cost estimate (queue depth x EWMA p99) diverges after the
    first slow observations and traffic concentrates on the fast
    replica, keeping overall latency bounded."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.fleet import Replica, ReplicaPool

    reg = MetricsRegistry()
    SLOW, FAST = 0.12, 0.003

    def slow_fn(batch):
        time.sleep(SLOW)
        return np.asarray(batch["x"])

    def fast_fn(batch):
        time.sleep(FAST)
        return np.asarray(batch["x"])

    slow = Replica(0, slow_fn, max_batch_size=4, batch_timeout_s=0.001,
                   registry=reg)
    fast = Replica(1, fast_fn, max_batch_size=4, batch_timeout_s=0.001,
                   registry=reg)
    pool = ReplicaPool([slow, fast])
    latencies = []
    lat_lock = threading.Lock()
    try:
        def call(i):
            t0 = time.perf_counter()
            out = pool.submit({"x": np.full((1, 2), float(i))}, 1)
            with lat_lock:
                latencies.append(time.perf_counter() - t0)
            return out

        with ThreadPoolExecutor(max_workers=4) as tp:
            list(tp.map(call, range(40)))
    finally:
        pool.close()

    total = slow.latency.count + fast.latency.count
    assert total == 40
    # The slow replica got probed, then shed: the fast replica serves the
    # overwhelming majority.
    assert fast.latency.count >= 3 * slow.latency.count, (
        slow.latency.count, fast.latency.count,
    )
    # Per-replica p99 gauges diverge (the operator-visible skew signal).
    p99 = reg.get("serving_replica_p99_seconds")
    assert p99.labels("0").get() >= SLOW * 0.8
    assert p99.labels("1").get() < SLOW * 0.5
    # Overall tail stays bounded: the router pays the slow replica a few
    # probes, not a steady share.  (p50 well under the slow step; and no
    # more than a handful of requests ever saw it.)
    latencies.sort()
    assert latencies[len(latencies) // 2] < SLOW
    assert sum(1 for d in latencies if d >= SLOW) <= slow.latency.count + 2


# ------------------------------------------------- ModelServer fleet mode


def _post(url, body=b"{}", timeout=30):
    req = urllib.request.Request(url, data=body)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_fleet_server_hot_swap_under_load_zero_5xx(tmp_path, fake_loader):
    """Acceptance (ISSUE 10): a multi-thread REST hammer runs across a
    blessed-version hot-swap on a 2-replica fleet; judged from the
    server's OWN /metrics scrape there are zero 5xx, the new version is
    active, and per-replica series exist."""
    from tpu_pipelines.serving import ModelServer

    base = tmp_path / "m"
    _fake_payload(base, 1, 1.0)
    server = ModelServer(
        "toy", str(base), replicas=2, max_versions=2, slo_p99_ms=25.0,
        max_batch_size=8, batch_timeout_s=0.002,
    )
    assert server._fleet is not None
    port = server.start()
    url = f"http://127.0.0.1:{port}/v1/models/toy:predict"
    body = json.dumps({"inputs": {"x": [[1.0, 2.0]]}}).encode()
    errors = []

    def fire(n):
        for _ in range(n):
            try:
                status, _ = _post(url, body)
                if status != 200:
                    errors.append(status)
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

    try:
        fire(2)  # warm-up; also captures the fleet's canary batch
        threads = [threading.Thread(target=fire, args=(25,))
                   for _ in range(3)]
        for t in threads:
            t.start()
        # Mid-storm: push v2 and notify — the reload surface the Pusher
        # hook hits.  Load happens outside the serving locks; swap is
        # atomic; v1 drains.
        _fake_payload(base, 2, 2.0)
        status, reload_reply = _post(
            f"http://127.0.0.1:{port}/v1/models/toy:reload"
        )
        assert (status, reload_reply["version"]) == (200, "2")
        for t in threads:
            t.join()
        assert errors == []

        # Post-swap requests answer with the new version's weights.
        _, out = _post(url, body)
        np.testing.assert_allclose(out["predictions"], [[2.0, 4.0]])

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

    # Zero 5xx across the hot-swap, from the fleet's own scrape.
    assert not re.search(r'serving_requests_total\{[^}]*code="5', scrape)
    # The swap is visible in the scrape: v2 active (1), v1 demoted (0).
    assert 'serving_model_info{model="toy",version="2"} 1' in scrape
    assert 'serving_model_info{model="toy",version="1"} 0' in scrape
    assert "serving_version_swaps_total 2" in scrape
    # Per-replica telemetry exists for both replicas and accounts for
    # every request.
    per_replica = {
        m.group(1): float(m.group(2))
        for m in re.finditer(
            r'serving_replica_requests_total\{replica="(\d+)"\} (\S+)',
            scrape,
        )
    }
    assert set(per_replica) == {"0", "1"}
    assert sum(per_replica.values()) >= 77  # warmup + hammer + post-swap
    # SLO batching engaged: the per-replica deadline gauges are live.
    assert 'serving_replica_batch_deadline_seconds{replica="0"}' in scrape
    assert health["healthy"] is True
    assert health["fleet"]["replicas"] == 2
    assert health["fleet"]["active_version"] == "2"


def test_fleet_canary_refuses_bad_push_with_409(tmp_path, fake_loader):
    """A pushed version whose predictions are non-finite is refused by
    the canary gate: :reload answers 409 (not a 5xx), the prior version
    keeps serving, and the failure is counted."""
    from tpu_pipelines.serving import ModelServer

    base = tmp_path / "m"
    _fake_payload(base, 1, 1.0)
    server = ModelServer("toy", str(base), replicas=2, max_versions=2)
    port = server.start()
    url = f"http://127.0.0.1:{port}/v1/models/toy:predict"
    body = json.dumps({"inputs": {"x": [[3.0, 4.0]]}}).encode()
    try:
        _post(url, body)  # captures the canary batch
        _fake_payload(base, 2, float("nan"))
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(f"http://127.0.0.1:{port}/v1/models/toy:reload")
        assert err.value.code == 409
        assert "canary" in json.load(err.value)["error"]
        assert server.version == "1"
        # Serving never blinked.
        status, out = _post(url, body)
        assert status == 200
        np.testing.assert_allclose(out["predictions"], [[3.0, 4.0]])
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape = r.read().decode()
        assert "serving_canary_failures_total 1" in scrape
    finally:
        server.stop()


def test_fleet_env_knobs(tmp_path, fake_loader, monkeypatch):
    from tpu_pipelines.serving import ModelServer

    base = tmp_path / "m"
    _fake_payload(base, 1, 1.0)
    monkeypatch.setenv("TPP_SERVING_REPLICAS", "3")
    monkeypatch.setenv("TPP_SERVING_MAX_VERSIONS", "2")
    monkeypatch.setenv("TPP_SERVING_SLO_P99_MS", "25")
    server = ModelServer("toy", str(base))
    try:
        assert server._fleet is not None
        health = server.health()
        assert health["fleet"]["replicas"] == 3
        assert health["fleet"]["slo_p99_ms"] == 25.0
        assert server.max_versions == 2
    finally:
        server.stop()

    # Constructor wins over env.
    server2 = ModelServer("toy", str(base), replicas=1, max_versions=1,
                          slo_p99_ms=0.0)
    try:
        assert server2._fleet is None  # explicit single-server mode
    finally:
        server2.stop()


def test_grpc_reload_rpc(tmp_path, fake_loader):
    grpc = pytest.importorskip("grpc")
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.serving.grpc_server import (
        PredictionClient,
        start_grpc_server,
    )

    base = tmp_path / "m"
    _fake_payload(base, 1, 1.0)
    server = ModelServer("g", str(base), replicas=2, max_versions=2)
    grpc_server, port = start_grpc_server(server)
    client = PredictionClient(f"127.0.0.1:{port}")
    try:
        _fake_payload(base, 2, 2.0)
        out = client.reload("g")
        assert out == {"version": "2", "state": "AVAILABLE"}
        assert server.version == "2"
        with pytest.raises(grpc.RpcError) as err:
            client.reload("other")
        assert err.value.code() == grpc.StatusCode.NOT_FOUND
    finally:
        client.close()
        grpc_server.stop(grace=2)
        server.stop()


# --------------------------------------------------------- Pusher hook


def test_pusher_notifies_live_fleet(tmp_path, fake_loader, monkeypatch):
    """Satellite (ROADMAP item 4 seam): a Pusher run against a LIVE fleet
    hot-swaps it through the push-URL hook instead of waiting for the
    server's poll interval."""
    from tpu_pipelines.components.pusher import Pusher
    from tpu_pipelines.dsl.component import ExecutorContext
    from tpu_pipelines.metadata.types import Artifact
    from tpu_pipelines.serving import ModelServer

    dest = tmp_path / "serving" / "toy"
    _fake_payload(dest, 1, 1.0)
    server = ModelServer("toy", str(dest), replicas=2, max_versions=2)
    port = server.start()
    try:
        assert server.version == "1"
        model_dir = tmp_path / "model_payload"
        model_dir.mkdir()
        (model_dir / "scale.txt").write_text("5.0")
        monkeypatch.setenv(
            "TPP_SERVING_PUSH_URL",
            f"http://127.0.0.1:{port}/v1/models/toy",
        )
        pushed_dir = tmp_path / "pushed"
        ctx = ExecutorContext(
            node_id="Pusher",
            inputs={"model": [Artifact(type_name="Model",
                                       uri=str(model_dir))]},
            outputs={"pushed_model": [Artifact(type_name="PushedModel",
                                               uri=str(pushed_dir))]},
            exec_properties={"push_destination": str(dest)},
        )
        result = Pusher.EXECUTOR(ctx)
        assert result["pushed"] is True
        assert result["pushed_version"] == 2
        assert result["reload_notified"] is True
        assert result["reload_version"] == "2"
        # The live fleet swapped without any poll.
        assert server.version == "2"
    finally:
        server.stop()


def test_pusher_notify_failure_does_not_fail_push(tmp_path, monkeypatch):
    from tpu_pipelines.components.pusher import Pusher
    from tpu_pipelines.dsl.component import ExecutorContext
    from tpu_pipelines.metadata.types import Artifact

    model_dir = tmp_path / "model_payload"
    model_dir.mkdir()
    (model_dir / "scale.txt").write_text("1.0")
    dest = tmp_path / "dest"
    # Nothing listens here: transient retries exhaust, push still lands.
    monkeypatch.setenv("TPP_SERVING_PUSH_URL", "http://127.0.0.1:9/v1/models/x")
    monkeypatch.setenv("TPP_RETRY_MAX_ATTEMPTS", "1")
    pushed_dir = tmp_path / "pushed"
    ctx = ExecutorContext(
        node_id="Pusher",
        inputs={"model": [Artifact(type_name="Model", uri=str(model_dir))]},
        outputs={"pushed_model": [Artifact(type_name="PushedModel",
                                           uri=str(pushed_dir))]},
        exec_properties={"push_destination": str(dest)},
    )
    result = Pusher.EXECUTOR(ctx)
    assert result["pushed"] is True
    assert result["reload_notified"] is False
    assert "reload_error" in result
    assert os.path.isdir(dest / str(result["pushed_version"]))
