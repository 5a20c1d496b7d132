"""Serving: ModelServer REST surface, version dirs, SavedModel export."""

import json
import os
import urllib.request

import numpy as np
import pytest

from tpu_pipelines.trainer.export import export_model

pytestmark = pytest.mark.slow


def _toy_module(tmp_path):
    mod = tmp_path / "toy_model.py"
    mod.write_text(
        "import jax.numpy as jnp\n"
        "def build_model(hp):\n"
        "    return None  # params-only model; apply_fn does the math\n"
        "def apply_fn(model, params, batch):\n"
        "    return jnp.asarray(batch['x'], jnp.float32) @ params['w']\n"
    )
    return str(mod)


def _export(tmp_path, dirname, scale=1.0):
    payload = tmp_path / dirname
    export_model(
        serving_model_dir=str(payload),
        params={"w": (scale * np.eye(3, 2)).astype(np.float32)},
        module_file=_toy_module(tmp_path),
    )
    return str(payload)


def test_server_versions_and_rest(tmp_path):
    from tpu_pipelines.serving import ModelServer

    base = tmp_path / "served" / "toy"
    _export(tmp_path, "served/toy/1", scale=1.0)
    server = ModelServer("toy", str(base))
    assert server.version == "1"

    port = server.start()
    try:
        # status endpoint
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/models/toy"
        ) as r:
            status = json.load(r)
        assert status["model_version_status"][0]["version"] == "1"

        # row-oriented predict
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/toy:predict",
            data=json.dumps(
                {"instances": [{"x": [1.0, 2.0, 3.0]},
                               {"x": [0.0, 1.0, 0.0]}]}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            preds = json.load(r)["predictions"]
        np.testing.assert_allclose(preds, [[1.0, 2.0], [0.0, 1.0]])

        # column-oriented predict
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/toy:predict",
            data=json.dumps(
                {"inputs": {"x": [[1.0, 0.0, 0.0]]}}
            ).encode(),
        )
        with urllib.request.urlopen(req) as r:
            assert json.load(r)["predictions"] == [[1.0, 0.0]]

        # bad request -> 400 with error body
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/toy:predict",
            data=b'{"bogus": 1}',
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req)
        assert e.value.code == 400

        # new version appears -> reload() hot-swaps, same endpoint
        _export(tmp_path, "served/toy/2", scale=2.0)
        assert server.reload() == "2"
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/toy:predict",
            data=json.dumps({"inputs": {"x": [[1.0, 0.0, 0.0]]}}).encode(),
        )
        with urllib.request.urlopen(req) as r:
            assert json.load(r)["predictions"] == [[2.0, 0.0]]
    finally:
        server.stop()


def test_server_flat_payload(tmp_path):
    from tpu_pipelines.serving import ModelServer

    payload = _export(tmp_path, "flat_model")
    server = ModelServer("flat", payload)
    out = server.predict({"inputs": {"x": [[0.0, 0.0, 1.0]]}})
    np.testing.assert_allclose(out["predictions"], [[0.0, 0.0]])


def test_infra_validator_http_canary(tmp_path):
    from tpu_pipelines.components.infra_validator import _http_canary

    payload = _export(tmp_path, "http_model")
    predict = _http_canary(payload)
    try:
        preds = predict({"x": np.eye(3, dtype=np.float32)})
        np.testing.assert_allclose(preds, np.eye(3, 2, dtype=np.float32))
    finally:
        predict.close()


def test_saved_model_export_roundtrip(tmp_path):
    tf = pytest.importorskip("tensorflow")
    from tpu_pipelines.serving.saved_model import export_saved_model

    payload = _export(tmp_path, "sm_model")
    out_dir = str(tmp_path / "saved_model")
    example = {"x": np.ones((2, 3), np.float32)}
    export_saved_model(payload, out_dir, example)

    reloaded = tf.saved_model.load(out_dir)
    fn = reloaded.signatures["serving_default"]
    # different batch size than the example -> polymorphic batch dim works
    x = np.asarray([[1.0, 0.0, 0.0], [0.0, 1.0, 0.0], [9.0, 0.0, 0.0]],
                   np.float32)
    out = fn(x=tf.constant(x))
    (val,) = out.values()
    np.testing.assert_allclose(
        np.asarray(val), x @ np.eye(3, 2, dtype=np.float32)
    )


def test_server_concurrent_requests(tmp_path):
    """Many simultaneous REST predicts answer correctly (thread safety)."""
    from concurrent.futures import ThreadPoolExecutor

    from tpu_pipelines.serving import ModelServer

    payload = _export(tmp_path, "conc_model")
    server = ModelServer("conc", payload)
    port = server.start()
    try:
        def call(i):
            x = [[float(i), 0.0, 0.0], [0.0, float(i), 0.0]]
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/models/conc:predict",
                data=json.dumps({"inputs": {"x": x}}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return i, json.load(r)["predictions"]

        with ThreadPoolExecutor(max_workers=16) as pool:
            for i, preds in pool.map(call, range(32)):
                # w = eye(3, 2): row j of preds is i * e_j (truncated to 2 cols)
                assert preds[0][0] == i and preds[1][1] == i
    finally:
        server.stop()


def test_request_batcher_coalesces_and_pads(tmp_path):
    """Concurrent submits merge into few device calls on bucket-sized batches."""
    import threading

    from tpu_pipelines.serving.batching import RequestBatcher, bucket_sizes

    seen_sizes = []
    gate = threading.Event()

    def predict_fn(batch):
        gate.wait(5)  # hold the first batch until all submitters queue
        n = len(batch["x"])
        seen_sizes.append(n)
        return np.asarray(batch["x"]) * 2.0

    b = RequestBatcher(predict_fn, max_batch_size=16, batch_timeout_s=0.05)
    try:
        from concurrent.futures import ThreadPoolExecutor

        def submit(i):
            x = np.full((3, 4), float(i), np.float32)
            return i, b.submit({"x": x}, 3)

        with ThreadPoolExecutor(max_workers=8) as pool:
            futs = [pool.submit(submit, i) for i in range(8)]
            import time as _t; _t.sleep(0.3)   # let every request enqueue
            gate.set()
            for f in futs:
                i, out = f.result(timeout=30)
                assert out.shape == (3, 4)
                np.testing.assert_allclose(out, np.full((3, 4), 2.0 * i))
        # 8 requests x 3 rows = 24 rows: far fewer device calls than requests,
        # and every batch the model saw was a power-of-two bucket.
        assert b.batches_run < b.requests_served == 8
        assert all(s in bucket_sizes(16) for s in seen_sizes), seen_sizes
    finally:
        b.close()


def test_server_batching_end_to_end(tmp_path):
    """REST requests through a batching server still answer row-correctly."""
    from concurrent.futures import ThreadPoolExecutor

    from tpu_pipelines.serving import ModelServer

    payload = _export(tmp_path, "batch_model")
    server = ModelServer(
        "bm", payload, batching=True, max_batch_size=32, batch_timeout_s=0.02
    )
    port = server.start()
    try:
        def call(i):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/models/bm:predict",
                data=json.dumps(
                    {"instances": [{"x": [float(i), 1.0, 2.0]}]}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return i, json.load(r)["predictions"]

        with ThreadPoolExecutor(max_workers=12) as pool:
            for i, preds in pool.map(call, range(24)):
                assert preds[0][0] == pytest.approx(float(i))
                assert preds[0][1] == pytest.approx(1.0)
        assert server._batcher.batches_run <= server._batcher.requests_served
    finally:
        server.stop()


def test_request_batcher_schema_isolation(tmp_path):
    """A malformed request must not poison the valid request batched with it."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from tpu_pipelines.serving.batching import RequestBatcher

    gate = threading.Event()

    def predict_fn(batch):
        gate.wait(5)
        return np.asarray(batch["x"]).sum(axis=1)

    b = RequestBatcher(predict_fn, max_batch_size=8, batch_timeout_s=0.05)
    try:
        with ThreadPoolExecutor(max_workers=3) as pool:
            good = pool.submit(b.submit, {"x": np.ones((2, 3), np.float32)}, 2)
            bad_key = pool.submit(b.submit, {"y": np.ones((2, 3), np.float32)}, 2)
            bad_shape = pool.submit(b.submit, {"x": np.ones((2, 5), np.float32)}, 2)
            import time as _t; _t.sleep(0.3)
            gate.set()
            np.testing.assert_allclose(good.result(timeout=30), [3.0, 3.0])
            with pytest.raises(Exception):
                bad_key.result(timeout=30)
            # schema-incompatible but individually valid: runs in its own group
            np.testing.assert_allclose(bad_shape.result(timeout=30), [5.0, 5.0])
    finally:
        b.close()


def test_request_batcher_closed_raises(tmp_path):
    from tpu_pipelines.serving.batching import RequestBatcher

    b = RequestBatcher(lambda batch: np.asarray(batch["x"]), max_batch_size=4)
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit({"x": np.ones((1, 2), np.float32)}, 1)


def test_serving_cli_boot_hotswap_and_shutdown(tmp_path):
    """python -m tpu_pipelines.serving serves, hot-swaps versions, stops."""
    import subprocess
    import sys
    import time

    base = tmp_path / "versions"
    base.mkdir()
    # Deliberately started BEFORE any version exists: the server must wait
    # for the first push instead of crash-looping.
    #
    # The child pins jax to CPU via config.update before any device use
    # (which __main__ guarantees), whatever the environment says.
    boot = (
        "import jax; jax.config.update('jax_platforms', 'cpu'); import sys; "
        "from tpu_pipelines.serving.__main__ import main; "
        "sys.exit(main(sys.argv[1:]))"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", boot,
         "--model-name", "m", "--base-dir", str(base),
         "--port", "0", "--host", "127.0.0.1", "--poll-seconds", "0.2"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    def push_version(n: int, scale: float) -> None:
        # Stage + rename: versions must appear atomically (as Pusher pushes
        # them) — the server polls every 0.2s and must never observe a
        # half-written payload as the newest version.
        import os

        stage = f".stage_{n}"
        _export(tmp_path, stage, scale=scale)
        os.rename(str(tmp_path / stage), str(base / str(n)))

    # Port 0 binds ephemerally; read the bound port from the log line.
    port = None
    waited = False
    deadline = time.time() + 90
    lines = []
    try:
        while time.time() < deadline and port is None:
            line = proc.stdout.readline()
            if not line:
                # EOF: fail fast (with the log) if the server died instead
                # of burning the deadline in a readline busy-loop.
                assert proc.poll() is None, (proc.returncode, lines)
                time.sleep(0.05)
                continue
            lines.append(line)
            if "waiting for the first push" in line and not waited:
                waited = True
                push_version(1, scale=1.0)
            if "serving 'm'" in line and "127.0.0.1:" in line:
                port = int(line.rsplit(":", 1)[1])
        assert port, lines
        assert waited, "server should have waited for the first version"

        def status():
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/models/m", timeout=10
            ) as r:
                return json.load(r)["model_version_status"][0]["version"]

        def predict():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/models/m:predict",
                data=json.dumps({"inputs": {"x": [[1.0, 0.0, 0.0]]}}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return json.load(r)["predictions"]

        assert status() == "1"
        assert predict()[0][0] == pytest.approx(1.0)

        # Push version 2 (doubled weights): the watcher must hot-swap.
        push_version(2, scale=2.0)
        deadline = time.time() + 30
        while time.time() < deadline and status() != "2":
            time.sleep(0.2)
        assert status() == "2"
        assert predict()[0][0] == pytest.approx(2.0)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
    assert proc.returncode == 0


def test_serving_manifest_emission(tmp_path):
    import yaml

    from tpu_pipelines.orchestration import TPUJobRunner, TPUJobRunnerConfig

    runner = TPUJobRunner(TPUJobRunnerConfig(
        image="img:1", pipeline_module="/app/p.py",
        output_dir=str(tmp_path / "m"), shared_volume_claim="pvc",
    ))
    path = runner.emit_serving_manifests(
        "taxi", "/pipeline/serving/taxi", replicas=2
    )
    docs = list(yaml.safe_load_all(open(path)))
    dep, svc = docs
    assert dep["kind"] == "Deployment" and svc["kind"] == "Service"
    assert dep["spec"]["replicas"] == 2
    c = dep["spec"]["template"]["spec"]["containers"][0]
    assert c["command"][:3] == ["python", "-m", "tpu_pipelines.serving"]
    assert "--batching" in c["command"]
    assert "/pipeline/serving/taxi" in c["command"]
    assert c["readinessProbe"]["httpGet"]["path"] == "/v1/models/taxi"
    # gRPC exposed alongside REST (TF Serving's 8500/8501 convention).
    assert "--grpc-port" in c["command"]
    port_names = {p["name"] for p in c["ports"]}
    assert port_names == {"http", "grpc"}
    svc_ports = {p["name"]: p["port"] for p in svc["spec"]["ports"]}
    assert svc_ports == {"http": 8501, "grpc": 8500}
    assert c["volumeMounts"]
    assert svc["spec"]["ports"][0]["port"] == 8501
    assert dep["spec"]["selector"]["matchLabels"] == svc["spec"]["selector"]


# ------------------------------------------------------------------- gRPC


def test_grpc_tensor_codec_roundtrip():
    from tpu_pipelines.serving.grpc_server import (
        array_to_tensor,
        tensor_to_array,
    )

    for arr in (
        np.arange(12, dtype=np.float32).reshape(3, 4),
        np.arange(6, dtype=np.int64).reshape(2, 3),
        np.asarray([True, False]),
        np.asarray([["a", "bb"], ["ccc", ""]], dtype=object),
    ):
        got = tensor_to_array(array_to_tensor(arr))
        assert got.shape == arr.shape
        if arr.dtype == object:
            assert got.tolist() == arr.tolist()
        else:
            np.testing.assert_array_equal(got, arr)
            assert got.dtype == arr.dtype


def test_grpc_predict_and_status(tmp_path):
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.serving.grpc_server import (
        PredictionClient,
        start_grpc_server,
    )

    payload = _export(tmp_path, "grpc_model", scale=3.0)
    server = ModelServer("g", payload)
    grpc_server, port = start_grpc_server(server)
    client = PredictionClient(f"127.0.0.1:{port}")
    try:
        preds, version = client.predict(
            "g", {"x": np.asarray([[1.0, 0.0, 0.0]], np.float32)}
        )
        np.testing.assert_allclose(preds, [[3.0, 0.0]])
        assert client.model_status("g")["state"] == "AVAILABLE"

        # Wrong model name -> NOT_FOUND; bad payload -> INVALID_ARGUMENT.
        import grpc

        with pytest.raises(grpc.RpcError) as e:
            client.predict("other", {"x": np.ones((1, 3), np.float32)})
        assert e.value.code() == grpc.StatusCode.NOT_FOUND
        with pytest.raises(grpc.RpcError) as e:
            client.predict("g", {"wrong_key": np.ones((1, 3), np.float32)})
        assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    finally:
        client.close()
        grpc_server.stop(grace=2)
        server.stop()


def test_grpc_concurrent_requests_through_shared_batcher(tmp_path):
    """Mirror of test_server_concurrent_requests on the gRPC surface, with
    batching=True so gRPC rides the same micro-batcher as REST."""
    from concurrent.futures import ThreadPoolExecutor

    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.serving.grpc_server import (
        PredictionClient,
        start_grpc_server,
    )

    payload = _export(tmp_path, "grpc_conc_model")
    server = ModelServer("conc", payload, batching=True, max_batch_size=16,
                         batch_timeout_s=0.01)
    grpc_server, port = start_grpc_server(server)
    client = PredictionClient(f"127.0.0.1:{port}")
    try:
        def call(i):
            x = np.asarray(
                [[float(i), 0.0, 0.0], [0.0, float(i), 0.0]], np.float32
            )
            preds, _ = client.predict("conc", {"x": x})
            return i, preds

        with ThreadPoolExecutor(max_workers=16) as pool:
            for i, preds in pool.map(call, range(32)):
                assert preds[0][0] == i and preds[1][1] == i
    finally:
        client.close()
        grpc_server.stop(grace=2)
        server.stop()


def test_infra_validator_grpc_canary(tmp_path):
    from tpu_pipelines.components.infra_validator import _grpc_canary

    payload = _export(tmp_path, "grpc_canary_model", scale=2.0)
    predict = _grpc_canary(payload)
    try:
        preds = predict({"x": np.asarray([[1.0, 0.0, 0.0]], np.float32)})
        np.testing.assert_allclose(preds, [[2.0, 0.0]])
    finally:
        predict.close()


def _seq2seq_module(tmp_path):
    mod = tmp_path / "toy_seq2seq.py"
    mod.write_text(
        "import jax.numpy as jnp\n"
        "from tpu_pipelines.models.t5 import T5, make_greedy_generate\n"
        "HP = dict(vocab_size=32, d_model=8, n_layers=1, n_heads=2,\n"
        "          head_dim=4, d_ff=16, dropout_rate=0.0, dtype=jnp.float32)\n"
        "def build_model(hp):\n"
        "    return T5(**HP)\n"
        "def make_generate_fn(model, params, hyperparameters):\n"
        "    gen = make_greedy_generate(model, max_decode_len=5, eos_id=3)\n"
        "    def fn(batch):\n"
        "        tokens, _ = gen(params, jnp.asarray(batch['inputs'],\n"
        "                                            jnp.int32))\n"
        "        return tokens\n"
        "    return fn\n"
    )
    return str(mod)


def test_server_generate_endpoint(tmp_path):
    """Seq2seq :generate route: decodes token sequences; :predict-only
    models answer 400 with a clear error."""
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models.t5 import T5
    from tpu_pipelines.serving import ModelServer

    module = _seq2seq_module(tmp_path)
    model = T5(vocab_size=32, d_model=8, n_layers=1, n_heads=2, head_dim=4,
               d_ff=16, dropout_rate=0.0, dtype=jnp.float32)
    params = model.init(
        jax.random.key(0),
        {"inputs": np.zeros((1, 4), np.int32),
         "targets": np.zeros((1, 3), np.int32)},
    )["params"]
    export_model(
        serving_model_dir=str(tmp_path / "s2s" / "1"),
        params=params, module_file=module,
    )
    server = ModelServer("s2s", str(tmp_path / "s2s"))
    port = server.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/s2s:generate",
            data=json.dumps(
                {"instances": [{"inputs": [5, 9, 3, 2]},
                               {"inputs": [7, 1, 4, 4]}]}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            out = json.load(r)
        toks = np.asarray(out["outputs"])
        assert toks.shape == (2, 5)
        assert toks.dtype.kind == "i"
    finally:
        server.stop()

    # A forward-only payload must reject :generate, not crash.
    base = tmp_path / "served2" / "toy"
    _export(tmp_path, "served2/toy/1")
    server2 = ModelServer("toy", str(base))
    port2 = server2.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port2}/v1/models/toy:generate",
            data=json.dumps({"instances": [{"x": [1.0, 0.0, 0.0]}]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req)
        assert exc.value.code == 400
        assert "generate" in json.load(exc.value)["error"]
    finally:
        server2.stop()


def test_grpc_generate(tmp_path):
    """gRPC Generate mirrors REST :generate; forward-only payloads get
    FAILED_PRECONDITION."""
    import grpc
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models.t5 import T5
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.serving.grpc_server import (
        PredictionClient,
        start_grpc_server,
    )

    module = _seq2seq_module(tmp_path)
    model = T5(vocab_size=32, d_model=8, n_layers=1, n_heads=2, head_dim=4,
               d_ff=16, dropout_rate=0.0, dtype=jnp.float32)
    params = model.init(
        jax.random.key(0),
        {"inputs": np.zeros((1, 4), np.int32),
         "targets": np.zeros((1, 3), np.int32)},
    )["params"]
    export_model(
        serving_model_dir=str(tmp_path / "gs2s" / "1"),
        params=params, module_file=module,
    )
    server = ModelServer("gs2s", str(tmp_path / "gs2s"))
    grpc_server, port = start_grpc_server(server)
    client = PredictionClient(f"127.0.0.1:{port}")
    try:
        tokens, version = client.generate(
            "gs2s", {"inputs": np.asarray([[5, 9, 3, 2], [7, 1, 4, 4]],
                                          np.int32)}
        )
        assert version == "1"
        assert tokens.shape == (2, 5)
        assert tokens.dtype.kind == "i"
    finally:
        client.close()
        grpc_server.stop(0)
        server.stop()

    # Forward-only model: Generate must fail with FAILED_PRECONDITION.
    base = tmp_path / "gtoy" / "toy"
    _export(tmp_path, "gtoy/toy/1")
    server2 = ModelServer("toy", str(base))
    grpc_server2, port2 = start_grpc_server(server2)
    client2 = PredictionClient(f"127.0.0.1:{port2}")
    try:
        with pytest.raises(grpc.RpcError) as err:
            client2.generate("toy", {"x": np.eye(3, dtype=np.float32)})
        assert err.value.code() == grpc.StatusCode.FAILED_PRECONDITION
    finally:
        client2.close()
        grpc_server2.stop(0)
        server2.stop()


def test_generate_empty_request_still_checks_capability(tmp_path):
    """{'instances': []} against a forward-only payload errors (400), not
    200 [] — the capability check runs before payload parsing."""
    base = tmp_path / "served3" / "toy"
    _export(tmp_path, "served3/toy/1")
    from tpu_pipelines.serving import ModelServer

    server = ModelServer("toy", str(base))
    port = server.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/toy:generate",
            data=json.dumps({"instances": []}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req)
        assert exc.value.code == 400
    finally:
        server.stop()
