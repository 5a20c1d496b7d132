"""Rewriter + AOT serving payloads (ISSUE 14).

The contracts under test, all tier-1-safe (tiny CPU payloads,
subprocesses only for the cross-process AOT cache):

  * export/load metadata: ``dtype`` + ``params_bytes`` recorded in the
    payload spec and exposed on ``LoadedModel``; bf16 payloads cast ONCE
    at load; aqt_int8 payloads stay int8-resident with the dequant fused
    into the jitted step;
  * quantized parity: int8/bf16 variants predict within tolerance of
    float on the toy payload AND on a real tiny-T5 parameter tree;
  * the quality gate: variants outside ``quality_tolerance`` of the
    float model's Evaluator metrics are NOT_BLESSED, never selected,
    never pushed (Pusher variant selection skips them), and the fleet's
    canary answers 409 for them (gate 2 of the double-gated deploy);
  * AOT: warmed bucket shapes dispatch pre-compiled executables (zero
    post-warm fallbacks), the serialized-executable cache hits across
    fresh processes, and the fleet's swap gate records warmup wall +
    per-version memory/dtype gauges.
"""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.rewriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY_MODULE = """
import jax.numpy as jnp

def build_model(hp):
    return None

def apply_fn(model, params, batch):
    ids = jnp.asarray(batch['ids'], jnp.int32)
    rows = params['emb'][ids]
    return (rows.mean(axis=1) @ params['w']).squeeze(-1)
"""


def _toy_payload(tmp_path, name="model", vocab=2000, dim=32, seed=0):
    """Export a small embedding-retrieval payload; returns (dir, params)."""
    from tpu_pipelines.trainer.export import export_model

    rng = np.random.default_rng(seed)
    module = tmp_path / "emb_module.py"
    module.write_text(TOY_MODULE)
    params = {
        "emb": rng.standard_normal((vocab, dim)).astype(np.float32),
        "w": rng.standard_normal((dim, 1)).astype(np.float32) / 8.0,
    }
    out = str(tmp_path / name)
    export_model(
        serving_model_dir=out, params=params, module_file=str(module)
    )
    return out, params


def _toy_examples(tmp_path, params, n=192, k=8, seed=1):
    """Eval split whose regression label is the float model + noise."""
    from tpu_pipelines.data.examples_io import (
        table_from_columns,
        write_split,
    )

    rng = np.random.default_rng(seed)
    vocab = params["emb"].shape[0]
    ids = rng.integers(0, vocab, size=(n, k)).astype(np.int32)
    label = (
        params["emb"][ids].mean(axis=1) @ params["w"]
    ).squeeze(-1) + 0.01 * rng.standard_normal(n)
    uri = str(tmp_path / "examples")
    write_split(uri, "eval", table_from_columns({
        "ids": ids, "label": label.astype(np.float32),
    }))
    return uri


def _rewriter_ctx(tmp_path, model_uri, examples_uri=None, **props):
    from tpu_pipelines.dsl.component import ExecutorContext
    from tpu_pipelines.metadata.types import Artifact

    defaults = {
        "variants": ["bfloat16", "aqt_int8"],
        "quality_tolerance": 0.5,
        "quality_metrics": None,
        "label_key": "label" if examples_uri else "",
        "problem": "regression",
        "eval_split": "eval",
        "batch_size": 64,
        "max_eval_examples": 192,
        "selection": "auto",
        "min_quant_size": 1024,
        "latency_batch_size": 4,
        "latency_iters": 3,
        "aot_warm_buckets": 0,
    }
    defaults.update(props)
    inputs = {"model": [Artifact(type_name="Model", uri=model_uri)]}
    if examples_uri:
        inputs["examples"] = [
            Artifact(type_name="Examples", uri=examples_uri)
        ]
    out = Artifact(type_name="Model", uri=str(tmp_path / "rewritten"))
    return ExecutorContext(
        node_id="Rewriter", inputs=inputs,
        outputs={"model": [out]}, exec_properties=defaults,
    ), out


# -------------------------------------------------- export/load metadata


def test_export_records_dtype_and_params_bytes(tmp_path):
    from tpu_pipelines.trainer.export import load_exported_model

    uri, params = _toy_payload(tmp_path)
    with open(os.path.join(uri, "model_spec.json")) as f:
        spec = json.load(f)
    expected = params["emb"].nbytes + params["w"].nbytes
    assert spec["dtype"] == "float32"
    assert spec["params_bytes"] == expected
    loaded = load_exported_model(uri)
    assert loaded.dtype == "float32"
    assert loaded.params_bytes == expected
    assert loaded.uri == os.path.abspath(uri)
    assert loaded.aot is not None and loaded.aot.entries == {}


def test_bf16_payload_casts_once_at_load(tmp_path):
    """A payload declaring dtype=bfloat16 over a float32 checkpoint loads
    with a bf16-resident tree (half the bytes) — the cast happens at
    load, not per request — and predicts close to float."""
    import jax.numpy as jnp

    from tpu_pipelines.trainer.export import (
        export_model,
        load_exported_model,
    )

    uri, params = _toy_payload(tmp_path)
    bf16_dir = str(tmp_path / "bf16")
    export_model(
        serving_model_dir=bf16_dir, params=params,
        module_file=os.path.join(uri, "module_copy.py"),
        serving_dtype="bfloat16",
    )
    base = load_exported_model(uri)
    loaded = load_exported_model(bf16_dir)
    assert loaded.dtype == "bfloat16"
    assert loaded.params["emb"].dtype == jnp.bfloat16
    assert loaded.params_bytes == base.params_bytes // 2
    batch = {"ids": np.arange(12, dtype=np.int32).reshape(4, 3)}
    a = np.asarray(base.predict(batch))
    b = np.asarray(loaded.predict(batch))
    np.testing.assert_allclose(a, b, atol=0.05)


# ---------------------------------------------------- quantization math


def test_quantize_roundtrip_toy_parity(tmp_path):
    from tpu_pipelines.trainer import quantize as qz

    rng = np.random.default_rng(3)
    w = rng.standard_normal((256, 64)).astype(np.float32)
    tree, report = qz.quantize_params(
        {"w": w, "bias": np.zeros(64, np.float32)}, min_size=1024
    )
    assert qz.is_quantized_leaf(tree["w"])
    assert not qz.is_quantized_leaf(tree["bias"])  # 1-D stays float
    assert report["num_quantized"] == 1
    assert qz.tree_is_quantized(tree)
    deq = np.asarray(qz.dequantize_params(tree)["w"])
    # Symmetric int8: per-channel error bounded by scale/2 = amax/254.
    bound = np.abs(w).max(axis=1, keepdims=True) / 254.0 + 1e-7
    assert (np.abs(deq - w) <= bound).all()
    # Resident bytes: int8 + f32 scales vs f32.
    assert qz.params_nbytes(tree) < w.nbytes // 3
    assert qz.infer_dtype(tree) == "aqt_int8"


def test_int8_payload_parity_and_resident_bytes(tmp_path):
    from tpu_pipelines.trainer import quantize as qz
    from tpu_pipelines.trainer.export import (
        export_model,
        load_exported_model,
        restore_exported_params,
    )

    uri, params = _toy_payload(tmp_path)
    qtree, _ = qz.quantize_params(
        restore_exported_params(uri), min_size=1024
    )
    int8_dir = str(tmp_path / "int8")
    export_model(
        serving_model_dir=int8_dir, params=qtree,
        module_file=os.path.join(uri, "module_copy.py"),
    )
    base = load_exported_model(uri)
    loaded = load_exported_model(int8_dir)
    assert loaded.dtype == "aqt_int8"
    assert loaded.params_bytes < base.params_bytes // 3
    rng = np.random.default_rng(5)
    batch = {
        "ids": rng.integers(
            0, params["emb"].shape[0], size=(8, 6)
        ).astype(np.int32)
    }
    a = np.asarray(base.predict(batch))
    b = np.asarray(loaded.predict(batch))
    np.testing.assert_allclose(a, b, atol=0.05)
    assert np.array_equal(
        np.asarray(loaded.predict_transformed(batch)), b
    )


def test_tiny_t5_quantized_parity():
    """Quantize a REAL tiny-T5 parameter tree: dequantized logits stay
    within tolerance and greedy top-1 tokens match the float model."""
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models.t5 import T5
    from tpu_pipelines.trainer import quantize as qz

    model = T5(
        vocab_size=48, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, dropout_rate=0.0, dtype=jnp.float32,
    )
    batch = {
        "inputs": np.arange(12, dtype=np.int32).reshape(2, 6) % 13 + 2,
        "targets": np.ones((2, 5), np.int32),
    }
    params = model.init(jax.random.key(0), batch)["params"]
    logits = np.asarray(model.apply({"params": params}, batch))
    qtree, report = qz.quantize_params(
        jax.tree.map(np.asarray, params), min_size=256
    )
    assert report["num_quantized"] >= 4  # embed + attention + mlp mats
    qlogits = np.asarray(model.apply(
        {"params": qz.dequantize_params(qtree)}, batch
    ))
    scale = np.abs(logits).max()
    assert np.abs(qlogits - logits).max() <= 0.05 * scale
    assert np.array_equal(logits.argmax(-1), qlogits.argmax(-1))
    # bf16 parity rides the same tree.
    blogits = np.asarray(model.apply(
        {"params": qz.cast_params(params, jnp.bfloat16)}, batch
    ))
    assert np.abs(blogits - logits).max() <= 0.05 * scale


# ------------------------------------------------------------- Rewriter


def test_rewriter_emits_gated_variants_and_selects(tmp_path):
    from tpu_pipelines.components.rewriter import Rewriter, variant_dirs
    from tpu_pipelines.trainer.export import load_exported_model

    model_uri, params = _toy_payload(tmp_path)
    examples_uri = _toy_examples(tmp_path, params)
    ctx, out = _rewriter_ctx(tmp_path, model_uri, examples_uri)
    report = Rewriter.EXECUTOR(ctx)

    assert set(report["variants"]) == {"float32", "bfloat16", "aqt_int8"}
    for name, info in report["variants"].items():
        assert info["blessed"] is True, (name, info)
        assert info["latency_ms"] > 0
        assert info["params_bytes"] > 0
    assert report["variants"]["aqt_int8"]["max_quality_delta"] > 0
    assert report["selected_variant"] in report["variants"]
    assert out.properties["selected_variant"] == report["selected_variant"]
    assert sorted(out.properties["blessed_variants"]) == sorted(
        report["variants"]
    )
    # Root payload IS the selected variant; every variant loads.
    dirs = variant_dirs(out.uri)
    assert sorted(dirs) == ["aqt_int8", "bfloat16", "float32"]
    root = load_exported_model(out.uri)
    assert root.dtype == report["variants"][
        report["selected_variant"]
    ]["dtype"]
    assert os.path.exists(os.path.join(out.uri, "rewrite_report.json"))


def test_rewriter_quality_gate_refuses_and_fails_closed(tmp_path):
    from tpu_pipelines.components.rewriter import (
        Rewriter,
        variant_blessed,
        variant_dirs,
    )

    model_uri, params = _toy_payload(tmp_path)
    examples_uri = _toy_examples(tmp_path, params)
    # Tolerance zero: any nonzero quantization delta refuses the variant.
    ctx, out = _rewriter_ctx(
        tmp_path, model_uri, examples_uri, quality_tolerance=0.0,
    )
    report = Rewriter.EXECUTOR(ctx)
    int8 = report["variants"]["aqt_int8"]
    assert int8["blessed"] is False
    assert "quality_tolerance" in int8["reason"]
    assert report["selected_variant"] != "aqt_int8"
    assert "aqt_int8" not in out.properties["blessed_variants"]
    vdir = variant_dirs(out.uri)["aqt_int8"]
    assert not variant_blessed(vdir)
    assert os.path.exists(os.path.join(vdir, "REWRITE_NOT_BLESSED"))
    with open(os.path.join(vdir, "model_spec.json")) as f:
        assert json.load(f)["rewriter"]["blessed"] is False

    # Pinning the refused variant is a hard error, not a silent push.
    ctx2, _ = _rewriter_ctx(
        tmp_path / "pinned", model_uri, examples_uri,
        quality_tolerance=0.0, selection="aqt_int8",
    )
    with pytest.raises(ValueError, match="quality gate"):
        Rewriter.EXECUTOR(ctx2)

    # No eval examples: the gate fails closed — float32 only.
    ctx3, out3 = _rewriter_ctx(tmp_path / "noeval", model_uri)
    report3 = Rewriter.EXECUTOR(ctx3)
    assert report3["selected_variant"] == "float32"
    assert out3.properties["blessed_variants"] == ["float32"]
    assert "fails closed" in report3["variants"]["aqt_int8"]["reason"]


def test_pusher_variant_selection(tmp_path):
    from tpu_pipelines.components.pusher import Pusher
    from tpu_pipelines.components.rewriter import Rewriter
    from tpu_pipelines.dsl.component import ExecutorContext
    from tpu_pipelines.metadata.types import Artifact

    model_uri, params = _toy_payload(tmp_path)
    examples_uri = _toy_examples(tmp_path, params)
    ctx, out = _rewriter_ctx(tmp_path, model_uri, examples_uri)
    Rewriter.EXECUTOR(ctx)

    def push(variant, dest):
        pushed = Artifact(
            type_name="PushedModel", uri=str(tmp_path / f"pushed-{variant}")
        )
        pctx = ExecutorContext(
            node_id="Pusher",
            inputs={"model": [Artifact(type_name="Model", uri=out.uri)]},
            outputs={"pushed_model": [pushed]},
            exec_properties={
                "push_destination": str(dest),
                "serving_push_url": "", "variant": variant,
            },
        )
        return Pusher.EXECUTOR(pctx), pushed

    result, pushed = push("int8", tmp_path / "dest-int8")
    assert result["pushed"] is True
    assert pushed.properties["variant"] == "aqt_int8"
    with open(os.path.join(
        result["destination"], "model_spec.json"
    )) as f:
        assert json.load(f)["dtype"] == "aqt_int8"

    # Unknown variant is a wiring error at the parameter surface.
    with pytest.raises(ValueError, match="unknown rewriter variant"):
        push("float32x", tmp_path / "d2")


def test_pusher_skips_unblessed_variant(tmp_path):
    from tpu_pipelines.components.pusher import Pusher
    from tpu_pipelines.components.rewriter import Rewriter
    from tpu_pipelines.dsl.component import ExecutorContext
    from tpu_pipelines.metadata.types import Artifact

    model_uri, params = _toy_payload(tmp_path)
    examples_uri = _toy_examples(tmp_path, params)
    ctx, out = _rewriter_ctx(
        tmp_path, model_uri, examples_uri, quality_tolerance=0.0
    )
    Rewriter.EXECUTOR(ctx)
    dest = tmp_path / "dest"
    pushed = Artifact(type_name="PushedModel", uri=str(tmp_path / "pm"))
    pctx = ExecutorContext(
        node_id="Pusher",
        inputs={"model": [Artifact(type_name="Model", uri=out.uri)]},
        outputs={"pushed_model": [pushed]},
        exec_properties={
            "push_destination": str(dest),
            "serving_push_url": "", "variant": "aqt_int8",
        },
    )
    result = Pusher.EXECUTOR(pctx)
    assert result["pushed"] is False
    assert "NOT_BLESSED" in result["skip_reason"]
    assert not os.path.isdir(dest) or not [
        d for d in os.listdir(dest) if d.isdigit()
    ]


# ------------------------------------------------------- fleet gate (409)


def test_fleet_canary_409_on_unblessed_variant(tmp_path):
    """Gate 2: an unblessed variant payload pushed into the version dir
    answers the ``:reload`` with HTTP 409 (CanaryRefused) and the prior
    version keeps serving."""
    from tpu_pipelines.components.rewriter import Rewriter, variant_dirs
    from tpu_pipelines.serving import ModelServer

    model_uri, params = _toy_payload(tmp_path)
    examples_uri = _toy_examples(tmp_path, params)
    ctx, out = _rewriter_ctx(
        tmp_path, model_uri, examples_uri, quality_tolerance=0.0
    )
    Rewriter.EXECUTOR(ctx)
    unblessed = variant_dirs(out.uri)["aqt_int8"]

    base = tmp_path / "serving"
    base.mkdir()
    import shutil

    shutil.copytree(model_uri, base / "1")
    server = ModelServer("toy", str(base), replicas=1, max_versions=2)
    port = server.start()
    try:
        body = json.dumps({
            "instances": [{"ids": [1, 2, 3, 4]}]
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/toy:predict", data=body
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
        shutil.copytree(unblessed, base / "2")
        reload_req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/toy:reload", data=b"{}"
        )
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(reload_req, timeout=60)
        assert err.value.code == 409
        assert "NOT_BLESSED" in err.value.read().decode()
        # Prior version still answers.
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
    finally:
        server.stop()


# ----------------------------------------------------------------- AOT


def test_aot_warm_dispatch_and_compile_accounting(tmp_path, monkeypatch):
    monkeypatch.setenv("TPP_AOT_CACHE", str(tmp_path / "aot-cache"))
    from tpu_pipelines.serving import aot
    from tpu_pipelines.trainer.export import load_exported_model

    uri, params = _toy_payload(tmp_path)
    loaded = load_exported_model(uri)
    batch = {"ids": np.arange(6, dtype=np.int32).reshape(1, 6)}
    cold = np.asarray(loaded.predict(
        {"ids": np.repeat(batch["ids"], 4, axis=0)}
    ))
    stats = aot.warm_loaded(loaded, batch, 8, raw=True)
    assert stats["fallback_warm"] is False
    assert stats["compiled"] == 4 and stats["cache_hits"] == 0
    assert stats["cached_to_disk"] == 4
    # Without a transform, one lowering serves both endpoints.
    assert len(loaded.aot.entries) == 8
    out = np.asarray(loaded.predict(
        {"ids": np.repeat(batch["ids"], 4, axis=0)}
    ))
    np.testing.assert_array_equal(cold, out)
    assert loaded.aot.fallbacks == 0
    # A shape outside the warmed set is a counted broken contract.
    fired = []
    loaded.aot.on_compile_after_warm = lambda: fired.append(1)
    odd = {"ids": np.repeat(batch["ids"], 3, axis=0)}
    loaded.predict(odd)
    loaded.predict(odd)
    assert loaded.aot.fallbacks == 2
    assert loaded.aot.compiles_after_warm == 1  # jit cached the repeat
    assert fired == [1]


def test_aot_cache_hits_across_processes(tmp_path):
    """The serialized-executable cache round-trips across fresh
    interpreters: process A compiles + persists, process B deserializes
    every bucket (0 compiles) and serves identical predictions."""
    uri, _ = _toy_payload(tmp_path)
    script = tmp_path / "warm.py"
    script.write_text(
        "import json, sys\n"
        "import numpy as np\n"
        "from tpu_pipelines.serving import aot\n"
        "from tpu_pipelines.trainer.export import load_exported_model\n"
        f"loaded = load_exported_model({uri!r})\n"
        "batch = {'ids': np.arange(6, dtype=np.int32).reshape(1, 6)}\n"
        "stats = aot.warm_loaded(loaded, batch, 8, raw=True)\n"
        "out = loaded.predict({'ids': np.repeat(batch['ids'], 4, 0)})\n"
        "print(json.dumps({'stats': {k: v for k, v in stats.items()},\n"
        "                  'fallbacks': loaded.aot.fallbacks,\n"
        "                  'out': np.asarray(out).tolist()}))\n"
    )
    env = {
        **os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
        "TPP_AOT_CACHE": str(tmp_path / "aot-cache"),
    }

    def run():
        res = subprocess.run(
            [sys.executable, str(script)], env=env,
            capture_output=True, text=True, timeout=300,
        )
        assert res.returncode == 0, res.stdout + res.stderr
        return json.loads(res.stdout.strip().splitlines()[-1])

    first = run()
    assert first["stats"]["compiled"] == 4
    assert first["stats"]["cache_hits"] == 0
    assert first["fallbacks"] == 0
    second = run()
    assert second["stats"]["compiled"] == 0
    assert second["stats"]["cache_hits"] == 4
    assert second["fallbacks"] == 0
    assert second["out"] == first["out"]
    # Warm deserialize is the fast path the swap gate banks on.
    assert second["stats"]["seconds"] < first["stats"]["seconds"]


def test_fleet_swap_records_warmup_and_version_gauges(tmp_path, monkeypatch):
    monkeypatch.setenv("TPP_AOT_CACHE", str(tmp_path / "aot-cache"))
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.fleet import ServingFleet
    from tpu_pipelines.trainer import quantize as qz
    from tpu_pipelines.trainer.export import (
        export_model,
        restore_exported_params,
    )

    uri, params = _toy_payload(tmp_path)
    base = tmp_path / "versions"
    base.mkdir()
    import shutil

    shutil.copytree(uri, base / "1")
    qtree, _ = qz.quantize_params(
        restore_exported_params(uri), min_size=1024
    )
    export_model(
        serving_model_dir=str(base / "2"), params=qtree,
        module_file=os.path.join(uri, "module_copy.py"),
    )

    reg = MetricsRegistry()
    fleet = ServingFleet(
        "toy", str(base), replicas=1, max_versions=2, registry=reg,
        max_batch_size=4,
    )
    try:
        fleet.set_canary_batch({
            "ids": np.arange(4, dtype=np.int32).reshape(1, 4)
        })
        fleet.load_version(str(base / "1"))
        warm1 = reg.get("serving_swap_warmup_seconds").get()
        assert warm1 > 0
        assert reg.get("serving_aot_compiles_total").get() >= 3
        mem = reg.get("serving_version_memory_bytes")
        f32_bytes = params["emb"].nbytes + params["w"].nbytes
        assert mem.labels("toy", "1").get() == f32_bytes
        dt = reg.get("serving_version_dtype")
        assert dt.labels("toy", "1", "float32").get() == 1
        fleet.load_version(str(base / "2"))
        assert mem.labels("toy", "2").get() < f32_bytes // 3
        assert dt.labels("toy", "2", "aqt_int8").get() == 1
        # Post-swap traffic at a warmed bucket: no compile after warm.
        out = fleet.submit({
            "ids": np.arange(8, dtype=np.int32).reshape(2, 4)
        }, 2)
        assert np.asarray(out).shape == (2,)
        assert (
            reg.get("serving_aot_compiles_after_warm_total").get() == 0
        )
    finally:
        fleet.close()


def test_aot_disabled_falls_back_to_legacy_warm(tmp_path, monkeypatch):
    monkeypatch.setenv("TPP_AOT", "0")
    from tpu_pipelines.serving import aot
    from tpu_pipelines.trainer.export import load_exported_model

    uri, _ = _toy_payload(tmp_path)
    loaded = load_exported_model(uri)
    batch = {"ids": np.arange(6, dtype=np.int32).reshape(1, 6)}
    stats = aot.warm_loaded(loaded, batch, 8, raw=True)
    assert stats["fallback_warm"] is True
    assert loaded.aot.entries == {}
    # The warm still pre-traced every bucket (the legacy guarantee).
    out = loaded.predict({"ids": np.repeat(batch["ids"], 8, axis=0)})
    assert np.asarray(out).shape == (8,)


def test_fleet_replicas_compute_on_their_own_devices(tmp_path, monkeypatch):
    """One replica per device, for real: each replica's predict runs
    against a params copy resident on ITS device (committed params used to
    drag every replica's work back to device 0), and a second version's
    swap-gate warm installs — and serves from — one AOT executable set per
    replica device with zero post-warm fallbacks."""
    import shutil

    import jax

    monkeypatch.setenv("TPP_AOT_CACHE", str(tmp_path / "aot-cache"))
    from tpu_pipelines.serving.fleet import ServingFleet

    uri, _ = _toy_payload(tmp_path)
    base = tmp_path / "versions"
    base.mkdir()
    shutil.copytree(uri, base / "1")
    fleet = ServingFleet("toy", str(base), replicas=4, max_versions=2,
                         max_batch_size=4)
    try:
        fleet.load_version(str(base / "1"))
        devices = [r.device for r in fleet.pool.replicas]
        assert len(set(devices)) == 4 and None not in devices
        batch = {"ids": np.arange(12, dtype=np.int32).reshape(2, 6)}
        loaded = fleet.active_loaded()
        want = np.asarray(loaded.predict(batch))
        for replica in fleet.pool.replicas:
            got = replica.submit(batch, 2, timeout_s=60.0)
            np.testing.assert_allclose(got, want, rtol=1e-6)
            leaves = jax.tree_util.tree_leaves(
                loaded.params_on(replica.device)
            )
            assert all(x.devices() == {replica.device} for x in leaves)

        # Second version: the canary batch exists now, so the swap gate
        # AOT-warms every bucket for every replica device.
        fleet.submit(batch, 2)
        shutil.copytree(uri, base / "2")
        fleet.load_version(str(base / "2"))
        loaded2 = fleet.active_loaded()
        homes = {key[2] for key in loaded2.aot.entries}
        assert homes == {None, *devices[1:]}      # device 0 is home
        for replica in fleet.pool.replicas:
            got = replica.submit(batch, 2, timeout_s=60.0)
            np.testing.assert_allclose(got, want, rtol=1e-6)
        assert loaded2.aot.fallbacks == 0
    finally:
        fleet.close()
