"""The Xing4.0-29B-A4B configuration and cell (ISSUE 42): the configuration
file held to the catalog row and to the issue's cut, the bytes of its
``memory`` recomputed from ``hparams``, benchmark/work_xing.py on numbers
worked by hand, the three readers on hand-built facts and on a stored
trace, and a rehearsal of the cell on the CPU from a fixture root of its
own (``fixture_xing/``).

``BENCHMARK.json`` does not list the three readers yet, nor
``latent_fetch_valid_share.serve`` for this cell: an accepted test
(``test_benchmark_latent_fetch.py``) holds that entry to be the LAST of
``per_layer`` and openPangu's cell to be its only one, and neither an
appended entry nor a longer list passes it; that file is the benchmark's
and not a ``model_config`` PR's to edit (as PR 31's three readers stood
unlisted until PR 39).  ``ENTRIES`` is what the ``benchmark`` PR that lists
them is to write.  Nothing here is a device number."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, run as bench_run, work_xing as work
from rehearsal import read_result
from test_benchmark_program_parts import write_trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_xing")
CELL = "tiny-xing.longdoc-closed"
REAL_CELL = "xing4.0-29b-a4b.longdoc-reason-closed"
PANGU_CELL = "openpangu-ultra-moe-718b.reason-decode-closed"
SPEC = manifest.load()
CONFIG = manifest.load_config(SPEC, "xing4.0-29b-a4b")
HP = CONFIG["hparams"]

# unit, layer, source, the end-to-end metric it moves, better
ENTRIES = {
    "xing_decode_hbm_share.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "xing_prefill_mfu.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "step_stream_mix_share.serve":
        ("%", "model step", "device_trace", "serve_tokens_per_s", "lower"),
}
# what the cell reports of the accepted readers, beside its own three
SHARED = (
    "batch_occupancy.serve", "ms_per_token_p95.offline",
    "decode_step_ms.serve", "device_idle_share.serve",
    "admission_share.serve", "prefill_device_share.serve",
    "expert_load_ratio.serve", "step_program_ms.serve",
    "step_attention_core_share.serve",
    "step_mlp_share.serve", "step_cache_write_share.serve",
    "step_unnamed_share.serve", "prefill_attention_core_share.serve")
MODEL = {
    "d_model": 3584, "d_ff": 9216, "n_layers": 6, "n_heads": 32,
    "head_dim": 128, "vocab_size": 131072, "weight_itemsize": 2,
    "kv_itemsize": 2,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["num_hidden_layers", "first_k_dense_replace",
           "num_nextn_predict_layers"]

ATTENTION = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
             + 32 * 128 * 3584)
EXPERT = 3 * 3584 * 1024
MIX = 4 * 3584 * 24 + 4 + 4 + 16 + 3
FIXED = (12 * MIX + ATTENTION + 3 * 3584 * 9216
         + 5 * (ATTENTION + 3584 * 64 + EXPERT) + 3584 * 131072)


# ------------------------------------------------------- the configuration


def test_widths_are_the_sources():
    src = CONFIG["source_config"]
    assert (HP["d_model"], HP["d_ff"], HP["n_heads"], HP["q_lora_rank"],
            HP["kv_lora_rank"], HP["qk_nope_head_dim"],
            HP["qk_rope_head_dim"], HP["v_head_dim"], HP["d_expert"],
            HP["n_experts"], HP["experts_held"], HP["experts_per_token"],
            HP["n_shared_experts"], HP["routed_scaling_factor"],
            HP["rope_theta"], HP["rms_norm_eps"], HP["vocab_size"],
            HP["hc_mult"], HP["hc_sinkhorn_iters"], HP["hc_eps"],
            HP["mhc_h_res_clamp_min"], HP["mhc_h_res_clamp_max"],
            HP["rope_scaling"]) == (
        src["hidden_size"], src["intermediate_size"],
        src["num_attention_heads"], src["q_lora_rank"], src["kv_lora_rank"],
        src["qk_nope_head_dim"], src["qk_rope_head_dim"],
        src["v_head_dim"], src["moe_intermediate_size"],
        src["n_routed_experts"], src["n_routed_experts"],
        src["num_experts_per_tok"], src["n_shared_experts"],
        src["routed_scaling_factor"], src["rope_theta"],
        src["rms_norm_eps"], src["vocab_size"], src["hc_mult"],
        src["hc_sinkhorn_iters"], src["hc_eps"],
        src["mhc_h_res_clamp_min"], src["mhc_h_res_clamp_max"],
        src["rope_scaling"])
    assert src["scoring_func"] == "sigmoid" and src["norm_topk_prob"] is True
    assert src["topk_method"] == "noaux_tc" and HP["selection_bias"] is True
    assert (src["n_group"], src["topk_group"], src["ep_size"]) == (1, 1, 1)
    assert HP["head_dim"] == src["v_head_dim"] and HP["expert_offset"] == 0
    assert CONFIG["weight_dtype"] == CONFIG["compute_dtype"] == "bfloat16"


def test_the_source_config_is_the_catalogs_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    assert CONFIG["source_config"] == row["config"]
    assert CONFIG["source"] == row["source_url"] == next(
        c for c in SPEC["configs"] if c["name"] == CONFIG["name"])["source"]


def test_the_cut_is_the_issues():
    """Every key of the source's config stands at the top of the file with
    the value the cell runs; only the three keys under ``reduced`` differ:
    depth.  No width, no expert and no vocabulary row is cut."""
    src = CONFIG["source_config"]
    differ = {k for k, v in src.items() if CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == set(REDUCED)
    assert next(c for c in SPEC["configs"]
                if c["name"] == CONFIG["name"])["reduced"] == REDUCED
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["num_nextn_predict_layers"]) == (6, 1, 0) == (
        HP["n_layers"], HP["n_dense_layers"], HP["n_mtp"])
    assert HP["n_layers"] - HP["n_dense_layers"] >= 4       # the floor
    for key in REDUCED:
        assert str(src[key]) in CONFIG["changed"][key]
    because = CONFIG["reduced_because"]
    assert "one chip shares each layer" in because
    assert "pipeline stages" in because
    for key in ("sinkhorn_order", "flat_norm", "streams_ends", "post_factor",
                "mtp_halves", "rotary_pairs", "selection_bias", "weights",
                "eos", "context"):
        assert CONFIG["assumed"][key]
    assert any("prediction module" in d for d in CONFIG["departures"])
    assert any("absorbed form" in d for d in CONFIG["departures"])
    assert CONFIG["program"] == {
        "module": "tpu_pipelines.models.xing", "build": "build_xing_model",
        "decode_fns": "make_continuous_decode_fns"}
    assert CONFIG["reference"] == "xing" and CONFIG["driver"] == "engine"


def test_the_cell_is_the_issues():
    cell = manifest.cell(SPEC, REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4.0-29b-a4b", "longdoc-reason-closed", 1)
    with open(manifest.traffic_path(cell["traffic"])) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["loop"], mix["callers"], mix["block"]) == (
        "requests", "closed", 64, 32)
    assert mix["prompt_len"] == {
        "dist": "lognormal_int", "median": 4096, "sigma": 0.8, "low": 1024,
        "high": 16384}
    assert mix["output_len"] == {
        "dist": "lognormal_int", "median": 1024, "sigma": 0.5, "low": 256,
        "high": 2048}
    assert (mix["settle_s"], mix["drain_s"]) == (20, 60)
    assert "engine" not in mix
    engine = CONFIG["engine"]
    assert engine["max_input_len"] == mix["prompt_len"]["high"]
    assert engine["max_decode_len"] == mix["output_len"]["high"]
    assert mix["callers"] == 2 * engine["max_batch_size"] == 64
    assert engine["page_size"] == 0
    assert engine["prefill_window_len"] in (512, 1024)
    # 128 assignments a step over 64 held experts: 2 rows an expert
    assert engine["max_batch_size"] * HP["experts_per_token"] \
        / HP["experts_held"] == 2
    listed = {
        m["name"] for section in ("end_to_end", "per_layer")
        for m in manifest.metrics_for(SPEC, section, cell["name"])}
    assert listed >= {"serve_tokens_per_s", "setup_s"} | set(SHARED)
    # every accepted reader it joins is one openPangu's cell reports too
    pangu = {m["name"] for m in manifest.metrics_for(
        SPEC, "per_layer", PANGU_CELL)}
    assert set(SHARED) <= pangu
    assert cell in SPEC["workloads"]
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}


def test_the_program_builds_what_the_file_says():
    """The parameter count of the model the driver builds and the bytes
    the ``memory`` text states, recomputed from ``hparams``."""
    import jax

    from tpu_pipelines.models import xing

    model = xing.build_xing_model(HP)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": np.ones((1, 8), np.int32)})["params"])
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    count = lambda keep: sum(
        int(np.prod(x.shape)) for p, x in flat if keep(str(p[-1])))
    held = work.held_params(HP)
    assert held == FIXED + 3584 * 131072 + 5 * 64 * EXPERT == 4_792_615_236
    # beside them: the norms' gains and the routers' selection bias
    assert count(lambda k: "scale" not in k and "e_score" not in k) == held
    assert count(lambda k: "scale" in k) == 6 * (2 * 3584 + 768 + 512) + 3584
    assert count(lambda k: "e_score" in k) == 5 * 64
    assert {str(x.dtype) for p, x in flat if "e_score" in str(p[-1])} \
        == {"float32"}
    engine = CONFIG["engine"]
    fns = xing.make_continuous_decode_fns(
        model, **{k: engine[k] for k in (
            "max_input_len", "max_decode_len", "prefill_window_len")})
    cache = jax.eval_shape(lambda: fns.blank_cache(32))
    assert {x.shape for x in jax.tree_util.tree_leaves(cache)} == {
        (32, 18432, 576)}
    slots = sum(x.size * 2 for x in jax.tree_util.tree_leaves(cache))
    assert slots == 32 * 18432 * 6912 == 4_076_863_488
    memory = CONFIG["memory"]
    for said, number, unit, worked in (
            ("weights 9.59 GB", 9.59, 1e9, 2 * held),
            ("32 slots 4.08 GB", 4.08, 1e9, slots),
            ("6912 B a position", 6912, 1, 576 * 2 * 6),
            ("positions 127.4 MB", 127.4, 1e6, 18432 * 6912),
            ("resident 13.79 GB", 13.79, 1e9, 2 * held + slots * 33 // 32),
            ("would need 72 GB", 72, 1e9, 32 * 18432 * 6 * 32 * 320 * 2)):
        assert said in memory, said
        assert number == pytest.approx(worked / unit, rel=1e-2), said


# ------------------------------------------------------ work, from shapes


def test_work_is_worked_by_hand():
    assert ATTENTION == 28_409_856 and EXPERT == 11_010_048
    assert work.mix_params(HP) == MIX == 344_091
    assert work.fixed_params(HP) == FIXED == 799_637_828
    # every expert touched: 8.65 GB, 10.6 ms at 819 GB/s
    assert work.decode_weight_bytes(HP, 2, 5 * 64) == 2 * (
        FIXED + 320 * EXPERT) == 8_645_706_376
    # 56 of 64 touched in each of 5 layers: 0.88 GB less
    assert work.decode_weight_bytes(HP, 2, 280.0) == 2 * (
        FIXED + 280 * EXPERT)
    # 32 rows' streams through 12 sub-layers, read once and written once
    assert work.stream_bytes(HP, 32) == 2 * 12 * 32 * 4 * 3584 * 4 \
        == 44_040_192
    latents = 32 * 6000 * 6912
    assert work.decode_step_bytes(HP, 2, 280.0, latents, 32) == (
        2 * (FIXED + 280 * EXPERT) + latents + 44_040_192)
    # the mixing: 2 n C 24 for phi, 2 n C (n + 2) for u and the write-back
    assert work.mix_flops(HP, 1) == 12 * (
        2 * 14336 * 24 + 2 * 14336 * 6) == 10_321_920
    from benchmark import work_pangu_moe

    depths = [6000] * 32
    assert work.decode_step_flops(HP, depths, 128 * 5.0) == (
        work_pangu_moe.decode_step_flops(HP, depths, 640.0)
        + 32 * 10_321_920)
    full = work.prefill_window_flops(HP, 1024)
    # 2 x 546 M parameters a token (every choice is held), the window's
    # own pairs and the mixing: 1.26 TFLOP, 6.4 ms at the chip's peak
    assert 1.2e12 < full < 1.3e12
    assert full - work_pangu_moe.prefill_window_flops(HP, 1024) \
        == 1024 * 10_321_920


# ------------------------------------------------------------ the readers


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_is_the_issues(name):
    """The reader says of itself what its entry is to say; once a
    ``benchmark`` PR lists it, the entry and the fixture's say the same."""
    reader = manifest.load_layer_metric(name)
    unit, layer, source, moves, better = ENTRIES[name]
    assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
        unit, layer, source, moves)
    assert reader.MOVES in {m["name"] for m in SPEC["end_to_end"]}
    assert reader.LAYER in {m["layer"] for m in SPEC["per_layer"]}
    assert manifest.NAME_RE.match(name) and manifest.UNIT_RE.match(unit)
    assert reader.read({}) is None
    for root in (manifest.ROOT, FIXTURE):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            listed = {m["name"]: m for m in json.load(f)["per_layer"]}
        if name in listed:
            entry = listed[name]
            assert (entry["unit"], entry["layer"], entry["source"],
                    entry["moves"], entry["better"]) == ENTRIES[name]


def telemetry(kind="latent", touched=280):
    """A private registry as an engine leaves it after 10 decode steps
    that read 1.3 GB of latents each and touched 280 experts, and 4
    prefill windows holding 3,072 prompt tokens."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reg = MetricsRegistry()
    t = DecodeTelemetry(reg, "0")
    for _ in range(10):
        t.on_step(0.02, 0.02, 32, 32, 0, 32)
        t.on_cache({
            "cache_bytes": {kind: 1.3e9}, "expert_assignments": 640,
            "experts_touched": touched, "expert_load_ratio": 3.0})
    for n in (1024, 1024, 1024, 0):
        t.on_prefill_window(n)
    return reg


@pytest.fixture
def registry():
    return telemetry()


def facts(modules, model=MODEL):
    return {
        "serve_steps": {"counter_steps": 10, "counter_tokens": 320},
        "serve_model": model, "peaks": PEAKS,
        "trace": {"modules": modules, "busy_s": 2.0},
    }


MODULES = [
    ("jit_run(1)", 0.0, 0.016), ("jit_run(1)", 0.1, 0.024),
    ("jit_prefill_window(2)", 0.2, 0.03),
    ("jit_prefill_window(2)", 0.4, 0.05),
    ("jit_insert(3)", 0.6, 0.01),
]


def test_decode_share_is_bytes_over_bandwidth_over_the_steps_time(registry):
    reader = manifest.load_layer_metric("xing_decode_hbm_share.serve")
    must = 2 * (FIXED + 280 * EXPERT) + 1.3e9 + 44_040_192
    want = 100.0 * (must / 819e9) / 0.02
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 55.0 < want < 56.0
    # a step that touches every expert must read more, never less
    assert reader.read(facts(MODULES), telemetry(touched=320)) > want


def test_prefill_mfu_is_the_mean_windows_flops_over_its_time(registry):
    reader = manifest.load_layer_metric("xing_prefill_mfu.serve")
    want = 100.0 * work.prefill_window_flops(HP, 768.0) / 0.04 / 197e12
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 10.0 < want < 14.0


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_returns_nothing_where_there_is_nothing_to_read(
        name, registry, tmp_path, monkeypatch):
    """The parent commit has no such configuration or scopes, another
    contract counts another kind of cache, another model has other sizes,
    openPangu's own cell has these counters and another model, and a CPU
    rehearsal's trace has no "XLA Modules" line: nothing, and no error."""
    from tpu_pipelines.observability.metrics import MetricsRegistry

    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))    # no trace there
    reader = manifest.load_layer_metric(name)
    if name == "step_stream_mix_share.serve":
        assert reader.read(facts(MODULES)) is None
        assert reader.read({"trace": {"busy_s": 1.0}}) is None
        return
    assert reader.read(
        {"trace": {"modules": MODULES, "busy_s": 2.0}}, registry) is None
    assert reader.read(facts(MODULES), MetricsRegistry()) is None
    assert reader.read(facts(MODULES), telemetry("window")) is None
    assert reader.read(facts([]), registry) is None
    pangu = {**MODEL, "d_model": 7680, "d_ff": 18432, "n_layers": 5,
             "n_heads": 128, "vocab_size": 19200}
    assert reader.read(facts(MODULES, pangu), registry) is None


# The stored trace: a step program of five operations.  The two fusions of
# the mixing carry its scopes inside the word that closes their sub-layer;
# ``fusion.9`` has no path of its own and calls a computation whose
# instructions all lie in ``mhc.apply``, ``fusion.10`` one that mixes the
# write-back with the experts' product.
STEP = "jit(run)/XingMoE.decode_step/layer_1."
TRACE = {
    "jit_run(7)": [
        ("fusion.1", STEP + "step/attn_mix.coefficients/attention_proj/"
         "mhc.mix/div", [], 1.0),
        ("fusion.2", STEP + "step/attn/attention_core/mla.attend/"
         "latent_decode_attention", [1], 4.0),
        ("fusion.3", STEP + "step/ffn_mix.write/mlp/mhc.apply/add", [2], 0.5),
        ("fusion.4", STEP + "step/ffn/mlp/moe.experts/gmm", [3], 4.0),
        ("fusion.5", "jit(run)/XingMoE.decode_step/mhc.mixer/mul", [4], 0.5),
    ],
    "jit_prefill_window(8)": [
        ("fusion.1", "jit(prefill_window)/layer_0.window/attn_mix.read/"
         "attention_proj/mhc.apply/mul", [], 5.0),
    ],
}


def test_stream_mix_share_reads_the_two_scopes_off_a_stored_trace(
        tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    path = write_trace(tmp_path, TRACE)
    reader = manifest.load_layer_metric("step_stream_mix_share.serve")
    serve = {"trace": {"busy_s": 1.0}, "serve_steps": {}}
    # 1.0 + 0.5 of the step's 10 seconds; a segment that only starts like
    # a scope is none, and the window program is not the step
    assert reader.read(serve) == pytest.approx(15.0)
    assert reader.shares(path) == pytest.approx((3.0, 20.0))
    assert reader.shares(path, ("jit_prefill_window",)) \
        == pytest.approx((10.0, 10.0))
    assert reader.read({"trace": {"busy_s": 1.0}, "train_windows": {}}) \
        is None
    assert reader.holds(STEP + "step/mlp/mhc.apply/add")
    assert not reader.holds(STEP + "step/mlp/moe.experts/gmm")


def test_an_operation_without_a_path_counts_by_what_it_calls():
    from benchmark import program_parts as pp

    reader = manifest.load_layer_metric("step_stream_mix_share.serve")
    module = pp.messages()["HloModule"]()
    inner = module.computations.add(name="fused", id=2)
    for i, path in enumerate(["a/mlp/mhc.apply/mul", "", "b/mhc.apply/add"]):
        inner.instructions.add(name=f"in.{i}", id=10 + i).metadata.op_name \
            = path
    mixed = module.computations.add(name="fused_mixed", id=3)
    for i, path in enumerate(["a/mlp/mhc.apply/mul", "a/mlp/moe.experts/x"]):
        mixed.instructions.add(name=f"mx.{i}", id=20 + i).metadata.op_name \
            = path
    blank = module.computations.add(name="fused_blank", id=4)
    blank.instructions.add(name="bl.0", id=30)
    main = module.computations.add(name="main", id=1)
    for n, (name, path, calls) in enumerate([
            ("fusion.9", "", [2]), ("fusion.10", "", [3]),
            ("fusion.11", "", [4]), ("copy.12", "", []),
            ("fusion.13", "x/attention_proj/mhc.mix/exp", [3])]):
        row = main.instructions.add(name=name, id=40 + n)
        row.metadata.op_name = path
        row.called_computation_ids.extend(calls)
    assert reader.in_scope(module) == {
        "in.0", "in.2", "mx.0", "fusion.9", "fusion.13"}


def test_readers_look_for_the_programs_own_names():
    from tpu_pipelines.models import xing
    from tpu_pipelines.serving import generative

    decode = manifest.load_layer_metric("xing_decode_hbm_share.serve")
    mfu = manifest.load_layer_metric("xing_prefill_mfu.serve")
    mix = manifest.load_layer_metric("step_stream_mix_share.serve")
    assert decode.STEP in generative.PROGRAM_NAMES
    assert set(mix.PROGRAMS) <= set(generative.PROGRAM_NAMES)
    assert mfu.WINDOW == generative.WINDOW_PROGRAM_NAME
    assert decode.CONFIG == CONFIG["name"]
    with open(generative.__file__) as f:
        text = f.read()
    for family in (decode.CACHE_READ, decode.STEPS, decode.TOUCHED,
                   mfu.TOKENS, mfu.WINDOWS):
        assert f'"{family}"' in text
    with open(xing.__file__) as f:
        text = f.read()
    for scope in mix.SCOPES:
        assert f'jax.named_scope("{scope}")' in text


# ---------------------------------------------------------- the rehearsal


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_ends_in_the_contracts_line(capsys, trace):
    """The unchanged ``engine`` driver, the engine with the contract of
    models/xing.py, prompts of one to six windows prefilled a window at a
    time into a by-position cache, the served tokens compared with
    reference/xing.py under its published constants."""
    code = bench_run.main([
        "--workload", CELL, "--seed", str(2 ** 31 + 42), "--seconds", "6",
        "--manifest-root", FIXTURE, "--rehearse", "--trace", str(trace),
        "--control"])
    out = capsys.readouterr().out
    assert code == 0
    result = read_result(out)
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "check served_token_gap.mean" in out and "(limit " in out
    assert "control[fp8] correct: False" in out
    for m in result["metrics"].values():
        assert np.isfinite(m["value"])
    if trace:
        assert {"batch_occupancy.serve", "decode_step_ms.serve",
                "device_idle_share.serve", "expert_load_ratio.serve",
                "latent_fetch_valid_share.serve"} <= set(result["metrics"])
        # the CPU's trace has no "XLA Modules" line for the three to read
        assert not set(ENTRIES) & set(result["metrics"])
        assert result["breakdown"]["device_ops"]
    else:
        assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
