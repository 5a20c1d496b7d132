"""The command-a-plus-05-2026 configuration and cell (ISSUE 35): the
configuration file held to the catalog row and to the issue's cut, the work
functions of benchmark/work_command_a.py on the issue's own arithmetic, the
readers of what the engine now counts on hand-built facts, and a rehearsal
of the cell on the CPU from a fixture root of its own
(``fixture_command_a/``).

``BENCHMARK.json`` lists the four new readers since PR 39; the table below
is the issue's, and holds the reader, the entry and the fixture's entry to
one another.  Nothing here is a device number."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, run as bench_run, work_command_a as work
from rehearsal import read_result

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_command_a")
CELL = "tiny-command-a.longctx-closed"
REAL_CELL = "command-a-plus-05-2026.longctx-decode-closed"
SPEC = manifest.load()
CONFIG = manifest.load_config(SPEC, "command-a-plus-05-2026")
HP = CONFIG["hparams"]

# unit, layer, source, the end-to-end metric it moves, better
ENTRIES = {
    "winfull_decode_hbm_share.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "winfull_prefill_mfu.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "cache_valid_share.serve":
        ("%", "model step", "program_counter", "serve_tokens_per_s",
         "higher"),
    "grouped_product_roofline.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
}
MODEL = {
    "d_model": 4096, "d_ff": 4096, "n_layers": 4, "n_heads": 128,
    "head_dim": 128, "vocab_size": 32768, "weight_itemsize": 2,
    "kv_itemsize": 2,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


# ------------------------------------------------------- the configuration


def test_widths_are_the_sources():
    src = CONFIG["source_config"]
    assert (HP["d_model"], HP["n_heads"], HP["n_kv_heads"], HP["head_dim"],
            HP["d_expert"], HP["n_experts"], HP["experts_per_token"],
            HP["n_shared_experts"], HP["window_size"], HP["full_every"],
            HP["rope_theta"], HP["layer_norm_eps"], HP["logit_scale"]) == (
        src["hidden_size"], src["num_attention_heads"],
        src["num_key_value_heads"], src["head_dim"],
        src["intermediate_size"], src["num_experts"],
        src["num_experts_per_tok"], src["num_shared_experts"],
        src["sliding_window"], src["layer_switch"], src["rope_theta"],
        src["layer_norm_eps"], src["logit_scale"])
    assert HP["d_ff"] == HP["d_expert"] and HP["shared_average"] == 1
    assert HP["routed_scaling_factor"] == 1.0
    assert src["use_parallel_block"] and src["norm_topk_prob"]
    assert src["tie_word_embeddings"] and not src["use_qk_norm"]
    assert (src["expert_selection_fn"],
            src["shared_expert_combination_strategy"],
            src["position_embedding_type"], src["first_k_dense_replace"]
            ) == ("sigmoid", "average", "rope_gptj", 0)
    # the pattern the program builds: three sliding, one full
    full = [i % HP["full_every"] == HP["full_every"] - 1 for i in range(32)]
    assert src["layer_types"] == [
        "full_attention" if f else "sliding_attention" for f in full]
    assert CONFIG["weight_dtype"] == CONFIG["compute_dtype"] == "bfloat16"


def test_the_source_config_is_the_catalogs_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "command-a-plus-05-2026")
    assert CONFIG["source_config"] == row["config"]
    assert CONFIG["source"] == row["source_url"] == next(
        c for c in SPEC["configs"] if c["name"] == CONFIG["name"])["source"]


def test_the_cut_is_the_issues():
    """Every key of the source's config stands at the top of the file with
    the value the cell runs; only the three keys under ``reduced`` differ,
    each at its floor, with the published count and the deployment said."""
    src = CONFIG["source_config"]
    differ = {k for k, v in src.items() if CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == set(REDUCED)
    assert next(c for c in SPEC["configs"]
                if c["name"] == CONFIG["name"])["reduced"] == REDUCED
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 16, 32768) == (
        HP["n_layers"], HP["experts_held"], HP["vocab_size"])
    # the floors: one whole period, 8 experts, 1/8 of the vocabulary
    assert HP["n_layers"] % HP["full_every"] == 0
    assert HP["experts_held"] >= 8
    assert HP["vocab_size"] * 8 >= src["vocab_size"]
    assert src["num_experts"] == 8 * HP["experts_held"]
    assert HP["expert_offset"] == 0
    for key in REDUCED:
        assert str(src[key]) in CONFIG["changed"][key]
    assert "8 chips share each layer" in CONFIG["reduced_because"]
    assert "pipeline stages of 4 layers" in CONFIG["reduced_because"]
    for key in ("shared_experts", "gate", "full_layers", "rotary_pairs",
                "expert_width", "norm", "prefix_dense", "window", "scores",
                "weights", "eos", "context"):
        assert CONFIG["assumed"][key]
    assert any("vision tower" in d for d in CONFIG["departures"])
    assert any("window" in d for d in CONFIG["departures"])
    for key in ("engine_because", "memory"):
        assert len(CONFIG[key]) > 100
    assert set(CONFIG["check"]["limits"]) == set(
        CONFIG["check"]["limits_because"]) - {"sample_requests"} == {
        "served_token_gap.widest", "served_token_gap.mean"}
    # the widest stands as PR 35 set it; PR 39 put the mean beside it
    assert CONFIG["check"]["limits"]["served_token_gap.widest"] == 0.2


def test_the_cell_is_the_issues():
    cell = manifest.cell(SPEC, REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command-a-plus-05-2026", "longctx-decode-closed", 1)
    assert "8x its share" in cell["why"]
    with open(manifest.traffic_path(cell["traffic"])) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["callers"], mix["block"]) == ("closed", 64, 32)
    assert mix["prompt_len"] == {
        "dist": "lognormal_int", "median": 6144, "sigma": 0.7, "low": 1024,
        "high": 16384}
    assert mix["output_len"] == {
        "dist": "lognormal_int", "median": 512, "sigma": 0.5, "low": 128,
        "high": 2048}
    assert (mix["settle_s"], mix["drain_s"]) == (20, 60)
    engine = CONFIG["engine"]
    assert engine["max_input_len"] == mix["prompt_len"]["high"]
    assert engine["max_decode_len"] == mix["output_len"]["high"]
    assert mix["callers"] == 2 * engine["max_batch_size"] == 64
    assert 256 <= engine["prefill_window_len"] <= 1024
    assert HP["window_size"] % engine["prefill_window_len"] == 0
    assert engine["prefill_chunk_pages"] >= 1
    # each held expert sees what it would if each of 8 chips decoded 4 rows
    per_expert = engine["max_batch_size"] * HP["experts_per_token"] \
        / HP["n_experts"]
    assert per_expert == 2
    listed = {
        m["name"] for section in ("end_to_end", "per_layer")
        for m in manifest.metrics_for(SPEC, section, cell["name"])}
    assert listed >= {
        "serve_tokens_per_s", "setup_s", "batch_occupancy.serve",
        "decode_step_ms.serve", "device_idle_share.serve",
        "ms_per_token_p95.offline"} | set(ENTRIES)
    assert cell in SPEC["workloads"]


def test_the_program_builds_what_the_file_says():
    """The parameter count of the model the driver builds is the issue's
    table: 4,733.3 M with the norms' gains aside, and the arena's arrays
    are the issue's 125.8 MB a slot."""
    import jax

    from tpu_pipelines.models import command_a

    model = command_a.build_command_a_model(HP)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": np.ones((1, 8), np.int32)})["params"])
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    count = lambda keep: sum(
        int(np.prod(x.shape)) for p, x in flat if keep(str(p[-1])))
    layer = 2 * 4096 * 16384 + 2 * 4096 * 1024 + 4096 * 128 \
        + 3 * 4096 * 16384 + 16 * 3 * 4096 * 4096
    assert layer == 1_149_763_584
    assert count(lambda k: "scale" not in k) == 4 * layer + 32768 * 4096 \
        == 4_733_272_064
    assert count(lambda k: "scale" in k) == 5 * 4096
    fns = command_a.make_continuous_decode_fns(
        model, **{k: v for k, v in CONFIG["engine"].items()
                  if k in ("max_input_len", "max_decode_len",
                           "prefill_window_len")})
    cache = jax.eval_shape(lambda: fns.blank_cache(32))
    assert fns.cache_positions == 18432
    assert sorted({x.shape for x in jax.tree_util.tree_leaves(cache)}) == [
        (32, 8, 4096, 128), (32, 8, 18432, 128)]
    assert sum(x.size * 2 for x in jax.tree_util.tree_leaves(cache)) \
        == 32 * (3 * 4096 + 18432) * 4096 == 4_026_531_840


# ------------------------------------------------------ work, from shapes


def test_work_is_the_issues_arithmetic():
    assert work.attention_params(HP) == 2 * 67_108_864 + 2 * 4_194_304
    assert work.expert_params(HP) == 3 * 4096 * 4096 == 50_331_648
    assert work.fixed_params(HP) == 344_457_216        # the issue's 344.46 M
    assert work.layer_kinds(HP) == (3, 1)
    assert work.entry_bytes(HP, 2) == 4096
    everything = work.decode_weight_bytes(HP, 2, 4 * 16)
    assert everything == 2 * 4_733_272_064
    assert 9.46e9 < everything < 9.47e9          # 11.6 ms at 819 GB/s
    # an expert that no row chose is not read: 100.7 MB less
    assert everything - work.decode_weight_bytes(HP, 2, 63) == 100_663_296
    # 32 rows at 1 k and 16 k positions: the rings stop at 4,096
    depths = [1024] * 16 + [16384] * 16
    entries = work.decode_cache_entries(HP, depths)
    assert entries == {"window": 3 * 16 * (1024 + 4096),
                       "full": 16 * (1024 + 16384)}
    assert sum(entries.values()) * 4096 == 2_147_483_648
    flops = work.decode_step_flops(HP, depths, 64.0)
    assert flops == pytest.approx(
        2.0 * (4 * 344_457_216 + 4096 * 32768) * 32
        + 2.0 * 50_331_648 * 64 + 4.0 * 128 * 128 * 524_288)
    assert 0.13e12 < flops < 0.14e12          # 0.7 ms at the chip's peak
    full = work.prefill_window_flops(HP, 512)
    assert 1.64e12 < full < 1.66e12           # 8.4 ms at the chip's peak
    assert 0.23 < work.prefill_window_flops(HP, 128) / full < 0.25
    # the grouped product at two assignments an expert: bound by bytes
    got = work.grouped_product_work(4096, 4096, 14.0, 32.0)
    assert got[0] == 2 * (14 * 4096 * 4096 + 32 * 4096) + 4 * 32 * 4096
    assert got[1] == 2.0 * 32 * 4096 * 4096
    assert work.roofline_seconds(got, PEAKS) == got[0] / 819e9
    assert work.roofline_seconds((1.0, 197e12), PEAKS) == 1.0


# ------------------------------------------------------------ the readers


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_is_the_issues_and_is_listed(name):
    reader = manifest.load_layer_metric(name)
    unit, layer, source, moves, _ = ENTRIES[name]
    assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
        unit, layer, source, moves)
    assert reader.MOVES in {m["name"] for m in SPEC["end_to_end"]}
    assert reader.LAYER in {m["layer"] for m in SPEC["per_layer"]}
    assert manifest.NAME_RE.match(name) and manifest.UNIT_RE.match(unit)
    listed = {m["name"]: m for m in SPEC["per_layer"]}[name]
    assert (listed["unit"], listed["layer"], listed["source"],
            listed["moves"], listed["better"]) == ENTRIES[name]
    assert REAL_CELL in listed["workloads"]
    assert reader.read({}) is None
    with open(os.path.join(FIXTURE, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[name]
    assert (entry["unit"], entry["layer"], entry["source"], entry["moves"],
            entry["better"]) == ENTRIES[name]


def telemetry(kinds=("window", "full")):
    """A private registry as an engine leaves it after 10 decode steps
    that read 1.5 GB of valid ring entries and 1 GB of valid full-cache
    entries each, out of arrays that span 1.6 and 2.4 GB, with 64
    assignments to 56 experts, and 4 prefill windows holding 1,024
    prompt tokens."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reg = MetricsRegistry()
    t = DecodeTelemetry(reg, "0")
    for i in range(10):
        t.on_step(0.03, 0.03, 32, 32, 0, 32)
        t.on_cache({
            "cache_bytes": dict(zip(kinds, (1.5e9, 1e9))),
            "cache_entries": dict(zip(kinds, (366210, 244140))),
            "cache_span_bytes": dict(zip(kinds, (1.6e9, 2.4e9))),
            "expert_assignments": 64, "experts_touched": 56,
            "expert_load_ratio": 1.5 if i % 2 else 2.5})
    for n in (512, 256, 256, 0):
        t.on_prefill_window(n)
    return reg


@pytest.fixture
def registry():
    return telemetry()


def facts(modules, model=MODEL):
    return {
        "serve_steps": {"counter_steps": 10}, "serve_model": model,
        "peaks": PEAKS, "trace": {"modules": modules, "busy_s": 2.0},
    }


MODULES = [
    ("jit_run(1)", 0.0, 0.02), ("jit_run(1)", 0.1, 0.03),
    ("jit_prefill_window(2)", 0.2, 0.028),
    ("jit_prefill_window(2)", 0.4, 0.032),
    ("jit_insert(3)", 0.6, 0.01),
]
# twelve products a step: 0.5 ms each in the first step, 0.7 in the second;
# one more inside a prefill window, which is not the step's
OPS = (
    [("gmm f32[256,4096]", 0.001 * i, 0.0005) for i in range(12)]
    + [("gmm f32[256,4096]", 0.1 + 0.002 * i, 0.0007) for i in range(12)]
    + [("gmm f32[4096,4096]", 0.21, 0.004),
       ("fusion f32[32,4096]", 0.005, 0.001)])


def test_decode_share_is_bytes_over_bandwidth_over_the_steps_time(registry):
    reader = manifest.load_layer_metric("winfull_decode_hbm_share.serve")
    want = 100.0 * ((work.decode_weight_bytes(HP, 2, 56.0) + 2.5e9)
                    / 819e9) / 0.025
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 50.0 < want < 60.0


def test_valid_share_is_valid_bytes_over_the_arrays_span(registry):
    reader = manifest.load_layer_metric("cache_valid_share.serve")
    assert reader.read(facts(MODULES), registry) == pytest.approx(62.5)
    assert reader.read(facts([]), registry) == pytest.approx(62.5)


def test_prefill_mfu_is_the_mean_windows_flops_over_its_time(registry):
    reader = manifest.load_layer_metric("winfull_prefill_mfu.serve")
    want = 100.0 * work.prefill_window_flops(HP, 256.0) / 0.03 / 197e12
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 10.0 < want < 20.0


def test_grouped_product_share_is_its_bytes_over_its_time_in_the_step(
        registry):
    reader = manifest.load_layer_metric("grouped_product_roofline.serve")
    assert reader.kernel_seconds_a_step(OPS, MODULES) == pytest.approx(
        12 * (0.0005 + 0.0007) / 2)
    one = work.roofline_seconds(
        work.grouped_product_work(4096, 4096, 56.0, 64.0), PEAKS)
    want = 100.0 * 3 * one / 0.0072
    assert reader.read(
        facts(MODULES), registry, events=(OPS, MODULES)
    ) == pytest.approx(want)
    assert 90.0 < want < 100.0
    # no such kernel in the trace (the CPU), or no step program
    assert reader.read(
        facts(MODULES), registry, events=(OPS[-1:], MODULES)) is None
    assert reader.read(
        facts(MODULES), registry, events=(OPS, MODULES[2:])) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_returns_nothing_where_there_is_nothing_to_read(
        name, registry):
    """The parent commit has no such counters, another contract counts
    other kinds of cache and no span, another model has other sizes, and
    a CPU rehearsal's trace has no "XLA Modules" line: nothing, and no
    error."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reader = manifest.load_layer_metric(name)
    other = MetricsRegistry()
    t = DecodeTelemetry(other, "0")
    t.on_step(0.02, 0.02, 8, 8, 0, 8)
    t.on_cache({"cache_bytes": {"latent": 1e9}, "expert_assignments": 256,
                "expert_load_ratio": 2.0})
    t.on_prefill_window(256)
    assert reader.read(
        {"trace": {"modules": MODULES, "busy_s": 2.0}}, registry) is None
    assert reader.read(facts(MODULES), MetricsRegistry()) is None
    assert reader.read(facts(MODULES), other) is None
    assert reader.read(facts(MODULES), telemetry(("window", "chunk"))) is None
    if name != "cache_valid_share.serve":
        assert reader.read(facts([]), registry) is None
        assert reader.read(
            facts(MODULES, {**MODEL, "n_layers": 16}), registry) is None


def test_readers_look_for_the_programs_own_names():
    from tpu_pipelines.models import command_a, pangu_moe
    from tpu_pipelines.serving import generative

    decode = manifest.load_layer_metric("winfull_decode_hbm_share.serve")
    mfu = manifest.load_layer_metric("winfull_prefill_mfu.serve")
    product = manifest.load_layer_metric("grouped_product_roofline.serve")
    assert decode.STEP in generative.PROGRAM_NAMES
    assert mfu.WINDOW == generative.WINDOW_PROGRAM_NAME
    assert decode.CONFIG == CONFIG["name"]
    with open(generative.__file__) as f:
        text = f.read()
    for family in (decode.CACHE_READ, decode.CACHE_SPAN, decode.STEPS,
                   decode.TOUCHED, mfu.TOKENS, mfu.WINDOWS,
                   product.ASSIGNMENTS, "serving_decode_cache_entries"):
        assert f'"{family}"' in text
    with open(command_a.__file__) as f:
        text = f.read()
    for kind in decode.KINDS:
        assert f'"{kind}": CacheKind(' in text
    for scope in ("attn.full", "attn.window"):
        assert f'"{scope}"' in text
    with open(pangu_moe.__file__) as f:
        text = f.read()
    for scope in ("moe.route", "moe.experts", "moe.shared"):
        assert f'jax.named_scope("{scope}")' in text
    # the kernel's events are named after the function that calls it
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    assert gmm.__name__ == product.KERNEL


# ---------------------------------------------------------- the rehearsal


@pytest.fixture
def tiny_reference(monkeypatch):
    """The reference states the published sizes that the weights do not
    show (``SIZES``); the fixture's model is the size of
    tests/test_command_a.py, and the test says so to the reference.  The
    rehearsal also gets a metrics registry of its own."""
    from benchmark.reference import command_a as ref

    with open(os.path.join(
            FIXTURE, "benchmark", "configs", "tiny-command-a.json")) as f:
        hp = json.load(f)["hparams"]
    for key, name in (("window", "window_size"), ("head_dim", "head_dim"),
                      ("top_k", "experts_per_token"),
                      ("n_shared", "n_shared_experts")):
        monkeypatch.setitem(ref.SIZES, key, hp[name])
    # A registry of the run's own: the readers take the process's totals,
    # and a worker that ran another fixture's engine before this one (a
    # latent cache) has other kinds of cache in them, which
    # cache_valid_share.serve rightly takes for another contract.
    from tpu_pipelines.observability import metrics

    monkeypatch.setattr(metrics, "_DEFAULT", metrics.MetricsRegistry())


def rehearse(capsys, *extra, seed=2 ** 31 + 35):
    code = bench_run.main([
        "--workload", CELL, "--seed", str(seed), "--seconds", "6",
        "--manifest-root", FIXTURE, "--rehearse", *extra])
    out = capsys.readouterr().out
    return code, out


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_ends_in_the_contracts_line(
        capsys, tiny_reference, trace):
    """The unchanged ``engine`` driver, the engine with the contract of
    models/command_a.py, prompts of two to twelve prefill windows (up to
    six rings) prefilled a window at a time into rings and by-position
    arrays, the served tokens compared with reference/command_a.py.  Six
    seconds of window: under six busy test workers a shorter one has seen
    no request come due."""
    code, out = rehearse(capsys, "--trace", str(trace), "--control")
    assert code == 0
    result = read_result(out)
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "check served_token_gap.widest" in out and "(limit " in out
    assert "control[fp8] correct: False" in out
    for m in result["metrics"].values():
        assert np.isfinite(m["value"])
    if trace:
        assert {"batch_occupancy.serve", "decode_step_ms.serve",
                "device_idle_share.serve", "cache_valid_share.serve",
                "expert_load_ratio.serve"} <= set(result["metrics"])
        # the CPU's trace has no "XLA Modules" line and no kernel
        assert not {"winfull_decode_hbm_share.serve",
                    "winfull_prefill_mfu.serve",
                    "grouped_product_roofline.serve"} & set(
            result["metrics"])
        assert 5.0 < result["metrics"]["cache_valid_share.serve"][
            "value"] < 100.0
        assert 1.0 <= result["metrics"]["expert_load_ratio.serve"][
            "value"] <= 4.0
        assert result["breakdown"]["device_ops"]
    else:
        assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
