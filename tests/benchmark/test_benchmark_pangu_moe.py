"""The openPangu-Ultra-MoE configuration and cell (ISSUE 31): the
configuration file held to the catalog row and to the issue's cut, the work
functions of benchmark/work_pangu_moe.py on the issue's own arithmetic, the
three readers of what the engine now counts on hand-built facts, and a
rehearsal of the cell on the CPU from a fixture root of its own
(``fixture_pangu_moe/``).

``BENCHMARK.json`` lists the three readers since PR 39; the table below is
the issue's, and holds the reader, the entry and the fixture's entry to one
another.  Nothing here is a device number."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, run as bench_run, work_pangu_moe as work
from rehearsal import read_result

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_pangu_moe")
CELL = "tiny-pangu-moe.reason-closed"
REAL_CELL = "openpangu-ultra-moe-718b.reason-decode-closed"
SPEC = manifest.load()
CONFIG = manifest.load_config(SPEC, "openpangu-ultra-moe-718b")
HP = CONFIG["hparams"]

# unit, layer, source, the end-to-end metric it moves, better
ENTRIES = {
    "pangu_decode_hbm_share.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "pangu_prefill_mfu.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "expert_load_ratio.serve":
        ("ratio", "model step", "program_counter", "serve_tokens_per_s",
         "lower"),
}
MODEL = {
    "d_model": 7680, "d_ff": 18432, "n_layers": 5, "n_heads": 128,
    "head_dim": 128, "vocab_size": 19200, "weight_itemsize": 2,
    "kv_itemsize": 2,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]


# ------------------------------------------------------- the configuration


def test_widths_are_the_sources():
    src = CONFIG["source_config"]
    assert (HP["d_model"], HP["d_ff"], HP["n_heads"], HP["q_lora_rank"],
            HP["kv_lora_rank"], HP["qk_nope_head_dim"],
            HP["qk_rope_head_dim"], HP["v_head_dim"], HP["d_expert"],
            HP["n_experts"], HP["experts_per_token"],
            HP["n_shared_experts"], HP["routed_scaling_factor"],
            HP["rope_theta"], HP["rms_norm_eps"]) == (
        src["hidden_size"], src["intermediate_size"],
        src["num_attention_heads"], src["q_lora_rank"], src["kv_lora_rank"],
        src["qk_nope_head_dim"], src["qk_rope_head_dim"],
        src["v_head_dim"], src["moe_intermediate_size"],
        src["n_routed_experts"], src["num_experts_per_tok"],
        src["n_shared_experts"], src["routed_scaling_factor"],
        src["rope_theta"], src["rms_norm_eps"])
    assert src["sandwich_norm"] is True and src["norm_topk_prob"] is True
    assert HP["head_dim"] == src["v_head_dim"]
    assert CONFIG["weight_dtype"] == CONFIG["compute_dtype"] == "bfloat16"


def test_the_source_config_is_the_catalogs_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "openPangu-Ultra-MoE-718B")
    assert CONFIG["source_config"] == row["config"]
    assert CONFIG["source"] == row["source_url"] == next(
        c for c in SPEC["configs"] if c["name"] == CONFIG["name"])["source"]


def test_the_cut_is_the_issues():
    """Every key of the source's config stands at the top of the file with
    the value the cell runs; only the five keys under ``reduced`` differ,
    each at its floor, with the published count and the deployment said."""
    src = CONFIG["source_config"]
    differ = {k for k, v in src.items() if CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == set(REDUCED)
    assert next(c for c in SPEC["configs"]
                if c["name"] == CONFIG["name"])["reduced"] == REDUCED
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["vocab_size"],
            CONFIG["num_nextn_predict_layers"]) == (5, 1, 16, 19200, 0) == (
        HP["n_layers"], HP["n_dense_layers"], HP["experts_held"],
        HP["vocab_size"], HP["n_mtp"])
    # the floors: four expert layers behind the dense ones, 8 experts, 1/8
    assert HP["n_layers"] - HP["n_dense_layers"] >= 4
    assert HP["experts_held"] >= 8
    assert HP["vocab_size"] * 8 >= src["vocab_size"]
    assert src["n_routed_experts"] % HP["experts_held"] == 0
    assert HP["expert_offset"] == 0
    for key in REDUCED:
        assert str(src[key]) in CONFIG["changed"][key]
    assert "16 chips share each layer" in CONFIG["reduced_because"]
    for key in ("gate", "norm_order", "latent_norms", "rotary_pairs",
                "mtp_halves", "scores", "weights", "eos", "context"):
        assert CONFIG["assumed"][key]
    assert any("prediction module" in d for d in CONFIG["departures"])
    assert any("window" in d for d in CONFIG["departures"])


def test_the_cell_is_the_issues():
    cell = manifest.cell(SPEC, REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "openpangu-ultra-moe-718b", "reason-decode-closed", 1)
    assert "16x its share" in cell["why"]
    with open(manifest.traffic_path(cell["traffic"])) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["callers"], mix["block"]) == ("closed", 256, 32)
    assert mix["prompt_len"] == {
        "dist": "lognormal_int", "median": 320, "sigma": 0.6, "low": 128,
        "high": 1024}
    assert mix["output_len"] == {
        "dist": "lognormal_int", "median": 640, "sigma": 0.5, "low": 256,
        "high": 1536}
    # the 256 requests outstanding at the window's end (136 k tokens
    # owed) drain in 52 to 53 s on the chip (PERF.md §4); one left
    # unfinished is a failed request
    assert mix["settle_s"] >= 16 and mix["drain_s"] == 60
    engine = CONFIG["engine"]
    assert engine["max_input_len"] == mix["prompt_len"]["high"]
    assert engine["max_decode_len"] == mix["output_len"]["high"]
    assert mix["callers"] == 2 * engine["max_batch_size"] == 256
    assert (engine["page_size"], engine["prefill_window_len"]) == (0, 256)
    # each held expert sees what it would if each of 16 chips decoded 8 rows
    per_expert = engine["max_batch_size"] * HP["experts_per_token"] \
        / HP["n_experts"]
    assert per_expert == 4
    listed = {
        m["name"] for section in ("end_to_end", "per_layer")
        for m in manifest.metrics_for(SPEC, section, cell["name"])}
    # the issue's six, and whatever later PRs listed for the cell beside
    # them: its own three readers are held to it by ENTRIES below
    assert listed >= {
        "serve_tokens_per_s", "setup_s", "batch_occupancy.serve",
        "decode_step_ms.serve", "device_idle_share.serve",
        "ms_per_token_p95.offline"} | set(ENTRIES)
    assert cell in SPEC["workloads"]
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}


def test_the_program_builds_what_the_file_says():
    """The parameter count of the model the driver builds is the issue's
    table: 4,918.97 M with the norms' gains aside."""
    import jax

    from tpu_pipelines.models import pangu_moe

    model = pangu_moe.build_pangu_moe_model(HP)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": np.ones((1, 8), np.int32)})["params"])
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    count = lambda keep: sum(
        int(np.prod(x.shape)) for p, x in flat if keep(str(p[-1])))
    assert count(lambda k: "scale" not in k) == 4_918_968_320
    assert count(lambda k: "scale" in k) == 5 * (4 * 7680 + 1536 + 512) + 7680
    fns = pangu_moe.make_continuous_decode_fns(
        model, **{k: v for k, v in CONFIG["engine"].items()
                  if k in ("max_input_len", "max_decode_len",
                           "prefill_window_len")})
    cache = jax.eval_shape(lambda: fns.blank_cache(128))
    assert {x.shape for x in jax.tree_util.tree_leaves(cache)} == {
        (128, 2560, 576)}
    assert sum(x.size * 2 for x in jax.tree_util.tree_leaves(cache)) \
        == 128 * 2560 * 5760 == 1_887_436_800


# ------------------------------------------------------ work, from shapes


def test_work_is_the_issues_arithmetic():
    assert work.attention_params(HP) == (
        7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256
        + 16384 * 7680) == 196_575_232
    assert work.expert_params(HP) == 3 * 7680 * 2048 == 47_185_920
    assert work.layer_params(HP, False) == 196_575_232 + 3 * 7680 * 18432 \
        == 621_248_512
    assert work.layer_params(HP, True) == (
        196_575_232 + 7680 * 256 + 17 * 47_185_920) == 1_000_701_952
    assert work.row_width(HP) == 576
    weights = work.decode_weight_bytes(HP, 2)
    assert weights == 2 * (
        621_248_512 + 4 * 1_000_701_952 + 7680 * 19200)
    assert 9.54e9 < weights < 9.55e9          # 11.7 ms at 819 GB/s
    # 128 rows at 1,300 positions: 5,760 B a position
    latents = work.decode_latent_bytes(HP, [1300] * 128, 2)
    assert latents == 128 * 1300 * 5760 == 958_464_000
    # the absorbed form: 128 heads x (576 + 512) multiply-adds a position
    # a layer, 1,152 B read for them: 242 FLOP a byte
    per_position = 2 * 128 * (576 + 512)
    assert per_position / 1152 == pytest.approx(241.8, abs=0.1)
    flops = work.decode_step_flops(HP, [1300] * 128, 256.0)
    fixed = (621_248_512 + 4 * (1_000_701_952 - 16 * 47_185_920)
             + 7680 * 19200)
    assert flops == pytest.approx(
        2.0 * fixed * 128 + 2.0 * 47_185_920 * 256
        + per_position * 5 * 128 * 1300)
    assert 0.70e12 < flops < 0.71e12          # 3.6 ms at the chip's peak
    full = work.prefill_window_flops(HP, 256)
    assert 0.92e12 < full < 0.93e12           # 4.7 ms at the chip's peak
    assert 0.2 < work.prefill_window_flops(HP, 64) / full < 0.25


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


def telemetry(kind="latent"):
    """A private registry as an engine leaves it after 10 decode steps
    that read 1 GB of latents each and made 256 assignments with the
    fullest expert at 1.5 and 2.5 times the mean, and 4 prefill windows
    holding 512 prompt tokens."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reg = MetricsRegistry()
    t = DecodeTelemetry(reg, "0")
    for i in range(10):
        t.on_step(0.02, 0.02, 128, 128, 0, 128)
        t.on_cache({
            "cache_bytes": {kind: 1e9}, "expert_assignments": 256,
            "expert_load_ratio": 1.5 if i % 2 else 2.5})
    for n in (256, 128, 128, 0):
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
    ("jit_prefill_window(2)", 0.2, 0.018),
    ("jit_prefill_window(2)", 0.4, 0.022),
    ("jit_insert(3)", 0.6, 0.01),
]


def test_decode_share_is_bytes_over_bandwidth_over_the_steps_time(registry):
    reader = manifest.load_layer_metric("pangu_decode_hbm_share.serve")
    want = 100.0 * ((work.decode_weight_bytes(HP, 2) + 1e9) / 819e9) / 0.025
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 50.0 < want < 55.0


def test_prefill_mfu_is_the_mean_windows_flops_over_its_time(registry):
    reader = manifest.load_layer_metric("pangu_prefill_mfu.serve")
    want = 100.0 * work.prefill_window_flops(HP, 128.0) / 0.02 / 197e12
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 10.0 < want < 15.0


def test_load_ratio_is_the_mean_of_the_steps_ratios(registry):
    reader = manifest.load_layer_metric("expert_load_ratio.serve")
    assert reader.read(facts(MODULES), registry) == pytest.approx(2.0)
    assert reader.read(facts([]), registry) == pytest.approx(2.0)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_returns_nothing_where_there_is_nothing_to_read(
        name, registry):
    """The parent commit has no such counters, another contract counts
    another kind of cache and no assignment, another model has other
    sizes, and a CPU rehearsal's trace has no "XLA Modules" line: nothing,
    and no error."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reader = manifest.load_layer_metric(name)
    other = MetricsRegistry()
    t = DecodeTelemetry(other, "0")
    t.on_step(0.02, 0.02, 8, 8, 0, 8)
    t.on_cache({"cache_bytes": {"window": 4e9, "chunk": 1e9},
                "window_rollovers": 0, "chunk_summaries": 1})
    t.on_prefill_window(2048)
    assert reader.read(
        {"trace": {"modules": MODULES, "busy_s": 2.0}}, registry) is None
    assert reader.read(facts(MODULES), MetricsRegistry()) is None
    assert reader.read(facts(MODULES), other) is None
    if name.startswith("pangu_"):
        assert reader.read(facts([]), registry) is None
        assert reader.read(
            facts(MODULES, {**MODEL, "n_layers": 16}), registry) is None


def test_readers_look_for_the_programs_own_names():
    from tpu_pipelines.serving import generative

    decode = manifest.load_layer_metric("pangu_decode_hbm_share.serve")
    mfu = manifest.load_layer_metric("pangu_prefill_mfu.serve")
    load = manifest.load_layer_metric("expert_load_ratio.serve")
    assert decode.STEP in generative.PROGRAM_NAMES
    assert mfu.WINDOW == generative.WINDOW_PROGRAM_NAME
    assert decode.CONFIG == CONFIG["name"]
    with open(generative.__file__) as f:
        text = f.read()
    for family in (decode.CACHE_READ, decode.STEPS, mfu.TOKENS, mfu.WINDOWS,
                   load.SUM, load.COUNT,
                   "serving_decode_expert_assignments_total"):
        assert f'"{family}"' in text
    from tpu_pipelines.models import pangu_moe

    with open(pangu_moe.__file__) as f:
        text = f.read()
    for scope in ("mla.attend", "moe.route", "moe.experts"):
        assert f'jax.named_scope("{scope}")' in text
    assert f'"{decode.KIND}": CacheKind(' in text


# ---------------------------------------------------------- the rehearsal


def rehearse(capsys, *extra, seed=2 ** 31 + 31):
    code = bench_run.main([
        "--workload", CELL, "--seed", str(seed), "--seconds", "6",
        "--manifest-root", FIXTURE, "--rehearse", *extra])
    out = capsys.readouterr().out
    return code, out


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_ends_in_the_contracts_line(capsys, trace):
    """The unchanged ``engine`` driver, the engine with the contract of
    models/pangu_moe.py, prompts of one to six windows prefilled a window
    at a time into a by-position cache, the served tokens compared with
    reference/pangu_moe.py.  Six seconds of window: under six busy test
    workers a shorter one has seen no request come due."""
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
                "device_idle_share.serve", "expert_load_ratio.serve"} <= set(
            result["metrics"])
        # the CPU's trace has no "XLA Modules" line for the two to read
        assert not {"pangu_decode_hbm_share.serve",
                    "pangu_prefill_mfu.serve"} & set(result["metrics"])
        assert 1.0 <= result["metrics"]["expert_load_ratio.serve"][
            "value"] <= 8.0
        assert result["breakdown"]["device_ops"]
    else:
        assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
