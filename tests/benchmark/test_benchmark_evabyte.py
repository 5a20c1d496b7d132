"""The EvaByte configuration and cell (ISSUE 27): the configuration file held
to its source, the work functions of benchmark/work_evabyte.py on the
issue's own arithmetic, the three readers of what the engine now counts on
hand-built facts, and a rehearsal of the cell on the CPU from a fixture root
of its own (``fixture_evabyte/``).

``BENCHMARK.json`` lists the three readers since PR 39; the table below is
the issue's, and holds the reader, the entry and the fixture's entry to one
another.  Nothing here is a device number."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, run as bench_run, work_evabyte
from rehearsal import read_result

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_evabyte")
CELL = "tiny-evabyte.doc-closed"
SPEC = manifest.load()
CONFIG = manifest.load_config(SPEC, "evabyte-6.5b")

# unit, layer, source, the end-to-end metric it moves, better
ENTRIES = {
    "eva_decode_hbm_share.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "eva_prefill_mfu.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "prefill_device_share.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "lower"),
}
MODEL = {
    "d_model": 4096, "d_ff": 11008, "n_layers": 16, "n_heads": 32,
    "head_dim": 128, "vocab_size": 320, "weight_itemsize": 2,
    "kv_itemsize": 2,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ------------------------------------------------------- the configuration


def test_evabyte_widths_are_the_sources():
    src, hp = CONFIG["source_config"], CONFIG["hparams"]
    assert (hp["d_model"], hp["d_ff"], hp["n_heads"], hp["vocab_size"],
            hp["window_size"], hp["chunk_size"], hp["num_pred_heads"],
            hp["rope_theta"], hp["rms_norm_eps"], hp["init_std"]) == (
        src["hidden_size"], src["intermediate_size"],
        src["num_attention_heads"], src["vocab_size"], src["window_size"],
        src["chunk_size"], src["num_pred_heads"], src["rope_theta"],
        src["rms_norm_eps"], src["init_std"])
    assert hp["head_dim"] * hp["n_heads"] == src["hidden_size"]
    assert src["num_key_value_heads"] == src["num_attention_heads"]
    # the one cut: depth, never under 12, and said so
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert 12 <= hp["n_layers"] < src["num_hidden_layers"] == 32
    assert "num_hidden_layers" in CONFIG["changed"]
    assert CONFIG["weight_dtype"] == CONFIG["compute_dtype"] == "bfloat16"


def test_the_file_holds_the_sources_keys_as_it_is_run():
    """Every key of the source's config stands at the top of the file with
    the value the cell runs; only the keys under ``reduced`` differ."""
    src = CONFIG["source_config"]
    differ = {k for k, v in src.items() if CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"])
    assert CONFIG["num_hidden_layers"] == CONFIG["hparams"]["n_layers"]
    for key in ("summary_scale", "summary_keys", "head_columns",
                "mixedp_attn", "weights", "eos"):
        assert CONFIG["assumed"][key]
    assert any("head 0" in d for d in CONFIG["departures"])


def test_the_cell_is_the_issues():
    cell = manifest.cell(SPEC, "evabyte-6.5b.doc-decode-closed")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte-6.5b", "doc-decode-closed", 1)
    with open(manifest.traffic_path(cell["traffic"])) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["callers"], mix["block"]) == ("closed", 16, 32)
    assert mix["prompt_len"] == {
        "dist": "uniform_int", "low": 4096, "high": 12288}
    assert mix["output_len"] == {
        "dist": "lognormal_int", "median": 256, "sigma": 0.5, "low": 64,
        "high": 768}
    engine = CONFIG["engine"]
    assert engine["max_input_len"] >= mix["prompt_len"]["high"]
    assert engine["max_decode_len"] >= mix["output_len"]["high"]
    assert mix["callers"] == 2 * engine["max_batch_size"]
    listed = {
        m["name"] for section in ("end_to_end", "per_layer")
        for m in manifest.metrics_for(SPEC, section, cell["name"])}
    assert listed >= {
        "serve_tokens_per_s", "setup_s", "batch_occupancy.serve",
        "decode_step_ms.serve", "device_idle_share.serve",
        "ms_per_token_p95.offline"} | set(ENTRIES)


# ------------------------------------------------------ work, from shapes


def test_work_is_the_issues_arithmetic():
    shape = {k: MODEL[k] for k in ("d_model", "d_ff", "n_heads", "head_dim")}
    per_layer = work_evabyte.layer_matmul_params(**shape)
    assert per_layer == 4 * 4096 ** 2 + 3 * 4096 * 11008 == 202_375_168
    weights = work_evabyte.decode_weight_bytes(
        **{k: v for k, v in MODEL.items() if k != "kv_itemsize"})
    assert weights == 2 * (16 * per_layer + 320 * 4096)
    assert 6.47e9 < weights < 6.49e9
    full = work_evabyte.prefill_window_flops(
        tokens=2048, n_layers=16, **shape)
    assert full == 16 * (
        2 * per_layer * 2048 + 4 * 4096 * 2048 * 2049 / 2)
    assert 13.8e12 < full < 13.9e12          # 70 ms at the chip's peak
    # a window a quarter full is a little under a quarter of the work
    assert 0.2 < work_evabyte.prefill_window_flops(
        tokens=512, n_layers=16, **shape) / full < 0.25


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
    assert "evabyte-6.5b.doc-decode-closed" in listed["workloads"]
    assert reader.read({}) is None
    with open(os.path.join(FIXTURE, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[name]
    assert (entry["unit"], entry["layer"], entry["source"], entry["moves"],
            entry["better"]) == ENTRIES[name]


@pytest.fixture
def registry():
    """A private registry as an engine leaves it after 10 decode steps
    that read 4 GB of ring and 1 GB of chunk table each, and 4 prefill
    windows holding 6,144 prompt tokens."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reg = MetricsRegistry()
    t = DecodeTelemetry(reg, "0")
    for _ in range(10):
        t.on_step(0.02, 0.02, 8, 8, 0, 8)
        t.on_cache({
            "cache_bytes": {"window": 4e9, "chunk": 1e9},
            "window_rollovers": 0, "chunk_summaries": 1})
    for n in (2048, 2048, 2048, 0):
        t.on_prefill_window(n)
    return reg


def facts(modules):
    return {
        "serve_steps": {"counter_steps": 10}, "serve_model": MODEL,
        "peaks": PEAKS, "trace": {"modules": modules, "busy_s": 2.0},
    }


MODULES = [
    ("jit_run(1)", 0.0, 0.02), ("jit_run(1)", 0.1, 0.03),
    ("jit_prefill_window(2)", 0.2, 0.1), ("jit_prefill_window(2)", 0.4, 0.14),
    ("jit_insert(3)", 0.6, 0.01), ("jit_prefill(4)", 0.7, 0.26),
]


def test_decode_share_is_bytes_over_bandwidth_over_the_steps_time(registry):
    reader = manifest.load_layer_metric("eva_decode_hbm_share.serve")
    weights = 2 * (16 * 202_375_168 + 320 * 4096)
    want = 100.0 * ((weights + 5e9) / 819e9) / 0.025
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 50.0 < want < 60.0


@pytest.mark.parametrize("kinds", [
    {"latent": 1e9},                        # openPangu's contract
    {"window": 4e9, "full": 1e9},           # command-a's
    {"window": 4e9},                        # a ring alone
    {"window": 4e9, "chunk": 1e9, "latent": 1e9},
])
def test_decode_share_asks_for_its_own_kinds_of_cache(kinds):
    """Another contract's cache bytes are not priced with EvaByte's weight
    arithmetic: until PR 39 the reader summed every kind and returned 44.2
    in openPangu's cell."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reg = MetricsRegistry()
    t = DecodeTelemetry(reg, "0")
    for _ in range(10):
        t.on_step(0.02, 0.02, 8, 8, 0, 8)
        t.on_cache({"cache_bytes": kinds})
    reader = manifest.load_layer_metric("eva_decode_hbm_share.serve")
    assert reader.read(facts(MODULES), reg) is None


def test_prefill_mfu_is_the_mean_windows_flops_over_its_time(registry):
    reader = manifest.load_layer_metric("eva_prefill_mfu.serve")
    flops = work_evabyte.prefill_window_flops(
        tokens=1536, **{k: MODEL[k] for k in (
            "d_model", "d_ff", "n_layers", "n_heads", "head_dim")})
    want = 100.0 * flops / 0.12 / 197e12
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 40.0 < want < 50.0


def test_prefill_share_is_both_prefill_programs_over_busy_time():
    reader = manifest.load_layer_metric("prefill_device_share.serve")
    assert reader.read(facts(MODULES)) == pytest.approx(25.0)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_returns_nothing_where_there_is_nothing_to_read(
        name, registry):
    """The parent commit has no such counters and a CPU rehearsal's trace
    no "XLA Modules" line: nothing, and no error."""
    from tpu_pipelines.observability.metrics import MetricsRegistry

    reader = manifest.load_layer_metric(name)
    takes_registry = name.startswith("eva_")
    call = (lambda f, reg: reader.read(f, reg)) if takes_registry \
        else (lambda f, reg: reader.read(f))
    assert call(facts([]), registry) is None
    assert call({"trace": {"modules": MODULES, "busy_s": 2.0}},
                registry) is None                       # not a serve cell
    if takes_registry:
        assert call(facts(MODULES), MetricsRegistry()) is None
        only_t5 = [m for m in MODULES if not m[0].startswith(
            ("jit_run", "jit_prefill_window"))]
        assert call(facts(only_t5), registry) is None


def test_readers_look_for_the_programs_own_names():
    from tpu_pipelines.serving import generative

    decode = manifest.load_layer_metric("eva_decode_hbm_share.serve")
    mfu = manifest.load_layer_metric("eva_prefill_mfu.serve")
    share = manifest.load_layer_metric("prefill_device_share.serve")
    assert decode.STEP in generative.PROGRAM_NAMES
    assert mfu.WINDOW == generative.WINDOW_PROGRAM_NAME
    assert share.PREFILL in generative.PROGRAM_NAMES
    assert generative.WINDOW_PROGRAM_NAME.startswith(share.PREFILL)
    with open(generative.__file__) as f:
        text = f.read()
    for family in (decode.CACHE_READ, decode.STEPS, mfu.TOKENS, mfu.WINDOWS):
        assert f'"{family}"' in text


# ---------------------------------------------------------- the rehearsal


def rehearse(capsys, *extra, seed=2 ** 31 + 27):
    code = bench_run.main([
        "--workload", CELL, "--seed", str(seed), "--seconds", "6",
        "--manifest-root", FIXTURE, "--rehearse", *extra])
    out = capsys.readouterr().out
    return code, out


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_ends_in_the_contracts_line(capsys, trace):
    """The unchanged ``engine`` driver, the engine with the contract of
    models/evabyte.py, prompts of one to three windows prefilled a window
    at a time, the served bytes compared with reference/evabyte.py.  Six
    seconds of window: under six busy test workers three have seen no
    request come due."""
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
                "device_idle_share.serve"} <= set(result["metrics"])
        # the CPU's trace has no "XLA Modules" line for the three to read
        assert not set(ENTRIES) & set(result["metrics"])
        assert result["breakdown"]["device_ops"]
    else:
        assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
