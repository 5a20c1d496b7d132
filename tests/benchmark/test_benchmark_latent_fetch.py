"""``latent_fetch_valid_share.serve`` (ISSUE 41): the reader held to its
entry, on hand-built registries (valid over span with the span counted,
nothing without it), and in a rehearsal of the tiny openPangu cell on the
CPU from a fixture root of its own (``fixture_latent_fetch/``), where the
engine's own counters feed it.  Nothing here is a device number."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, run as bench_run
from rehearsal import read_result

NAME = "latent_fetch_valid_share.serve"
FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_latent_fetch")
CELL = "tiny-pangu-moe.reason-closed"
REAL_CELL = "openpangu-ultra-moe-718b.reason-decode-closed"
SPEC = manifest.load()
ENTRY = {
    "name": NAME, "unit": "%", "better": "higher",
    "source": "program_counter", "layer": "kernels / device",
    "moves": "serve_tokens_per_s", "workloads": [REAL_CELL],
}
FACTS = {"serve_steps": {"counter_steps": 10}}


def telemetry(account, steps=10):
    """A private registry as an engine leaves it after ``steps`` decode
    steps that each booked ``account``."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reg = MetricsRegistry()
    t = DecodeTelemetry(reg, "0")
    for _ in range(steps):
        t.on_step(0.02, 0.02, 128, 128, 0, 128)
        t.on_cache(account)
    return reg


def test_the_entry_is_the_issues_and_the_last_of_its_list():
    assert SPEC["per_layer"][-1] == ENTRY
    reader = manifest.load_layer_metric(NAME)
    assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
        ENTRY["unit"], ENTRY["layer"], ENTRY["source"], ENTRY["moves"])
    assert manifest.NAME_RE.match(NAME) and manifest.UNIT_RE.match("%")
    listed = {m["name"] for m in manifest.metrics_for(
        SPEC, "per_layer", REAL_CELL)}
    assert NAME in listed and "cache_valid_share.serve" not in listed
    for cell in SPEC["workloads"]:
        if cell["name"] != REAL_CELL:
            assert NAME not in {m["name"] for m in manifest.metrics_for(
                SPEC, "per_layer", cell["name"])}
    with open(os.path.join(FIXTURE, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        k: v for k, v in ENTRY.items() if k != "workloads"}


def test_share_is_valid_bytes_over_the_blocks_fetched():
    reader = manifest.load_layer_metric(NAME)
    reg = telemetry({
        "cache_bytes": {"latent": 0.8e9},
        "cache_span_bytes": {"latent": 1e9}})
    assert reader.read(FACTS, reg) == pytest.approx(80.0)
    # totals of the run, not a mean of the steps' shares
    from tpu_pipelines.serving.generative import DecodeTelemetry

    DecodeTelemetry(reg, "0").on_cache({
        "cache_bytes": {"latent": 1e9}, "cache_span_bytes": {"latent": 9e9}})
    assert reader.read(FACTS, reg) == pytest.approx(100.0 * 9 / 19)


@pytest.mark.parametrize("account", [
    # the parent commit: latent bytes read, no span counted
    {"cache_bytes": {"latent": 1e9}},
    # another contract: a span, of other kinds
    {"cache_bytes": {"window": 1.5e9, "full": 1e9},
     "cache_span_bytes": {"window": 1.6e9, "full": 2.4e9}},
    {"cache_bytes": {"window": 4e9, "chunk": 1e9}},
], ids=["no_span", "other_kinds_with_span", "other_kinds"])
def test_reader_returns_nothing_where_there_is_nothing_to_read(account):
    from tpu_pipelines.observability.metrics import MetricsRegistry

    reader = manifest.load_layer_metric(NAME)
    assert reader.read(FACTS, telemetry(account)) is None
    assert reader.read(FACTS, MetricsRegistry()) is None
    assert reader.read({}, telemetry({
        "cache_bytes": {"latent": 1e9},
        "cache_span_bytes": {"latent": 2e9}})) is None


def test_command_as_reader_does_not_take_the_latent_span_for_its_own():
    """``cache_valid_share.serve`` reads the same two families and asks
    for exactly its contract's kinds: a latent span is not its."""
    other = manifest.load_layer_metric("cache_valid_share.serve")
    reg = telemetry({
        "cache_bytes": {"latent": 0.8e9},
        "cache_span_bytes": {"latent": 1e9}})
    assert other.read(
        {**FACTS, "trace": {"modules": [], "busy_s": 1.0}}, reg) is None


def test_the_reader_asks_for_the_programs_own_names():
    from tpu_pipelines.models import pangu_moe
    from tpu_pipelines.serving import generative

    reader = manifest.load_layer_metric(NAME)
    with open(generative.__file__) as f:
        text = f.read()
    for family in (reader.CACHE_READ, reader.CACHE_SPAN):
        assert f'"{family}"' in text
    with open(pangu_moe.__file__) as f:
        text = f.read()
    assert f'"cache_span_bytes": {{"{reader.KIND}":' in text
    assert f'"{reader.KIND}": CacheKind(' in text


def test_rehearsal_of_the_cell_reports_the_share(capsys):
    """The engine with the contract of models/pangu_moe.py, the kernel
    interpreted: 4 slots of 144 positions, one key block over them, so
    every live row is handed 144 positions a step and the share is the
    rows' mean depth over 144."""
    code = bench_run.main([
        "--workload", CELL, "--seed", str(2 ** 31 + 41), "--seconds", "6",
        "--manifest-root", FIXTURE, "--rehearse", "--trace", "1"])
    out = capsys.readouterr().out
    assert code == 0
    result = read_result(out)
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {NAME, "batch_occupancy.serve", "decode_step_ms.serve"} <= set(
        result["metrics"])
    share = result["metrics"][NAME]
    assert share["unit"] == "%" and np.isfinite(share["value"])
    # prompts of 10 to 96 and up to 40 tokens behind them
    assert 100.0 * 10 / 144 < share["value"] < 100.0 * 136 / 144
