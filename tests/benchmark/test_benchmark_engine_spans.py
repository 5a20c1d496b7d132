"""The six readers of the engine's own spans and counters (ISSUE 25), each
on hand-built facts, and one traced rehearsal after which the four counter
readers find something to read.  Both manifests list them since PR 39
(``BENCHMARK.json`` and ``fixture/BENCHMARK.json``).  Nothing here is a
device number."""

import json
import math

import pytest

from benchmark import host_plane, manifest
from rehearsal import read_result, run_cell

SERVE = {"serve_steps": {"counter_steps": 10}}
COUNTER_METRICS = (
    "admission_share.serve", "prefill_ms.serve",
    "queue_wait_mean_ms.serve", "ttft_mean_ms.serve",
)
DEVICE_METRICS = ("prefill_device_ms.serve", "arena_admin_device_share.serve")
CELL = "tiny-t5.prompt-heavy"


# What each reader's manifest entry says (ISSUE 25's table): unit, layer,
# source, the end-to-end metric it moves.
ENTRIES = {
    "admission_share.serve":
        ("%", "engine scheduler", "program_counter", "serve_tokens_per_s"),
    "prefill_ms.serve":
        ("ms", "model step", "program_counter", "serve_tokens_per_s"),
    "queue_wait_mean_ms.serve":
        ("ms", "engine scheduler", "program_counter",
         "serve_ms_per_token_p95"),
    "ttft_mean_ms.serve":
        ("ms", "engine scheduler", "program_counter",
         "serve_ms_per_token_p95"),
    "prefill_device_ms.serve":
        ("ms", "kernels / device", "device_trace", "serve_tokens_per_s"),
    "arena_admin_device_share.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s"),
}


@pytest.mark.parametrize("name", COUNTER_METRICS + DEVICE_METRICS)
def test_reader_is_the_issues_and_is_listed(name):
    reader = manifest.load_layer_metric(name)
    assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == \
        ENTRIES[name]
    spec = manifest.load()
    entry = {m["name"]: m for m in spec["per_layer"]}[name]
    assert entry["better"] == "lower"
    # the two waits move the tail, which the paced cell alone reports
    tail = reader.MOVES == "serve_ms_per_token_p95"
    assert (entry["workloads"] == ["t5-large.decode-paced"]) == tail
    assert reader.read({}) is None
    with open(manifest.layer_metric_path(name)) as f:
        text = f.read()
    unsound = ("device_collective", "TrainResult", "examples_per_sec",
               "per_token_latency")
    assert not any(word in text for word in unsound)


@pytest.fixture
def registry():
    """A private registry as an engine that served 4 requests (one from
    the prefix cache) leaves it."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reg = MetricsRegistry()
    t = DecodeTelemetry(reg, "0")
    for phase, seconds in (
        ("idle", 50.0), ("admit", 0.25), ("admit", 0.25), ("prefill", 0.75),
        ("prefill", 1.0), ("prefill", 1.25), ("insert", 0.5),
        ("step", 5.0), ("emit", 0.5), ("retire", 0.5),
    ):
        t.on_phase(phase, seconds)
    for wait_s, ttft_s in ((0.0, 0.25), (0.5, 1.0), (1.0, 1.5), (0.5, 0.5)):
        t.on_admitted(wait_s)
        t.on_first_token(ttft_s)
    return reg


@pytest.mark.parametrize("name,value", [
    # admit 0.5 + prefill 3 + insert 0.5 + retire 0.5 of 10 working seconds
    ("admission_share.serve", 45.0),
    ("prefill_ms.serve", 1000.0),            # 3 s over 3 prefills
    ("queue_wait_mean_ms.serve", 500.0),     # 2 s over 4 requests
    ("ttft_mean_ms.serve", 812.5),           # 3.25 s over 4 requests
])
def test_counter_readers(registry, name, value):
    from tpu_pipelines.observability.metrics import MetricsRegistry

    reader = manifest.load_layer_metric(name)
    assert reader.read(SERVE, registry) == pytest.approx(value)
    assert reader.read({}, registry) is None          # not a serve cell
    # a program that has no such series: nothing, and no error
    assert reader.read(SERVE, MetricsRegistry()) is None


def test_admission_share_counts_a_windowed_prefill(registry):
    """A contract that prefills by windows books ``prefill.window`` in
    place of ``prefill``: until PR 39 the share left it out and read 0.3 %
    in EvaByte's cell, where the thread spends a third of its time there."""
    from tpu_pipelines.serving.generative import DecodeTelemetry

    DecodeTelemetry(registry, "0").on_phase("prefill.window", 10.0)
    share = manifest.load_layer_metric("admission_share.serve")
    # admit 0.5 + prefill 3 + windows 10 + insert 0.5 + retire 0.5 of 20
    assert share.read(SERVE, registry) == pytest.approx(72.5)


def test_counter_readers_add_up_the_replicas(registry):
    from tpu_pipelines.serving.generative import DecodeTelemetry

    other = DecodeTelemetry(registry, "1")
    other.on_phase("step", 10.0)
    other.on_admitted(3.0)
    share = manifest.load_layer_metric("admission_share.serve")
    assert share.read(SERVE, registry) == pytest.approx(22.5)
    wait = manifest.load_layer_metric("queue_wait_mean_ms.serve")
    assert wait.read(SERVE, registry) == pytest.approx(1000.0)


@pytest.mark.parametrize("name,value", [
    ("prefill_device_ms.serve", 30.0),               # (0.02 + 0.04) / 2
    ("arena_admin_device_share.serve", 12.5),        # 0.25 of 2 busy seconds
])
def test_device_trace_readers(name, value):
    reader = manifest.load_layer_metric(name)
    modules = [
        ("jit_run(123)", 0.0, 0.5), ("jit_prefill(7)", 0.6, 0.02),
        ("jit_insert(8)", 0.7, 0.1), ("jit_prefill(7)", 0.9, 0.04),
        ("jit_move(9)", 1.0, 0.05), ("jit_clear(10)", 1.1, 0.1),
        ("jit_accept(11)", 1.3, 0.2),
    ]
    facts = {**SERVE, "trace": {"modules": modules, "busy_s": 2.0}}
    assert reader.read(facts) == pytest.approx(value)
    # a CPU rehearsal's trace has no "XLA Modules" line
    assert reader.read(
        {**SERVE, "trace": {"modules": [], "busy_s": 2.0}}) is None
    assert reader.read({"trace": facts["trace"]}) is None
    assert reader.read(SERVE) is None


def test_device_trace_readers_look_for_the_programs_own_names():
    from tpu_pipelines.serving.generative import PROGRAM_NAMES

    prefill = manifest.load_layer_metric("prefill_device_ms.serve")
    admin = manifest.load_layer_metric("arena_admin_device_share.serve")
    step = manifest.load_layer_metric("decode_step_hbm_share.serve")
    assert {prefill.PREFILL, step.STEP, *admin.ADMIN} <= set(PROGRAM_NAMES)


def test_innermost_cuts_nested_spans_to_one_label_a_moment():
    spans = [
        ("engine.admit", 0.0, 10.0), ("engine.prefill", 2.0, 6.0),
        ("engine.insert", 7.0, 8.0), ("engine.step", 10.0, 20.0),
        ("engine.step.wait", 11.0, 20.0), ("engine.idle", 25.0, 30.0),
    ]
    assert host_plane.innermost(spans) == [
        ("engine.admit", 0.0, 2.0), ("engine.prefill", 2.0, 6.0),
        ("engine.admit", 6.0, 7.0), ("engine.insert", 7.0, 8.0),
        ("engine.admit", 8.0, 10.0), ("engine.step", 10.0, 11.0),
        ("engine.step.wait", 11.0, 20.0), ("engine.idle", 25.0, 30.0),
    ]
    assert host_plane.innermost([]) == []


def test_after_a_traced_rehearsal_the_counter_readers_have_a_reading(capsys):
    """An open-loop fixture cell, traced: the engine it ran booked its
    phases and its requests in the process's registry, where the four
    counter readers find them, and left its spans in the trace, on the
    clock of what stands in for the device.  Not ``tiny-t5.paced``:
    test_benchmark_rehearse_serve.py traces that cell, a traced run clears
    the cell's trace directory, and the two files may run side by side."""
    code, out = run_cell(
        capsys, CELL, "--rehearse", "--trace", "1")
    assert code == 0
    old = read_result(out)["metrics"]
    assert {"decode_step_ms.serve", "batch_occupancy.serve"} <= set(old)
    metrics = {
        name: manifest.load_layer_metric(name).read(SERVE)
        for name in COUNTER_METRICS}
    for name, value in metrics.items():
        assert value is not None and math.isfinite(value), name
        assert value >= 0.0
    assert metrics["admission_share.serve"] <= 100.0
    assert metrics["ttft_mean_ms.serve"] >= \
        metrics["queue_wait_mean_ms.serve"]
    # a CPU rehearsal's trace has no "XLA Modules" line
    for name in DEVICE_METRICS:
        assert manifest.load_layer_metric(name).read(
            {**SERVE, "trace": {"modules": [], "busy_s": 1.0}}) is None

    assert host_plane.main([CELL, "--rehearse"]) == 0
    printed = capsys.readouterr().out
    report = json.loads(printed.strip().splitlines()[-1])
    assert report["leaf_spans"] > 0
    assert 0.9 < report["leaf_spans_cover_share"] <= 1.0 + 1e-9
    labels = {label for label, _ in report["idle_by_leaf_span"]}
    assert labels & {"engine.step.wait", "engine.idle", "engine.prefill"}
    assert report["idle_without_span_share"] < 0.1
