"""The manifest and the files it names: every cell, configuration, traffic
mix and per-layer metric is found by name, and every name is well formed."""

import json
import os
import re

import pytest

from benchmark import manifest, traffic

SPEC = manifest.load()
WHERE = {"device_trace", "program_span", "program_counter", "host_clock"}


def _named():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[section]:
            yield section, entry["name"]


def test_keys_are_the_contracts():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"][:2] == ["python3", "benchmark/run.py"]
    assert os.path.getsize(
        os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("section,name", list(_named()))
def test_names_are_well_formed_and_unique(section, name):
    assert manifest.NAME_RE.match(name), name
    assert [n for s, n in _named() if s == section].count(name) == 1


@pytest.mark.parametrize(
    "metric", SPEC["end_to_end"] + SPEC["per_layer"],
    ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert manifest.UNIT_RE.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in WHERE
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
        assert set(metric) <= {
            "name", "unit", "better", "bound", "source", "workloads"}
    else:
        assert set(metric) <= {
            "name", "unit", "better", "source", "layer", "moves",
            "workloads"}


@pytest.mark.parametrize(
    "cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_are_found_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    assert manifest.NAME_RE.match(cell["traffic"])
    config = manifest.load_config(SPEC, cell["config"])
    assert os.path.exists(os.path.join(
        manifest.HERE, "drivers", config["driver"] + ".py"))
    assert os.path.exists(os.path.join(
        manifest.HERE, "reference", config["reference"] + ".py"))
    mix = traffic.load(manifest.traffic_path(cell["traffic"]))
    assert mix["why"]
    reported = {
        m["name"] for m in manifest.metrics_for(
            SPEC, "end_to_end", cell["name"])}
    assert "setup_s" in reported and len(reported) >= 2
    layer = manifest.metrics_for(SPEC, "per_layer", cell["name"])
    assert layer
    assert {m["moves"] for m in layer} <= reported


@pytest.mark.parametrize(
    "entry", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_files(entry):
    assert entry["file"].startswith("benchmark/configs/")
    assert entry["source"].startswith("https://")
    used = {w["config"] for w in SPEC["workloads"]}
    assert entry["name"] in used
    config = manifest.load_config(SPEC, entry["name"])
    for key in ("source_config", "hparams", "changed", "reduced",
                "assumed", "departures", "weights", "check"):
        assert key in config, key
    assert config["reduced"] == entry["reduced"]
    assert set(entry["reduced"]) <= set(config["source_config"])
    assert config["source"] == entry["source"]


def test_widths_are_the_sources():
    bert = manifest.load_config(SPEC, "bert-base")
    src, hp = bert["source_config"], bert["hparams"]
    assert (hp["d_model"], hp["n_layers"], hp["n_heads"], hp["d_ff"]) == (
        src["hidden_size"], src["num_hidden_layers"],
        src["num_attention_heads"], src["intermediate_size"])
    assert hp["max_len"] == src["max_position_embeddings"]
    assert hp["vocab_size"] >= src["vocab_size"]
    t5 = manifest.load_config(SPEC, "t5-large")
    src, hp = t5["source_config"], t5["hparams"]
    assert (hp["d_model"], hp["head_dim"], hp["d_ff"], hp["n_heads"],
            hp["vocab_size"]) == (
        src["d_model"], src["d_kv"], src["d_ff"], src["num_heads"],
        src["vocab_size"])
    assert hp["n_layers"] == src["num_layers"] == src["num_decoder_layers"]


@pytest.mark.parametrize(
    "metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_reader_matches_its_entry(metric):
    reader = manifest.load_layer_metric(metric["name"])
    assert reader.LAYER == metric["layer"]
    assert reader.UNIT == metric["unit"]
    assert reader.MOVES == metric["moves"]
    assert reader.SOURCE == metric["source"]
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    # A reader that finds nothing to read returns nothing.
    assert reader.read({}) is None


def test_no_reader_reads_an_unsound_source():
    unsound = ("device_collective", "TrainResult", "examples_per_sec",
               "per_token_latency")
    for metric in SPEC["per_layer"]:
        with open(manifest.layer_metric_path(metric["name"])) as f:
            text = f.read()
        assert not any(word in text for word in unsound), metric["name"]


def _perf_md_bounds():
    """``{metric: (bound, [spreads in per cent])}`` from the table of
    ``PERF.md`` section 2, whose last two columns are the bound and the
    spreads of the builder's sets in the metric's widest cell."""
    with open(os.path.join(manifest.ROOT, "PERF.md"), encoding="utf-8") as f:
        section = f.read().split("\n## 2.")[1].split("\n## 3.")[0]
    rows = {}
    for line in section.splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) >= 3 and re.fullmatch(r"`[\w.\-]+`", cells[0]):
            spreads = [float(x) for x in re.findall(
                r"(\d+(?:\.\d+)?) ?%", cells[-1])]
            rows[cells[0].strip("`")] = (float(cells[-2]), spreads)
    return rows


@pytest.mark.parametrize(
    "metric", SPEC["end_to_end"], ids=lambda m: m["name"])
def test_bounds_are_the_contracts_and_the_ones_perf_md_prints(metric):
    """A bound is at most 0.1 and never under 1 %; it is the one PERF.md
    section 2 prints; and the driver would not call it too loose: it is
    at most 8 times the narrower of the spreads PERF.md sets it from, or
    1 % if that is more (the ledger's sentence on PR 23).  ``setup_s`` is
    judged by its median alone and stands at 0.1."""
    printed = _perf_md_bounds()
    assert metric["name"] in printed, "PERF.md section 2 lacks the metric"
    bound, spreads = printed[metric["name"]]
    assert metric["bound"] == bound
    assert 0.01 <= metric["bound"] <= 0.1
    if metric["name"] == "setup_s":
        assert metric["bound"] == 0.1
        return
    assert spreads, "PERF.md prints no spread for the bound"
    assert metric["bound"] <= max(0.01, 8 * min(spreads) / 100.0)


def test_paced_rate_is_a_number_in_its_traffic_file():
    mix = traffic.load(manifest.traffic_path("decode-heavy-stratified"))
    assert mix["loop"] == "open"
    assert mix["rate_rps"] == 9.13 and mix["rate_from"]
    closed = traffic.load(manifest.traffic_path("decode-heavy-closed"))
    for key in ("prompt_len", "output_len", "block"):
        assert mix[key] == closed[key]


def _fixture_manifests():
    """``{directory: manifest}`` of every ``tests/benchmark/fixture*/``."""
    here = os.path.dirname(__file__)
    found = {}
    for name in sorted(os.listdir(here)):
        path = os.path.join(here, name, "BENCHMARK.json")
        if name.startswith("fixture") and os.path.exists(path):
            with open(path) as f:
                found[name] = json.load(f)
    return found


FIXTURES = _fixture_manifests()


@pytest.mark.parametrize("directory", sorted(FIXTURES))
def test_a_fixture_manifest_names_only_listed_metrics(directory):
    """A later PR lists a metric by adding an entry here, a reader and a
    fixture directory of its own: it edits no manifest that is there.  So
    a fixture manifest need not name every metric, only listed ones, and
    each of its entries says what the listed one says."""
    listed = {
        section: {m["name"]: m for m in SPEC[section]}
        for section in ("end_to_end", "per_layer")}
    for section, entries in listed.items():
        for m in FIXTURES[directory][section]:
            assert m["name"] in entries, (section, m["name"])
            for key in ("unit", "better", "source", "layer", "moves"):
                assert m.get(key) == entries[m["name"]].get(key), (
                    m["name"], key)


@pytest.mark.parametrize(
    "metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_a_listed_layer_metric_stands_in_a_fixture_manifest(metric):
    """Every per-layer entry has a fixture cell in which a rehearsal can
    drive its reader (test_layer_metric_reader_matches_its_entry loads
    the reader itself)."""
    assert any(
        metric["name"] in {m["name"] for m in spec["per_layer"]}
        for spec in FIXTURES.values())


@pytest.mark.parametrize(
    "entry", SPEC["configs"], ids=lambda c: c["name"])
def test_limits_because_speaks_of_the_limits_that_are_there(entry):
    """Where a configuration's check gives its limits' readings
    (``limits_because``; the two oldest have theirs in PERF.md section 6),
    each limit has its text and no text is left behind for a limit that is
    gone.  A text may also speak of another key of the check itself."""
    check = manifest.load_config(SPEC, entry["name"])["check"]
    assert check["limits"]
    if "limits_because" in check:
        assert set(check["limits_because"]) - set(check) == set(
            check["limits"])
