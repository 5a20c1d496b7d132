"""Shared by the rehearsal tests: run one cell of the fixture manifest in
this process and read its result line."""

import json
import os

import numpy as np

from benchmark import manifest, run as bench_run

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture")
with open(os.path.join(FIXTURE, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def run_cell(capsys, workload, *extra, seed=2 ** 31 + 5, seconds=1.5):
    code = bench_run.main([
        "--workload", workload, "--seed", str(seed),
        "--seconds", str(seconds), "--manifest-root", FIXTURE, *extra,
    ])
    out = capsys.readouterr().out
    return code, out


def read_result(out):
    line = out.strip().splitlines()[-1]
    result = json.loads(line)
    assert set(result) >= {
        "correct", "attempted", "failed", "metrics", "device"}
    assert set(result["device"]) >= {
        "platform", "kind", "count", "memory_peak_bytes"}
    return result


def check_contracts_line(capsys, workload, trace):
    """A rehearsal of ``workload`` ends in the contract's line, with the
    cell's end-to-end metrics (``trace`` 0) or layer metrics (1)."""
    code, out = run_cell(
        capsys, workload, "--rehearse", "--trace", str(trace))
    assert code == 0
    result = read_result(out)
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    cell = manifest.cell(SPEC, workload)
    assert result["device"]["count"] == cell["chips"]
    section = "per_layer" if trace else "end_to_end"
    allowed = {
        m["name"] for m in manifest.metrics_for(SPEC, section, workload)}
    assert set(result["metrics"]) <= allowed
    for name, m in result["metrics"].items():
        assert np.isfinite(m["value"]), name
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] >= result["device"]["busy_s"]
        assert len(result["breakdown"]["device_ops"]) <= 10
        assert len(result["breakdown"]["idle_gaps"]) <= 10
        assert result["metrics"]      # every cell reports a layer metric
    else:
        assert set(result["metrics"]) == allowed
        assert "setup_s" in result["metrics"]
    # every number compared is printed beside its limit
    assert "check " in out and "(limit " in out
