"""bench.py survivability + stdout contract: evidence must always emit.

Rounds 1 and 2 lost their TPU evidence to bench crashes; rounds 3 and 4
lost it to the stdout contract — the full cumulative report (3.7 KB by
round 4) overflowed the driver's 2,000-byte stdout tail, so the captured
final line started mid-JSON and ``parsed`` stayed null.  Both contracts
are guarded here:

  - survivability: a smoke run of the full bench path (taxi, e2e pipeline,
    BERT, probes, all shrunk via BENCH_SMOKE=1) must exit 0 with every
    workload measured or carrying an error field;
  - stdout: EVERY stdout line is a compact headline-only JSON well under
    the driver's 2,000-byte tail; the full report lives only in
    BENCH_PARTIAL.json.
"""

import json
import os

import pytest
import subprocess
import sys

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The driver tail keeps 2,000 bytes and JSON-parses the LAST line, which
# is intact as long as it fits the tail whole; cap below that with real
# headroom.  (1500 until ISSUE 19, 1600 until ISSUE 20 — the drift-drill
# headline keys push the full-report line to ~1650 B, still 250+ B clear
# of the tail.)
MAX_STDOUT_LINE_BYTES = 1750


def _run_bench(extra_env, timeout):
    # The CPU is in no peak table, and an unknown device is an error: the
    # smoke states a placeholder peak (its numbers are meaningless anyway).
    env = {**os.environ, "BENCH_SMOKE": "1", "JAX_PLATFORMS": "cpu",
           "TPP_PEAK_FLOPS": "1e12", **extra_env}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert lines, proc.stdout
    for line in lines:
        assert len(line.encode()) <= MAX_STDOUT_LINE_BYTES, (
            f"stdout line {len(line.encode())} B breaks the driver-tail "
            f"contract: {line[:200]}"
        )
    return lines


def test_bench_smoke_emits_compact_stdout_and_full_report():
    lines = _run_bench({}, timeout=1200)
    compact = json.loads(lines[-1])

    # The compact line alone must answer the driver's questions.
    assert compact["unit"] == "examples/sec/chip"
    assert compact["value"] > 0
    assert compact["bert_e2e_green"] is True
    assert compact["taxi_e2e_green"] is True
    assert compact["error_legs"] == []
    assert compact["skipped"] == []
    assert compact["elapsed_s"] > 0
    assert compact["full_report"] == "BENCH_PARTIAL.json"

    # Survivability: one compact flush per workload.
    assert len(lines) >= 6, f"expected per-workload flushes, got {len(lines)}"

    # The full report — everything rounds 1-4 printed to stdout — now lives
    # in the partial file, and must agree with the compact headline.
    with open(os.path.join(REPO, "BENCH_PARTIAL.json")) as f:
        report = json.load(f)
    assert report["smoke"] is True
    assert report["metric"] == compact["metric"]
    assert report["value"] == compact["value"]
    for key in ("bert", "taxi", "taxi_device", "taxi_window",
                "taxi_window_mesh", "bert_parallelism", "mnist", "resnet",
                "pipeline_e2e", "flash_probe", "t5_decode"):
        assert report.get(key) is not None or key in report["errors"], (
            key, report.get("errors")
        )
    assert report["errors"] == {}, report["errors"]
    for name, min_nodes in (("taxi", 9), ("bert", 4)):
        e2e = report["pipeline_e2e"][name]
        assert e2e["green"] is True, (name, e2e)
        assert e2e["wall_clock_s"] > 0
        assert len(e2e["nodes"]) >= min_nodes
        # Scheduler config is recorded per leg (BENCH comparability).
        assert e2e["max_parallel_nodes"] >= 1
    # RunTrace-derived keys on the taxi e2e leg (ISSUE 4): present and
    # self-consistent — the sum of scheduler node spans bounds the
    # measured critical path from above, the longest single node from
    # below; a fresh home means every driver verdict was a cache miss.
    tr = report["pipeline_e2e"]["taxi"]["trace"]
    assert tr is not None and "error" not in tr, tr
    assert (
        tr["span_duration_total_s"]
        >= tr["critical_path_measured_s"]
        >= tr["longest_node_s"]
        > 0
    ), tr
    assert tr["critical_path_nodes"], tr
    assert tr["queue_wait_total_s"] >= 0
    assert tr["gate_wait_total_s"] >= 0
    assert tr["cache_hit_ratio"] == 0.0  # fresh pipeline home
    assert tr["events"] > 0
    # And the trace-off comparison leg ran (overhead bound evidence) —
    # with TPP_TRACE=0 writing no event log at all.
    ov = report["pipeline_e2e"]["taxi"]["trace_overhead"]
    assert ov["wall_trace_on_s"] > 0 and ov["wall_trace_off_s"] > 0
    assert ov["trace_off_wrote_no_events"] is True
    # The sequential-vs-concurrent scheduler sub-leg: both modes green,
    # walls measured, identical published artifacts/lineage, per-node
    # critical-path breakdown present.  (The strict concurrent<sequential
    # inequality is a multicore-host claim — the driver's bench asserts it
    # by inspection there; a 1-cpu CI box can only show parity.)
    sched = report["pipeline_e2e"]["taxi_sched"]
    assert sched["green"] is True, sched
    assert sched["sequential_wall_s"] > 0
    assert sched["concurrent_wall_s"] > 0
    assert sched["lineage_identical"] is True
    assert sched["lineage_executions"] >= 9
    assert sched["max_parallel_nodes"]["sequential"] == 1
    assert sched["max_parallel_nodes"]["concurrent"] > 1
    assert sched["critical_path"] and sched["critical_path_s"] > 0
    # Both scheduler modes carry their measured (trace-derived) profile.
    for key in ("trace_concurrent", "trace_sequential"):
        assert sched[key]["critical_path_measured_s"] > 0, (key, sched[key])
    # And the run-wide concurrency config lands in the report JSON.
    conc = report["concurrency"]
    assert conc["default_policy"] == "n_dag_roots"
    assert conc["e2e_sched_leg_workers"] == sched[
        "max_parallel_nodes"]["concurrent"]
    # The sharded-data-plane leg: both modes green, identity checks hold
    # (row multisets + statistics — the shard-count-invariance contract),
    # walls measured, config block present.  (The >= 1.3x speedup is a
    # multicore-host claim, asserted by inspection on the driver's bench;
    # a 1-cpu CI box can only show parity.)
    dp = report["data_plane"]["taxi_shards"]
    assert dp["green"] is True, dp
    assert dp["rows_identical"] is True
    assert dp["stats_identical"] is True
    assert dp["transform_rows_identical"] is True
    assert dp["single_ingest_stats_s"] > 0
    assert dp["sharded_ingest_stats_s"] > 0
    assert dp["shards"] >= 4
    assert all(n == dp["shards"] for n in dp["shard_layout"].values())
    assert dp["host_cpus"] >= 1
    dp_conf = report["data_plane"]["config"]
    assert dp_conf["bench_leg_shards"] == dp["shards"]
    assert "TPP_DATA_SHARDS" in dp_conf["default_shard_policy"]
    # And the compact line carries the data-plane verdict.
    assert compact["data_plane_green"] is True
    # Live-telemetry serving leg (ISSUE 5): tail latency read off the
    # server's OWN /metrics scrape (Prometheus histogram), healthy under
    # concurrent load, and surfaced on the compact line.
    sv = report["serving"]
    assert sv["green"] is True, sv
    assert sv["p99_ms"] > 0 and sv["p50_ms"] > 0
    assert sv["p99_ms"] >= sv["p50_ms"]
    assert sv["request_errors"] == 0
    assert sv["healthz"]["healthy"] is True
    assert compact["serving_green"] is True
    assert compact["serving_p99_ms"] == sv["p99_ms"]
    # Serving-fleet leg (ISSUE 10): 2-replica fleet with SLO batching
    # takes a hot-swap mid-hammer — p99 under the SLO target and zero
    # 5xx, both judged from the fleet's own /metrics scrape; per-replica
    # router series account for every request.
    fl = report["serving_fleet"]
    assert fl["green"] is True, fl
    assert fl["p99_ms"] is not None and fl["p99_ms"] < fl["slo_p99_ms"]
    assert fl["slo_met"] is True
    assert fl["reload_5xx"] == 0
    assert fl["reloaded_to"] == "2"
    assert fl["version_swaps"] >= 2
    assert fl["request_errors"] == 0
    assert set(fl["per_replica_requests"]) == {"0", "1"}
    assert sum(fl["per_replica_requests"].values()) >= fl["requests"] - 3
    assert fl["healthz"]["healthy"] is True
    assert fl["healthz"]["fleet"]["replicas"] == 2
    assert fl["healthz"]["fleet"]["active_version"] == "2"
    assert compact["fleet_green"] is True
    assert compact["fleet_p99_ms"] == fl["p99_ms"]
    assert compact["fleet_reload_5xx"] == 0
    assert compact["fleet_shed_requests"] == fl["shed_requests"]
    # Request tracing + SLO burn-rate monitor (ISSUE 12): the traced
    # pass ran at matched counts with sampling on (measured overhead on
    # the record), and the rollback drill proved the whole loop — burn
    # breach detected, auto-rollback to the prior version, interval p99
    # recovered under the drill SLO, the quarantined version's re-push
    # answering 409, zero 5xx.
    tr = fl["traced"]
    assert tr["errors"] == 0
    assert tr["traced_requests"] > 0 and tr["ring_events"] > 0
    assert tr["mean_latency_ms"] is not None
    assert fl["untraced_mean_latency_ms"] is not None
    assert fl["trace_overhead_pct"] is not None
    dr = fl["rollback_drill"]
    assert dr["green"] is True, dr
    assert "latency_p99" in dr["breached_slos"]
    assert dr["rolled_back_to"] == "1"
    assert dr["auto_rollbacks"] >= 1
    assert dr["quarantined_reload_code"] == 409
    assert dr["recovered_p99_ms"] < dr["slo_p99_ms"]
    assert dr["drill_5xx"] == 0
    assert compact["trace_overhead_pct"] == fl["trace_overhead_pct"]
    assert compact["slo_rollback_green"] is True
    # Quantized + AOT serving leg (ISSUE 14): the Rewriter's int8
    # variant passes the Evaluator-surface quality gate, deploys through
    # the Pusher's variant selection + push-URL hook, serves the
    # identical hammer at lower mean latency than float, and the
    # post-swap scrape proves the AOT contract — executables
    # deserialized from the export-time cache (no swap compiles) and
    # zero compiles after warm.
    sq = report["serving_quantized"]
    assert sq["green"] is True, sq
    assert sq["quantized_speedup"] > 1.0
    assert sq["quantized_quality_delta"] <= sq["quality_tolerance"]
    assert sq["aot_compiles_after_warm"] == 0
    assert sq["aot_cache_hits"] >= 1
    assert sq["request_errors"] == 0
    assert sq["reload_notified"] is True
    assert sq["selected_variant"] == "aqt_int8"
    assert sq["swap_warmup_seconds"] is not None
    assert sq["memory_bytes"]["aqt_int8"] < sq["memory_bytes"]["float32"] // 3
    variants = sq["variants"]
    assert set(variants) == {"float32", "bfloat16", "aqt_int8"}
    for name in ("bfloat16", "aqt_int8"):
        assert variants[name]["blessed"] is True, variants[name]
        assert variants[name]["latency_ms"] > 0
    assert compact["quantized_green"] is True
    assert compact["quantized_speedup"] == sq["quantized_speedup"]
    assert compact["quantized_quality_delta"] == sq[
        "quantized_quality_delta"
    ]
    assert compact["aot_compiles_after_warm"] == 0
    # Continuous-batching decode leg (ISSUE 11): the generative fleet
    # beats whole-request decode >= 2x on identical mixed-length traffic
    # at equal-or-better client p99-per-token, with zero 5xx across a
    # hot-swap with generations in flight — tokens/s and the headline
    # p99-per-token judged from the fleet's own scrape.
    gs = report["generative_serving"]
    assert gs["green"] is True, gs
    assert gs["continuous_vs_request_speedup"] >= 2.0
    assert gs["decode_tok_s"] > 0
    assert gs["decode_p99_ms_per_token"] is not None
    assert gs["decode_5xx"] == 0
    assert gs["reloaded_to"] == "2"
    assert gs["continuous"]["errors"] == 0
    assert gs["whole_request"]["errors"] == 0
    # Identical useful-token accounting on both sides of the A/B.
    assert (
        gs["continuous"]["useful_tokens"]
        == gs["whole_request"]["useful_tokens"] > 0
    )
    cp = gs["client_p99_ms_per_token"]
    assert cp["continuous"] <= cp["whole_request"]
    assert gs["scraped_decode_steps"] > 0
    # Iteration-level batching: strictly fewer steps than tokens (several
    # sequences advance per step).
    assert gs["scraped_decode_steps"] < gs["scraped_decode_tokens"]
    assert gs["healthz"]["healthy"] is True
    assert compact["generative_green"] is True
    assert compact["decode_tok_s"] == gs["decode_tok_s"]
    assert (
        compact["decode_p99_ms_per_token"] == gs["decode_p99_ms_per_token"]
    )
    assert (
        compact["continuous_vs_request_speedup"]
        == gs["continuous_vs_request_speedup"]
    )
    assert compact["decode_5xx"] == 0
    # Continuous-pipeline leg (ISSUE 13): three synthetic spans fed to a
    # RUNNING controller — bootstrap deploy, then span 3 lands mid-loop:
    # only the new span's ingest+stats execute (work saved (K-1)/K), the
    # incremental merged statistics equal a cold full-window run byte for
    # byte, and the retrained model reaches the fleet (deploy latency on
    # the record).
    cont = report["continuous"]["taxi_spans"]
    assert cont["green"] is True, cont
    assert cont["bootstrap_deploy_ok"] is True
    assert cont["incremental_deploy_ok"] is True
    assert cont["stats_identical"] is True
    assert abs(cont["work_saved_ratio"] - 2 / 3) < 1e-3
    assert cont["deploy_to_serving_s"] > 0
    assert cont["serving_version"] == "3"
    assert cont["deploys"] == 2
    assert cont["spans_seen"] == 3
    assert compact["continuous_green"] is True
    assert compact["incremental_work_saved"] == cont["work_saved_ratio"]
    # Live drift & skew drill (ISSUE 20): the monitored fleet stays quiet
    # under control traffic drawn from the training distribution, catches
    # the covariate shift within 3 tumbling windows of it landing, and
    # the RUNNING controller's scrape poll answers with EXACTLY ONE
    # out-of-cadence retrain, evidence recorded in the metadata store.
    # (Sampler overhead is recorded, not gated — a shared-core smoke box
    # cannot make a fair latency claim; the driver's bench inspects it.)
    mon = report["monitoring"]["drift_drill"]
    assert mon["green"] is True, mon
    assert mon["bootstrap_deploy_ok"] is True
    assert mon["false_alarms"] == 0
    assert mon["control_windows"] >= 3
    assert mon["detect_windows"] is not None
    assert mon["detect_windows"] <= 3
    assert mon["drift_triggered_runs"] == 1
    assert mon["drift_evidence_contexts"] >= 1
    assert mon["sampled_total"] > 0
    assert mon["dropped_total"] == 0
    assert mon["sampler_overhead_pct"] is not None
    assert compact["drift_green"] is True
    assert compact["drift_detect_windows"] == mon["detect_windows"]
    assert compact["drift_false_alarms"] == 0
    assert (
        compact["drift_sampler_overhead_pct"] == mon["sampler_overhead_pct"]
    )
    # t5_decode now carries the flash-decode datapoint: per-cache-length
    # dense-vs-tuned-flash timings, the recorded decode crossover, and
    # what "auto" resolves to at each measured length.
    fdec = report["t5_decode"]["flash_decode"]
    assert set(fdec["per_len"]) == {"128", "256"}
    for row in fdec["per_len"].values():
        assert row["dense_ms"] > 0
        assert row["flash_ms"] is None or row["flash_ms"] > 0
        assert row["candidates_timed"] >= 1
    assert "crossover_kv_len" in fdec
    assert set(fdec["auto_choice"]) == set(fdec["per_len"])
    assert all(
        v in ("dense", "flash") for v in fdec["auto_choice"].values()
    )
    # Unified fault-tolerance chaos leg (ISSUE 7): the taxi run completes
    # under the injected schedule with lineage identical to fault-free,
    # exact merged statistics, a quarantined poison shard in the salvage
    # demo, and a zero-5xx serving reload under the hammer — all
    # quantified from the metrics registry and surfaced on the compact
    # line.
    chaos = report["robustness"]["taxi_chaos"]
    assert chaos["green"] is True, chaos
    assert chaos["lineage_identical"] is True
    assert chaos["stats_identical"] is True
    assert chaos["trainer_retries"] == 2
    assert chaos["retries_total"] >= 2
    assert chaos["store_retries"] >= 2
    assert chaos["taxi_worker_deaths"] >= 1
    assert chaos["shards_quarantined"] >= 1  # the salvage demo's poison
    assert chaos["salvage"]["ok"] is True
    assert chaos["reload_5xx"] == 0
    assert chaos["serving"]["reload_ok"] is True
    assert chaos["serving"]["request_errors"] == 0
    assert compact["chaos_green"] is True
    assert compact["reload_5xx"] == 0
    assert compact["retries_total"] == chaos["retries_total"]
    assert compact["shards_quarantined"] == chaos["shards_quarantined"]
    assert compact["shed_requests"] == chaos["shed_requests"]
    # Self-healing fleet chaos leg (ISSUE 17): kill 1-of-2 replicas
    # mid-hammer — zero lost requests, the victim's breaker opens and
    # closes, full-capacity recovery, bounded incident p99, and the
    # recovered decode streams bitwise-identical — all judged from the
    # fleet's own scrape and surfaced on the compact line.
    schaos = report["robustness"]["serving_chaos"]
    assert schaos["green"] is True, schaos
    assert schaos["lost_requests"] == 0
    assert schaos["served_5xx"] == 0
    assert len(schaos["killed"]) == 1
    assert schaos["failovers"] >= 1
    assert schaos["breaker_transitions"] >= 2
    assert schaos["recovered_full_capacity"] is True
    assert schaos["incident_p99_ms"] < 5000.0
    assert schaos["sessions_recovered"] >= 1
    assert schaos["recovered_streams_identical"] is True
    assert schaos["host_cpus"] >= 1  # the 1-core p99 honesty caveat
    assert compact["chaos_serving_green"] is True
    assert compact["failovers"] == schaos["failovers"]
    assert compact["sessions_recovered"] == schaos["sessions_recovered"]
    assert compact["incident_p99_ms"] == schaos["incident_p99_ms"]
    assert compact["lost_requests"] == 0
    # And the resume leg still reports alongside it.
    robust = report["robustness"]["taxi_faults"]
    assert robust["green"] is True, robust
    assert compact["robust_green"] is True
    # Cross-run trace-diff self-report: the key is always present and
    # list-typed (first run against a foreign/absent baseline => []).
    td = report["trace_diff"]
    assert isinstance(td["regression_flags"], list)
    assert isinstance(compact["regression_flags"], list)
    assert compact["regression_flags"] == td["regression_flags"][:8]
    # The taxi trace carries the per-node profile `trace diff` consumes.
    # (Not `tr`: that name was reused for the traced-pass block above —
    # reading it here checked the wrong dict and KeyError'd the test.)
    taxi_tr = report["pipeline_e2e"]["taxi"]["trace"]
    assert taxi_tr["per_node"] and all(
        "wall_s" in v for v in taxi_tr["per_node"].values()
    )
    # The A100 comparison point is pinned with provenance (auditable ratio).
    ref = report["a100_reference"]
    assert ref["ex_per_sec"] > 0
    assert "source" in ref and "provenance" in ref
    # Host-loop-tax window sweep (ISSUE 8): the windowed train_loop leg
    # records throughput per window_steps, publishes taxi_device as the
    # ceiling, and the compact line carries the speedup key.  (The >=5x
    # windowed speedup is a real-chip claim; a CPU smoke box only shows
    # the keys and sane ratios.)
    tw = report["taxi_window"]
    assert set(tw["window_sweep"]) == {
        str(w) for w in tw["window_steps_swept"]
    }
    assert all(v > 0 for v in tw["window_sweep"].values()), tw
    assert tw["window_speedup"] is not None and tw["window_speedup"] > 0
    assert tw["best_window_steps"] in tw["window_steps_swept"]
    assert tw["taxi_device_ceiling"] > 0
    assert tw["gap_to_device_ceiling"] > 0
    assert compact["window_speedup"] == tw["window_speedup"]
    assert compact["gap_to_ceiling"] == tw["gap_to_device_ceiling"]
    # Multi-chip window sweep (ISSUE 15): the same window sweep on the
    # full device mesh with the bucketed in-scan collective, a 1-device
    # reference at equal global batch, and the honest shared-core note.
    # (mesh_window_speedup > 1 and scaling_efficiency near 1 are
    # real-chip claims; the smoke box records the keys and the caveat.)
    twm = report["taxi_window_mesh"]
    assert set(twm["window_sweep"]) == {
        str(w) for w in twm["window_steps_swept"]
    }
    assert all(v > 0 for v in twm["window_sweep"].values()), twm
    # Under pytest the bench inherits conftest's forced 8-device CPU
    # topology and sweeps inline, naming the platform it ran on; a bare
    # 1-device bench run records `skipped: needs >1 device` — one process
    # per chip, no child on virtual CPU devices.
    assert twm["platform"] == "cpu"
    assert twm["mesh_devices"] == 8
    assert twm["mesh_window_speedup"] is not None
    assert twm["mesh_window_speedup"] > 0
    assert twm["single_device_eps"] > 0
    assert twm["scaling_efficiency"] is not None
    assert twm["scaling_efficiency"] > 0
    assert twm["dp_collective"] == "psum_bucketed"
    assert twm["taxi_device_ceiling"] > 0
    assert twm["gap_to_ceiling"] > 0
    assert twm["host_cpus"] >= 1
    assert isinstance(twm["virtual_devices_share_cores"], bool)
    # Figures from virtual CPU devices never reach the compact line.
    assert "mesh_window_speedup" not in compact
    assert "scaling_efficiency" not in compact
    # Training-telemetry acceptance drill (ISSUE 19), on BOTH windowed
    # legs: the scraped four-phase attribution sums to the trace-recorded
    # window wall-clock within 5%, compiles-after-warm reads 0 at steady
    # state, the scrape is the MERGED federated endpoint, and the run
    # left a replayable (>= 2 snapshot) metrics-history ring whose
    # headline feeds trace diff.  The mesh leg's drill is the multi-chip
    # acceptance run: same contract with the bucketed in-scan collective.
    for leg in (tw, twm):
        tt = leg["train_telemetry"]
        assert tt["green"] is True, tt
        assert tt["phase_sum_within_5pct"] is True, tt
        assert tt["compiles_after_warm"] == 0
        assert tt["attributed_s"] > 0
        assert tt["attributed_s"] <= tt["wall_s"]
        assert set(tt["phase_seconds"]) == {
            "infeed_wait", "device_compute", "device_collective", "host",
        }
        assert tt["federated_scrape"] is True
        assert tt["federation_sources"] >= 1
        assert tt["history_snapshots"] >= 2
        assert "window_phase_seconds" in tt["history_headline_keys"]
        assert "infeed_wait_share" in tt["history_headline_keys"]
    # The mesh drill ran THROUGH the collective: device_collective time
    # was actually attributed, not a structural zero.
    assert twm["train_telemetry"]["phase_seconds"]["device_collective"] > 0
    # And the compact line carries the telemetry headline keys.
    assert compact["train_infeed_wait_pct"] == tw["train_telemetry"][
        "infeed_wait_pct"
    ]
    assert compact["train_compiles_after_warm"] == 0
    # The BERT leg carries its windowed datapoint at the bench log window.
    bw = report["bert"]["window_sweep"]
    assert set(bw) == {"1", str(report["bert"]["window_steps_log_every"])}
    assert all(v > 0 for v in bw.values()), bw
    # The window sweep's parallelism axis (ISSUE 18): dp | fsdp |
    # fsdp+accum | ring-attn long-context, each with MFU and the per-device
    # memory evidence; fsdp params must actually live sharded (1/N bytes).
    bpar = report["bert_parallelism"]
    assert bpar["platform"] == "cpu"
    assert bpar["mesh_devices"] == 8
    par = bpar["parallelism"]
    assert set(par) == {"dp", "fsdp", "fsdp_accum", "ring_long"}
    for name, row in par.items():
        assert row["examples_per_sec_per_chip"] > 0, (name, row)
        assert row["mfu"] > 0, (name, row)
        assert row["param_bytes_total"] > 0
        assert row["param_bytes_per_device"] > 0
        assert "device_memory_peak_bytes" in row
    assert par["dp"]["dp_collective"] == "psum_bucketed"
    assert par["fsdp"]["dp_collective"] == "fsdp"
    assert par["fsdp_accum"]["grad_accum_steps"] == 2
    assert par["ring_long"]["dp_collective"] == "implicit"
    assert par["ring_long"]["seq_len"] > par["dp"]["seq_len"]
    # ZeRO-3 evidence: fsdp keeps ~1/8 of the params per device; dp
    # replicates them all.
    assert bpar["fsdp_param_shard_ratio"] <= 0.25
    assert (par["dp"]["param_bytes_per_device"]
            == par["dp"]["param_bytes_total"])
    assert bpar["fsdp_mfu_vs_dp"] is not None
    assert "fsdp_mfu_vs_dp" not in compact
    assert "fsdp_param_shard_ratio" not in compact
    # Kernel-autotune sweep leg (ISSUE 9): flash_probe sweeps seq lengths
    # recording tuned-vs-default-vs-dense, the tuned config can never lose
    # to the default (it is IN the candidate grid), dense is skipped via
    # the expected-temp-bytes precheck rather than a backend error string,
    # and an EMPTY-cache cache-only cold run completed on defaults without
    # sweeping — the jit-trace-time contract.
    fp = report["flash_probe"]
    assert fp["autotune"]["mode_cold"] == "cache-only"
    assert fp["autotune"]["cold_cache_completed"] is True
    assert fp["autotune"]["sweeps_during_cold_run"] == 0
    assert set(fp["sweep"]) == {str(s) for s in fp["seqs_swept"]}
    for row in fp["sweep"].values():
        assert row["tuned_not_worse"] is True, row
        assert row["tuned_ms"] > 0 and row["default_ms"] > 0
        assert row["dense_expected_temp_bytes"] > 0
        # Dense either measured or cleanly precheck-skipped — never an
        # error-string dependency.
        assert row["dense_skipped_oom_precheck"] or "dense" in row, row
    assert fp["flash_tuned_speedup"] > 0
    assert "crossover_seq_len" in fp
    assert set(fp["auto_choice"]) == set(fp["sweep"])
    assert all(v in ("dense", "flash") for v in fp["auto_choice"].values())
    assert compact["flash_tuned_speedup"] == fp["flash_tuned_speedup"]
    assert compact["crossover_seq_len"] == fp["crossover_seq_len"]
    # Static-analyzer health (ISSUE 6): all six examples lint clean and
    # the compact line carries the analyzer verdict.
    lint = report["lint"]
    assert lint["green"] is True, lint
    assert lint["findings_total"] == 0
    assert sorted(lint["per_example"]) == [
        "bert", "mnist", "resnet", "staged", "t5", "taxi",
    ]
    assert all(v["findings"] == 0 for v in lint["per_example"].values())
    # "milliseconds before a chip is touched": the graph layer is measured.
    assert lint["graph_layer_ms_max"] < 1000
    assert compact["lint_findings"] == 0


def test_bench_budget_skips_but_emits():
    """BENCH_BUDGET_S=0: every leg must be skipped for budget, yet the
    process still exits 0 with a parseable, self-describing compact line —
    the driver-timeout path can never yield nothing again."""
    lines = _run_bench({"BENCH_BUDGET_S": "0"}, timeout=300)
    compact = json.loads(lines[-1])
    assert compact["metric"] == "bench_failed"
    # Each skip entry carries WHY it was skipped — `name(need Xs, had Ys)`
    # — a bare name read as "forgot to run it" (ISSUE 16).
    names = {s.split("(", 1)[0] for s in compact["skipped"]}
    assert all("(need " in s and "s, had " in s for s in compact["skipped"]), (
        compact["skipped"]
    )
    assert "taxi" in names
    assert "bert" in names
    assert "bert_goodput" in names
    # e2e legs are prefixed so they never collide with the same-named
    # throughput legs, and the list is dup-free.
    assert "e2e_bert" in names
    assert "e2e_taxi_sched" in names
    assert len(compact["skipped"]) == len(set(compact["skipped"]))
    with open(os.path.join(REPO, "BENCH_PARTIAL.json")) as f:
        report = json.load(f)
    assert report["taxi"]["skipped_budget"] is True
    assert report["bert"]["skipped_budget"] is True
    assert report["pipeline_e2e"]["bert"]["skipped_budget"] is True
    assert report["data_plane"]["skipped_budget"] is True
    assert "data_plane" in names
    assert "serving" in names
    assert "serving_fleet" in names
    assert "generative_serving" in names
    assert "monitoring" in names
    # No taxi leg ran, so the trace-diff self-report degrades to empty
    # flags (never a crash, never a missing key).
    assert compact["regression_flags"] == []
