"""Framework CLI: ``python -m tpu_pipelines {run,lint,inspect,trace} ...``.

``run`` — execute a pipeline module locally (the ``tfx run`` /
LocalDagRunner-notebook equivalent):

    python -m tpu_pipelines run --pipeline-module examples/taxi/pipeline.py
    python -m tpu_pipelines run --pipeline-module p.py --param steps=500 \
        --from-node Trainer          # partial run, upstream from cache

``lint`` — static pipeline + executor analysis (docs/ANALYSIS.md): compiles
the module's pipeline and runs the TPP1xx graph rules on the IR plus the
TPP2xx code rules on every executor and module-file entry point, without
executing anything:

    python -m tpu_pipelines lint --pipeline-module examples/taxi/pipeline.py
    python -m tpu_pipelines lint --pipeline-module p.py --json --fail-on warn

Exit codes mirror ``trace diff``: 0 = clean at the --fail-on level
(default: error), 3 = blocking findings, 1 = the module itself failed to
load/compile.  The same analysis gates ``LocalDagRunner.run(...,
lint="error")`` / env ``TPP_LINT`` and the cluster runner's manifest
emission.

``inspect`` — the MLMD-UI / KFP-UI equivalent surface (SURVEY.md §5
metrics/observability): the metadata store is the observability backbone —
every artifact, execution, lineage edge, and per-node wall-clock is recorded
there — and this CLI is the user-facing way to read it back:

    python -m tpu_pipelines inspect runs <pipeline> --metadata md.sqlite
    python -m tpu_pipelines inspect lineage <artifact-id> --metadata md.sqlite
    python -m tpu_pipelines inspect artifacts [--type Model] --metadata md.sqlite

Reads the shared SQLite schema directly (works on stores written by either
the python or the native C++ backend).

``trace`` — summarize/export/compare a run's RunTrace event log
(docs/OBSERVABILITY.md):

    python -m tpu_pipelines trace latest --pipeline-root /pipe/root
    python -m tpu_pipelines trace <run-id> --pipeline-root /pipe/root \
        --perfetto trace.json --metrics metrics.json
    python -m tpu_pipelines trace diff <run-a> <run-b> \
        --pipeline-root /pipe/root [--threshold 0.2]

Prints the measured run profile (per-node durations, critical path,
queue/gate waits, cache-hit ratio); ``--perfetto`` writes a Chrome/
Perfetto-loadable timeline, ``--metrics`` the machine-readable summary
the cluster runner consumes.  ``trace diff`` compares
two runs node by node (baseline first) and exits 3 when any node or the
critical path regressed past the threshold — the CI tripwire.

``--json`` on ``trace``, ``trace diff``, and ``inspect runs`` switches
the table output to machine-readable JSON for scripts.
"""

from __future__ import annotations

import argparse
import sys

from tpu_pipelines.metadata.store import MetadataStore


def _fmt_props(props: dict, keys=None) -> str:
    items = [
        (k, v) for k, v in sorted(props.items())
        if keys is None or k in keys
    ]
    return " ".join(f"{k}={v}" for k, v in items)


def _run_trace_metrics(pipeline_root: str, run_id: str) -> dict:
    """Per-node RunTrace metrics for a run, {} when no trace exists."""
    if not pipeline_root:
        return {}
    import os

    from tpu_pipelines.observability import (
        compute_metrics,
        events_path,
        read_events,
    )

    path = events_path(pipeline_root, run_id)
    if not os.path.exists(path):
        return {}
    return compute_metrics(read_events(path))


def cmd_runs(
    store: MetadataStore,
    pipeline: str,
    pipeline_root: str = "",
    as_json: bool = False,
) -> int:
    import json as _json

    prefix = f"{pipeline}."
    runs = [
        c for c in store.get_contexts("pipeline_run")
        if c.name.startswith(prefix)
    ]
    if not runs:
        print(f"no runs recorded for pipeline {pipeline!r}", file=sys.stderr)
        return 1
    json_runs = []
    for ctx in runs:
        run_id = ctx.properties.get("run_id") or ctx.name[len(prefix):]
        # Trace-derived per-node columns (queue wait) when the run's
        # RunTrace log is reachable via --pipeline-root; the metadata
        # store alone still yields state + duration.
        trace_nodes = _run_trace_metrics(pipeline_root, run_id).get(
            "per_node", {}
        )
        if as_json:
            json_runs.append({
                "run_id": run_id,
                "context_id": ctx.id,
                "nodes": [
                    {
                        "node": ex.node_id or ex.type_name,
                        "state": ex.state.value,
                        "execution_id": ex.id,
                        "properties": ex.properties,
                        **(
                            {"trace": trace_nodes[ex.node_id]}
                            if ex.node_id in trace_nodes else {}
                        ),
                    }
                    for ex in store.get_executions_by_context(ctx.id)
                ],
            })
            continue
        print(f"run {run_id}  (context #{ctx.id})")
        header = f"  {'node':<24} {'state':<10} {'dur_s':>9}"
        if trace_nodes:
            header += f" {'queue_s':>8}"
        print(header)
        for ex in store.get_executions_by_context(ctx.id):
            wall = ex.properties.get("wall_clock_s", "")
            dur = f"{wall}s" if wall != "" else "-"
            extra = _fmt_props(
                ex.properties,
                keys=(
                    "examples_per_sec_per_chip", "retries", "cache_hit",
                    "error",
                ),
            )
            line = (
                f"  {ex.node_id or ex.type_name:<24} "
                f"{ex.state.value:<10} {dur:>9}"
            )
            if trace_nodes:
                q = trace_nodes.get(ex.node_id, {}).get("queue_wait_s")
                line += f" {q if q is not None else '-':>8}"
            print(f"{line}  {extra}".rstrip())
    if as_json:
        print(_json.dumps({"pipeline": pipeline, "runs": json_runs},
                          indent=1, sort_keys=True, default=str))
    return 0


def _resolve_run_id(pipeline_root: str, run_id: str):
    """Resolve 'latest' to the newest run dir; (run_id, error) tuple."""
    import os

    if run_id != "latest":
        return run_id, None
    runs_dir = os.path.join(pipeline_root, ".runs")
    candidates = sorted(
        (d for d in (os.listdir(runs_dir) if os.path.isdir(runs_dir)
                     else [])
         # "_"-prefixed dirs are cross-run stores (.runs/_metrics), not
         # runs — they'd otherwise win "latest" by mtime on every scrape.
         if not d.startswith("_")
         and os.path.isdir(os.path.join(runs_dir, d))),
        key=lambda d: os.path.getmtime(os.path.join(runs_dir, d)),
    )
    if not candidates:
        return None, f"no traced runs under {runs_dir}"
    return candidates[-1], None


def _load_run_metrics(pipeline_root: str, run_id: str):
    """((run_id, events, metrics), error) for one traced run."""
    import os

    from tpu_pipelines.observability import (
        compute_metrics,
        read_events,
        run_trace_dir,
    )

    run_id, err = _resolve_run_id(pipeline_root, run_id)
    if err:
        return None, err
    events_file = os.path.join(
        run_trace_dir(pipeline_root, run_id), "trace", "events.jsonl"
    )
    if not os.path.exists(events_file):
        return None, (
            f"no trace event log at {events_file} (was the run traced? "
            "TPP_TRACE=0 disables tracing)"
        )
    events = read_events(events_file)
    if not events:
        return None, f"trace event log {events_file} is empty"
    return (run_id, events, compute_metrics(events)), None


def _attach_history_telemetry(
    pipeline_root: str, run_id: str, metrics: dict
) -> None:
    """Backfill ``metrics['train_telemetry']`` from the durable snapshot
    ring (<root>/.runs/_metrics/) when the trace itself recorded none —
    the ring outlives the trainer process, so ``trace``/``trace diff``
    can compare telemetry for runs whose event log predates the summary
    instant or was trimmed.  No ring, no change."""
    if metrics.get("train_telemetry"):
        return
    from tpu_pipelines.observability import MetricsHistory

    try:
        headline = MetricsHistory.for_pipeline_root(
            pipeline_root
        ).headline(run_id)
    except OSError:
        return
    if headline:
        metrics["train_telemetry"] = headline


def cmd_trace(args) -> int:
    import json as _json

    from tpu_pipelines.observability import (
        export_metrics,
        export_perfetto,
        format_summary,
    )

    if args.run_id[0] == "diff":
        return cmd_trace_diff(args)
    if args.run_id[0] == "serve":
        return cmd_trace_serve(args)
    if len(args.run_id) != 1:
        print("trace takes one run id (or: trace diff <a> <b>, "
              "trace serve <trace_dir>)", file=sys.stderr)
        return 2
    if not args.pipeline_root:
        print("trace <run-id> requires --pipeline-root", file=sys.stderr)
        return 2
    loaded, err = _load_run_metrics(args.pipeline_root, args.run_id[0])
    if err:
        print(err, file=sys.stderr)
        return 1
    run_id, events, metrics = loaded
    _attach_history_telemetry(args.pipeline_root, run_id, metrics)
    if args.json:
        print(_json.dumps(
            {"run_id": run_id, "events": len(events), **metrics},
            indent=1, sort_keys=True,
        ))
    else:
        print(f"run {run_id}  ({len(events)} events)")
        print(format_summary(metrics))
    if args.perfetto:
        path = export_perfetto(events, args.perfetto)
        if not args.json:
            print(
                f"perfetto timeline: {path} "
                "(load in https://ui.perfetto.dev)"
            )
    if args.metrics:
        path = export_metrics(events, args.metrics)
        if not args.json:
            print(f"metrics summary: {path}")
    return 0


def cmd_trace_diff(args) -> int:
    """``trace diff <run_a> <run_b>``: per-node deltas + regression
    flags; exit 0 = clean, 3 = regressed past threshold, 1 = error."""
    import json as _json

    from tpu_pipelines.observability import diff_metrics, format_diff

    ids = args.run_id[1:]
    if len(ids) != 2:
        print("trace diff needs exactly two run ids: trace diff <a> <b>",
              file=sys.stderr)
        return 2
    if not args.pipeline_root:
        print("trace diff requires --pipeline-root", file=sys.stderr)
        return 2
    loaded = []
    for rid in ids:
        got, err = _load_run_metrics(args.pipeline_root, rid)
        if err:
            print(err, file=sys.stderr)
            return 1
        loaded.append(got)
    (id_a, _, metrics_a), (id_b, _, metrics_b) = loaded
    _attach_history_telemetry(args.pipeline_root, id_a, metrics_a)
    _attach_history_telemetry(args.pipeline_root, id_b, metrics_b)
    diff = diff_metrics(metrics_a, metrics_b, threshold=args.threshold)
    if args.json:
        print(_json.dumps(
            {"run_a": id_a, "run_b": id_b, **diff},
            indent=1, sort_keys=True,
        ))
    else:
        print(f"trace diff: {id_a} (baseline) -> {id_b}")
        print(format_diff(diff))
    return 3 if diff["regressed"] else 0


def cmd_trace_serve(args) -> int:
    """``trace serve <trace_dir>``: read/filter/export the serving tier's
    request traces (<trace_dir>/serving/events.jsonl, written when
    TPP_REQUEST_TRACE is on and a trace dir is configured).  ``--trace-id``
    narrows to one trace (the id a traceparent response header / metrics
    exemplar carries), ``--perfetto`` writes the replica/batch-group
    timeline, ``--exemplars`` lists the scrape-interval exemplar links."""
    import json as _json
    import os

    from tpu_pipelines.observability import read_events
    from tpu_pipelines.observability.export import (
        export_perfetto_requests,
        format_request_traces,
        summarize_request_traces,
    )

    if len(args.run_id) != 2:
        print("trace serve needs a trace dir: trace serve <trace_dir>",
              file=sys.stderr)
        return 2
    trace_dir = args.run_id[1]
    events_file = os.path.join(trace_dir, "serving", "events.jsonl")
    if not os.path.exists(events_file):
        # Accept the serving/ dir (or the file) directly too.
        for cand in (
            os.path.join(trace_dir, "events.jsonl"), trace_dir,
        ):
            if os.path.isfile(cand):
                events_file = cand
                break
        else:
            print(
                f"no serving trace log at {events_file} (was the server "
                "started with TPP_REQUEST_TRACE=sample:N|all and a "
                "TPP_REQUEST_TRACE_DIR?)", file=sys.stderr,
            )
            return 1
    events = read_events(events_file)
    if args.trace_id:
        events = [
            e for e in events
            if e.get("trace") == args.trace_id
            or (e.get("args") or {}).get("trace_id") == args.trace_id
        ]
        if not events:
            print(f"no events for trace id {args.trace_id}",
                  file=sys.stderr)
            return 1
    summary = summarize_request_traces(events)
    if args.json:
        print(_json.dumps(
            {"events": len(events), **summary}, indent=1, sort_keys=True,
            default=str,
        ))
    else:
        print(f"serving traces: {summary['trace_count']} "
              f"({len(events)} events, {events_file})")
        print(format_request_traces(summary))
        if args.exemplars:
            print("exemplars (slowest request per scrape interval):")
            for ex in summary["exemplars"]:
                print(
                    f"  {ex['endpoint']:<9} "
                    f"{(ex['latency_s'] or 0.0) * 1e3:>9.2f}ms  "
                    f"trace {ex['trace_id']}"
                )
            if not summary["exemplars"]:
                print("  <none recorded — /metrics scrapes drain them>")
    if args.perfetto:
        path = export_perfetto_requests(events, args.perfetto)
        if not args.json:
            print(f"perfetto timeline: {path} "
                  "(one track per replica and batch group)")
    return 0


def cmd_lineage(store: MetadataStore, artifact_id: int) -> int:
    text = store.format_lineage(artifact_id)
    print(text)
    return 1 if text.startswith("<no artifact") else 0


def cmd_artifacts(store: MetadataStore, type_name: str) -> int:
    arts = store.get_artifacts(type_name=type_name or None)
    if not arts:
        print("no artifacts", file=sys.stderr)
        return 1
    for a in arts:
        print(f"#{a.id:<5} {a.type_name:<16} [{a.state.value}] {a.uri}")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tpu_pipelines", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_run = sub.add_parser("run", help="run a pipeline module locally")
    p_run.add_argument("--pipeline-module", required=True,
                       help="file defining create_pipeline() -> Pipeline")
    p_run.add_argument("--param", action="append", default=[],
                       metavar="NAME=VALUE",
                       help="runtime parameter override (JSON value or "
                            "string); repeatable")
    p_run.add_argument("--from-node", action="append", default=[],
                       help="partial run: start here, upstreams from store")
    p_run.add_argument("--to-node", action="append", default=[],
                       help="partial run: stop here")
    p_run.add_argument("--resume-from", default=None, metavar="RUN_ID",
                       help="continue a crashed run: 'latest' or a prior "
                            "run id; adopts published executions, fences "
                            "and re-runs the rest (docs/RECOVERY.md)")
    p_run.add_argument("--max-retries", type=int, default=0)
    p_run.add_argument("--max-parallel-nodes", type=int, default=None,
                       help="scheduler worker-pool size (default: DAG root "
                            "count, or TPP_MAX_PARALLEL_NODES; 1 = strict "
                            "sequential)")
    p_run.add_argument("--lint", default=None, choices=["error", "warn", "off"],
                       help="pre-flight static analysis gate (default: env "
                            "TPP_LINT, else off); 'error' refuses to run on "
                            "ERROR findings, 'warn' on any finding")

    p_lint = sub.add_parser(
        "lint",
        help="static pipeline + executor analysis; exit 0 clean, 3 on "
             "blocking findings (docs/ANALYSIS.md)",
    )
    p_lint.add_argument("--pipeline-module", required=True,
                        help="file defining create_pipeline() -> Pipeline")
    p_lint.add_argument("--spmd-sync", action="store_true",
                        help="lint as if running under the multi-host "
                             "spmd runner (arms TPP108: in-runner retry "
                             "policies are refused there)")
    p_lint.add_argument("--continuous", action="store_true",
                        help="lint as if handed to the continuous "
                             "controller (arms TPP111: nodes with no "
                             "deadline and no retry policy wedge the "
                             "always-on loop)")
    p_lint.add_argument("--json", action="store_true",
                        help="machine-readable output (one JSON object)")
    p_lint.add_argument("--fail-on", default="error",
                        choices=["error", "warn"],
                        help="findings at/above this severity exit 3 "
                             "(default: error)")

    p_cont = sub.add_parser(
        "continuous",
        help="run the continuous controller: watch a {SPAN} pattern, "
             "ingest new spans incrementally, retrain over a rolling "
             "window, deploy blessed models into the serving fleet "
             "(docs/CONTINUOUS.md)",
    )
    p_cont.add_argument("--pipeline-module", required=True,
                        help="file defining create_continuous() -> "
                             "ContinuousConfig")
    p_cont.add_argument("--poll-interval", type=float, default=None,
                        metavar="SECONDS",
                        help="override the config's watcher poll interval")
    p_cont.add_argument("--state-dir", default=None,
                        help="override the config's controller state dir "
                             "(watcher acks + in-flight run marker; "
                             "enables resume across restarts)")
    p_cont.add_argument("--max-iterations", type=int, default=0,
                        help="stop after N loop iterations (0 = run until "
                             "signalled)")
    p_cont.add_argument("--once", action="store_true",
                        help="run exactly one iteration and exit "
                             "(cron-style operation)")
    p_cont.add_argument("--lint", default=None,
                        choices=["error", "warn", "off"],
                        help="lint gate level for handed pipelines "
                             "(default: config, then env TPP_LINT); "
                             "TPP111 is armed either way")

    inspect = sub.add_parser("inspect", help="read the metadata store")
    # On the parent AND each leaf, so both argument orders work:
    #   inspect --metadata md.sqlite runs <p>   /   inspect runs <p> --metadata md.sqlite
    inspect.add_argument("--metadata", default=None,
                         help="path to the pipeline's metadata sqlite")
    md_parent = argparse.ArgumentParser(add_help=False)
    # SUPPRESS: a leaf parse without --metadata must not clobber the value
    # the parent-level option already set.
    md_parent.add_argument("--metadata", default=argparse.SUPPRESS)
    isub = inspect.add_subparsers(dest="what", required=True)

    p_runs = isub.add_parser("runs", parents=[md_parent],
                             help="runs + per-node duration/state columns")
    p_runs.add_argument("pipeline", help="pipeline name")
    p_runs.add_argument("--pipeline-root", default="",
                        help="pipeline root; adds trace-derived columns "
                             "(queue wait) from <root>/.runs/<id>/trace")
    p_runs.add_argument("--json", action="store_true",
                        help="machine-readable output (one JSON object)")

    p_trace = sub.add_parser(
        "trace",
        help="summarize/export a run's RunTrace event log, compare two "
             "runs (trace diff <a> <b>), or read the serving tier's "
             "request traces (trace serve <trace_dir>)",
    )
    p_trace.add_argument(
        "run_id", nargs="+",
        help="run id or 'latest'; or: diff <run-a> <run-b>; or: "
             "serve <trace_dir>",
    )
    p_trace.add_argument("--pipeline-root", default="",
                         help="pipeline root containing .runs/<run-id>/ "
                              "(required except for trace serve)")
    p_trace.add_argument("--perfetto", default="", metavar="OUT_JSON",
                         help="write a Chrome/Perfetto trace.json here")
    p_trace.add_argument("--metrics", default="", metavar="OUT_JSON",
                         help="write the metrics.json summary here")
    p_trace.add_argument("--json", action="store_true",
                         help="machine-readable output (one JSON object)")
    p_trace.add_argument(
        "--threshold", type=float, default=0.2,
        help="diff regression threshold as a fraction (default 0.2 = "
             "20%% slower flags; exit code 3 on any flag)",
    )
    p_trace.add_argument(
        "--trace-id", default="",
        help="trace serve: only this trace id (from a traceparent "
             "response header or a /metrics exemplar)",
    )
    p_trace.add_argument(
        "--exemplars", action="store_true",
        help="trace serve: list the slowest-request-per-scrape exemplar "
             "links next to the trace table",
    )

    p_drift = sub.add_parser(
        "drift",
        help="live drift & skew report off a serving fleet's /metrics "
             "scrape (observability/drift.py; docs/OBSERVABILITY.md "
             "\"Live drift & skew\")",
    )
    p_drift.add_argument(
        "--url", required=True,
        help="serving base URL (the Pusher push-URL works, e.g. "
             "http://127.0.0.1:8501/v1/models/taxi — only scheme+host "
             "are used; /metrics is derived)",
    )
    p_drift.add_argument("--json", action="store_true",
                         help="machine-readable output (one JSON object)")
    p_drift.add_argument(
        "--fail-on-alert", action="store_true",
        help="exit 3 when the fleet has counted any drift/skew alert "
             "(CI gate parity with `tpp lint`)",
    )

    p_lin = isub.add_parser("lineage", parents=[md_parent],
                            help="provenance chain of an artifact")
    p_lin.add_argument("artifact_id", type=int)

    p_art = isub.add_parser("artifacts", parents=[md_parent],
                            help="list artifacts")
    p_art.add_argument("--type", default="", help="filter by artifact type")

    args = parser.parse_args(argv)
    if args.cmd == "run":
        return cmd_run(args)
    if args.cmd == "lint":
        return cmd_lint(args)
    if args.cmd == "trace":
        return cmd_trace(args)
    if args.cmd == "continuous":
        return cmd_continuous(args)
    if args.cmd == "drift":
        return cmd_drift(args)
    if not args.metadata:
        inspect.error("the following arguments are required: --metadata")
    store = MetadataStore(args.metadata)
    try:
        if args.what == "runs":
            return cmd_runs(
                store, args.pipeline, args.pipeline_root,
                as_json=args.json,
            )
        if args.what == "lineage":
            return cmd_lineage(store, args.artifact_id)
        return cmd_artifacts(store, args.type)
    finally:
        store.close()


def cmd_lint(args) -> int:
    """``lint --pipeline-module M [--json] [--fail-on error|warn]``."""
    import json as _json

    from tpu_pipelines.analysis import (
        EXIT_GATED,
        analyze_pipeline,
        check_metric_docs,
        check_serving_metric_docs,
        format_findings,
        gated,
        lint_report,
        sort_findings,
    )
    from tpu_pipelines.utils.module_loader import load_fn

    try:
        pipeline = load_fn(args.pipeline_module, "create_pipeline")()
        findings = analyze_pipeline(
            pipeline,
            spmd_sync=getattr(args, "spmd_sync", False),
            continuous=getattr(args, "continuous", False),
        )
        # TPP211/TPP214 are repo-scoped (metric emissions vs the doc
        # catalogs), not pipeline-scoped — they ride along with every lint
        # so the same gate catches a metric family shipped without its
        # catalog row.
        findings = sort_findings(
            list(findings)
            + check_serving_metric_docs()
            + check_metric_docs()
        )
    except Exception as e:
        # The module failing to load/compile is a tool error (1), not a
        # lint verdict (3): CI must distinguish "pipeline is broken at
        # import" from "pipeline linted dirty".
        print(f"lint: cannot analyze {args.pipeline_module}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    blocking = gated(findings, args.fail_on)
    if args.json:
        report = lint_report(findings)
        report["fail_on"] = args.fail_on
        report["gated"] = len(blocking)
        print(_json.dumps(report, indent=1, sort_keys=True))
    else:
        print(format_findings(findings))
        if blocking:
            print(f"lint: {len(blocking)} finding(s) at/above "
                  f"--fail-on={args.fail_on}; refusing (exit {EXIT_GATED})")
    return EXIT_GATED if blocking else 0


def cmd_continuous(args) -> int:
    """``continuous --pipeline-module M``: the long-lived controller loop
    with drain-and-stop signal handling — the first SIGINT/SIGTERM lets
    the in-flight pipeline run finish and persists state before exiting
    (no half-acked span, no orphaned pending marker); a second signal
    aborts hard via the default handler."""
    import dataclasses
    import logging
    import signal
    import threading

    from tpu_pipelines.analysis import EXIT_GATED, LintGateError
    from tpu_pipelines.continuous import ContinuousController
    from tpu_pipelines.utils.module_loader import load_fn

    logging.basicConfig(level=logging.INFO)
    try:
        cfg = load_fn(args.pipeline_module, "create_continuous")()
    except Exception as e:  # noqa: BLE001 — tool error, not a verdict
        print(f"continuous: cannot load {args.pipeline_module}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    overrides = {}
    if args.poll_interval is not None:
        overrides["poll_interval_s"] = args.poll_interval
    if args.state_dir is not None:
        overrides["state_dir"] = args.state_dir
    if args.lint is not None:
        overrides["lint"] = args.lint
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    stop = threading.Event()
    default_handlers = {}

    def on_signal(signum, frame):  # noqa: ARG001
        print(
            f"continuous: signal {signum} — draining (in-flight run "
            "finishes, state persists; signal again to abort hard)",
            file=sys.stderr,
        )
        stop.set()
        # Re-arm the default handler: the SECOND signal kills us.
        for sig, handler in default_handlers.items():
            signal.signal(sig, handler)

    for sig in (signal.SIGINT, signal.SIGTERM):
        default_handlers[sig] = signal.getsignal(sig)
        signal.signal(sig, on_signal)

    try:
        controller = ContinuousController(cfg)
        controller.run(
            stop_event=stop,
            max_iterations=1 if args.once else args.max_iterations,
        )
    except LintGateError as e:
        print(str(e), file=sys.stderr)
        return EXIT_GATED
    finally:
        for sig, handler in default_handlers.items():
            signal.signal(sig, handler)
    status = controller.status()
    print(f"continuous: stopped after {status['iterations']} iteration(s); "
          f"spans seen: {status['spans_seen']}")
    return 0


def cmd_drift(args) -> int:
    """``drift --url U [--json] [--fail-on-alert]``: scrape a live
    fleet's /metrics and render the drift/skew report (the same parse
    the continuous controller's scrape consumer uses)."""
    import json as _json
    import urllib.parse
    import urllib.request

    from tpu_pipelines.analysis import EXIT_GATED
    from tpu_pipelines.observability.drift import (
        format_drift_report,
        parse_drift_scrape,
    )

    parts = urllib.parse.urlsplit(args.url)
    url = urllib.parse.urlunsplit(
        (parts.scheme, parts.netloc, "/metrics", "", "")
    )
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            text = r.read().decode("utf-8", "replace")
    except Exception as e:  # noqa: BLE001 — tool error, not a verdict
        print(f"drift: cannot scrape {url}: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    report = parse_drift_scrape(text)
    if args.json:
        print(_json.dumps(report, indent=2, sort_keys=True))
    else:
        print(format_drift_report(report))
    if args.fail_on_alert and report.get("alerts_total", 0) > 0:
        return EXIT_GATED
    return 0


def cmd_run(args) -> int:
    import json
    import logging

    from tpu_pipelines.orchestration import LocalDagRunner
    from tpu_pipelines.utils.module_loader import load_fn

    logging.basicConfig(level=logging.INFO)
    params = {}
    for spec in args.param:
        name, eq, raw = spec.partition("=")
        if not eq:
            print(f"--param needs NAME=VALUE, got {spec!r}")
            return 2
        try:
            params[name] = json.loads(raw)
        except json.JSONDecodeError:
            params[name] = raw  # plain string value
    pipeline = load_fn(args.pipeline_module, "create_pipeline")()
    from tpu_pipelines.analysis import EXIT_GATED, LintGateError

    try:
        result = LocalDagRunner(
            max_retries=args.max_retries,
            max_parallel_nodes=args.max_parallel_nodes,
        ).run(
            pipeline,
            runtime_parameters=params,
            from_nodes=args.from_node or None,
            to_nodes=args.to_node or None,
            raise_on_failure=False,
            resume_from=args.resume_from,
            lint=args.lint,
        )
    except LintGateError as e:
        print(str(e), file=sys.stderr)
        return EXIT_GATED
    print(f"run {result.run_id}: "
          f"{'OK' if result.succeeded else 'FAILED'}")
    for node_id, nr in result.nodes.items():
        mark = {"COMPLETE": "done", "CACHED": "cached"}.get(
            nr.status, nr.status
        )
        if nr.adopted:
            mark = f"adopted ({mark})"
        wall = f" ({nr.wall_clock_s:.1f}s)" if nr.wall_clock_s else ""
        err = f"  !! {nr.error}" if nr.error else ""
        print(f"  {node_id}: {mark}{wall}{err}")
    print(f"metadata: {pipeline.metadata_path}")
    return 0 if result.succeeded else 1


if __name__ == "__main__":
    sys.exit(main())
