"""Unified fault-tolerance layer (ISSUE 7, docs/RECOVERY.md).

The tentpole contracts, each proven here:
  - one RetryPolicy (attempts, exponential backoff + full jitter,
    deadline budget) and one transient-vs-permanent taxonomy serve every
    retry loop, with retries counted in retry_attempts_total{site=...};
  - the runner's per-node launcher retries ONLY transient failures, under
    the component > pipeline > env precedence, and refuses in-runner
    retries on spmd_sync pipelines;
  - ShardPlan fan-outs retry per shard, quarantine poison shards after
    their strikes, and replace dead fork workers; StatisticsGen's
    partial-salvage mode keeps merged statistics exact over survivors;
  - the metadata store is multi-process-safe (flock writer lock + publish
    contention retry + torn-write detection on load): N concurrent
    writers lose nothing and tear nothing;
  - the ModelServer sheds load with 429 + Retry-After instead of
    dropping, and a hot reload under a hammer serves zero 5xx.

Everything here is CPU-only and tier-1-fast (marker: robustness).
"""

import json
import multiprocessing
import os
import sqlite3
import threading
import time
import urllib.error
import urllib.request

import pytest

from tpu_pipelines.dsl.component import ExecutorContext, component
from tpu_pipelines.dsl.pipeline import Pipeline
from tpu_pipelines.metadata import MetadataStore
from tpu_pipelines.metadata.store import StoreUnavailableError
from tpu_pipelines.metadata.types import (
    Artifact,
    Context,
    Execution,
    ExecutionState,
)
from tpu_pipelines.observability.metrics import default_registry
from tpu_pipelines.orchestration import LocalDagRunner, PipelineRunError
from tpu_pipelines.robustness import (
    FileLock,
    PermanentError,
    RetryPolicy,
    TransientError,
    atomic_write_json,
    classify_error,
    load_json_tolerant,
    retry_call,
)
from tpu_pipelines.testing.faults import (
    STORE_CONTENTION,
    STORE_KEY,
    TRANSIENT_EXECUTOR_ERROR,
    FaultPlan,
    NodeFault,
)

pytestmark = pytest.mark.robustness


def _counter_total(name, label_prefix=""):
    metric = default_registry().get(name)
    if metric is None:
        return 0.0
    return sum(
        float(v) for key, v in metric._snapshot_series().items()
        if not label_prefix or (key and key[0].startswith(label_prefix))
    )


# ------------------------------------------------------------- taxonomy


def test_classify_error_table():
    import errno

    cases = [
        (TransientError("x"), "transient"),
        (PermanentError("x"), "permanent"),
        (RuntimeError("unknown executor flake"), "transient"),  # default
        (ValueError("bad config"), "permanent"),
        (TypeError("bad call"), "permanent"),
        (KeyError("missing"), "permanent"),
        (FileNotFoundError("gone"), "permanent"),
        (PermissionError("wall"), "permanent"),
        (ConnectionResetError("reset"), "transient"),
        (TimeoutError("slow"), "transient"),
        (StoreUnavailableError("busy"), "transient"),
        (OSError(errno.ECONNREFUSED, "refused"), "transient"),
        (OSError(errno.ENOSPC, "disk full"), "permanent"),
        (urllib.error.URLError("conn refused"), "transient"),
        (
            urllib.error.HTTPError("u", 500, "boom", {}, None),
            "permanent",  # the server ANSWERED; its verdict stands
        ),
    ]
    for exc, want in cases:
        assert classify_error(exc) == want, (exc, want)


def test_classify_error_follows_cause_chain():
    try:
        try:
            raise OSError("preempted")
        except OSError as inner:
            raise TransientError("wrapped") from inner
    except TransientError as exc:
        assert classify_error(exc) == "transient"
    # A permanent marker wrapping a transient cause stays permanent.
    exc = PermanentError("poisoned")
    exc.__cause__ = ConnectionError("reset")
    assert classify_error(exc) == "permanent"


# ----------------------------------------------------------- RetryPolicy


def test_backoff_exponential_cap_and_jitter_bounds():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.1, max_delay_s=0.4)
    for failures, cap in [(1, 0.1), (2, 0.2), (3, 0.4), (4, 0.4)]:
        for _ in range(20):
            d = p.backoff_s(failures)
            assert 0.0 <= d <= cap + 1e-9, (failures, d)
    det = RetryPolicy(
        max_attempts=3, base_delay_s=0.1, max_delay_s=10.0, jitter=False
    )
    assert det.backoff_s(1) == 0.1
    assert det.backoff_s(2) == 0.2
    assert det.backoff_s(3) == 0.4


def test_policy_validation_and_roundtrip():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(base_delay_s=-1)
    p = RetryPolicy(max_attempts=4, base_delay_s=0.5, deadline_s=9.0)
    assert RetryPolicy.from_json(p.to_json()) == p
    assert RetryPolicy.from_json(None) is None
    assert p.retries == 3


def test_policy_from_env(monkeypatch):
    assert RetryPolicy.from_env() is None
    monkeypatch.setenv("TPP_RETRY_MAX_ATTEMPTS", "4")
    monkeypatch.setenv("TPP_RETRY_BASE_DELAY_S", "0.01")
    p = RetryPolicy.from_env()
    assert p.max_attempts == 4 and p.base_delay_s == 0.01
    monkeypatch.setenv("TPP_RETRY_MAX_ATTEMPTS", "1")
    assert RetryPolicy.from_env() is None  # 1 attempt = no policy
    monkeypatch.setenv("TPP_RETRY_MAX_ATTEMPTS", "bogus")
    assert RetryPolicy.from_env() is None


def test_retry_call_retries_transient_and_counts():
    before = _counter_total("retry_attempts_total", "test.site")
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("blip")
        return "ok"

    out = retry_call(
        flaky,
        policy=RetryPolicy(max_attempts=3, base_delay_s=0.001),
        site="test.site",
    )
    assert out == "ok" and calls["n"] == 3
    assert _counter_total("retry_attempts_total", "test.site") - before == 2


def test_retry_call_fails_fast_on_permanent():
    calls = {"n": 0}

    def poisoned():
        calls["n"] += 1
        raise ValueError("bad input")

    with pytest.raises(ValueError):
        retry_call(
            poisoned,
            policy=RetryPolicy(max_attempts=5, base_delay_s=0.001),
            site="test.permanent",
        )
    assert calls["n"] == 1  # no budget burned on a provable re-failure


def test_retry_call_respects_deadline_budget():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        time.sleep(0.03)
        raise ConnectionError("slow flake")

    t0 = time.monotonic()
    with pytest.raises(ConnectionError):
        retry_call(
            always,
            policy=RetryPolicy(
                max_attempts=100, base_delay_s=0.01, deadline_s=0.1,
                jitter=False,
            ),
            site="test.deadline",
        )
    assert time.monotonic() - t0 < 2.0
    assert calls["n"] < 100  # the budget, not the attempt count, stopped it


def test_retry_call_cancel_event_stops_retrying():
    cancel = threading.Event()
    cancel.set()

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise ConnectionError("blip")

    with pytest.raises(ConnectionError):
        retry_call(
            flaky,
            policy=RetryPolicy(max_attempts=5, base_delay_s=0.001),
            site="test.cancel", cancel_event=cancel,
        )
    assert calls["n"] == 1


# ------------------------------------------------------ runner integration


CALLS = []


def _flaky_component(name="Flaky", fail_times=2, exc_factory=None):
    state = {"n": 0}

    @component(outputs={"examples": "Examples"}, name=name)
    def C(ctx):
        CALLS.append(ctx.node_id)
        state["n"] += 1
        if state["n"] <= fail_times:
            raise (exc_factory or TransientError)("injected")
        with open(os.path.join(ctx.output("examples").uri, "ok"), "w") as f:
            f.write("ok")

    return C


def _one_node_pipeline(tmp_path, comp, **kw):
    return Pipeline(
        "r", [comp], pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"), **kw,
    )


@pytest.fixture(autouse=True)
def _clear_calls():
    CALLS.clear()


def test_component_retry_policy_absorbs_transient_fault(tmp_path):
    node = _flaky_component()().with_retry_policy(
        max_attempts=3, base_delay_s=0.001
    )
    result = LocalDagRunner().run(_one_node_pipeline(tmp_path, node))
    assert result.nodes["Flaky"].status == "COMPLETE"
    assert result.nodes["Flaky"].retries == 2


def test_permanent_error_not_retried_despite_policy(tmp_path):
    node = _flaky_component(
        fail_times=99, exc_factory=ValueError
    )().with_retry_policy(max_attempts=5, base_delay_s=0.001)
    result = LocalDagRunner().run(
        _one_node_pipeline(tmp_path, node), raise_on_failure=False
    )
    nr = result.nodes["Flaky"]
    assert nr.status == "FAILED"
    assert nr.retries == 0  # classified permanent on attempt 1
    assert len(CALLS) == 1


def test_pipeline_default_policy_and_node_override(tmp_path):
    # Pipeline default says no retries; the node override wins and saves
    # the run — the documented precedence ladder.
    node = _flaky_component(fail_times=1)().with_retry_policy(
        max_attempts=2, base_delay_s=0.001
    )
    result = LocalDagRunner().run(_one_node_pipeline(
        tmp_path, node, retry_policy=RetryPolicy(max_attempts=1),
    ))
    assert result.nodes["Flaky"].retries == 1

    CALLS.clear()
    # And the pipeline default alone arms retries for plain nodes.
    node2 = _flaky_component(name="Flaky2", fail_times=1)()
    result = LocalDagRunner().run(Pipeline(
        "r2", [node2], pipeline_root=str(tmp_path / "root2"),
        metadata_path=str(tmp_path / "md2.sqlite"),
        retry_policy={"max_attempts": 2, "base_delay_s": 0.001},
    ))
    assert result.nodes["Flaky2"].retries == 1


def test_env_policy_rung(tmp_path, monkeypatch):
    monkeypatch.setenv("TPP_RETRY_MAX_ATTEMPTS", "2")
    monkeypatch.setenv("TPP_RETRY_BASE_DELAY_S", "0.001")
    node = _flaky_component(fail_times=1)()
    result = LocalDagRunner().run(_one_node_pipeline(tmp_path, node))
    assert result.nodes["Flaky"].retries == 1


def test_transient_fault_kind_with_retry_policy(tmp_path):
    """The TRANSIENT_EXECUTOR_ERROR fault fires `times` times then goes
    inert — with a policy the node completes; the retries are counted."""
    before = _counter_total("retry_attempts_total", "node:Gen")

    @component(outputs={"examples": "Examples"}, name="Gen")
    def Gen(ctx):
        with open(os.path.join(ctx.output("examples").uri, "ok"), "w") as f:
            f.write("ok")

    node = Gen().with_retry_policy(max_attempts=3, base_delay_s=0.001)
    plan = FaultPlan({"Gen": NodeFault(TRANSIENT_EXECUTOR_ERROR, times=2)})
    with plan.activate():
        result = LocalDagRunner().run(_one_node_pipeline(tmp_path, node))
    assert result.nodes["Gen"].status == "COMPLETE"
    assert result.nodes["Gen"].retries == 2
    assert [e for _, e in plan.log] == [
        "transient_executor_error", "transient_executor_error",
    ]
    assert _counter_total("retry_attempts_total", "node:Gen") - before == 2


def test_run_under_a_fault_schedule_publishes_a_fault_free_lineage(tmp_path):
    """The whole schedule at once (transient executor errors at one node,
    store contention on its publish): the run completes on the fleet's
    retry rung, and what it published is what a fault-free run does."""
    from test_concurrent_runner import _node_executions

    def two_nodes(home):
        @component(outputs={"examples": "Examples"}, name="Gen")
        def Gen(ctx):
            with open(os.path.join(ctx.output("examples").uri, "d"), "w") as f:
                f.write("rows")

        @component(inputs={"examples": "Examples"},
                   outputs={"model": "Model"}, name="Fit")
        def Fit(ctx):
            assert os.path.exists(os.path.join(ctx.input("examples").uri, "d"))
            with open(os.path.join(ctx.output("model").uri, "m"), "w") as f:
                f.write("fit")

        gen = Gen()
        return Pipeline(
            "chaos", [gen, Fit(examples=gen.outputs["examples"])],
            pipeline_root=str(home / "root"),
            metadata_path=str(home / "md.sqlite"),
            retry_policy={"max_attempts": 3, "base_delay_s": 0.001},
        )

    clean = two_nodes(tmp_path / "clean")
    assert LocalDagRunner().run(clean).succeeded
    chaos = two_nodes(tmp_path / "chaos")
    plan = FaultPlan({
        "Fit": NodeFault(TRANSIENT_EXECUTOR_ERROR, times=2),
        STORE_KEY: NodeFault(STORE_CONTENTION, times=2),
    })
    with plan.activate():
        result = LocalDagRunner().run(chaos)
    assert result.succeeded and result.nodes["Fit"].retries == 2
    assert {e for _, e in plan.log} == {
        "transient_executor_error", "store_contention:publish_execution"}
    assert _node_executions(
        chaos.metadata_path, chaos.pipeline_root
    ) == _node_executions(clean.metadata_path, clean.pipeline_root)


def test_spmd_sync_refuses_retry_policies(tmp_path):
    node = _flaky_component()().with_retry_policy(max_attempts=3)
    with pytest.raises(ValueError, match="spmd_sync is incompatible"):
        LocalDagRunner(spmd_sync=True).run(
            _one_node_pipeline(tmp_path, node)
        )


def test_retry_without_any_policy_unchanged(tmp_path):
    """No policy anywhere: single attempt, FAILED — the legacy default."""
    node = _flaky_component(fail_times=1)()
    with pytest.raises(PipelineRunError):
        LocalDagRunner().run(_one_node_pipeline(tmp_path, node))
    assert len(CALLS) == 1


# ------------------------------------------------------ shard resilience
# (The fork-pool kill/replacement paths are covered by the
# sanity-by-construction tests below.)


_POISON_STRIKES = {"n": 0}


def _shard_sq(x):
    return x * x


def _shard_poison(x):
    if x == 1:
        raise PermanentError("poisoned shard file")
    return x + 100


def _shard_flaky(args):
    x, flag_dir = args
    marker = os.path.join(flag_dir, f"fired-{x}")
    if x == 2 and not os.path.exists(marker):
        with open(marker, "w") as f:
            f.write("1")
        raise TransientError("worker blip")
    return x


def test_map_shards_resilient_retries_transient(tmp_path):
    from tpu_pipelines.data.shard_plan import map_shards_resilient

    res = map_shards_resilient(
        _shard_flaky, [(i, str(tmp_path)) for i in range(4)], workers=2,
        retry_policy=RetryPolicy(max_attempts=3, base_delay_s=0.001),
    )
    assert res.ok and res.results == [0, 1, 2, 3]
    assert res.retries >= 1


def test_map_shards_resilient_quarantines_permanent(tmp_path):
    from tpu_pipelines.data.shard_plan import map_shards_resilient

    before = _counter_total("shards_quarantined_total")
    res = map_shards_resilient(
        _shard_poison, [0, 1, 2], workers=2,
        retry_policy=RetryPolicy(max_attempts=4, base_delay_s=0.001),
    )
    assert not res.ok
    assert res.quarantined == [1]
    assert res.results == [100, None, 102]  # survivors intact, in order
    assert "poisoned" in res.failure_summary()[1]
    assert _counter_total("shards_quarantined_total") - before == 1
    with pytest.raises(PermanentError):
        res.raise_on_failure()


def test_map_shards_compat_raises_original_exception():
    from tpu_pipelines.data.shard_plan import map_shards

    with pytest.raises(PermanentError):
        map_shards(_shard_poison, [0, 1, 2], workers=2)
    assert map_shards(_shard_sq, [1, 2, 3], workers=2) == [1, 4, 9]


def _shard_killer(x):
    if x == 1:
        os._exit(17)  # SIGKILL-equivalent: the preempted-worker shape
    return x * 2


def test_dead_fork_worker_replaced_and_poison_quarantined():
    """A worker that dies mid-task breaks the whole pool; the fan-out
    must replace it, finish every innocent shard, and quarantine only
    the shard that keeps killing its workers."""
    from tpu_pipelines.data.shard_plan import map_shards_resilient

    if (os.cpu_count() or 1) < 1:  # pragma: no cover
        pytest.skip("needs fork")
    res = map_shards_resilient(
        _shard_killer, [0, 1, 2, 3], workers=2,
        retry_policy=RetryPolicy(max_attempts=2, base_delay_s=0.001),
    )
    assert res.quarantined == [1]
    assert res.results == [0, None, 4, 6]
    assert res.pool_replacements >= 1


def test_statistics_gen_salvage_mode(tmp_path):
    """A corrupt shard file: without salvage the node fails; with
    salvage_shards=True the shard is quarantined, the degradation is
    lineage-visible, and merged statistics are exact over survivors."""
    from tpu_pipelines.components import CsvExampleGen, StatisticsGen
    from tpu_pipelines.data import examples_io
    from tpu_pipelines.data.statistics import load_statistics

    csv = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests", "testdata", "taxi_sample.csv",
    )
    gen = CsvExampleGen(input_path=csv, num_shards=2)
    p = Pipeline(
        "salvage", [gen], pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    examples = LocalDagRunner().run(p).outputs_of(
        "CsvExampleGen", "examples"
    )[0]
    shard_paths = examples_io.split_shard_paths(examples.uri, "train")
    assert len(shard_paths) == 2
    row_counts = examples_io.shard_row_counts(examples.uri, "train")
    with open(shard_paths[1], "wb") as f:
        f.write(b"definitely not parquet")

    def run_stats(salvage: bool, out_name: str):
        outdir = tmp_path / out_name
        outdir.mkdir()
        out_art = Artifact(type_name="ExampleStatistics", uri=str(outdir))
        ctx = ExecutorContext(
            node_id="StatisticsGen",
            inputs={"examples": [examples]},
            outputs={"statistics": [out_art]},
            exec_properties={
                "chunk_rows": 0, "num_shards": 2,
                "salvage_shards": salvage,
            },
        )
        return StatisticsGen.EXECUTOR(ctx), out_art

    with pytest.raises(Exception):
        run_stats(False, "stats_strict")

    props, out_art = run_stats(True, "stats_salvaged")
    assert props["partial_statistics"] is True
    assert list(props["quarantined_shards"]["train"]) == [1]
    assert out_art.properties["quarantined_shards"]["train"] == [1]
    stats = load_statistics(out_art.uri)
    # Exact over survivors: every row of shard 0, none of shard 1.
    assert stats["train"].num_examples == row_counts[0]
    # The untouched split is complete.
    assert stats["eval"].num_examples > 0


# ------------------------------------------------- multi-writer store


def _publish_worker(db_path, worker_id, n_rows):
    try:
        store = MetadataStore(db_path)
        for i in range(n_rows):
            art_in = Artifact(
                type_name="Examples", uri=f"/in/{worker_id}/{i}"
            )
            store.put_artifact(art_in)
            art_out = Artifact(
                type_name="Model", uri=f"/out/{worker_id}/{i}"
            )
            ex = Execution(
                type_name="Stub",
                node_id=f"node-{worker_id}",
                state=ExecutionState.COMPLETE,
                properties={"worker": worker_id, "row": i},
            )
            store.publish_execution(
                ex, {"examples": [art_in]}, {"model": [art_out]},
                [Context("pipeline", "shared-run")],
            )
        store.close()
        os._exit(0)
    except BaseException:  # pragma: no cover - surfaces as exitcode != 0
        import traceback

        traceback.print_exc()
        os._exit(1)


def test_concurrent_multiprocess_writers_no_corruption(tmp_path):
    """ISSUE 7 acceptance: >= 4 processes publishing against one store
    root — no lost writes, no torn JSON, consistent lineage walk."""
    db = str(tmp_path / "md.sqlite")
    MetadataStore(db).close()  # create schema up front
    n_workers, n_rows = 4, 12
    ctx = multiprocessing.get_context("fork")
    procs = [
        ctx.Process(target=_publish_worker, args=(db, w, n_rows))
        for w in range(n_workers)
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=120)
        assert p.exitcode == 0, p.exitcode

    store = MetadataStore(db)  # quick_check runs on open: not torn
    executions = store.get_executions()
    assert len(executions) == n_workers * n_rows  # no lost writes
    seen = set()
    for ex in executions:
        assert ex.state == ExecutionState.COMPLETE
        seen.add((ex.properties["worker"], ex.properties["row"]))
        events = store.get_events_by_execution(ex.id)
        assert len(events) == 2  # one INPUT + one OUTPUT each
    assert len(seen) == n_workers * n_rows
    shared = store.get_context("pipeline", "shared-run")
    assert shared is not None
    assert len(store.get_executions_by_context(shared.id)) == (
        n_workers * n_rows
    )
    # Raw JSON columns parse (no torn rows behind the typed accessors).
    conn = sqlite3.connect(db)
    for (raw,) in conn.execute("SELECT properties FROM executions"):
        json.loads(raw)
    conn.close()
    # Lineage walk over a sampled artifact is consistent.
    art = store.get_artifacts_by_uri("/out/0/0")[0]
    lineage = store.get_lineage(art.id)
    assert lineage.producer is not None
    assert lineage.parents and lineage.parents[0].artifact.uri == "/in/0/0"
    store.close()


def test_store_contention_fault_absorbed_by_publish_retry(tmp_path):
    before = _counter_total("retry_attempts_total", "metadata.publish")
    store = MetadataStore(str(tmp_path / "md.sqlite"))
    plan = FaultPlan({
        STORE_KEY: NodeFault(STORE_CONTENTION, times=2),
    })
    art = Artifact(type_name="Model", uri="/m/1")
    ex = Execution(
        type_name="Stub", node_id="N", state=ExecutionState.COMPLETE
    )
    with plan.activate():
        store.publish_execution(ex, {}, {"model": [art]}, [])
    assert [e for _, e in plan.log] == [
        "store_contention:publish_execution",
    ] * 2
    assert _counter_total(
        "retry_attempts_total", "metadata.publish"
    ) - before == 2
    # The retried publish landed exactly once, ids intact.
    assert len(store.get_executions()) == 1
    assert store.get_execution(ex.id).node_id == "N"
    assert len(store.get_events_by_execution(ex.id)) == 1
    store.close()


def test_store_contention_exhausted_raises(tmp_path):
    store = MetadataStore(str(tmp_path / "md.sqlite"))
    plan = FaultPlan({
        STORE_KEY: NodeFault(STORE_CONTENTION, times=99),
    })
    ex = Execution(
        type_name="Stub", node_id="N", state=ExecutionState.COMPLETE
    )
    with plan.activate():
        with pytest.raises(StoreUnavailableError):
            store.publish_execution(ex, {}, {}, [])
    assert store.get_executions() == []
    store.close()


def test_torn_store_detected_on_load(tmp_path):
    db = tmp_path / "md.sqlite"
    db.write_bytes(b"SQLite format 3\x00 torn garbage that is not a db")
    with pytest.raises(StoreUnavailableError):
        MetadataStore(str(db))


def test_store_verify_disabled_skips_quick_check(tmp_path, monkeypatch):
    calls = {"n": 0}
    orig = MetadataStore._quick_check

    def counting(self):
        calls["n"] += 1
        return orig(self)

    monkeypatch.setattr(MetadataStore, "_quick_check", counting)
    monkeypatch.setenv("TPP_STORE_VERIFY", "0")
    MetadataStore(str(tmp_path / "md.sqlite")).close()
    assert calls["n"] == 0
    monkeypatch.delenv("TPP_STORE_VERIFY")
    MetadataStore(str(tmp_path / "md.sqlite")).close()
    assert calls["n"] == 1


# -------------------------------------------------- atomic + file lock


def test_atomic_write_and_tolerant_load(tmp_path):
    path = str(tmp_path / "ledger.json")
    atomic_write_json(path, {"a": 1})
    assert load_json_tolerant(path) == {"a": 1}
    # Torn legacy write: tolerated as None, never an exception.
    with open(path, "w") as f:
        f.write('{"a": 1, "b"')
    assert load_json_tolerant(path) is None
    assert load_json_tolerant(str(tmp_path / "missing.json")) is None
    # No temp litter after a successful atomic write.
    atomic_write_json(path, {"a": 2})
    assert sorted(os.listdir(tmp_path)) == ["ledger.json"]


def test_file_lock_reentrant_and_cross_process(tmp_path):
    target = str(tmp_path / "lockfile")
    lock = FileLock(target)
    with lock:
        with lock:  # reentrant within the process
            pass

    release_at = [0.0]

    def child():
        clock = FileLock(target)
        with clock:
            # Written only once the parent released.
            with open(target + ".order", "w") as f:
                f.write(str(time.monotonic()))
        os._exit(0)

    ctx = multiprocessing.get_context("fork")
    with lock:
        proc = ctx.Process(target=child)
        proc.start()
        time.sleep(0.3)
        release_at[0] = time.monotonic()
    proc.join(timeout=30)
    assert proc.exitcode == 0
    acquired_at = float(open(target + ".order").read())
    assert acquired_at >= release_at[0] - 0.01


# ------------------------------------------------------ serving tier


def _toy_server(tmp_path, **kw):
    from tpu_pipelines.serving import ModelServer
    from tpu_pipelines.trainer.export import export_model

    mod = tmp_path / "toy_model.py"
    mod.write_text(
        "import jax.numpy as jnp\n"
        "def build_model(hp):\n"
        "    return None\n"
        "def apply_fn(model, params, batch):\n"
        "    return jnp.asarray(batch['x'], jnp.float32) @ params['w']\n"
    )
    import numpy as np

    for version, scale in (("1", 1.0),):
        export_model(
            serving_model_dir=str(tmp_path / "m" / version),
            params={"w": (scale * np.eye(3, 2)).astype(np.float32)},
            module_file=str(mod),
        )
    return ModelServer("toy", str(tmp_path / "m"), **kw)


def test_admission_control_sheds_with_429_retry_after(tmp_path):
    server = _toy_server(tmp_path, max_queue_depth=1)
    port = server.start()
    body = json.dumps({"instances": [{"x": [1.0, 0.0, 0.0]}]}).encode()
    url = f"http://127.0.0.1:{port}/v1/models/toy:predict"
    try:
        with urllib.request.urlopen(
            urllib.request.Request(url, data=body), timeout=30
        ) as r:
            assert r.status == 200
            r.read()
        # The handler thread's _release() may still be in its finally
        # block; wait for the count to settle before saturating the
        # bound (deterministic — no other requests are in flight).
        deadline = time.monotonic() + 5
        while server._inflight != 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server._inflight == 0
        server._inflight = 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(url, data=body), timeout=30
            )
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        assert "overloaded" in json.loads(ei.value.read())["error"]
        server._inflight = 0
        # Shed is observable on the scrape, and load resumes after.
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape = r.read().decode()
        assert 'serving_load_shed_total{endpoint="predict"} 1' in scrape
        assert 'serving_requests_total{endpoint="predict",code="429"} 1' \
            in scrape
        with urllib.request.urlopen(
            urllib.request.Request(url, data=body), timeout=30
        ) as r:
            assert r.status == 200
    finally:
        server.stop()


def test_env_fallback_arms_admission_bound(tmp_path, monkeypatch):
    monkeypatch.setenv("TPP_SERVING_MAX_QUEUE", "7")
    server = _toy_server(tmp_path)
    assert server.max_queue_depth == 7


def test_reload_under_hammer_zero_5xx(tmp_path):
    """The reload-under-load guarantee: a concurrent predict hammer
    across a hot version swap sees only 200s — zero 5xx, zero dropped
    connections — and ends on the new version."""
    import numpy as np

    from tpu_pipelines.trainer.export import export_model

    server = _toy_server(tmp_path)
    port = server.start()
    url = f"http://127.0.0.1:{port}/v1/models/toy:predict"
    body = json.dumps({"instances": [{"x": [1.0, 2.0, 3.0]}]}).encode()
    codes = []
    errors = []
    lock = threading.Lock()

    def fire(n):
        for _ in range(n):
            try:
                with urllib.request.urlopen(
                    urllib.request.Request(url, data=body), timeout=30
                ) as r:
                    r.read()
                    with lock:
                        codes.append(r.status)
            except urllib.error.HTTPError as e:
                with lock:
                    codes.append(e.code)
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(repr(e))

    try:
        fire(2)  # warm the compile
        export_model(
            serving_model_dir=str(tmp_path / "m" / "2"),
            params={"w": (2.0 * np.eye(3, 2)).astype(np.float32)},
            module_file=str(tmp_path / "toy_model.py"),
        )
        threads = [
            threading.Thread(target=fire, args=(25,)) for _ in range(3)
        ]
        for t in threads:
            t.start()
        server.reload()  # hot swap mid-hammer
        for t in threads:
            t.join()
    finally:
        server.stop()
    assert errors == []
    assert all(c == 200 for c in codes), codes
    assert server.version == "2"


def test_urlopen_backoff_on_shared_policy_counts_retries():
    before = _counter_total(
        "retry_attempts_total", "infra_validator.urlopen"
    )
    from tpu_pipelines.components.infra_validator import _urlopen_backoff

    req = urllib.request.Request("http://127.0.0.1:9/never")  # closed port
    t0 = time.monotonic()
    with pytest.raises(urllib.error.URLError):
        _urlopen_backoff(req, timeout=1, attempts=2, base_delay_s=0.01)
    assert time.monotonic() - t0 < 10
    assert _counter_total(
        "retry_attempts_total", "infra_validator.urlopen"
    ) - before == 1


# ------------------------------------------------- cluster compile mapping


def test_cluster_compile_maps_retry_policy(tmp_path):
    """The Argo/JobSet mirror of the local loop: component/pipeline
    policies become retryStrategy limit+backoff; multi-host nodes get
    whole-set JobSet restarts (per-pod backoffLimit stays 0)."""
    yaml = pytest.importorskip("yaml")
    from tpu_pipelines.orchestration.cluster_runner import (
        TPUJobRunner,
        TPUJobRunnerConfig,
    )

    @component(outputs={"examples": "Examples"}, name="Gen")
    def Gen(ctx):
        pass

    @component(inputs={"examples": "Examples"},
               outputs={"model": "Model"}, name="Trainer",
               resource_class="tpu")
    def Trainer(ctx):
        pass

    gen = Gen()
    trainer = Trainer(
        examples=gen.outputs["examples"]
    ).with_retry_policy(max_attempts=4, base_delay_s=1.5, max_delay_s=30.0)
    pipeline = Pipeline(
        "cluster-retry", [gen, trainer],
        pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
        retry_policy={"max_attempts": 2, "base_delay_s": 0.5},
    )
    out = TPUJobRunner(TPUJobRunnerConfig(
        image="img", pipeline_module="m.py",
        output_dir=str(tmp_path / "out"), num_hosts=2,
    )).run(pipeline)

    with open(out["workflow"]) as f:
        wf = yaml.safe_load(f)
    by_name = {t["name"]: t for t in wf["spec"]["templates"]}
    # Component override: limit 3 (= max_attempts - 1) + backoff schedule.
    assert by_name["trainer"]["retryStrategy"] == {
        "limit": 3,
        "backoff": {"duration": "1.5s", "factor": 2, "maxDuration": "30s"},
    }
    # Pipeline default on the plain node.
    assert by_name["gen"]["retryStrategy"]["limit"] == 1
    assert by_name["gen"]["retryStrategy"]["backoff"]["duration"] == "0.5s"
    # Trainer is distributed (num_hosts=2): JobSet restarts whole-set.
    with open(out["jobset_Trainer"]) as f:
        js = yaml.safe_load(f)
    assert js["spec"]["failurePolicy"] == {"maxRestarts": 3}
    job = js["spec"]["replicatedJobs"][0]["template"]["spec"]
    assert job["backoffLimit"] == 0  # never per-pod under a collective


def test_cluster_compile_default_retry_strategy_unchanged(tmp_path):
    """No policy anywhere: the historical limit-2 default survives."""
    yaml = pytest.importorskip("yaml")
    from tpu_pipelines.orchestration.cluster_runner import (
        TPUJobRunner,
        TPUJobRunnerConfig,
    )

    @component(outputs={"examples": "Examples"}, name="Gen")
    def Gen(ctx):
        pass

    pipeline = Pipeline(
        "cluster-plain", [Gen()],
        pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    out = TPUJobRunner(TPUJobRunnerConfig(
        image="img", pipeline_module="m.py",
        output_dir=str(tmp_path / "out"),
    )).run(pipeline)
    with open(out["workflow"]) as f:
        wf = yaml.safe_load(f)
    by_name = {t["name"]: t for t in wf["spec"]["templates"]}
    assert by_name["gen"]["retryStrategy"] == {"limit": 2}


# --------------------------------------- self-healing fleet (ISSUE 17)


def test_classify_xla_runtime_errors():
    """Device-runtime taxonomy: RESOURCE_EXHAUSTED cannot clear on an
    equally-sized replica (permanent); transfer/comms failures can
    (transient).  Matched by class NAME so errors.py never imports
    jaxlib — a lookalike hierarchy stands in for the real one."""

    class XlaRuntimeError(RuntimeError):
        pass

    class SubError(XlaRuntimeError):
        pass

    table = [
        ("RESOURCE_EXHAUSTED: Out of memory allocating 4.1G", "permanent"),
        ("Out of memory while trying to allocate 8589934592 bytes",
         "permanent"),
        ("INTERNAL: Failed to transfer buffer to device", "transient"),
        ("UNAVAILABLE: collective-permute peer preempted", "transient"),
        ("DATA_LOSS: device-to-host copy returned short read", "transient"),
        ("INTERNAL: unspecified launch failure", "transient"),
    ]
    for msg, verdict in table:
        assert classify_error(XlaRuntimeError(msg)) == verdict, msg
        assert classify_error(SubError(msg)) == verdict, msg  # via MRO
    # Explicit markers still dominate the name match.
    assert classify_error(
        PermanentError("wrapped")
    ) == "permanent"


def test_circuit_breaker_half_open_table():
    """Breaker state table with an injected clock: threshold opens,
    open_s elapses into half-open, half-open admits exactly one probe,
    the probe's outcome closes or re-opens."""
    from tpu_pipelines.serving.fleet import CircuitBreaker

    now = [0.0]
    transitions = []
    br = CircuitBreaker(
        threshold=2, open_s=5.0, clock=lambda: now[0],
        on_transition=lambda frm, to: transitions.append((frm, to)),
    )
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "closed" and br.allow()  # below threshold
    br.record_failure()
    assert br.state == "open" and not br.allow()
    now[0] = 4.9
    assert not br.allow()  # open_s not elapsed
    now[0] = 5.0
    assert br.allow()       # half-open: the single probe
    assert not br.allow()   # concurrent second request shed
    br.record_failure()     # probe failed -> re-open for another open_s
    assert br.state == "open" and not br.allow()
    now[0] = 10.0
    assert br.allow()
    br.record_success()     # probe succeeded -> closed, admission re-armed
    assert br.state == "closed" and br.allow() and br.allow()
    assert transitions == [
        ("closed", "open"), ("open", "half_open"), ("half_open", "open"),
        ("open", "half_open"), ("half_open", "closed"),
    ]
    # A success resets the consecutive-failure count.
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == "closed"


class _FleetLoaded:
    """Stub LoadedModel: y = 2x, with a poison marker that raises a
    PERMANENT-classifying error (failover on it would re-fail)."""

    def __init__(self):
        self.params = {}
        self.generate = None
        self.transform = None

    def predict(self, batch):
        import numpy as np

        if "boom" in batch:
            raise ValueError("poison row")
        return np.asarray(batch["x"], np.float64) * 2

    predict_transformed = predict


def _stub_fleet(monkeypatch, tmp_path, registry=None, **kw):
    import tpu_pipelines.serving.fleet.versions as versions_mod
    from tpu_pipelines.serving.fleet import ServingFleet

    monkeypatch.setattr(
        versions_mod, "_default_loader", lambda d: _FleetLoaded()
    )
    vdir = tmp_path / "fleetm" / "1"
    vdir.mkdir(parents=True)
    fleet = ServingFleet(
        "fleetm", str(tmp_path / "fleetm"), replicas=2, max_versions=1,
        registry=registry, **kw
    )
    fleet.load_version(str(vdir))
    return fleet


def test_supervisor_state_machine_eject_and_rebuild(monkeypatch, tmp_path):
    """KILL_REPLICA latches a replica dead: consecutive probe failures
    walk healthy -> degraded -> ejected (gauge follows), the next pass
    rebuilds in place, and the rebuilt incarnation is healthy again —
    all driven synchronously through probe_once()."""
    import numpy as np

    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.testing.faults import (
        KILL_REPLICA,
        REPLICA_KEY,
    )

    reg = MetricsRegistry()
    fleet = _stub_fleet(
        monkeypatch, tmp_path, registry=reg, supervisor_interval_s=0.05
    )
    fleet.supervisor.stop()  # drive the passes by hand
    try:
        plan = FaultPlan({
            REPLICA_KEY: NodeFault(KILL_REPLICA, replica="0")
        })
        with plan.activate():
            r1 = fleet.supervisor.probe_once()
            assert r1["0"][0] == "degraded" and r1["1"][0] == "healthy"
            assert reg.get("serving_replica_state").labels("0").get() == 1
            r2 = fleet.supervisor.probe_once()
            assert r2["0"][0] == "ejected"
            assert reg.get("serving_replica_state").labels("0").get() == 2
            assert not fleet.supervisor.allow(fleet.pool.replicas[0])
            # Routing survives the ejection: every submit lands on 1.
            for _ in range(8):
                out = fleet.submit({"x": np.ones((1,))}, 1)
                assert out.tolist() == [2.0]
            # Next pass rebuilds in place and re-probes: healthy in ONE
            # pass (generation bump clears the kill latch).
            r3 = fleet.supervisor.probe_once()
            assert r3["0"][0] == "healthy"
            assert reg.get("serving_replica_state").labels("0").get() == 0
            assert fleet.pool.replicas[0].generation == 1
        assert ("__replica__", "kill_replica:0") in plan.log
        assert fleet.health()["replica_states"] == {
            "0": "healthy", "1": "healthy"
        }
        # Breaker round trip (trip + close) is on the scrape.
        assert reg.get(
            "serving_breaker_transitions_total"
        ).labels("0").get() == 2
    finally:
        fleet.close()


def test_failover_once_on_transient_then_permanent_fails_fast(
    monkeypatch, tmp_path
):
    """A transient device error on the routed replica fails over ONCE to
    a healthy peer (counted); a permanent error returns immediately —
    retrying a poison row elsewhere would just re-fail it."""
    import numpy as np

    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.testing.faults import DEVICE_ERROR, REPLICA_KEY

    reg = MetricsRegistry()
    fleet = _stub_fleet(
        monkeypatch, tmp_path, registry=reg, supervisor_interval_s=0.05
    )
    fleet.supervisor.stop()
    try:
        # times=2: the batcher's own per-row isolation retries a failed
        # group one-by-one IN PLACE, absorbing a one-shot blip — only a
        # replica that fails the solo retry too escalates to failover.
        plan = FaultPlan({REPLICA_KEY: NodeFault(DEVICE_ERROR, times=2)})
        with plan.activate():
            out = fleet.submit({"x": np.ones((2,))}, 2)
        assert out.tolist() == [2.0, 2.0]
        assert any(
            entry[1].startswith("device_error:") for entry in plan.log
        )
        assert reg.get("serving_failovers_total").get() == 1
        # Permanent error: straight to the caller, no second replica.
        with pytest.raises(ValueError, match="poison row"):
            fleet.submit(
                {"x": np.ones((1,)), "boom": np.ones((1,))}, 1
            )
        assert reg.get("serving_failovers_total").get() == 1
    finally:
        fleet.close()


def test_all_replicas_down_fleet_unavailable(monkeypatch, tmp_path):
    """Every breaker open => FleetUnavailable from submit (counted on
    the scrape); recovery re-admits traffic."""
    import numpy as np

    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.fleet import FleetUnavailable

    reg = MetricsRegistry()
    fleet = _stub_fleet(
        monkeypatch, tmp_path, registry=reg, supervisor_interval_s=0.05,
        supervisor_breaker_open_s=60.0,
    )
    fleet.supervisor.stop()
    try:
        for breaker in fleet.supervisor.breakers.values():
            breaker.trip()
        with pytest.raises(FleetUnavailable):
            fleet.submit({"x": np.ones((1,))}, 1)
        assert reg.get("serving_fleet_unavailable_total").get() == 1
        # One probe pass heals (heartbeats succeed -> breakers close).
        fleet.supervisor.probe_once()
        out = fleet.submit({"x": np.ones((1,))}, 1)
        assert out.tolist() == [2.0]
    finally:
        fleet.close()


def test_all_replicas_down_http_503_retry_after(tmp_path):
    """The REST surface maps FleetUnavailable to 503 + Retry-After (the
    load-shed idiom: tell the client when, never drop silently), and the
    refusal is visible on /metrics."""
    server = _toy_server(
        tmp_path, replicas=2, supervisor_interval_s=3600.0
    )
    port = server.start()
    body = json.dumps({"instances": [{"x": [1.0, 0.0, 0.0]}]}).encode()
    url = f"http://127.0.0.1:{port}/v1/models/toy:predict"
    try:
        with urllib.request.urlopen(
            urllib.request.Request(url, data=body), timeout=30
        ) as r:
            assert r.status == 200
        for breaker in server._fleet.supervisor.breakers.values():
            breaker.trip()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(url, data=body), timeout=30
            )
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
        assert "unavailable" in json.loads(ei.value.read())["error"]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            scrape = r.read().decode()
        assert "serving_fleet_unavailable_total 1" in scrape
        # Re-admission: close the breakers, traffic flows again.
        for breaker in server._fleet.supervisor.breakers.values():
            breaker.record_success()
        with urllib.request.urlopen(
            urllib.request.Request(url, data=body), timeout=30
        ) as r:
            assert r.status == 200
    finally:
        server.stop()


def test_wedged_replica_hammer_bounded_p99_zero_errors(
    monkeypatch, tmp_path
):
    """Chaos leg in miniature: one replica's predict wedges mid-hammer.
    Queue-age detection ejects it, rebuild fails the stuck futures, the
    pool fails those requests over — every caller gets a correct answer,
    p99 stays bounded, and the fleet returns to full capacity."""
    import numpy as np

    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.testing.faults import REPLICA_KEY, WEDGE_PREDICT

    reg = MetricsRegistry()
    fleet = _stub_fleet(
        monkeypatch, tmp_path, registry=reg,
        supervisor_interval_s=0.05, supervisor_queue_age_s=0.2,
    )
    fleet.supervisor.stop()  # start it only after the wedge is claimed
    errors = []
    latencies = []
    lock = threading.Lock()

    def fire(n):
        for _ in range(n):
            t0 = time.monotonic()
            try:
                out = fleet.submit({"x": np.ones((1,))}, 1, timeout_s=30)
                assert out.tolist() == [2.0]
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(repr(e))
            finally:
                with lock:
                    latencies.append(time.monotonic() - t0)

    fault = NodeFault(WEDGE_PREDICT, times=1, max_hang_s=20.0)
    plan = FaultPlan({REPLICA_KEY: fault})
    try:
        with plan.activate():
            threads = [
                threading.Thread(target=fire, args=(12,))
                for _ in range(8)
            ]
            for t in threads:
                t.start()
            # Wait for a batcher worker to claim the wedge, THEN start
            # supervision (so the wedge never parks a probe thread).
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not any(
                v.startswith("wedge_predict:") for _, v in plan.log
            ):
                time.sleep(0.005)
            assert any(
                v.startswith("wedge_predict:") for _, v in plan.log
            )
            fleet.supervisor.start()
            for t in threads:
                t.join()
            # Full-capacity recovery: both replicas healthy again.
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                states = fleet.health()["replica_states"]
                if set(states.values()) == {"healthy"}:
                    break
                time.sleep(0.02)
            assert set(
                fleet.health()["replica_states"].values()
            ) == {"healthy"}
        fault.release.set()  # unpark the wedged (old-incarnation) worker
        assert errors == []
        assert len(latencies) == 96
        p99 = sorted(latencies)[int(0.99 * len(latencies)) - 1]
        assert p99 < 15.0, p99  # bounded: nobody waited out the wedge
        # The wedged replica was ejected and rebuilt at least once.
        wedged = [v for _, v in plan.log if v.startswith("wedge_predict:")]
        name = wedged[0].split(":", 1)[1]
        assert reg.get(
            "serving_breaker_transitions_total"
        ).labels(name).get() >= 2
        rebuilt = {r.name: r.generation for r in fleet.pool.replicas}
        assert rebuilt[name] >= 1
    finally:
        fault.release.set()
        fleet.close()


def test_rebuild_reserves_resident_versions_without_recompile(tmp_path):
    """An ejected replica's in-place rebuild re-creates its batcher and
    re-serves every resident version from the version manager — and the
    shared AOT dispatch table makes that free: zero compiles after warm
    across the eject/rebuild cycle."""
    import numpy as np

    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.fleet import ServingFleet
    from tpu_pipelines.testing.faults import KILL_REPLICA, REPLICA_KEY
    from tpu_pipelines.trainer.export import export_model

    mod = tmp_path / "toy_model.py"
    mod.write_text(
        "import jax.numpy as jnp\n"
        "def build_model(hp):\n"
        "    return None\n"
        "def apply_fn(model, params, batch):\n"
        "    return jnp.asarray(batch['x'], jnp.float32) @ params['w']\n"
    )
    export_model(
        serving_model_dir=str(tmp_path / "m" / "1"),
        params={"w": np.eye(3, 2).astype(np.float32)},
        module_file=str(mod),
    )
    reg = MetricsRegistry()
    fleet = ServingFleet(
        "toy", str(tmp_path / "m"), replicas=2, max_versions=1,
        registry=reg, max_batch_size=4, supervisor_interval_s=0.05,
    )
    fleet.supervisor.stop()
    try:
        fleet.set_canary_batch({"x": np.ones((1, 3), np.float32)})
        fleet.load_version(str(tmp_path / "m" / "1"))
        out = fleet.submit({"x": np.ones((2, 3), np.float32)}, 2)
        assert np.asarray(out).shape == (2, 2)
        plan = FaultPlan({
            REPLICA_KEY: NodeFault(KILL_REPLICA, replica="0")
        })
        with plan.activate():
            fleet.supervisor.probe_once()
            fleet.supervisor.probe_once()
            assert fleet.supervisor.state(fleet.pool.replicas[0]) \
                == "ejected"
            fleet.supervisor.probe_once()  # rebuild + re-admit
        assert fleet.health()["replica_states"]["0"] == "healthy"
        assert fleet.versions.resident_versions() == ["1"]
        # Rebuilt replica serves the resident version at warmed buckets.
        for _ in range(6):
            out = fleet.submit({"x": np.ones((2, 3), np.float32)}, 2)
            assert np.allclose(np.asarray(out), [[1, 1], [1, 1]])
        after_warm = reg.get("serving_aot_compiles_after_warm_total")
        assert after_warm is not None and after_warm.get() == 0
    finally:
        fleet.close()


def test_supervisor_disabled_mode_invariant(monkeypatch, tmp_path):
    """Default knobs => no supervisor thread, no router gate, no
    failover hook, and none of the supervision metric families on the
    scrape — the disabled fleet is the pre-supervision fleet."""
    import numpy as np

    from tpu_pipelines.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    fleet = _stub_fleet(monkeypatch, tmp_path, registry=reg)
    try:
        assert fleet.supervisor is None
        assert fleet.pool.supervisor is None
        assert fleet.pool.router.gate is None
        assert fleet.pool.on_failover is None
        out = fleet.submit({"x": np.ones((2,))}, 2)
        assert out.tolist() == [2.0, 2.0]
        scrape = reg.to_prometheus()
        for family in (
            "serving_replica_state",
            "serving_breaker_transitions_total",
            "serving_failovers_total",
            "serving_fleet_unavailable_total",
            "serving_decode_sessions_recovered_total",
        ):
            assert family not in scrape, family
        assert "replica_states" not in fleet.health()
    finally:
        fleet.close()
