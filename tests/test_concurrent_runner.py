"""Concurrent ready-set scheduler: overlap, determinism, cache, fail-fast.

The tentpole contracts of the concurrent LocalDagRunner:
  - independent branches actually overlap (timestamped stub executors);
  - execution registration is deterministic (ids/URIs match across runs)
    and the published lineage is complete;
  - cache hits behave identically under concurrency;
  - a failing branch fail-fasts its descendants without orphaning or
    cancelling in-flight / independent work;
  - "tpu" resource-class nodes are serialized against each other while
    "host" nodes overlap freely;
  - a 1-worker scheduler reproduces the sequential runner's metadata trace
    byte for byte (modulo wall-clock timestamps).
"""

import json
import os
import sqlite3
import time

import pytest

from tpu_pipelines.dsl.component import component
from tpu_pipelines.dsl.compiler import Compiler
from tpu_pipelines.dsl.pipeline import Pipeline
from tpu_pipelines.orchestration import LocalDagRunner, PipelineRunError

CALLS = []
SPANS = {}  # node_id -> (start, end) perf_counter


@pytest.fixture(autouse=True)
def _clear():
    CALLS.clear()
    SPANS.clear()


def _stub(name, outs, ins=None, sleep_s=0.0, resource_class="host",
          fail=False):
    """Component whose executor records its invocation span and writes a
    fixed payload per output (deterministic fingerprints)."""

    @component(inputs=ins or {}, outputs=outs, name=name,
               resource_class=resource_class)
    def C(ctx):
        t0 = time.perf_counter()
        CALLS.append(ctx.node_id)
        if sleep_s:
            time.sleep(sleep_s)
        if fail:
            SPANS[ctx.node_id] = (t0, time.perf_counter())
            raise RuntimeError(f"{ctx.node_id} exploded")
        for key in ctx.outputs:
            with open(os.path.join(ctx.output(key).uri, "data.txt"),
                      "w") as f:
                f.write(f"{ctx.node_id}:{key}")
        SPANS[ctx.node_id] = (t0, time.perf_counter())
        return {"marker": ctx.node_id}

    return C


def _overlap(a, b):
    (a0, a1), (b0, b1) = SPANS[a], SPANS[b]
    return min(a1, b1) - max(a0, b0)


def _diamond(tmp_path, sleep_s=0.3, subdir="d", **pipeline_kw):
    """Gen -> {Left, Right} -> Join: the minimal branching DAG."""
    Gen = _stub("Gen", {"examples": "Examples"})
    Left = _stub("Left", {"statistics": "ExampleStatistics"},
                 {"examples": "Examples"}, sleep_s=sleep_s)
    Right = _stub("Right", {"schema": "Schema"},
                  {"examples": "Examples"}, sleep_s=sleep_s)
    Join = _stub("Join", {"model": "Model"},
                 {"statistics": "ExampleStatistics", "schema": "Schema"})
    gen = Gen()
    left = Left(examples=gen.outputs["examples"])
    right = Right(examples=gen.outputs["examples"])
    join = Join(statistics=left.outputs["statistics"],
                schema=right.outputs["schema"])
    home = tmp_path / subdir
    pipeline_kw.setdefault("metadata_path", str(home / "md.sqlite"))
    return Pipeline(
        "diamond", [gen, left, right, join],
        pipeline_root=str(home / "root"), **pipeline_kw,
    )


# --------------------------------------------------------------- overlap


def test_parallel_branches_overlap(tmp_path):
    p = _diamond(tmp_path, sleep_s=0.4)
    t0 = time.perf_counter()
    result = LocalDagRunner(max_parallel_nodes=2).run(p)
    wall = time.perf_counter() - t0
    assert result.succeeded
    assert result.max_parallel_nodes == 2
    # The two 0.4 s branches genuinely ran at the same time...
    assert _overlap("Left", "Right") > 0.2
    # ...so the run beats the 0.8 s serialized branch cost.
    assert wall < 0.8 + SPANS["Gen"][1] - SPANS["Gen"][0] + 0.3
    # Dependencies still honored: Join started only after both published.
    assert SPANS["Join"][0] >= max(SPANS["Left"][1], SPANS["Right"][1])


def test_sequential_default_for_single_root_dag(tmp_path):
    # Default pool size = DAG root count; the diamond has one root, so the
    # default stays the sequential loop and branches do NOT overlap.
    p = _diamond(tmp_path, sleep_s=0.2)
    result = LocalDagRunner().run(p)
    assert result.max_parallel_nodes == 1
    assert _overlap("Left", "Right") <= 0


def test_tpu_resource_class_serialized_host_overlaps(tmp_path):
    """At most one "tpu" node holds the chip; "host" nodes overlap it."""
    Gen = _stub("Gen", {"examples": "Examples"})
    T1 = _stub("T1", {"model": "Model"}, {"examples": "Examples"},
               sleep_s=0.3, resource_class="tpu")
    T2 = _stub("T2", {"transform_graph": "TransformGraph"},
               {"examples": "Examples"}, sleep_s=0.3, resource_class="tpu")
    H = _stub("H", {"statistics": "ExampleStatistics"},
              {"examples": "Examples"}, sleep_s=0.45)
    gen = Gen()
    nodes = [gen, T1(examples=gen.outputs["examples"]),
             T2(examples=gen.outputs["examples"]),
             H(examples=gen.outputs["examples"])]
    p = Pipeline(
        "gated", nodes, pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    result = LocalDagRunner(max_parallel_nodes=4).run(p)
    assert result.succeeded
    assert _overlap("T1", "T2") <= 0          # chip gate: tpu ∥ tpu never
    assert (
        _overlap("H", "T1") > 0 or _overlap("H", "T2") > 0
    )                                          # host ∥ tpu freely


# ---------------------------------------------- determinism + lineage


def _node_executions(metadata_path, root=None):
    """node id -> [(execution id, state, input events, output events)];
    with ``root``, URIs relative to it, so that two homes compare."""
    from tpu_pipelines.metadata import MetadataStore
    from tpu_pipelines.metadata.types import EventType

    store = MetadataStore(metadata_path)
    out = {}

    def uri(ev):
        u = store.get_artifact(ev.artifact_id).uri
        return os.path.relpath(u, root) if root else u

    for ex in store.get_executions():
        events = store.get_events_by_execution(ex.id)
        ins = sorted(
            (ev.path, ev.index, uri(ev))
            for ev in events if ev.type == EventType.INPUT
        )
        outs = sorted(
            (ev.path, ev.index, uri(ev))
            for ev in events if ev.type == EventType.OUTPUT
        )
        out.setdefault(ex.node_id, []).append(
            (ex.id, ex.state.value, ins, outs)
        )
    store.close()
    return out


def test_execution_ids_deterministic_and_lineage_complete(tmp_path):
    """Two concurrent runs of the same DAG, and a sequential one, register
    the same execution ids (and so the same output URIs), and every
    COMPLETE execution carries its full input/output event lineage."""
    recs = []
    for sub, workers in (("a", 3), ("b", 3), ("seq", 1)):
        p = _diamond(tmp_path, sleep_s=0.15, subdir=sub)
        result = LocalDagRunner(max_parallel_nodes=workers).run(
            p, run_id="fixed")
        assert result.max_parallel_nodes == workers
        recs.append(_node_executions(p.metadata_path, p.pipeline_root))

    a = recs[0]
    assert a == recs[1] == recs[2]
    for node in ("Gen", "Left", "Right", "Join"):
        (ex_id, state, ins, outs), = a[node]
        assert state == "COMPLETE"
        assert outs, f"{node}: no OUTPUT events recorded"
    # Join's inputs reference exactly the branch outputs (lineage edges).
    (_, _, join_ins, _), = a["Join"]
    in_paths = {p for p, _, _ in join_ins}
    assert in_paths == {"statistics", "schema"}


def test_cache_hits_identical_under_concurrency(tmp_path):
    p = _diamond(tmp_path, sleep_s=0.05)
    LocalDagRunner(max_parallel_nodes=3).run(p)
    assert sorted(CALLS) == ["Gen", "Join", "Left", "Right"]
    CALLS.clear()
    result = LocalDagRunner(max_parallel_nodes=3).run(
        _diamond(tmp_path, sleep_s=0.05)
    )
    assert CALLS == []  # nothing re-executed
    assert all(n.status == "CACHED" for n in result.nodes.values())
    # Cached outputs resolve to the original artifacts/URIs.
    model = result.outputs_of("Join", "model")[0]
    assert open(os.path.join(model.uri, "data.txt")).read() == "Join:model"


# ------------------------------------------------------------- fail-fast


def test_failing_branch_fail_fasts_without_orphaning(tmp_path):
    """Boom fails immediately: its descendants never start; the slow
    sibling branch (already in flight) drains, publishes, and its own
    descendant still runs — no orphaned in-flight work, no cancelled
    independent branches (sequential-loop parity)."""
    Gen = _stub("Gen", {"examples": "Examples"})
    Boom = _stub("Boom", {"statistics": "ExampleStatistics"},
                 {"examples": "Examples"}, fail=True)
    Slow = _stub("Slow", {"schema": "Schema"}, {"examples": "Examples"},
                 sleep_s=0.4)
    DownBoom = _stub("DownBoom", {"anomalies": "ExampleAnomalies"},
                     {"statistics": "ExampleStatistics"})
    DownSlow = _stub("DownSlow", {"model": "Model"}, {"schema": "Schema"})
    gen = Gen()
    boom = Boom(examples=gen.outputs["examples"])
    slow = Slow(examples=gen.outputs["examples"])
    down_boom = DownBoom(statistics=boom.outputs["statistics"])
    down_slow = DownSlow(schema=slow.outputs["schema"])
    p = Pipeline(
        "failfast", [gen, boom, slow, down_boom, down_slow],
        pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    with pytest.raises(PipelineRunError) as ei:
        LocalDagRunner(max_parallel_nodes=3).run(p)
    result = ei.value.result
    assert result.nodes["Boom"].status == "FAILED"
    assert "exploded" in result.nodes["Boom"].error
    assert result.nodes["DownBoom"].status == "FAILED"
    assert result.nodes["DownBoom"].error == "upstream failure"
    assert "DownBoom" not in CALLS  # never started
    # In-flight sibling drained and published; its descendant ran.
    assert result.nodes["Slow"].status == "COMPLETE"
    assert result.nodes["DownSlow"].status == "COMPLETE"
    execs = _node_executions(p.metadata_path)
    (_, state, _, outs), = execs["Slow"]
    assert state == "COMPLETE" and outs  # published, not orphaned
    assert execs["Boom"][0][1] == "FAILED"  # failure recorded too


# -------------------------------------- sequential-trace equivalence


def _normalized_store_dump(metadata_path, pipeline_root):
    """Every metadata table, row order and ids included, with the only
    legitimately nondeterministic fields (timestamps, measured wall-clocks,
    absolute roots) normalized away."""
    conn = sqlite3.connect(metadata_path)

    def norm_props(raw):
        d = json.loads(raw)
        d.pop("wall_clock_s", None)
        return json.dumps(d, sort_keys=True)

    def norm_uri(uri):
        return os.path.relpath(uri, pipeline_root) if uri else uri

    dump = {
        "artifacts": [
            (r[0], r[1], norm_uri(r[2]), r[3], r[4], r[5])
            for r in conn.execute(
                "SELECT id, type_name, uri, state, properties, fingerprint "
                "FROM artifacts ORDER BY rowid"
            )
        ],
        "executions": [
            (r[0], r[1], r[2], r[3], norm_props(r[4]), r[5])
            for r in conn.execute(
                "SELECT id, type_name, node_id, state, properties, "
                "cache_key FROM executions ORDER BY rowid"
            )
        ],
        "events": list(conn.execute(
            "SELECT artifact_id, execution_id, type, path, idx "
            "FROM events ORDER BY rowid"
        )),
        "contexts": list(conn.execute(
            "SELECT id, type_name, name, properties "
            "FROM contexts ORDER BY rowid"
        )),
        "associations": list(conn.execute(
            "SELECT context_id, execution_id FROM associations ORDER BY rowid"
        )),
        "attributions": list(conn.execute(
            "SELECT context_id, artifact_id FROM attributions ORDER BY rowid"
        )),
    }
    conn.close()
    return dump


def test_one_worker_scheduler_reproduces_sequential_trace(tmp_path):
    """max_parallel_nodes=1 through the concurrent scheduler writes a
    byte-for-byte identical metadata store to the sequential topo loop —
    same row ids, same row order, same URIs, same cache keys — across a
    cold run AND a warm (all-cached) rerun."""
    dumps = []
    for sub, force in (("seq", "0"), ("sched", "1")):
        os.environ["TPP_FORCE_SCHEDULER"] = force
        try:
            p = _diamond(tmp_path, sleep_s=0.02, subdir=sub)
            runner = LocalDagRunner(max_parallel_nodes=1)
            runner.run(p, run_id="r1")
            runner.run(_diamond(tmp_path, sleep_s=0.02, subdir=sub),
                       run_id="r2")  # warm: exercises the CACHED path
            dumps.append(
                _normalized_store_dump(p.metadata_path, p.pipeline_root)
            )
        finally:
            os.environ.pop("TPP_FORCE_SCHEDULER", None)
    assert dumps[0] == dumps[1]


# ------------------------------------------- fault plans + recovery
# (Concurrent-scheduler versions of the crash-safety contracts; the
# sequential-path coverage lives in tests/test_recovery.py.)


@pytest.mark.robustness
def test_crash_after_publish_then_resume_adopts_under_concurrency(tmp_path):
    """Orchestrator death right after a node's COMPLETE publish: the resume
    adopts that execution as-is (same id) and re-runs only its consumers."""
    from tpu_pipelines.metadata import MetadataStore
    from tpu_pipelines.metadata.types import ExecutionState
    from tpu_pipelines.testing.faults import (
        CRASH_AFTER_PUBLISH,
        FaultPlan,
        NodeFault,
        SimulatedCrash,
    )

    p = _diamond(tmp_path, sleep_s=0.02)
    plan = FaultPlan({"Left": NodeFault(CRASH_AFTER_PUBLISH)})
    with plan.activate():
        with pytest.raises(SimulatedCrash):
            LocalDagRunner(max_parallel_nodes=3).run(p)
    store = MetadataStore(p.metadata_path)
    (left_id,) = [e.id for e in store.get_executions(node_id="Left")
                  if e.state == ExecutionState.COMPLETE]
    store.close()

    CALLS.clear()
    result = LocalDagRunner(max_parallel_nodes=3).run(
        _diamond(tmp_path, sleep_s=0.02), resume_from="latest"
    )
    assert result.succeeded
    assert result.nodes["Left"].adopted
    assert result.nodes["Left"].execution_id == left_id
    assert "Left" not in CALLS and "Gen" not in CALLS
    assert "Join" in CALLS  # downstream of the crash point re-runs


@pytest.mark.robustness
def test_crash_before_publish_then_resume_reruns_with_clean_uri(tmp_path):
    """Orchestrator death between executor success and publish: the resume
    fences the RUNNING orphan (ABANDONED + dir reclaimed) and the re-run
    gets a fresh execution id/URI, never the half-trusted old one."""
    from tpu_pipelines.metadata import MetadataStore
    from tpu_pipelines.metadata.types import ExecutionState
    from tpu_pipelines.testing.faults import (
        CRASH_BEFORE_PUBLISH,
        FaultPlan,
        NodeFault,
        SimulatedCrash,
    )

    p = _diamond(tmp_path, sleep_s=0.02)
    plan = FaultPlan({"Right": NodeFault(CRASH_BEFORE_PUBLISH)})
    with plan.activate():
        with pytest.raises(SimulatedCrash):
            LocalDagRunner(max_parallel_nodes=3).run(p)
    store = MetadataStore(p.metadata_path)
    (orphan_id,) = [e.id for e in store.get_executions(node_id="Right")
                    if e.state == ExecutionState.RUNNING]
    store.close()
    orphan_dir = os.path.join(
        p.pipeline_root, "Right", "schema", str(orphan_id)
    )
    assert os.path.isdir(orphan_dir)

    result = LocalDagRunner(max_parallel_nodes=3).run(
        _diamond(tmp_path, sleep_s=0.02), resume_from="latest"
    )
    assert result.succeeded
    assert not os.path.isdir(orphan_dir)  # fenced + reclaimed
    right = result.nodes["Right"]
    assert not right.adopted and right.execution_id != orphan_id
    assert right.outputs["schema"][0].uri.endswith(str(right.execution_id))
    store = MetadataStore(p.metadata_path)
    states = {e.state for e in store.get_executions(node_id="Right")}
    store.close()
    assert ExecutionState.ABANDONED in states


@pytest.mark.robustness
def test_tpu_timeout_releases_chip_mutex_for_drain(tmp_path):
    """A hung tpu-class node hits its deadline: the watchdog releases the
    chip gate, so the QUEUED tpu sibling still runs during the drain."""
    from tpu_pipelines.testing.faults import FaultPlan, HANG, NodeFault

    Gen = _stub("Gen", {"examples": "Examples"})
    THang = _stub("THang", {"model": "Model"}, {"examples": "Examples"},
                  resource_class="tpu")
    TNext = _stub("TNext", {"transform_graph": "TransformGraph"},
                  {"examples": "Examples"}, resource_class="tpu")
    gen = Gen()
    thang = THang(examples=gen.outputs["examples"]).with_execution_timeout(
        0.5
    )
    tnext = TNext(examples=gen.outputs["examples"])
    p = Pipeline(
        "tpu-timeout", [gen, thang, tnext],
        pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    plan = FaultPlan({"THang": NodeFault(HANG, max_hang_s=10)})
    with plan.activate():
        result = LocalDagRunner(max_parallel_nodes=3).run(
            p, raise_on_failure=False
        )
    assert result.nodes["THang"].status == "FAILED"
    assert "timeout" in result.nodes["THang"].error
    # The chip was released: the other tpu node ran to completion.  (The
    # hang fires inside THang's attempt, so the chip gate had admitted
    # THang first — TNext could only run because the watchdog freed it.)
    assert result.nodes["TNext"].status == "COMPLETE"
    # The watchdog's cancel event (not the safety ceiling) freed the hang.
    assert ("THang", "hang_released") in plan.log


# ----------------------------------------------------- IR / compiler


def test_ir_resource_class_and_topo_levels(tmp_path):
    from tpu_pipelines.components import (
        CsvExampleGen, SchemaGen, StatisticsGen, Trainer, Transform,
    )

    csv = tmp_path / "d.csv"
    csv.write_text("a,b\n1,2\n3,4\n")
    gen = CsvExampleGen(input_path=str(csv))
    stats = StatisticsGen(examples=gen.outputs["examples"])
    schema = SchemaGen(statistics=stats.outputs["statistics"])
    transform = Transform(
        examples=gen.outputs["examples"],
        schema=schema.outputs["schema"],
        module_file=str(csv),
    )
    trainer = Trainer(
        examples=transform.outputs["transformed_examples"],
        module_file=str(csv),
    )
    p = Pipeline(
        "rc", [gen, stats, schema, transform, trainer],
        pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    ir = Compiler().compile(p)
    classes = {n.id: n.resource_class for n in ir.nodes}
    assert classes["Trainer"] == "tpu" and classes["Transform"] == "tpu"
    assert classes["CsvExampleGen"] == "host"
    assert classes["StatisticsGen"] == "host"
    # resource_class round-trips through the IR JSON.
    as_json = json.loads(ir.to_json_str())
    assert {n["id"]: n["resource_class"] for n in as_json["nodes"]} == classes
    # Stage groups follow dependency depth; roots count feeds the default
    # pool size.
    assert ir.topo_levels() == [
        ["CsvExampleGen"], ["StatisticsGen"], ["SchemaGen"], ["Transform"],
        ["Trainer"],
    ]
    assert ir.n_roots() == 1
