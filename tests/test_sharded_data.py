"""Sharded Examples artifacts + parallel data plane (ISSUE 3).

Covers the tentpole contracts: sharded read == legacy read (row multiset),
hash-split membership invariant under shard count, shard-merge statistics
identity (exact where promised, tolerance-bounded for reservoir order
statistics past capacity), execution-cache stability across shard counts,
legacy single-file artifacts staying readable, and file-granular multi-host
shard assignment in the input pipeline."""

import os

import numpy as np
import pyarrow as pa
import pytest

from tpu_pipelines.components import CsvExampleGen, StatisticsGen
from tpu_pipelines.data import examples_io
from tpu_pipelines.data.input_pipeline import BatchIterator, InputConfig
from tpu_pipelines.data.shard_plan import ShardPlan, map_shards, thread_map
from tpu_pipelines.data.statistics import (
    SplitStatsAccumulator,
    accumulate_split_shard,
    load_statistics,
    merge_accumulators,
)
from tpu_pipelines.dsl.pipeline import Pipeline
from tpu_pipelines.orchestration import LocalDagRunner

TAXI_CSV = os.path.join(
    os.path.dirname(__file__), "testdata", "taxi_sample.csv"
)


def _table(n=1000, seed=0):
    rng = np.random.default_rng(seed)
    return examples_io.table_from_columns({
        "x": rng.normal(size=n),
        "k": rng.integers(0, 40, size=n),
        "s": np.asarray([f"v{i % 7}" for i in range(n)], dtype=object),
    })


def _row_multiset(uri, split):
    table = examples_io.read_split_table(uri, split)
    cols = [table.column(c).to_pylist() for c in sorted(table.column_names)]
    return sorted(zip(*cols)) if cols else []


# ------------------------------------------------------------- layout / io


def test_sharded_write_roundtrip(tmp_path):
    table = _table()
    examples_io.write_split(
        str(tmp_path), "train", table, num_shards=4, row_group_size=128
    )
    assert examples_io.num_split_shards(str(tmp_path), "train") == 4
    assert examples_io.num_rows(str(tmp_path), "train") == 1000
    assert examples_io.split_names(str(tmp_path)) == ["train"]
    # Contiguous shard slices concatenate back to the exact input table.
    assert examples_io.read_split_table(str(tmp_path), "train").equals(table)
    # Per-shard reads partition the split.
    per_shard = [
        sum(
            len(next(iter(c.values())))
            for c in examples_io.iter_column_chunks(
                str(tmp_path), "train", shards=[i]
            )
        )
        for i in range(4)
    ]
    assert sum(per_shard) == 1000
    assert all(n == 250 for n in per_shard)


def test_legacy_single_file_still_readable(tmp_path):
    table = _table()
    examples_io.write_split(str(tmp_path), "train", table)  # legacy layout
    assert os.path.isfile(
        os.path.join(str(tmp_path), "Split-train", "data.parquet")
    )
    assert examples_io.num_split_shards(str(tmp_path), "train") == 1
    assert examples_io.read_split_table(str(tmp_path), "train").equals(table)
    assert examples_io.split_data_path(str(tmp_path), "train").endswith(
        "data.parquet"
    )
    it = BatchIterator(
        str(tmp_path), "train",
        InputConfig(batch_size=100, shuffle=False, num_epochs=1),
    )
    assert it.num_examples == 1000


def test_split_data_path_refuses_multi_shard(tmp_path):
    examples_io.write_split(str(tmp_path), "train", _table(), num_shards=2)
    with pytest.raises(ValueError, match="sharded"):
        examples_io.split_data_path(str(tmp_path), "train")


def test_inconsistent_shard_set_detected(tmp_path):
    examples_io.write_split(str(tmp_path), "train", _table(), num_shards=3)
    os.remove(
        os.path.join(
            str(tmp_path), "Split-train",
            examples_io.shard_file_name(1, 3),
        )
    )
    with pytest.raises(ValueError, match="inconsistent shard set"):
        examples_io.split_shard_paths(str(tmp_path), "train")


def test_zstd_compression_written(tmp_path):
    import pyarrow.parquet as pq

    examples_io.write_split(str(tmp_path), "train", _table(), num_shards=2)
    path = examples_io.split_shard_paths(str(tmp_path), "train")[0]
    meta = pq.read_metadata(path)
    assert meta.row_group(0).column(0).compression.lower() == "zstd"


# -------------------------------------------------------------- shard plan


def test_shard_plan_precedence(monkeypatch):
    monkeypatch.delenv("TPP_DATA_SHARDS", raising=False)
    assert ShardPlan.resolve(3) == ShardPlan(3, "param")
    monkeypatch.setenv("TPP_DATA_SHARDS", "5")
    assert ShardPlan.resolve() == ShardPlan(5, "env")
    assert ShardPlan.resolve(2).num_shards == 2  # param beats env
    monkeypatch.delenv("TPP_DATA_SHARDS")
    plan = ShardPlan.resolve()
    assert plan.source == "host_cpus" and 1 <= plan.num_shards <= 8
    with pytest.raises(ValueError):
        ShardPlan.resolve(0)


def test_map_shards_process_pool(monkeypatch):
    # Force a real 2-worker pool even on a 1-core host: the fork/pickle
    # path must round-trip module-level fns and plain-data tasks.
    monkeypatch.setenv("TPP_DATA_POOL_WORKERS", "2")
    assert map_shards(abs, [-1, -2, -3]) == [1, 2, 3]
    monkeypatch.setenv("TPP_DATA_POOL", "thread")
    assert map_shards(abs, [-4, -5]) == [4, 5]
    monkeypatch.setenv("TPP_DATA_POOL", "none")
    assert map_shards(abs, [-6]) == [6]
    assert thread_map(lambda t: t * 2, [1, 2, 3], workers=2) == [2, 4, 6]


# ------------------------------------------------------------- stats merge


def test_stats_merge_identity_exact(tmp_path):
    """Merged per-shard stats == single-pass stats while the split fits the
    reservoir: exact for counts/min/max/zeros/missing/top-k/unique, float-
    summation-order tolerance for mean/std, exact order statistics."""
    rng = np.random.default_rng(1)
    n = 4000
    table = pa.table({
        "x": pa.array(
            [None if i % 17 == 0 else float(v) for i, v in
             enumerate(rng.normal(size=n))]
        ),
        "z": pa.array((rng.integers(0, 3, size=n) == 0).astype(np.int64)),
        "s": pa.array([f"tok{i % 29}" for i in range(n)]),
    })
    examples_io.write_split(str(tmp_path), "train", table, num_shards=5)

    single = SplitStatsAccumulator("train")
    for chunk in examples_io.iter_table_chunks(
        str(tmp_path), "train", rows=333
    ):
        single.update(chunk)
    s1 = single.finalize()

    accs = map_shards(
        accumulate_split_shard,
        [(str(tmp_path), "train", i, 333, 1 << 17) for i in range(5)],
    )
    s2 = merge_accumulators(accs).finalize()

    assert s2.num_examples == s1.num_examples == n
    assert set(s2.features) == set(s1.features)
    for name, f1 in s1.features.items():
        f2 = s2.features[name]
        assert (f2.type, f2.num_missing) == (f1.type, f1.num_missing), name
        if f1.numeric:
            assert f2.numeric.min == f1.numeric.min
            assert f2.numeric.max == f1.numeric.max
            assert f2.numeric.num_zeros == f1.numeric.num_zeros
            assert f2.numeric.mean == pytest.approx(
                f1.numeric.mean, rel=1e-12, abs=1e-12
            )
            assert f2.numeric.std_dev == pytest.approx(
                f1.numeric.std_dev, rel=1e-9, abs=1e-12
            )
            # Under reservoir capacity both reservoirs hold every value:
            # order statistics are exact, not approximate.
            assert f2.numeric.median == f1.numeric.median
            assert f2.numeric.histogram_counts == f1.numeric.histogram_counts
        if f1.string:
            assert f2.string.unique == f1.string.unique
            assert f2.string.top_values == f1.string.top_values
            assert f2.string.avg_length == pytest.approx(
                f1.string.avg_length
            )


def test_reservoir_merge_overflow_bounded(tmp_path):
    """Past reservoir capacity the merged reservoir is a uniform subsample:
    count bookkeeping stays exact and the median lands within a tolerance
    band of the true median."""
    rng = np.random.default_rng(2)
    n = 8000
    vals = rng.normal(size=n)
    table = examples_io.table_from_columns({"x": vals})
    examples_io.write_split(str(tmp_path), "train", table, num_shards=4)
    accs = [
        accumulate_split_shard((str(tmp_path), "train", i, 500, 256))
        for i in range(4)
    ]
    merged = merge_accumulators(accs)
    stats = merged.finalize().features["x"].numeric
    acc_x = merged._numeric["x"]
    assert acc_x.count == n
    assert acc_x._filled == 256  # capacity, not the union
    assert stats.min == float(np.min(vals))
    assert stats.max == float(np.max(vals))
    # 256-sample median of a standard normal: loose but real bound.
    assert abs(stats.median - float(np.median(vals))) < 0.25


def test_merge_type_mismatch_raises():
    a = SplitStatsAccumulator("s")
    b = SplitStatsAccumulator("s")
    a.update(pa.table({"c": pa.array([1.0, 2.0])}))
    b.update(pa.table({"c": pa.array(["x", "y"])}))
    with pytest.raises(ValueError, match="shards of one split"):
        a.merge(b)


# --------------------------------------------------- components end-to-end


def _run_gen(tmp_path, with_stats=False, **gen_params):
    gen = CsvExampleGen(input_path=TAXI_CSV, **gen_params)
    nodes = [gen]
    if with_stats:
        nodes.append(StatisticsGen(examples=gen.outputs["examples"]))
    p = Pipeline(
        "gen", nodes, pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    return LocalDagRunner().run(p)


def test_csv_gen_sharded_membership_identical(tmp_path):
    single = _run_gen(
        tmp_path / "single", num_shards=1
    ).outputs_of("CsvExampleGen", "examples")[0]
    sharded = _run_gen(
        tmp_path / "sharded", num_shards=3
    ).outputs_of("CsvExampleGen", "examples")[0]
    assert sharded.properties["num_shards"] == 3
    for split in ("train", "eval"):
        assert examples_io.num_split_shards(sharded.uri, split) == 3
        assert _row_multiset(single.uri, split) == _row_multiset(
            sharded.uri, split
        )
        # Same split COUNTS too (membership, not just multiset).
        assert (
            single.properties["split_counts"][split]
            == sharded.properties["split_counts"][split]
        )


def test_csv_gen_streaming_sharded_membership_identical(tmp_path):
    # streaming_threshold_bytes=0 forces the incremental reader + the
    # round-robin ingest worker fan-out.
    single = _run_gen(
        tmp_path / "single", num_shards=1
    ).outputs_of("CsvExampleGen", "examples")[0]
    streamed = _run_gen(
        tmp_path / "streamed", num_shards=2, streaming_threshold_bytes=0
    ).outputs_of("CsvExampleGen", "examples")[0]
    for split in ("train", "eval"):
        assert examples_io.num_split_shards(streamed.uri, split) == 2
        assert _row_multiset(single.uri, split) == _row_multiset(
            streamed.uri, split
        )


def test_statistics_gen_sharded_equals_single(tmp_path, monkeypatch):
    # Exercise the real process pool even on a 1-core host.
    monkeypatch.setenv("TPP_DATA_POOL_WORKERS", "2")
    r1 = _run_gen(tmp_path / "a", with_stats=True, num_shards=1)
    r4 = _run_gen(tmp_path / "b", with_stats=True, num_shards=4)
    s1 = load_statistics(r1.outputs_of("StatisticsGen", "statistics")[0].uri)
    s4 = load_statistics(r4.outputs_of("StatisticsGen", "statistics")[0].uri)
    assert set(s1) == set(s4) == {"train", "eval"}
    for split in s1:
        a, b = s1[split], s4[split]
        assert a.num_examples == b.num_examples
        for name, fa in a.features.items():
            fb = b.features[name]
            assert fa.num_missing == fb.num_missing
            if fa.numeric:
                assert fa.numeric.min == fb.numeric.min
                assert fa.numeric.max == fb.numeric.max
                assert fa.numeric.num_zeros == fb.numeric.num_zeros
                assert fa.numeric.mean == pytest.approx(
                    fb.numeric.mean, rel=1e-12
                )
                assert fa.numeric.median == fb.numeric.median
            if fa.string:
                assert fa.string.top_values == fb.string.top_values


def test_transform_sharded_rows_identical_and_layout_mirrored(tmp_path):
    """Transform over sharded Examples: shard i in is shard i out, and the
    transformed rows are those of the single-file run."""
    from tpu_pipelines.components import SchemaGen, Transform

    module = os.path.join(
        os.path.dirname(os.path.dirname(__file__)),
        "examples", "taxi", "taxi_preprocessing.py")
    out = {}
    for tag, shards in (("single", 1), ("sharded", 3)):
        gen = CsvExampleGen(input_path=TAXI_CSV, num_shards=shards)
        stats = StatisticsGen(examples=gen.outputs["examples"])
        schema = SchemaGen(statistics=stats.outputs["statistics"])
        transform = Transform(
            examples=gen.outputs["examples"],
            schema=schema.outputs["schema"], module_file=module)
        result = LocalDagRunner().run(Pipeline(
            "tf", [transform], pipeline_root=str(tmp_path / tag / "root"),
            metadata_path=str(tmp_path / tag / "md.sqlite")))
        out[tag] = result.outputs_of(
            "Transform", "transformed_examples")[0].uri
    for split in ("train", "eval"):
        assert examples_io.num_split_shards(out["sharded"], split) == 3
        assert _row_multiset(out["single"], split) == _row_multiset(
            out["sharded"], split)


def test_cache_hit_across_shard_count_env(tmp_path, monkeypatch):
    """Shard count is a performance knob, not a semantic input: a re-run
    with a different TPP_DATA_SHARDS env must still hit the execution cache
    (adopting the prior layout) rather than re-ingesting."""
    monkeypatch.delenv("TPP_DATA_SHARDS", raising=False)
    first = _run_gen(tmp_path, with_stats=True)
    assert first.succeeded
    monkeypatch.setenv("TPP_DATA_SHARDS", "4")
    second_gen = CsvExampleGen(input_path=TAXI_CSV)
    second_stats = StatisticsGen(examples=second_gen.outputs["examples"])
    p = Pipeline(
        "gen", [second_gen, second_stats],
        pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    second = LocalDagRunner().run(p)
    assert second.succeeded
    assert all(nr.status == "CACHED" for nr in second.nodes.values()), {
        n: r.status for n, r in second.nodes.items()
    }


def test_legacy_artifact_feeds_sharded_components(tmp_path):
    """A pre-sharding Examples artifact (legacy data.parquet) flows through
    the shard-aware StatisticsGen/readers with no migration."""
    table = _table(600)
    art_dir = tmp_path / "legacy_art"
    examples_io.write_split(str(art_dir), "train", table)  # legacy
    acc = SplitStatsAccumulator("train")
    for chunk in examples_io.iter_table_chunks(str(art_dir), "train"):
        acc.update(chunk)
    assert acc.finalize().num_examples == 600
    it = BatchIterator(
        str(art_dir), "train",
        InputConfig(batch_size=50, shuffle=False, num_epochs=1),
    )
    assert sum(len(b["x"]) for b in it) == 600


# ------------------------------------------------- input pipeline sharding


def test_file_granular_shard_assignment(tmp_path):
    table = _table(1000, seed=3)
    examples_io.write_split(str(tmp_path), "train", table, num_shards=4)
    seen = []
    for host in range(2):
        it = BatchIterator(
            str(tmp_path), "train",
            InputConfig(
                batch_size=64, shuffle=False, num_epochs=1,
                drop_remainder=False, shard_index=host, num_shards=2,
            ),
        )
        assert it._shard_files == [host, host + 2]
        rows = [
            tuple(b["k"][i] for i in range(len(b["k"])))
            for b in it
        ]
        got = [v for batch in rows for v in batch]
        assert len(got) == it.num_examples
        seen.append(got)
    # Disjoint and complete: the two hosts together see exactly the split.
    assert sorted(seen[0] + seen[1]) == sorted(
        table.column("k").to_pylist()
    )
    assert len(seen[0]) == len(seen[1]) == 500


def test_file_granular_streaming_path(tmp_path):
    table = _table(2000, seed=4)
    examples_io.write_split(str(tmp_path), "train", table, num_shards=3)
    cfg = InputConfig(
        batch_size=100, shuffle=False, num_epochs=1, drop_remainder=False,
        shard_index=1, num_shards=3, max_in_memory_rows=10,  # force stream
    )
    it = BatchIterator(str(tmp_path), "train", cfg)
    assert it.streaming and it._shard_files == [1]
    n = sum(len(b["x"]) for b in it)
    assert n == it.num_examples == examples_io.shard_row_counts(
        str(tmp_path), "train"
    )[1]


def test_strided_fallback_when_fewer_files_than_hosts(tmp_path):
    table = _table(300, seed=5)
    examples_io.write_split(str(tmp_path), "train", table)  # 1 legacy file
    it = BatchIterator(
        str(tmp_path), "train",
        InputConfig(
            batch_size=10, shuffle=False, num_epochs=1,
            drop_remainder=False, shard_index=0, num_shards=2,
        ),
    )
    assert it._shard_files is None
    assert it.num_examples == 150  # strided i%2 rows


def test_grain_source_spans_shards(tmp_path):
    from tpu_pipelines.data.grain_source import ParquetRowSource

    table = _table(700, seed=6)
    examples_io.write_split(
        str(tmp_path), "train", table, num_shards=3, row_group_size=64
    )
    src = ParquetRowSource(str(tmp_path), "train")
    assert len(src) == 700
    ks = table.column("k").to_pylist()
    for idx in (0, 63, 64, 233, 234, 466, 467, 699):  # file/group borders
        assert src[idx]["k"] == ks[idx]
    sub = ParquetRowSource(str(tmp_path), "train", shards=[2])
    counts = examples_io.shard_row_counts(str(tmp_path), "train")
    assert len(sub) == counts[2]
    assert sub[0]["k"] == ks[counts[0] + counts[1]]


# --------------------------------------------------------- col projection


def test_model_input_columns_projection():
    from tpu_pipelines.data.schema import Feature, FeatureType, Schema
    from tpu_pipelines.trainer.export import LoadedModel, model_input_columns
    from tpu_pipelines.transform.graph import TransformGraph

    schema = Schema(features={
        "a": Feature("a", FeatureType.FLOAT),
        "b": Feature("b", FeatureType.FLOAT),
        "unused": Feature("unused", FeatureType.BYTES),
    })
    graph = TransformGraph.build(
        lambda inputs, tft: {"a_z": tft.scale_to_z_score(inputs["a"]),
                             "ab": inputs["a"] + inputs["b"]},
        schema,
    )
    assert graph.input_feature_names() == ["a", "b"]  # not "unused"
    loaded = LoadedModel(
        params=None, model=None, spec={"hyperparameters": {}},
        transform=graph, predict=None, predict_transformed=None,
    )
    assert model_input_columns(loaded, raw=True) == ["a", "b"]
    assert model_input_columns(loaded, raw=False) == ["a_z", "ab"]
    loaded_no_tf = LoadedModel(
        params=None, model=None, spec={}, transform=None,
        predict=None, predict_transformed=None,
    )
    assert model_input_columns(loaded_no_tf, raw=True) is None
