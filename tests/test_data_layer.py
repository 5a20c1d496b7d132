"""Data layer: ExampleGen splitting, IO roundtrip, input pipeline, mesh."""

import os
import time

import numpy as np
import pyarrow as pa
import pytest

from tpu_pipelines.data import examples_io
from tpu_pipelines.data.input_pipeline import BatchIterator, InputConfig
from tpu_pipelines.dsl.pipeline import Pipeline
from tpu_pipelines.components import CsvExampleGen, ImportExampleGen
from tpu_pipelines.orchestration import LocalDagRunner

TAXI_CSV = os.path.join(os.path.dirname(__file__), "testdata", "taxi_sample.csv")


def _run_csv_gen(tmp_path, **params):
    gen = CsvExampleGen(input_path=TAXI_CSV, **params)
    p = Pipeline(
        "gen", [gen], pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    result = LocalDagRunner().run(p)
    return result.outputs_of("CsvExampleGen", "examples")[0]


def test_csv_example_gen_splits(tmp_path):
    art = _run_csv_gen(tmp_path)
    assert examples_io.split_names(art.uri) == ["eval", "train"]
    train = examples_io.read_split_table(art.uri, "train")
    eval_ = examples_io.read_split_table(art.uri, "eval")
    assert train.num_rows + eval_.num_rows == 120
    # 2:1 hash split: not exact, but roughly proportioned.
    assert 60 <= train.num_rows <= 100
    assert art.properties["split_counts"]["train"] == train.num_rows

    # Deterministic: rerunning into a new root yields identical splits.
    art2 = _run_csv_gen(tmp_path / "again")
    train2 = examples_io.read_split_table(art2.uri, "train")
    assert train.equals(train2)


def test_read_split_numpy_roundtrip(tmp_path):
    art = _run_csv_gen(tmp_path)
    cols = examples_io.read_split(art.uri, "train")
    assert set(cols) == {
        "trip_miles", "fare", "trip_start_hour", "payment_type", "company", "tips"
    }
    assert cols["fare"].dtype == np.float64
    assert cols["trip_start_hour"].dtype == np.int64
    assert cols["payment_type"].dtype == object
    with pytest.raises(FileNotFoundError, match="no split"):
        examples_io.read_split(art.uri, "test")


def test_import_example_gen_npz(tmp_path):
    npz = tmp_path / "mnist_like.npz"
    np.savez(
        npz,
        image=np.arange(40 * 4 * 4, dtype=np.float32).reshape(40, 4, 4),
        label=np.arange(40) % 10,
    )
    gen = ImportExampleGen(input_path=str(npz))
    p = Pipeline(
        "imp", [gen], pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    result = LocalDagRunner().run(p)
    art = result.outputs_of("ImportExampleGen", "examples")[0]
    cols = examples_io.read_split(art.uri, "train")
    # 4x4 images flattened to 16-wide list column.
    assert np.asarray(list(cols["image"])).shape[1] == 16


def test_import_example_gen_parquet_dir(tmp_path):
    d = tmp_path / "pre_split"
    d.mkdir()
    import pyarrow.parquet as pq

    pq.write_table(pa.table({"x": [1, 2, 3]}), d / "train.parquet")
    pq.write_table(pa.table({"x": [4]}), d / "test.parquet")
    gen = ImportExampleGen(input_path=str(d))
    p = Pipeline(
        "imp2", [gen], pipeline_root=str(tmp_path / "root"),
        metadata_path=str(tmp_path / "md.sqlite"),
    )
    result = LocalDagRunner().run(p)
    art = result.outputs_of("ImportExampleGen", "examples")[0]
    assert examples_io.split_names(art.uri) == ["test", "train"]


def test_batch_iterator_static_shapes_and_seed(tmp_path):
    art = _run_csv_gen(tmp_path)
    cfg = InputConfig(batch_size=16, shuffle=True, seed=7, num_epochs=1)
    it = BatchIterator(art.uri, "train", cfg)
    batches = list(it)
    assert len(batches) == it.steps_per_epoch()
    for b in batches:
        assert b["fare"].shape == (16,)
    # Same seed -> same order; different seed -> different.
    b2 = list(BatchIterator(art.uri, "train", cfg))
    assert np.array_equal(batches[0]["fare"], b2[0]["fare"])
    cfg3 = InputConfig(batch_size=16, shuffle=True, seed=8, num_epochs=1)
    b3 = list(BatchIterator(art.uri, "train", cfg3))
    assert not np.array_equal(batches[0]["fare"], b3[0]["fare"])


def test_batch_iterator_host_sharding(tmp_path):
    art = _run_csv_gen(tmp_path)
    full = BatchIterator(
        art.uri, "train", InputConfig(batch_size=4, shuffle=False, num_epochs=1)
    )
    s0 = BatchIterator(
        art.uri, "train",
        InputConfig(batch_size=4, shuffle=False, num_epochs=1,
                    shard_index=0, num_shards=2),
    )
    s1 = BatchIterator(
        art.uri, "train",
        InputConfig(batch_size=4, shuffle=False, num_epochs=1,
                    shard_index=1, num_shards=2),
    )
    assert s0.num_examples + s1.num_examples == full.num_examples

    def rows(it):
        # A row's identity is (fare, trip_miles, tips): unique in the
        # sample, where fare alone repeats (five values occur twice).
        return {
            key
            for b in it
            for key in zip(b["fare"].tolist(), b["trip_miles"].tolist(),
                           b["tips"].tolist())
        }

    rows0, rows1 = rows(s0), rows(s1)
    assert rows0 and rows1
    assert not rows0 & rows1  # the shards are disjoint


def test_batch_iterator_prefetch_matches_lazy_stream(tmp_path):
    """prefetch=N (background decode thread + device-put lookahead) yields
    the byte-identical batch stream as the strictly lazy prefetch=0 path."""
    art = _run_csv_gen(tmp_path)
    base = dict(batch_size=16, shuffle=True, seed=7, num_epochs=2)
    lazy = list(BatchIterator(art.uri, "train",
                              InputConfig(**base, prefetch=0)))
    pre = list(BatchIterator(art.uri, "train",
                             InputConfig(**base, prefetch=2)))
    assert len(pre) == len(lazy) > 0
    for a, b in zip(lazy, pre):
        assert set(a) == set(b)
        for k in a:
            assert np.array_equal(a[k], b[k])
    # Transform exceptions surface at the consumer, not in a dead thread.
    def boom(batch):
        raise ValueError("bad transform")

    it = BatchIterator(art.uri, "train", InputConfig(**base, prefetch=2),
                       transform=boom)
    with pytest.raises(ValueError, match="bad transform"):
        next(iter(it))


def test_batch_iterator_prefetch_abandoned_consumer_stops_thread(tmp_path):
    """Breaking out of an infinite (num_epochs=None) prefetched iterator
    must stop the producer thread — no leaked threads across many loops."""
    import threading

    art = _run_csv_gen(tmp_path)
    before = threading.active_count()
    for _ in range(5):
        it = iter(BatchIterator(
            art.uri, "train",
            InputConfig(batch_size=8, num_epochs=None, prefetch=2),
        ))
        next(it)
        it.close()  # consumer abandons mid-stream
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before + 1


def test_mesh_and_shard_batch():
    import jax
    from tpu_pipelines.parallel import MeshConfig, make_mesh, shard_batch

    assert len(jax.devices()) == 8  # conftest forces 8 CPU devices
    mesh = make_mesh(MeshConfig(data=-1))
    assert mesh.shape == {"data": 8, "model": 1, "seq": 1,
                          "expert": 1, "pipe": 1}

    batch = {"x": np.ones((16, 3), np.float32), "y": np.zeros((16,), np.int32)}
    on_dev = shard_batch(batch, mesh)
    shards = on_dev["x"].addressable_shards
    assert len(shards) == 8
    assert shards[0].data.shape == (2, 3)  # 16/8 per device

    mesh2 = make_mesh(MeshConfig(data=-1, model=2))
    assert mesh2.shape == {"data": 4, "model": 2, "seq": 1,
                           "expert": 1, "pipe": 1}
    with pytest.raises(ValueError, match="not divisible"):
        make_mesh(MeshConfig(data=-1, model=3))


def _write_big_split(tmp_path, n=5000, row_group=512):
    uri = str(tmp_path / "examples")
    table = pa.table({
        "x": np.arange(n, dtype=np.int64),
        "y": np.arange(n, dtype=np.float32) * 0.5,
    })
    examples_io.write_split(uri, "train", table, row_group_size=row_group)
    return uri


def test_streaming_iterator_covers_every_row_once(tmp_path):
    """Split larger than the reader budget streams row groups; one epoch
    must yield each row exactly once (minus the drop_remainder tail)."""
    n = 5000
    uri = _write_big_split(tmp_path, n=n)
    cfg = InputConfig(
        batch_size=64, shuffle=True, seed=3, num_epochs=1,
        max_in_memory_rows=1000,        # force streaming: 5000 > 1000
        shuffle_buffer_rows=700, drop_remainder=False,
    )
    it = BatchIterator(uri, "train", cfg)
    assert it.streaming
    assert it.num_examples == n
    seen = np.concatenate([b["x"] for b in it])
    assert len(seen) == n
    assert sorted(seen.tolist()) == list(range(n))
    # Shuffled: not in file order.
    assert seen.tolist() != list(range(n))


def test_streaming_iterator_drop_remainder_and_shapes(tmp_path):
    n = 5000
    uri = _write_big_split(tmp_path, n=n)
    cfg = InputConfig(
        batch_size=128, shuffle=True, seed=0, num_epochs=1,
        max_in_memory_rows=1000, shuffle_buffer_rows=512,
    )
    batches = list(BatchIterator(uri, "train", cfg))
    assert all(len(b["x"]) == 128 for b in batches)
    total = sum(len(b["x"]) for b in batches)
    assert total == (n // 128) * 128


def test_streaming_iterator_sharding_partitions_rows(tmp_path):
    n = 3000
    uri = _write_big_split(tmp_path, n=n)
    shards = []
    for idx in range(2):
        cfg = InputConfig(
            batch_size=32, shuffle=False, num_epochs=1,
            max_in_memory_rows=1000, shuffle_buffer_rows=256,
            drop_remainder=False, shard_index=idx, num_shards=2,
        )
        it = BatchIterator(uri, "train", cfg)
        assert it.num_examples == 1500
        shards.append(np.concatenate([b["x"] for b in it]))
    merged = np.concatenate(shards)
    assert sorted(merged.tolist()) == list(range(n))
    assert set(shards[0] % 2) == {0} and set(shards[1] % 2) == {1}


def test_in_memory_mode_unchanged_for_small_splits(tmp_path):
    uri = _write_big_split(tmp_path, n=500)
    cfg = InputConfig(batch_size=50, shuffle=True, seed=1, num_epochs=2)
    it = BatchIterator(uri, "train", cfg)
    assert not it.streaming
    batches = list(it)
    assert len(batches) == 20  # 2 epochs x 10


def _write_examples(tmp_path, n=200):
    """An Examples artifact with a train split of n rows, small row groups."""
    from tpu_pipelines.data import examples_io

    uri = str(tmp_path / "examples")
    cols = {
        "x": np.arange(n, dtype=np.float32),
        "name": np.asarray([f"row{i}" for i in range(n)], dtype=object),
    }
    examples_io.write_split(
        uri, "train", examples_io.table_from_columns(cols), row_group_size=32
    )
    return uri, cols


def test_grain_backend_matches_rows(tmp_path):
    """Grain-backed BatchIterator yields every shard row exactly once/epoch."""
    from tpu_pipelines.data.input_pipeline import BatchIterator, InputConfig

    uri, cols = _write_examples(tmp_path)
    it = BatchIterator(uri, "train", InputConfig(
        batch_size=16, shuffle=True, seed=3, num_epochs=1,
        drop_remainder=False, use_grain=True,
    ))
    seen = []
    for batch in it:
        assert set(batch) == {"x", "name"}
        seen.extend(np.asarray(batch["x"]).tolist())
    assert sorted(seen) == list(range(200))


def test_grain_backend_sharded_and_multiprocess(tmp_path):
    """Two shards partition the data; worker subprocesses do the reads."""
    from tpu_pipelines.data.input_pipeline import BatchIterator, InputConfig

    uri, _ = _write_examples(tmp_path)
    seen = {}
    for shard in (0, 1):
        it = BatchIterator(uri, "train", InputConfig(
            batch_size=10, shuffle=False, num_epochs=1, drop_remainder=False,
            shard_index=shard, num_shards=2,
            use_grain=True, grain_workers=2,   # real reader subprocesses
        ))
        seen[shard] = sorted(
            v for b in it for v in np.asarray(b["x"]).tolist()
        )
    assert len(seen[0]) + len(seen[1]) == 200
    assert not (set(seen[0]) & set(seen[1]))


def test_grain_source_random_access(tmp_path):
    from tpu_pipelines.data.grain_source import ParquetRowSource

    uri, cols = _write_examples(tmp_path, n=100)
    src = ParquetRowSource(uri, "train")
    assert len(src) == 100
    assert src[0]["x"] == 0.0 and src[99]["name"] == "row99"
    assert src[37]["x"] == 37.0  # crosses a row-group boundary (32-row groups)
    import pickle

    clone = pickle.loads(pickle.dumps(src))  # what grain ships to workers
    assert clone[64]["x"] == 64.0


def test_grain_source_thread_safety(tmp_path):
    """Concurrent __getitem__ from many threads (grain's per-worker prefetch
    pool) must be safe: shared pyarrow handles segfault natively, so each
    thread gets its own handle/cache."""
    from concurrent.futures import ThreadPoolExecutor

    from tpu_pipelines.data.grain_source import ParquetRowSource

    uri, _ = _write_examples(tmp_path, n=512)
    src = ParquetRowSource(uri, "train")
    idxs = np.random.default_rng(0).permutation(512).tolist() * 4

    def read(i):
        return i, float(src[i]["x"])

    with ThreadPoolExecutor(max_workers=8) as pool:
        for i, x in pool.map(read, idxs):
            assert x == float(i)


def test_grain_backend_epoch_aligned_multi_epoch(tmp_path):
    """num_epochs=2 yields epoch-aligned batches: 2 x floor(n/bs) with
    drop_remainder, each epoch a full pass, reshuffled per epoch."""
    from tpu_pipelines.data.input_pipeline import BatchIterator, InputConfig

    uri, _ = _write_examples(tmp_path, n=200)
    it = BatchIterator(uri, "train", InputConfig(
        batch_size=16, shuffle=True, seed=5, num_epochs=2,
        drop_remainder=True, use_grain=True,
    ))
    batches = [np.asarray(b["x"]).tolist() for b in it]
    assert len(batches) == 2 * (200 // 16) == 2 * it.steps_per_epoch()
    ep1 = [v for b in batches[:12] for v in b]
    ep2 = [v for b in batches[12:] for v in b]
    # Each epoch is its own pass (no cross-epoch duplicates within a pass)...
    assert len(set(ep1)) == len(ep1) and len(set(ep2)) == len(ep2)
    # ...and the two epochs are differently shuffled.
    assert ep1 != ep2


def test_csv_example_gen_streaming_matches_whole_table(tmp_path):
    """Streamed ingest (threshold 0) assigns every row to the same split as
    whole-table ingest, with identical Parquet layout semantics."""
    from tpu_pipelines.components import CsvExampleGen
    from tpu_pipelines.dsl.pipeline import Pipeline
    from tpu_pipelines.orchestration import LocalDagRunner
    from tpu_pipelines.data import examples_io

    csv = tmp_path / "data.csv"
    csv.write_text(
        "a,b\n" + "\n".join(f"{i},{i % 7}" for i in range(500)) + "\n"
    )
    outs = {}
    for mode, threshold in (("whole", 1 << 40), ("stream", 0)):
        gen = CsvExampleGen(
            input_path=str(csv), streaming_threshold_bytes=threshold
        )
        p = Pipeline(
            f"gen-{mode}", [gen],
            pipeline_root=str(tmp_path / mode),
            metadata_path=str(tmp_path / f"{mode}.sqlite"),
        )
        r = LocalDagRunner().run(p)
        uri = r.outputs_of("CsvExampleGen", "examples")[0].uri
        outs[mode] = {
            s: examples_io.read_split(uri, s) for s in ("train", "eval")
        }
    for s in ("train", "eval"):
        w, st = outs["whole"][s], outs["stream"][s]
        assert sorted(w["a"].tolist()) == sorted(st["a"].tolist())
        assert len(w["a"]) > 0


def test_csv_streaming_type_flip_friendly_error(tmp_path):
    """A type flip beyond the first streamed block raises actionable
    guidance (name the column_types escape hatch), not a raw Arrow error;
    pinning the type makes the same file ingest cleanly."""
    import pytest

    from tpu_pipelines.components import CsvExampleGen
    from tpu_pipelines.dsl.pipeline import Pipeline
    from tpu_pipelines.orchestration import LocalDagRunner, PipelineRunError

    # ~2 MB file: first ~1 MB block is all ints, the tail is not.
    csv = tmp_path / "flip.csv"
    with open(csv, "w") as f:
        f.write("x,y\n")
        for i in range(90_000):
            f.write(f"{i},{i}\n")
        for i in range(90_000):
            f.write(f"not_an_int_{i},{i}\n")

    def pipeline(name, **params):
        gen = CsvExampleGen(
            input_path=str(csv), streaming_threshold_bytes=1, **params
        )
        return Pipeline(
            name, [gen], pipeline_root=str(tmp_path / name),
            metadata_path=str(tmp_path / f"{name}.sqlite"),
        )

    with pytest.raises(PipelineRunError, match="column_types"):
        LocalDagRunner().run(pipeline("flip-fails"))

    result = LocalDagRunner().run(
        pipeline("flip-pinned", column_types={"x": "string"})
    )
    assert result.succeeded


def test_span_pattern_resolution(tmp_path):
    from tpu_pipelines.utils.span import resolve_span_pattern

    for d in ("span-1", "span-2", "span-10", "span-003"):
        (tmp_path / d).mkdir()
    pattern = str(tmp_path / "span-{SPAN}")

    path, span, version = resolve_span_pattern(pattern)
    assert span == 10 and path.endswith("span-10") and version is None
    path, span, _ = resolve_span_pattern(pattern, span=2)
    assert span == 2 and path.endswith("span-2")
    # Zero-padded layout, pinned by numeric value.
    path, span, _ = resolve_span_pattern(pattern, span=3)
    assert span == 3 and path.endswith("span-003")

    import pytest

    with pytest.raises(FileNotFoundError):
        resolve_span_pattern(str(tmp_path / "nope-{SPAN}"))
    with pytest.raises(FileNotFoundError):
        resolve_span_pattern(pattern, span=99)

    # {VERSION} nests inside the chosen span.
    (tmp_path / "span-10" / "v-1").mkdir()
    (tmp_path / "span-10" / "v-2").mkdir()
    path, span, version = resolve_span_pattern(
        str(tmp_path / "span-{SPAN}" / "v-{VERSION}")
    )
    assert (span, version) == (10, 2) and path.endswith("v-2")


def test_csv_example_gen_spans_and_cache_rollover(tmp_path):
    """New span at an unchanged pattern -> re-run on the new data; unchanged
    spans -> cache hit (the TFX span-driven continuous-ingest shape)."""
    from tpu_pipelines.components import CsvExampleGen
    from tpu_pipelines.dsl.pipeline import Pipeline
    from tpu_pipelines.orchestration import LocalDagRunner

    def write_span(n, rows):
        d = tmp_path / f"span-{n}"
        d.mkdir()
        with open(d / "data.csv", "w") as f:
            f.write("x,y\n")
            for i in range(rows):
                f.write(f"{i},{i * 2}\n")

    write_span(1, 40)
    write_span(2, 60)

    def pipeline():
        gen = CsvExampleGen(input_path=str(tmp_path / "span-{SPAN}"))
        return Pipeline(
            "spans", [gen], pipeline_root=str(tmp_path / "root"),
            metadata_path=str(tmp_path / "md.sqlite"),
        )

    r1 = LocalDagRunner().run(pipeline())
    assert r1.succeeded and r1.nodes["CsvExampleGen"].status == "COMPLETE"
    art = r1.outputs_of("CsvExampleGen", "examples")[0]
    assert art.properties["span"] == 2
    assert sum(art.properties["split_counts"].values()) == 60

    # Same pattern, nothing new: cache hit.
    r2 = LocalDagRunner().run(pipeline())
    assert r2.nodes["CsvExampleGen"].status == "CACHED"

    # Span 3 lands: the pattern now resolves to new content -> re-run.
    write_span(3, 80)
    r3 = LocalDagRunner().run(pipeline())
    assert r3.nodes["CsvExampleGen"].status == "COMPLETE"
    art3 = r3.outputs_of("CsvExampleGen", "examples")[0]
    assert art3.properties["span"] == 3
    assert sum(art3.properties["split_counts"].values()) == 80
