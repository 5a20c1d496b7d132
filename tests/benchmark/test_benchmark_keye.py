"""The Keye-VL-2.0-30B-A3B configuration and cell (ISSUE 44): the
configuration file held to the catalog row and to the issue's cut, the
bytes of its ``memory`` recomputed from ``hparams``, the traffic file held
to the issue's parameters, benchmark/work_keye.py on numbers worked by hand,
the four readers on hand-built facts and on a stored trace, and a rehearsal
of the cell on the CPU from a fixture root of its own (``fixture_keye/``).

``BENCHMARK.json`` does not list the four readers: an accepted test
(``test_benchmark_latent_fetch.py``) holds ``latent_fetch_valid_share.serve``
to be the LAST of ``per_layer``, so an appended entry fails it, and the
driver's rules read an entry put in the middle of a list as a change to
what was there; that test file is the benchmark's and not a ``model_config``
PR's to edit (as PR 42's three readers stand unlisted).  A fixture manifest
may name only listed metrics (``test_benchmark_manifest.py``), so
``fixture_keye/BENCHMARK.json`` does not name them either, and the
rehearsal below asks the one reader that a CPU run can feed itself.
``ENTRIES`` is what the ``benchmark`` PR that lists them is to write, in
the root's manifest and in the fixture's.  Nothing here is a device
number."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, run as bench_run, work_keye as work
from rehearsal import read_result
from test_benchmark_program_parts import write_trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_keye")
CELL = "tiny-keye.longctx-closed"
REAL_CELL = "keye-vl-2.0-30b-a3b.longctx-sparse-closed"
XING_CELL = "xing4.0-29b-a4b.longdoc-reason-closed"
SPEC = manifest.load()
CONFIG = manifest.load_config(SPEC, "keye-vl-2.0-30b-a3b")
HP = CONFIG["hparams"]

# unit, layer, source, the end-to-end metric it moves, better
ENTRIES = {
    "keye_decode_hbm_share.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "keye_prefill_mfu.serve":
        ("%", "kernels / device", "device_trace", "serve_tokens_per_s",
         "higher"),
    "step_sparse_select_share.serve":
        ("%", "model step", "device_trace", "serve_tokens_per_s", "lower"),
    "sparse_fetch_share.serve":
        ("%", "kernels / device", "program_counter", "serve_tokens_per_s",
         "lower"),
}
MODEL = {
    "d_model": 2048, "d_ff": 6144, "n_layers": 6, "n_heads": 32,
    "head_dim": 128, "vocab_size": 151936, "weight_itemsize": 2,
    "kv_itemsize": 2,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

ATTENTION = 2 * 2048 * 4096 + 2 * 2048 * 512
INDEXER = 2048 * (1024 + 64 + 16)
EXPERT = 3 * 2048 * 768
FIXED = ATTENTION + INDEXER + 2048 * 128
HEAD = 2048 * 151936
KV_ENTRY, INDEX_ENTRY = 2 * 4 * 128 * 2, 64 * 2          # bytes


# ------------------------------------------------------- the configuration


def test_widths_are_the_sources():
    src = CONFIG["source_config"]
    sa = src["sa_config"]
    assert (HP["d_model"], HP["d_ff"], HP["n_heads"], HP["n_kv_heads"],
            HP["head_dim"], HP["mrope_section"], HP["index_heads"],
            HP["index_dim"], HP["index_topk"], HP["d_expert"],
            HP["n_experts"], HP["experts_held"], HP["experts_per_token"],
            HP["rope_theta"], HP["rms_norm_eps"], HP["vocab_size"]) == (
        src["hidden_size"], src["intermediate_size"],
        src["num_attention_heads"], src["num_key_value_heads"],
        src["head_dim"], src["rope_scaling"]["mrope_section"],
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
        src["moe_intermediate_size"], src["num_experts"],
        src["num_local_experts"], src["num_experts_per_tok"],
        src["rope_theta"], src["rms_norm_eps"], src["vocab_size"])
    assert (HP["d_model"], HP["n_heads"], HP["n_kv_heads"], HP["head_dim"],
            HP["index_heads"], HP["index_dim"], HP["index_topk"],
            HP["n_experts"], HP["d_expert"], HP["experts_per_token"],
            HP["vocab_size"]) == (
        2048, 32, 4, 128, 16, 64, 2048, 128, 768, 8, 151936)
    assert sa["indexer_num_kv_heads"] == 1
    assert src["norm_topk_prob"] is True and src["mlp_only_layers"] == []
    assert src["decoder_sparse_step"] == 1
    assert src["use_sliding_window"] is False
    assert src["tie_word_embeddings"] is False
    assert src["rope_scaling"]["rope_type"] == "default"
    assert (HP["scoring_func"], HP["n_shared_experts"], HP["expert_offset"],
            HP["routed_scaling_factor"]) == ("softmax", 0, 0, 1.0)
    assert CONFIG["weight_dtype"] == CONFIG["compute_dtype"] == "bfloat16"


def test_the_source_config_is_the_catalogs_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert CONFIG["source_config"] == row["config"]
    assert CONFIG["source"] == row["source_url"] == next(
        c for c in SPEC["configs"] if c["name"] == CONFIG["name"])["source"]


def test_the_cut_is_the_issues():
    """Every key of the source's config stands at the top of the file with
    the value the cell runs; only ``num_hidden_layers`` differs.  No width,
    no head, no expert and no vocabulary row is cut."""
    src = CONFIG["source_config"]
    differ = {k for k, v in src.items() if CONFIG[k] != v}
    assert differ == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    assert next(c for c in SPEC["configs"]
                if c["name"] == CONFIG["name"])["reduced"] == [
        "num_hidden_layers"]
    assert CONFIG["num_hidden_layers"] == HP["n_layers"] == 6
    assert src["num_hidden_layers"] == 48 == 8 * HP["n_layers"]
    assert "48" in CONFIG["changed"]["num_hidden_layers"]
    because = CONFIG["reduced_because"]
    for said in ("one chip holds each layer whole", "8 pipeline stages of 6",
                 "the embedding and the head", "The only cut is depth"):
        assert said in because, said
    for key in ("qk_norm", "rotary_pairs", "rope_type", "indexer", "chunks",
                "ties", "router", "scores", "weights", "eos", "context"):
        assert CONFIG["assumed"][key]
    assert "no effect on WHICH positions" in CONFIG["assumed"]["chunks"]
    departures = " ".join(CONFIG["departures"])
    for said in ("no tower", "window of tokens per program call",
                 "exact threshold mask", "the embedding and the head"):
        assert said in departures, said
    assert CONFIG["program"] == {
        "module": "tpu_pipelines.models.keye", "build": "build_keye_model",
        "decode_fns": "make_continuous_decode_fns"}
    assert CONFIG["reference"] == "keye" and CONFIG["driver"] == "engine"
    assert CONFIG["check"]["control_modes"] == ["fp8", "int8"]
    assert set(CONFIG["check"]["limits"]) == {
        "served_token_gap.mean", "served_token_gap.widest"}


def test_the_cell_is_the_issues():
    cell = manifest.cell(SPEC, REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye-vl-2.0-30b-a3b", "longctx-sparse-closed", 1)
    with open(manifest.traffic_path(cell["traffic"])) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["loop"], mix["callers"], mix["block"]) == (
        "requests", "closed", 32, 32)
    assert mix["prompt_len"] == {
        "dist": "lognormal_int", "median": 12288, "sigma": 0.5, "low": 4096,
        "high": 20480}
    assert mix["output_len"] == {
        "dist": "lognormal_int", "median": 768, "sigma": 0.5, "low": 128,
        "high": 2048}
    assert (mix["settle_s"], mix["drain_s"]) == (20, 60)
    assert "engine" not in mix
    engine = CONFIG["engine"]
    assert engine["max_input_len"] == mix["prompt_len"]["high"]
    assert engine["max_decode_len"] == mix["output_len"]["high"]
    assert mix["callers"] == 2 * engine["max_batch_size"] == 32
    assert engine["page_size"] == 0 and engine["prefill_chunk_pages"] == 1
    assert engine["prefill_window_len"] in (512, 1024)
    # every prompt is deeper than what a query attends over: every step of
    # every row selects
    assert mix["prompt_len"]["low"] >= 2 * HP["index_topk"]
    listed = {
        m["name"] for section in ("end_to_end", "per_layer")
        for m in manifest.metrics_for(SPEC, section, cell["name"])}
    # every per-layer metric that lists Xing's cell lists this one
    xing = {m["name"] for section in ("end_to_end", "per_layer")
            for m in manifest.metrics_for(SPEC, section, XING_CELL)}
    assert listed == xing >= {
        "serve_tokens_per_s", "setup_s", "expert_load_ratio.serve"}
    assert cell == SPEC["workloads"][-1]
    assert cell["config"] == SPEC["configs"][-1]["name"]
    for entry in (cell, SPEC["configs"][-1]):
        assert len(entry["why"]) <= 200


def test_the_program_builds_what_the_file_says():
    """The parameter count of the model the driver builds and the bytes
    the ``memory`` text states, recomputed from ``hparams``."""
    import jax

    from tpu_pipelines.models import keye

    model = keye.build_keye_model(HP)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": np.ones((1, 8), np.int32)})["params"])
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    count = lambda keep: sum(
        int(np.prod(x.shape)) for p, x in flat if keep(str(p[-1])))
    held = work.held_params(HP)
    assert held == 6 * (FIXED + 128 * EXPERT) + 2 * HEAD == 4_374_593_536
    # beside them: the norms' gains
    assert count(lambda k: "scale" not in k) == held
    assert count(lambda k: "scale" in k) == 6 * (
        2 * 2048 + 2 * 128 + 64) + 2048
    assert not any("shared" in str(p) for p, _ in flat)
    engine = CONFIG["engine"]
    fns = keye.make_continuous_decode_fns(
        model, **{k: engine[k] for k in (
            "max_input_len", "max_decode_len", "prefill_window_len")})
    assert fns.cache_positions == 22528
    cache = jax.eval_shape(lambda: fns.blank_cache(16))
    assert {x.shape for x in jax.tree_util.tree_leaves(cache)} == {
        (16, 22528, 512), (16, 22528, 64)}
    slots = sum(x.size * 2 for x in jax.tree_util.tree_leaves(cache))
    assert slots == 16 * 22528 * 13056 == 4_706_009_088
    memory, because = CONFIG["memory"], CONFIG["reduced_because"]
    for text, said, number, unit, worked in (
            (because, "= 18.87 M", 18.87, 1e6, ATTENTION),
            (because, "= 2.26 M", 2.26, 1e6, INDEXER),
            (because, "= 603.98 M", 603.98, 1e6, 128 * EXPERT),
            (because, "a layer 625.4 M", 625.4, 1e6, FIXED + 128 * EXPERT),
            (because, "= 622.3 M", 622.3, 1e6, 2 * HEAD),
            (because, "held 4375 M", 4375, 1e6, held),
            (memory, "weights 8.75 GB", 8.75, 1e9, 2 * held),
            (memory, "13056 B a position", 13056, 1,
             6 * (KV_ENTRY + INDEX_ENTRY)),
            (memory, "22528 positions 294.1 MB", 294.1, 1e6, 22528 * 13056),
            (memory, "16 slots 4.71 GB", 4.71, 1e9, slots),
            (memory, "resident 13.75 GB", 13.75, 1e9,
             2 * held + slots * 17 // 16)):
        assert said in text, said
        assert number == pytest.approx(worked / unit, rel=1e-3), said


# ------------------------------------------------------ work, from shapes


def test_work_is_worked_by_hand():
    assert ATTENTION == 18_874_368 and INDEXER == 2_260_992
    assert EXPERT == 4_718_592 and FIXED == 21_397_504
    assert work.attention_params(HP) == ATTENTION
    assert work.indexer_params(HP) == INDEXER
    assert work.expert_params(HP) == EXPERT
    assert work.fixed_params(HP) == FIXED
    assert work.kv_entry_bytes(HP, 2) == KV_ENTRY == 2048
    assert work.index_entry_bytes(HP, 2) == INDEX_ENTRY == 128
    # every expert touched: 8.12 GB (9.9 ms at 819 GB/s); the 63 % that 16
    # rows x 8 choices touch of 128: 5.4 GB
    assert work.decode_weight_bytes(HP, 2, 6 * 128) == 2 * (
        6 * FIXED + 768 * EXPERT + HEAD) == 8_126_857_216
    assert work.decode_weight_bytes(HP, 2, 6 * 81.0) == pytest.approx(
        5.465e9, rel=1e-3)
    # 16 rows 13 k deep: 0.16 GB of index keys, 0.40 GB of selected entries
    index = 16 * 13000 * 6 * INDEX_ENTRY
    selected = 16 * 2048 * 6 * KV_ENTRY
    assert (index, selected) == (159_744_000, 402_653_184)
    assert work.decode_step_bytes(HP, 2, 486.0, index, selected) == (
        2 * (6 * FIXED + 486 * EXPERT + HEAD) + index + selected)
    # every valid key and value of those rows would be 2.56 GB
    assert 16 * 13000 * 6 * KV_ENTRY == 2_555_904_000
    full = work.prefill_window_flops(HP, 1024)
    # 2 x (21.4 M + 8 x 4.72 M) a token a layer, and the window's own
    # 524,800 pairs at 2 x 16 x 64 + 4 x 32 x 128: 0.79 TFLOP
    assert full == 6 * (
        2.0 * FIXED * 1024 + 2.0 * EXPERT * 8 * 1024
        + (2048 + 16384) * 524800.0)
    assert 0.78e12 < full < 0.80e12
    assert work.prefill_window_flops(HP, 512) < full / 2


# ------------------------------------------------------------ the readers


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_is_the_issues(name):
    """The reader says of itself what its entry is to say; once a
    ``benchmark`` PR lists it, the root's entry and the fixture's say the
    same."""
    reader = manifest.load_layer_metric(name)
    unit, layer, source, moves, better = ENTRIES[name]
    assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
        unit, layer, source, moves)
    assert reader.MOVES in {m["name"] for m in SPEC["end_to_end"]}
    assert reader.LAYER in {m["layer"] for m in SPEC["per_layer"]}
    assert manifest.NAME_RE.match(name) and manifest.UNIT_RE.match(unit)
    assert reader.read({}) is None
    for root in (manifest.ROOT, FIXTURE):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            listed = {m["name"]: m for m in json.load(f)["per_layer"]}
        if name in listed:
            entry = listed[name]
            assert (entry["unit"], entry["layer"], entry["source"],
                    entry["moves"], entry["better"]) == ENTRIES[name]


def telemetry(kinds=("kv", "index"), touched=486):
    """A private registry as an engine leaves it after 10 decode steps of
    16 rows 13,000 deep (2.56 GB of keys and values valid, 0.16 GB of
    index keys; fetched: the 2,048 selected a row and every index key of a
    bucket of 22,528), 486 experts touched, and 4 prefill windows holding
    3,072 prompt tokens."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import DecodeTelemetry

    reg = MetricsRegistry()
    t = DecodeTelemetry(reg, "0")
    valid = 16 * 13000 * 6
    for _ in range(10):
        t.on_step(0.02, 0.02, 16, 16, 0, 16)
        t.on_cache({
            "cache_bytes": {
                kinds[0]: valid * KV_ENTRY, kinds[1]: valid * INDEX_ENTRY},
            "cache_span_bytes": {
                kinds[0]: 16 * 2048 * 6 * KV_ENTRY,
                kinds[1]: 16 * 22528 * 6 * INDEX_ENTRY},
            "selected_entries": 16 * 2048 * 6,
            "expert_assignments": 768, "experts_touched": touched,
            "expert_load_ratio": 3.5})
    for n in (1024, 1024, 1024, 0):
        t.on_prefill_window(n)
    return reg


@pytest.fixture
def registry():
    return telemetry()


def facts(modules, model=MODEL):
    return {
        "serve_steps": {"counter_steps": 10, "counter_tokens": 160},
        "serve_model": model, "peaks": PEAKS,
        "trace": {"modules": modules, "busy_s": 2.0},
    }


MODULES = [
    ("jit_run(1)", 0.0, 0.016), ("jit_run(1)", 0.1, 0.024),
    ("jit_prefill_window(2)", 0.2, 0.06),
    ("jit_prefill_window(2)", 0.4, 0.10),
    ("jit_insert(3)", 0.6, 0.01),
]


def test_decode_share_is_bytes_over_bandwidth_over_the_steps_time(registry):
    reader = manifest.load_layer_metric("keye_decode_hbm_share.serve")
    must = 2 * (6 * FIXED + 486 * EXPERT + HEAD) \
        + 16 * 13000 * 6 * INDEX_ENTRY + 16 * 2048 * 6 * KV_ENTRY
    want = 100.0 * (must / 819e9) / 0.02
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 36.0 < want < 38.0
    # a step that touches every expert must read more, never less; and the
    # valid keys and values that were NOT selected are not in it
    assert reader.read(facts(MODULES), telemetry(touched=768)) > want
    assert must < 2 * (6 * FIXED + 486 * EXPERT + HEAD) + 1.0e9


def test_prefill_mfu_is_the_mean_windows_flops_over_its_time(registry):
    reader = manifest.load_layer_metric("keye_prefill_mfu.serve")
    want = 100.0 * work.prefill_window_flops(HP, 768.0) / 0.08 / 197e12
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 3.0 < want < 4.5


def test_fetch_share_is_what_was_fetched_over_the_valid_keys_and_values(
        registry):
    reader = manifest.load_layer_metric("sparse_fetch_share.serve")
    want = 100.0 * (2048 * KV_ENTRY + 22528 * INDEX_ENTRY) \
        / (13000 * KV_ENTRY)
    assert reader.read(facts(MODULES), registry) == pytest.approx(want)
    assert 26.0 < want < 27.0
    # no trace is asked for: a program counter
    assert reader.read({"serve_steps": {}}, registry) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_returns_nothing_where_there_is_nothing_to_read(
        name, registry, tmp_path, monkeypatch):
    """The parent commit has no such configuration, scopes or kinds of
    cache, another contract counts other kinds, another model has other
    sizes, and a CPU rehearsal's trace has no "XLA Modules" line: nothing,
    and no error."""
    from tpu_pipelines.observability.metrics import MetricsRegistry

    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))    # no trace there
    reader = manifest.load_layer_metric(name)
    if name == "step_sparse_select_share.serve":
        assert reader.read(facts(MODULES)) is None
        assert reader.read({"trace": {"busy_s": 1.0}}) is None
        return
    assert reader.read(
        {"trace": {"modules": MODULES, "busy_s": 2.0}}, registry) is None
    assert reader.read(facts(MODULES), MetricsRegistry()) is None
    assert reader.read(
        facts(MODULES), telemetry(("window", "full"))) is None
    assert reader.read(facts(MODULES), telemetry(("latent", "x"))) is None
    if name == "sparse_fetch_share.serve":
        return
    assert reader.read(facts([]), registry) is None
    xing = {**MODEL, "d_model": 3584, "d_ff": 9216, "vocab_size": 131072}
    assert reader.read(facts(MODULES, xing), registry) is None


# The stored trace: a step program of six operations.  The indexer's
# projection lies inside ``attention_proj``, its scores, the top-k and the
# gather inside ``attention_core``; the kernel over the fetched entries and
# the experts' product carry no such scope.
STEP = "jit(run)/Keye.decode_step/layer_1.step/"
TRACE = {
    "jit_run(7)": [
        ("fusion.1", STEP + "attn.step/attn.index/attention_proj/dsa.index/"
         "dot_general", [], 0.5),
        ("fusion.2", STEP + "attn.step/attn.choose/attention_core/dsa.index/"
         "reduce_sum", [1], 1.0),
        ("fusion.3", STEP + "attn.step/attn.choose/attention_core/"
         "dsa.select/top_k", [2], 3.0),
        ("fusion.4", STEP + "attn.step/attention_core/dsa.gather/gather",
         [3], 1.5),
        ("fusion.5", STEP + "attn.step/attention_core/"
         "grouped_decode_attention", [4], 1.0),
        ("fusion.6", STEP + "layer_1._rest/ffn/mlp/moe.experts/gmm", [5],
         3.0),
    ],
    "jit_prefill_window(8)": [
        ("fusion.1", "jit(prefill_window)/layer_0.window/attn.over/"
         "attention_core/dsa.select/while", [], 5.0),
    ],
}


def test_select_share_reads_the_three_scopes_off_a_stored_trace(
        tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    path = write_trace(tmp_path, TRACE)
    reader = manifest.load_layer_metric("step_sparse_select_share.serve")
    mix = manifest.load_layer_metric("step_stream_mix_share.serve")
    serve = {"trace": {"busy_s": 1.0}, "serve_steps": {}}
    # 0.5 + 1.0 + 3.0 + 1.5 of the step's 10 seconds; the window program
    # is not the step, and the kernel over the fetched entries is not the
    # selection
    assert reader.read(serve) == pytest.approx(60.0)
    assert mix.shares(path, reader.PROGRAMS, reader.SCOPES) \
        == pytest.approx((12.0, 20.0))
    assert mix.shares(path, ("jit_prefill_window",), reader.SCOPES) \
        == pytest.approx((10.0, 10.0))
    # Xing's reader finds none of its scopes in this step
    assert mix.read(serve) is None
    assert reader.read({"trace": {"busy_s": 1.0}, "train_windows": {}}) \
        is None


def test_readers_look_for_the_programs_own_names():
    from tpu_pipelines.models import keye
    from tpu_pipelines.serving import generative

    decode = manifest.load_layer_metric("keye_decode_hbm_share.serve")
    mfu = manifest.load_layer_metric("keye_prefill_mfu.serve")
    select = manifest.load_layer_metric("step_sparse_select_share.serve")
    assert decode.STEP in generative.PROGRAM_NAMES
    assert set(select.PROGRAMS) <= set(generative.PROGRAM_NAMES)
    assert mfu.WINDOW == generative.WINDOW_PROGRAM_NAME
    assert decode.CONFIG == CONFIG["name"]
    with open(generative.__file__) as f:
        text = f.read()
    for family in (decode.CACHE_READ, decode.CACHE_SPAN, decode.STEPS,
                   decode.TOUCHED, mfu.TOKENS, mfu.WINDOWS):
        assert f'"{family}"' in text
    with open(keye.__file__) as f:
        text = f.read()
    for scope in select.SCOPES:
        assert f'jax.named_scope("{scope}")' in text
    model = keye.build_keye_model({**HP, "n_layers": 1})
    fns = keye.make_continuous_decode_fns(model)
    assert set(fns.cache_kinds) == set(decode.KINDS)


# ---------------------------------------------------------- the rehearsal


@pytest.fixture
def tiny_reference(monkeypatch):
    """The plain reference reads the widths off the weights; what it
    cannot read there (how many experts a token takes, how many positions a
    query attends over, the rotary sections) it holds as the published
    values.  The fixture's model is smaller in those too, as
    tests/test_keye.py's is, and the test says so to the reference.  The
    rehearsal also gets a metrics registry of its own: the readers take
    the process's totals, and a worker that ran another fixture's engine
    before this one has other kinds of cache in them."""
    from benchmark.reference import keye as ref
    from tpu_pipelines.observability import metrics

    with open(os.path.join(
            FIXTURE, "benchmark", "configs", "tiny-keye.json")) as f:
        hp = json.load(f)["hparams"]
    assert hp["index_topk"] == 12           # smaller than its prompts
    monkeypatch.setitem(ref.SIZES, "top_k", hp["experts_per_token"])
    monkeypatch.setitem(ref.SIZES, "index_topk", hp["index_topk"])
    monkeypatch.setitem(ref.SIZES, "sections", tuple(hp["mrope_section"]))
    monkeypatch.setattr(metrics, "_DEFAULT", metrics.MetricsRegistry())


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_ends_in_the_contracts_line(
        capsys, tiny_reference, trace):
    """The unchanged ``engine`` driver, the engine with the contract of
    models/keye.py, prompts of two to twelve windows (one to eight times
    the 12 positions a query attends over) prefilled a window at a time
    into the two kinds of cache, decode steps that select and fetch, the
    served tokens compared with reference/keye.py."""
    code = bench_run.main([
        "--workload", CELL, "--seed", str(2 ** 31 + 44), "--seconds", "6",
        "--manifest-root", FIXTURE, "--rehearse", "--trace", str(trace),
        "--control"])
    out = capsys.readouterr().out
    assert code == 0
    result = read_result(out)
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "check served_token_gap.mean" in out and "(limit " in out
    assert "control[fp8] correct: False" in out
    for m in result["metrics"].values():
        assert np.isfinite(m["value"])
    if trace:
        assert {"batch_occupancy.serve", "decode_step_ms.serve",
                "device_idle_share.serve", "expert_load_ratio.serve"} <= set(
            result["metrics"])
        assert result["breakdown"]["device_ops"]
    else:
        assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # The four readers are not listed yet, so the line leaves them out.
    # What the run's own engine counted feeds the one that needs no device
    # trace: 12 selected entries and 144 index keys a row over rows 10 to
    # 140 deep, well under what a dense step would read.  The other three
    # find no "XLA Modules" line in a CPU's trace, or no trace at all.
    assert not set(ENTRIES) & set(result["metrics"])
    serve = {"serve_steps": {}, "trace": {"modules": [], "busy_s": 1.0},
             "serve_model": MODEL, "peaks": PEAKS}
    fetch = manifest.load_layer_metric("sparse_fetch_share.serve")
    assert 20.0 < fetch.read(serve) < 90.0
    for name in ("keye_decode_hbm_share.serve", "keye_prefill_mfu.serve"):
        assert manifest.load_layer_metric(name).read(serve) is None
