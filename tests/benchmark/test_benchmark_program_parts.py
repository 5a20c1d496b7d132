"""``benchmark/program_parts.py``: the arithmetic on hand-built lists, the
rule on hand-built instructions, the readers on a synthetic trace.  Nothing
here is a device number."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, os.path.abspath(ROOT))

from benchmark import manifest, program_parts as pp  # noqa: E402

TRAIN_READERS = (
    "attention_core_share.train", "mlp_share.train", "norm_share.train",
    "dropout_mask_share.train", "optimizer_share.train",
    "unnamed_share.train",
)
SERVE_READERS = (
    "step_program_ms.serve", "step_attention_core_share.serve",
    "step_mlp_share.serve", "step_cache_write_share.serve",
    "step_unnamed_share.serve", "prefill_attention_core_share.serve",
)


# ------------------------------------------------------------ the path


@pytest.mark.parametrize("path, part", [
    ("jit(run)/T5.decode/decoder/layer_0/attn/attention_core/dot_general",
     "attention_core"),
    # flax's own module name is a word too: the innermost decides
    ("jit(train_window)/while/body/closed_call/embed_head/"
     "transpose(jvp(BertClassifier))/encoder/layer_0/mlp/dropout/"
     "Dropout_0/jit(_bernoulli)/lt", "dropout"),
    ("jit(train_window)/while/body/closed_call/embed_head/"
     "jvp(BertClassifier)/encoder/layer_0/mlp/mlp/wi/dot_general", "mlp"),
    # a transformation's wrapper is split at its brackets
    ("jit(run)/transpose(jvp(norm))/mul", "norm"),
    # the older, finer scopes are no words; the part encloses them
    ("jit(run)/PanguMoE.decode_step/layer_3.step/ffn/mlp/moe.experts/gmm",
     "mlp"),
    ("jit(prefill_window)/attn.window/attention_core/attn.full/"
     "grouped_attention", "attention_core"),
    # a word inside a longer segment is no word
    ("jit(run)/layer_0/attn_norm/mul", None),
    ("jit(run)/while/body/add", None),
    ("", None),
])
def test_the_part_is_the_innermost_word_of_the_path(path, part):
    assert pp.part_of_path(path) == part


def test_a_program_is_named_without_its_number():
    assert pp.program_name("jit_run(12923865437516446565)") == "jit_run"
    assert pp.program_name("jit_train_window") == "jit_train_window"


# ------------------------------------------------------ the arithmetic


def test_an_operation_belongs_to_the_program_that_holds_its_start():
    modules = [("jit_run(1)", 10.0, 2.0), ("jit_prefill(2)", 13.0, 1.0)]
    ops = [
        ("a", 10.0, 0.5),        # on the program's first instant: inside
        ("b", 11.999, 0.5),      # starts inside, ends past the end: inside
        ("c", 12.0, 0.1),        # on the program's end: outside
        ("d", 9.9, 0.3),         # starts before, ends inside: outside
        ("e", 13.5, 0.1),
    ]
    keyed = pp.in_programs(ops, modules)
    assert [k for k, _, _ in keyed] == [
        ("jit_run(1)", "a"), ("jit_run(1)", "b"), ("", "c"), ("", "d"),
        ("jit_prefill(2)", "e")]


def test_a_nested_event_is_not_counted_twice():
    modules = [("p(1)", 0.0, 11.0)]
    ops = pp.in_programs([
        ("while", 0.0, 10.0), ("fusion.1", 1.0, 2.0), ("call", 4.0, 4.0),
        ("fusion.2", 5.0, 1.0), ("fusion.1", 6.5, 1.0), ("copy", 10.0, 1.0),
    ], modules)
    row = pp.book(ops, modules, {})["p"]
    assert row["ops"] == {
        (pp.UNNAMED, "while"): pytest.approx(4.0),
        (pp.UNNAMED, "call"): pytest.approx(2.0),
        (pp.UNNAMED, "fusion.1"): pytest.approx(3.0),
        (pp.UNNAMED, "fusion.2"): pytest.approx(1.0),
        (pp.UNNAMED, "copy"): pytest.approx(1.0)}
    assert row["op_seconds"] == pytest.approx(11.0)


def _table():
    """Two programs that hold an operation of one kind each, one of them
    twice; a loop around two of the events."""
    modules = [("jit_run(1)", 0.0, 4.0), ("jit_run(1)", 10.0, 4.0),
               ("jit_prefill(2)", 20.0, 3.0)]
    ops = pp.in_programs([
        ("while.1", 0.0, 4.0), ("fusion.3", 0.5, 1.0), ("fusion.4", 2.0, 1.5),
        ("while.1", 10.0, 4.0), ("fusion.3", 10.5, 1.0),
        ("fusion.4", 12.0, 1.5),
        ("fusion.3", 20.0, 2.0), ("copy.9", 22.0, 0.5),
        ("fusion.3", 30.0, 1.0),     # in no program: not booked
    ], modules)
    parts = {
        ("jit_run(1)", "fusion.3"): ("attention_core", "own"),
        ("jit_run(1)", "fusion.4"): ("mlp", "caller"),
        ("jit_run(1)", "while.1"): ("arena", "neighbours"),
        ("jit_prefill(2)", "fusion.3"): ("mlp", "own"),
    }
    kinds = {key: "fusion f32[8]" for key in parts if "fusion" in key[1]}
    return pp.book(ops, modules, parts, kinds)


def test_two_programs_holding_one_kind_are_booked_apart():
    table = _table()
    assert set(table) == {"jit_run", "jit_prefill"}
    run, prefill = table["jit_run"], table["jit_prefill"]
    assert run["events"] == 2 and run["seconds"] == pytest.approx(8.0)
    assert run["parts"] == {
        "attention_core": pytest.approx(2.0), "mlp": pytest.approx(3.0),
        "arena": pytest.approx(3.0)}
    assert run["ops"][("attention_core", "fusion f32[8]")] \
        == pytest.approx(2.0)
    assert run["ops"][("mlp", "fusion f32[8]")] == pytest.approx(3.0)
    # the same instruction name in the other program is another operation
    assert prefill["parts"] == {
        "mlp": pytest.approx(2.0), pp.UNNAMED: pytest.approx(0.5)}
    assert prefill["ways"] == {
        "own": pytest.approx(2.0), "none": pytest.approx(0.5)}
    assert pp.mean_ms(table, "jit_run") == pytest.approx(4000.0)
    assert pp.mean_ms(table, "jit_move") is None


def test_the_parts_sum_to_the_programs_operation_seconds():
    for row in _table().values():
        assert sum(row["parts"].values()) == pytest.approx(
            row["op_seconds"], rel=1e-12)
        assert sum(row["ways"].values()) == pytest.approx(
            row["op_seconds"], rel=1e-12)
        assert sum(row["ops"].values()) == pytest.approx(
            row["op_seconds"], rel=1e-12)


def test_a_share_is_of_all_the_named_programs_together():
    table = _table()
    assert pp.share(table, ("jit_run",), ("mlp",)) == pytest.approx(37.5)
    assert pp.share(table, ("jit_run",), ("mlp", "arena")) \
        == pytest.approx(75.0)
    assert pp.share(table, ("jit_run", "jit_prefill"), ("mlp",)) \
        == pytest.approx(100 * 5.0 / 10.5)
    assert pp.share(table, ("jit_prefill", "jit_prefill_window"),
                    (pp.UNNAMED,)) == pytest.approx(20.0)
    assert pp.share(table, ("jit_run",), ("norm",)) == 0.0
    assert pp.share(table, ("jit_train_window",), ("mlp",)) is None
    assert pp.share(None, ("jit_run",), ("mlp",)) is None


# ----------------------------------------------------------- the rule


def _ins(id_, name, op_name="", operands=(), calls=()):
    return SimpleNamespace(
        id=id_, name=name, op_name=op_name, operand_ids=list(operands),
        called_computation_ids=list(calls))


def _comp(id_, *instructions):
    return SimpleNamespace(id=id_, instructions=list(instructions))


def test_an_instruction_without_a_word_inherits_by_the_rule():
    fused = _comp(
        3,
        _ins(30, "param.fused"),
        _ins(31, "dynamic-slice.1", "jit(f)/layer_0/cache_write/dynamic_slice",
             [30]),
        _ins(32, "dynamic-update-slice.1", "", [31]),
    )
    mixed = _comp(
        4,
        _ins(40, "mul.1", "jit(f)/layer_0/norm/mul"),
        _ins(41, "add.1", "jit(f)/layer_0/mlp/add", [40]),
    )
    body = _comp(
        2,
        _ins(20, "param.body"),
        _ins(21, "fusion.in_body", "jit(f)/while/body/mul", [20]),
        # its own word wins over the caller's
        _ins(22, "fusion.own", "jit(f)/attention_core/while/body/norm/mul",
             [21]),
    )
    entry = _comp(
        1,
        _ins(1, "param.0"),
        _ins(2, "fusion.q", "jit(f)/layer_0/attention_proj/dot_general", [1]),
        # fed by one part and feeding the same: that part
        _ins(3, "copy.1", "", [2]),
        _ins(4, "fusion.o", "jit(f)/layer_0/attention_proj/add", [3]),
        # the body of a loop inside the attention is attention
        _ins(5, "while.1", "jit(f)/layer_0/attention_core/while", [4], [2]),
        # a chain of copies reaches the part at its end
        _ins(12, "param.1"),
        _ins(6, "copy-start.1", "", [12]),
        _ins(7, "copy-done.1", "", [6]),
        _ins(8, "fusion.mlp", "jit(f)/layer_0/mlp/wi/dot_general", [7]),
        # neighbours that disagree: no part
        _ins(9, "copy.2", "", [5]),
        _ins(10, "fusion.norm", "jit(f)/layer_0/norm/mul", [9, 8]),
        # nothing around it has a part
        _ins(13, "param.2"),
        _ins(11, "copy.3", "", [13]),
        # a fusion the compiler made keeps its members' paths: what they
        # agree on wins over the neighbours
        _ins(14, "fusion.made", "", [2], [3]),
        _ins(15, "fusion.o2", "jit(f)/layer_0/attention_proj/mul", [14]),
        # members that disagree say nothing: the neighbours decide
        _ins(16, "fusion.mixed", "", [8], [4]),
        _ins(17, "fusion.mlp2", "jit(f)/layer_0/mlp/wo/dot_general", [16]),
    )
    got = pp.resolve([fused, mixed, body, entry])
    assert got["fusion.made"] == ("cache_write", "inside")
    assert got["dynamic-update-slice.1"] == ("cache_write", "caller")
    assert got["fusion.mixed"] == ("mlp", "neighbours")
    assert got["fusion.q"] == ("attention_proj", "own")
    assert got["copy.1"] == ("attention_proj", "neighbours")
    assert got["while.1"] == ("attention_core", "own")
    assert got["fusion.in_body"] == ("attention_core", "caller")
    assert got["param.body"] == ("attention_core", "caller")
    assert got["fusion.own"] == ("norm", "own")
    assert got["copy-start.1"] == ("mlp", "neighbours")
    assert got["copy-done.1"] == ("mlp", "neighbours")
    assert got["copy.2"] == (pp.UNNAMED, "none")
    assert got["copy.3"] == (pp.UNNAMED, "none")
    # a weight is its one reader's
    assert got["param.0"] == ("attention_proj", "neighbours")
    assert got["param.1"] == ("mlp", "neighbours")
    assert got["param.2"] == (pp.UNNAMED, "none")
    assert set(way for _, way in got.values()) <= set(pp.WAYS)


def test_neighbours_are_asked_inside_their_own_computation_only():
    inner = _comp(2, _ins(20, "add.inner", "jit(f)/mlp/add"))
    entry = _comp(
        1,
        # operand 20 lies in another computation: not a neighbour
        _ins(1, "copy.1", "", [20]),
        _ins(2, "call.1", "jit(f)/closed_call", [], [2]),
    )
    got = pp.resolve([inner, entry])
    assert got["copy.1"] == (pp.UNNAMED, "none")
    # a caller is what its members agree on
    assert got["call.1"] == ("mlp", "inside")
    assert got["add.inner"] == ("mlp", "own")


# -------------------------------------------- the readers, on a trace


def _hlo_module(name, instructions):
    """A serialized ``HloProto`` of one computation: ``instructions`` are
    ``(name, op_name, operand ids)``, numbered from 1."""
    msg = pp.messages()
    proto = msg["HloProto"]()
    proto.hlo_module.name = name
    comp = proto.hlo_module.computations.add(name="main", id=1)
    for i, (ins, op_name, operands) in enumerate(instructions, 1):
        row = comp.instructions.add(name=ins, opcode="fusion", id=i)
        row.metadata.op_name = op_name
        row.operand_ids.extend(operands)
    return proto.SerializeToString()


PROGRAMS = {
    # program event name: (instruction, op_name, operands), seconds each
    "jit_train_window(11)": [
        ("fusion.1", "jit(train_window)/while/body/embed_head/"
         "jvp(Bert)/layer_0/attn/attention_core/dot_general", [], 3.0),
        ("fusion.2", "jit(train_window)/while/body/embed_head/"
         "jvp(Bert)/layer_0/mlp/mlp/wi/dot_general", [1], 4.0),
        ("fusion.3", "jit(train_window)/while/body/embed_head/"
         "jvp(Bert)/layer_0/mlp_norm/norm/mul", [2], 0.5),
        ("fusion.4", "jit(train_window)/while/body/embed_head/"
         "jvp(Bert)/layer_0/mlp/dropout/Dropout_0/lt", [3], 0.25),
        ("fusion.5", "jit(train_window)/while/body/optimizer/mul", [4], 1.0),
        ("fusion.6", "jit(train_window)/while/body/add", [], 0.25),
        ("copy.7", "", [5], 1.0),
    ],
    "jit_run(22)": [
        ("fusion.1", "jit(run)/T5.decode/attention_core/dot_general", [],
         2.0),
        ("fusion.2", "jit(run)/T5.decode/mlp/mlp/wo/dot_general", [1], 1.0),
        ("fusion.3", "jit(run)/T5.decode/cache_write/dynamic_update_slice",
         [2], 0.5),
        ("fusion.4", "jit(run)/arena/dynamic_update_slice", [3], 0.25),
        ("fusion.5", "jit(run)/squeeze", [], 0.25),
    ],
    "jit_prefill(33)": [
        ("fusion.1", "jit(prefill)/T5.encode/attention_core/dot_general", [],
         1.5),
        ("fusion.2", "jit(prefill)/T5.encode/attention_proj/dot_general",
         [1], 0.5),
    ],
}


def write_trace(root, programs, repeats=2):
    """A synthetic ``.xplane.pb`` where ``newest_path`` looks for one: each
    program runs ``repeats`` times, its operations one after another."""
    msg = pp.messages()
    space = msg["XSpace"]()
    meta = space.planes.add(name=pp.METADATA_PLANE)
    meta.stat_metadata.add(key=1).value.name = pp.HLO_STAT
    host = space.planes.add(name="/host:CPU")
    host.lines.add(name="python")
    device = space.planes.add(name="/device:TPU:0")
    modules = device.lines.add(name="XLA Modules", timestamp_ns=1000)
    ops = device.lines.add(name="XLA Ops", timestamp_ns=1000)
    device.lines.add(name="Steps", timestamp_ns=1000)
    ids, at = {}, 0

    def event_id(name, display=""):
        if name not in ids:
            ids[name] = len(ids) + 1
            entry = device.event_metadata.add(key=ids[name])
            entry.value.id = ids[name]
            entry.value.name = name
            entry.value.display_name = display
        return ids[name]

    ps = lambda seconds: int(round(seconds * 1e12))
    for n, (program, rows) in enumerate(programs.items(), 1):
        entry = meta.event_metadata.add(key=n)
        entry.value.name = program
        stat = entry.value.stats.add(metadata_id=1)
        stat.bytes_value = _hlo_module(
            program, [(i, path, operands) for i, path, operands, _ in rows])
        for _ in range(repeats):
            total = sum(s for *_, s in rows)
            modules.events.add(metadata_id=event_id(program), offset_ps=at,
                               duration_ps=ps(total))
            for ins, _, _, seconds in rows:
                # The TPU names an operation by its whole HLO line; two
                # programs that hold the same line share its metadata.
                line = f"%{ins} = f32[8,128]{{1,0}} fusion(f32[8] %p), kind=k"
                ops.events.add(metadata_id=event_id(line), offset_ps=at,
                               duration_ps=ps(seconds))
                at += ps(seconds)
            at += ps(0.125)          # the device idles between programs
    path = os.path.join(
        str(root), ".cache", "benchmark_out", "cell", "trace", "plugins",
        "profile", "t", "host.xplane.pb")
    os.makedirs(os.path.dirname(path))
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return path


@pytest.fixture
def traced(tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    return write_trace(tmp_path, PROGRAMS)


TRAIN = {"trace": {"busy_s": 1.0}, "train_windows": {}}
SERVE = {"trace": {"busy_s": 1.0}, "serve_steps": {}}
EXPECTED = {
    "attention_core_share.train": 30.0, "mlp_share.train": 40.0,
    "norm_share.train": 5.0, "dropout_mask_share.train": 2.5,
    # the copy behind the optimizer's fusion is the optimizer's
    "optimizer_share.train": 20.0, "unnamed_share.train": 2.5,
    "step_program_ms.serve": 4000.0,
    "step_attention_core_share.serve": 50.0, "step_mlp_share.serve": 25.0,
    "step_cache_write_share.serve": 18.75, "step_unnamed_share.serve": 6.25,
    "prefill_attention_core_share.serve": 75.0,
}


@pytest.mark.parametrize("name", TRAIN_READERS + SERVE_READERS)
def test_a_reader_reads_its_part_off_the_trace(name, traced):
    reader = manifest.load_layer_metric(name)
    facts = TRAIN if name.endswith(".train") else SERVE
    assert reader.read(facts) == pytest.approx(EXPECTED[name])
    assert reader.SOURCE == "device_trace"


@pytest.mark.parametrize("name", TRAIN_READERS + SERVE_READERS)
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(
        name, traced, tmp_path, monkeypatch):
    reader = manifest.load_layer_metric(name)
    mine, other = (TRAIN, SERVE) if name.endswith(".train") else (SERVE, TRAIN)
    # the other kind of cell, and an untraced run of its own kind
    assert reader.read(other) is None
    assert reader.read({k: v for k, v in mine.items() if k != "trace"}) \
        is None
    assert reader.read({**mine, "trace": None}) is None
    # a checkout with no trace at all
    empty = tmp_path / "empty"
    empty.mkdir()
    monkeypatch.setattr(manifest, "ROOT", str(empty))
    assert reader.read(mine) is None


def test_a_trace_without_its_program_or_a_device_reads_as_nothing(
        tmp_path, monkeypatch):
    serve_only = tmp_path / "serve"
    monkeypatch.setattr(manifest, "ROOT", str(serve_only))
    write_trace(serve_only, {
        k: v for k, v in PROGRAMS.items() if k.startswith("jit_run")})
    for name in TRAIN_READERS + ("prefill_attention_core_share.serve",):
        facts = TRAIN if name.endswith(".train") else SERVE
        assert manifest.load_layer_metric(name).read(facts) is None
    # the CPU's trace has no device plane
    cpu = tmp_path / "cpu"
    monkeypatch.setattr(manifest, "ROOT", str(cpu))
    path = write_trace(cpu, {})
    space = pp.messages()["XSpace"]()
    space.planes.add(name="/host:CPU").lines.add(name="tf_XLACpu")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    assert pp.read(path) is None
    for name in TRAIN_READERS + SERVE_READERS:
        facts = TRAIN if name.endswith(".train") else SERVE
        assert manifest.load_layer_metric(name).read(facts) is None


def test_twelve_readers_parse_the_trace_once(traced, monkeypatch):
    calls = []
    real = pp.read
    monkeypatch.setattr(pp, "read", lambda path: calls.append(path)
                        or real(path))
    pp._read_once.cache_clear()
    for name in TRAIN_READERS + SERVE_READERS:
        facts = TRAIN if name.endswith(".train") else SERVE
        assert manifest.load_layer_metric(name).read(facts) is not None
    assert calls == [traced]


def test_the_report_names_programs_parts_and_ways(traced, capsys):
    assert pp.main(["--top", "2", traced]) == 0
    out = capsys.readouterr().out
    assert "jit_train_window: 2 events" in out
    assert "10000.0000 ms an event" in out
    assert "booked by: own 87.50 %, inside 0.00 %, caller 0.00 %, " \
        "neighbours 10.00 %, none 2.50 %" in out
    assert "fusion f32[8,128]" in out
    # the largest program first, its largest part first
    assert out.index("jit_train_window") < out.index("jit_run") \
        < out.index("jit_prefill")
    window = out[out.index("jit_train_window"):out.index("jit_run")]
    assert window.index(" mlp ") < window.index(" attention_core ") \
        < window.index(" optimizer ")


def test_the_report_says_so_where_there_is_no_trace(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    assert pp.main([]) == 1
    assert "no trace" in capsys.readouterr().err
