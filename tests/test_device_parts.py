"""The parts of the device programs (ISSUE 40): every program a cell of
the benchmark runs names its parts with ``jax.named_scope``, in the one
vocabulary ``observability.trace.DEVICE_PARTS``.

* The scopes are metadata and nothing else: the lowered text of every
  program at fixture size hashes to what the commit before the scopes
  (79564dd) lowered to, the module's name aside (the train window's was
  ``jit__lambda``).  A PR that changes a program on purpose lowers it
  again and replaces the hash: ``python tests/test_device_parts.py``
  prints the table.  PR 41 did so for ``pangu_moe step``, PR 43 for
  ``command_a step`` and PR 46 for ``evabyte step`` (their attention
  became one kernel); the others stand as they were.
* Every instruction of the lowered HLO that a line of the program wrote
  and that computes carries a word of the vocabulary in its scope path
  or inherits one by the rule of ``benchmark/program_parts.py``; under
  2 % stay ``unnamed``.
* The train window's program is ``jit_train_window``.
* openPangu's step hands its attention kernel every layer's cache where
  it lies, and the kernel's own operations are attention (ISSUE 41).
* Command A+'s step hands its attention kernel every layer's ring or
  array, keys and values, where they lie (ISSUE 43).
* EvaByte's step hands its attention kernel every layer's ring and chunk
  table, keys and values, where they lie (ISSUE 46).
"""

import hashlib
import importlib
import os
import re
import sys

import numpy as np
import pytest

# sha256 of ``lowered.as_text()`` with the module's name taken out, at
# commit 79564dd (the parent of the scopes), jax 0.9.0, 8 CPU devices;
# ``pangu_moe step`` at PR 41, the two of ``xing`` at PR 42, ``command_a
# step`` at PR 43, the two of ``keye`` at PR 44, ``keye prefill_window``
# again at PR 45 (its attention under the selection is one kernel),
# ``evabyte step`` at PR 46 (its attention over ring and table is one).
PARENT_SHA256 = {
    "t5 prefill":
        "78e3c0297e8727c951835f623307a467a28c383a2f2fb948af2a20bbad3c584c",
    "t5 insert":
        "0f2c8cb4807b8ba8297f117bcaf0f8dd374a467b0b38d7151a825a4023975498",
    "t5 move":
        "f0354c989720b1db3839c3d0d7d6e037e4b2c2bc3d0a769867c90717292791c1",
    "t5 clear":
        "4847510cc08740e7785e65e5815b99f7d90a8667a056c7890b10de5ddb1adc74",
    "t5 step 2x4":
        "964f204aba8c9969113a9e48817099ad891275aa5a72806f953b2c155b62d48f",
    "t5 step 4x8":
        "24119531de8bdc60a36bc497fbb47eee11850561961afe2fcaf8e4ee57d6d438",
    "evabyte step":
        "22edc58a956dc8f7abb91f3510b699654a890c55d060c234254f649346efc0b0",
    "evabyte prefill_window":
        "225d0ab75b35d40ac33bdab436e2a1b231c9dffb6550a51307fcd06bdc12fc3b",
    "pangu_moe step":
        "c161da6fa0d013c7b1894d9e0d57ec29a793993fb57fbcc5a076cb0c8a0db3cf",
    "pangu_moe prefill_window":
        "b8521f090df4036c850fc824f22c2c5c6bb36eff295679066ab4dd2d151f9574",
    "command_a step":
        "146fc5412644dccbf0f3fbac36638185cb2053528f82ab9828ffe01e1f05a4cb",
    "command_a prefill_window":
        "7e9824a475451993b54c051b67f99ee0a9982652f5c5161283a01b21cde08ba5",
    "bert train window":
        "7d82ea7ece7539cce25527b6839238514229a4fca89d159572e35d93bcbf696a",
    # new in PR 42, as that PR lowered them: a guard from there on
    "xing step":
        "aad54e255503e26a996e8f1141e2b914ded8250ad5ccddd6b0890901a0b7c3a5",
    "xing prefill_window":
        "53b5e966d36b60dd07935419c0c58b2d7a5f61bc48499a82bfee47961e66b568",
    # new in PR 44, as that PR lowered them; the window again at PR 45
    "keye step":
        "551d46ca0249454b63225940e194913909e95c08316bb3edf35a92ed3cb27c53",
    "keye prefill_window":
        "eb5c0867bb8da9db752363a0a7465331374c4362c625e0bc10f36ecadb105b86",
}
PROGRAMS = (
    "t5 prefill", "t5 insert", "t5 move", "t5 clear", "t5 step 2x4",
    "t5 step 4x8", "evabyte step", "evabyte prefill_window",
    "pangu_moe step", "pangu_moe prefill_window", "command_a step",
    "command_a prefill_window", "bert train window",
    "xing step", "xing prefill_window",
    "keye step", "keye prefill_window",
)
OLDER_SCOPES = {
    "eva.attend", "eva.summarize", "mla.attend", "moe.route",
    "moe.experts", "moe.shared", "mhc.mix", "mhc.apply",
    "dsa.index", "dsa.select", "dsa.gather",
}


def _t5_programs(note):
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models.t5 import T5, make_continuous_decode_fns
    from tpu_pipelines.serving.generative import GenerativeEngine

    model = T5(
        vocab_size=48, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, dropout_rate=0.0, dtype=jnp.float32)
    batch = {
        "inputs": np.arange(12, dtype=np.int32).reshape(2, 6) % 13 + 2,
        "targets": np.ones((2, 5), np.int32),
    }
    params = model.init(jax.random.key(0), batch)["params"]
    fns = make_continuous_decode_fns(
        model, max_decode_len=8, eos_id=1, max_input_len=6)
    engine = GenerativeEngine(fns, params, max_batch_size=4, page_size=2)
    try:
        engine._ensure_arena()
        zin = np.zeros((1, 6), np.int32)
        c1, e1, _ = engine._jit_prefill(engine.params, zin, zin)
        slot, one = np.int32(0), np.int32(1)
        a = engine._arena
        note("t5 prefill", engine._jit_prefill.lower(engine.params, zin, zin))
        note("t5 insert",
             engine._jit_insert.lower(a, c1, e1, zin, one, slot))
        note("t5 move", engine._jit_move.lower(a, slot, slot))
        note("t5 clear", engine._jit_clear.lower(a, slot))
        note("t5 step 2x4", engine._step_for(2, 4).lower(engine.params, a))
        note("t5 step 4x8", engine._step_for(4, 8).lower(engine.params, a))
    finally:
        engine.close()


def _decoder_programs(note):
    from tpu_pipelines.serving.generative import GenerativeEngine

    for name in ("evabyte", "pangu_moe", "command_a", "xing", "keye"):
        tiny = importlib.import_module("test_" + name)
        model, params = tiny.build()
        engine = GenerativeEngine(
            tiny.decode_fns(model), params, max_batch_size=4)
        try:
            engine._ensure_arena()
            tokens = np.zeros((1, engine._window_len), np.int32)
            note(f"{name} step", engine._step_for(
                2, engine.kv_buckets[-1]).lower(engine.params, engine._arena))
            note(f"{name} prefill_window", engine._jit_prefill_window.lower(
                engine.params, engine._row_cache, tokens, np.int32(1),
                np.int32(0)))
        finally:
            engine.close()


def _train_window(note, monkeypatch):
    """The window program as ``train_loop`` itself builds it for a tiny
    BERT with dropout: the sharded programs are caught on their way into
    ``jax.jit`` and lowered at their first call.  -> the function's
    name."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_pipelines.models.bert import build_bert_model
    from tpu_pipelines.trainer import TrainLoopConfig, train_loop

    real_jit = jax.jit
    called = {}

    def catching(fn, **kw):
        jitted = real_jit(fn, **kw)
        if "in_shardings" not in kw:
            return jitted

        def call(*args):
            if fn.__name__ not in called:
                called[fn.__name__] = jitted.lower(*args)
            return jitted(*args)

        return call

    monkeypatch.setattr(jax, "jit", catching)
    model = build_bert_model(dict(
        vocab_size=64, d_model=16, n_layers=2, n_heads=2, d_ff=32,
        max_len=8, dropout_rate=0.1, num_classes=2, attn_impl="dense"))
    rng = np.random.default_rng(0)

    def batches(n):
        for _ in range(n):
            yield {
                "input_ids": rng.integers(0, 64, (8, 8)).astype(np.int32),
                "attention_mask": np.ones((8, 8), np.int32),
                "label": rng.integers(0, 2, (8,)).astype(np.int32),
            }

    def features(b):
        return {k: v for k, v in b.items() if k != "label"}

    def loss_fn(params, b, step_rng):
        logits = model.apply(
            {"params": params}, features(b), deterministic=False,
            rngs={"dropout": step_rng})
        labels = jnp.asarray(b["label"], jnp.int32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(), {}

    train_loop(
        loss_fn=loss_fn,
        init_params_fn=lambda r, b: model.init(r, features(b))["params"],
        optimizer=optax.adamw(1e-3), train_iter=batches(4),
        config=TrainLoopConfig(
            train_steps=4, batch_size=8, log_every=2, window_steps=2))
    monkeypatch.setattr(jax, "jit", real_jit)
    # every step of the run went through one sharded program: the window
    (name, program), = called.items()
    note("bert train window", program)
    return name


def lower_all(monkeypatch):
    """``{program: (module name, sha256 of its text, HloModuleProto, HLO
    text)}``."""
    out = {}

    def note(name, lowered):
        text = lowered.as_text()
        module = re.search(r"module @(\w+)", text).group(1)
        text = re.sub(r"module @\w+", "module @_", text, count=1)
        hlo = lowered.compiler_ir(dialect="hlo")
        out[name] = (
            module, hashlib.sha256(text.encode()).hexdigest(),
            hlo.as_serialized_hlo_module_proto(), hlo.as_hlo_text())

    _t5_programs(note)
    _decoder_programs(note)
    out["window function"] = _train_window(note, monkeypatch)
    return out


@pytest.fixture(scope="module")
def lowered():
    patch = pytest.MonkeyPatch()
    try:
        return lower_all(patch)
    finally:
        patch.undo()


@pytest.mark.parametrize("program", PROGRAMS)
def test_scopes_leave_the_lowered_program_as_it_was(program, lowered):
    assert lowered[program][1] == PARENT_SHA256[program], (
        f"{program} lowers to another text than at 79564dd: a scope moved "
        "an operation, or the program was changed on purpose (then print "
        "the table anew: python tests/test_device_parts.py)")


# What computes nothing of its own: wires, the callers of other
# computations, and a mesh's sharding annotations (the only custom calls
# of a CPU lowering).
CARRIERS = {"parameter", "constant", "tuple", "get-tuple-element", "while",
            "call", "conditional", "custom-call"}


@pytest.mark.parametrize("program", PROGRAMS)
def test_every_instruction_has_a_part_or_inherits_one(program, lowered):
    """Of the instructions that a line of the program wrote (the converter's
    own carry no ``op_name``) and that compute, under 2 % are left without
    a part: the scan's own slicing and counting in the train window."""
    from benchmark import program_parts

    raw = lowered[program][2]
    parts = program_parts.module_parts(raw)
    module = program_parts.messages()["HloModule"].FromString(raw)
    written = [
        ins.name for comp in module.computations
        for ins in comp.instructions
        if ins.metadata.op_name and ins.opcode not in CARRIERS]
    unnamed = [n for n in written if parts[n][0] == program_parts.UNNAMED]
    assert len(written) > 0.25 * len(parts)
    assert sum(parts[n][1] == "own" for n in written) > 0.5 * len(written)
    assert len(unnamed) < 0.02 * len(written), unnamed[:20]
    assert {part for part, _ in parts.values()} - {
        program_parts.UNNAMED} <= set(program_parts.PARTS)


def test_pangu_step_hands_its_kernel_the_cache_where_it_lies(lowered):
    """The fixture's arena is 4 slots x 160 positions x 24 numbers in
    each of 3 layers, and the step runs 2 rows.  Nothing of the arena's
    size is copied, padded, sliced or gathered on its way to the
    attention (the parent sliced ``cache[:2, :160]`` out): each layer's
    array is written row by row and handed over whole, as ONE view with
    its positions last, which is how the chip keeps such an array
    (tests/test_tpu_compile.py holds the compiled step to "nothing
    moved").  Every operation of the kernel, interpreted here, is booked
    to the attention itself."""
    from benchmark import program_parts

    raw, text = lowered["pangu_moe step"][2:]
    module = program_parts.messages()["HloModule"].FromString(raw)
    path = {ins.name: ins.metadata.op_name
            for comp in module.computations for ins in comp.instructions}
    parts = program_parts.module_parts(raw)
    kernel = [n for n, p in path.items() if "latent_decode_attention" in p]
    assert len(kernel) > 100
    assert {parts[n] for n in kernel} == {("attention_core", "own")}

    arena, live = ["4", "160", "24"], ["2", "160", "24"]
    made = {}         # opcode -> [(name, dims, the line)] of the arena's size
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if m is None:
            continue
        name, dims, opcode = m.groups()
        assert sorted(dims.split(",")) != sorted(live), line
        if sorted(dims.split(",")) == sorted(arena):
            made.setdefault(opcode, []).append((name, dims, line))
    assert set(made) == {
        "parameter", "dynamic-update-slice", "transpose"}, sorted(made)
    assert len(made["transpose"]) == 3                  # one a layer
    for name, dims, line in made["transpose"]:
        assert dims == "4,24,160" and "dimensions={0,2,1}" in line
        assert "mla.attend" in path[name]
        assert parts[name] == ("attention_core", "own")
    # the writes: 2 rows x 3 layers, each into the array as it came
    assert len(made["dynamic-update-slice"]) == 2 * 3
    assert {dims for _, dims, _ in made["dynamic-update-slice"]} == {
        "4,160,24"}


def test_command_a_step_hands_its_kernel_both_caches_where_they_lie(lowered):
    """The fixture's arena is 4 slots x 2 key/value heads x (a ring of 16
    or 104 positions) x 16 numbers, keys and values, in each of 8 layers,
    and the step runs 2 rows.  Nothing of an array's size is copied,
    padded, sliced, transposed or gathered on its way to the attention
    (the parent sliced ``ck[:2, :, :entries]`` out): each array is written
    row by row and handed over whole and as it lies (ISSUE 43).  Every
    operation of the kernel, interpreted here, is booked to the attention
    itself, under the layer kind's own scope."""
    from benchmark import program_parts

    raw, text = lowered["command_a step"][2:]
    module = program_parts.messages()["HloModule"].FromString(raw)
    path = {ins.name: ins.metadata.op_name
            for comp in module.computations for ins in comp.instructions}
    parts = program_parts.module_parts(raw)
    kernel = [n for n, p in path.items() if "grouped_decode_attention" in p]
    assert len(kernel) > 100
    assert {parts[n] for n in kernel} == {("attention_core", "own")}
    by_kind = {kind: sum(kind in path[n] for n in kernel)
               for kind in ("attn.window", "attn.full")}
    assert by_kind["attn.window"] + by_kind["attn.full"] == len(kernel)
    assert by_kind["attn.window"] > by_kind["attn.full"] > 0  # 6 and 2 layers

    made = {}         # dims -> opcode -> count, of an array's size
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if m is None:
            continue
        _, dims, opcode = m.groups()
        # the live rows' part of an array is never cut out
        assert dims not in ("2,2,16,16", "2,2,104,16"), line
        if sorted(dims.split(",")) in (
                sorted("4,2,16,16".split(",")),
                sorted("4,2,104,16".split(","))):
            made.setdefault(dims, {}).setdefault(opcode, 0)
            made[dims][opcode] += 1
    # 2 arrays a layer, each handed in (to the program, and on to the
    # interpreted kernel's own computation) and written once a row: 6
    # rings and 2 arrays by position
    assert set(made) == {"4,2,16,16", "4,2,104,16"}, made
    for dims, arrays in (("4,2,16,16", 12), ("4,2,104,16", 4)):
        assert set(made[dims]) == {"parameter", "dynamic-update-slice"}, made
        assert made[dims]["dynamic-update-slice"] == 2 * arrays


def test_evabyte_step_hands_its_kernel_ring_and_table_where_they_lie(lowered):
    """The fixture's arena is 4 slots x (a ring of 32 entries or a table
    of 40) x 4 heads x 16 numbers, keys and values, in each of 2 layers,
    and the step runs 2 rows.  Nothing of an array's size is copied,
    padded, sliced, reshaped, transposed or gathered on its way to the
    attention (the parent sliced ``ring_k[:2]`` and ``chunk_k[:2]`` out):
    each array is written row by row and handed over whole and as it lies
    (ISSUE 46).  Every operation of the kernel, interpreted here, is
    booked to the attention itself."""
    from benchmark import program_parts

    raw, text = lowered["evabyte step"][2:]
    module = program_parts.messages()["HloModule"].FromString(raw)
    path = {ins.name: ins.metadata.op_name
            for comp in module.computations for ins in comp.instructions}
    parts = program_parts.module_parts(raw)
    kernel = [n for n, p in path.items()
              if "ring_table_decode_attention" in p]
    assert len(kernel) > 100
    # The layers share ONE lowering of the kernel (a function of the
    # module, called once a layer): its operations take their part from
    # the call, which stands under the scope of the lines it replaced.
    assert {parts[n] for n in kernel} == {("attention_core", "caller")}
    calls = sorted(
        p for p in path.values() if p.endswith("jit(_ring_table_call)"))
    assert len(calls) == 2 and all(
        f"layer_{i}.step/attn.step/attention_core/eva.attend/" in p
        for i, p in enumerate(calls))

    made = {}         # dims -> opcode -> count, of an array's size
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if m is None:
            continue
        _, dims, opcode = m.groups()
        # the live rows' part of an array is never cut out
        assert dims not in ("2,32,4,16", "2,40,4,16"), line
        if sorted(dims.split(",")) in (
                sorted("4,32,4,16".split(",")),
                sorted("4,40,4,16".split(","))):
            made.setdefault(dims, {}).setdefault(opcode, 0)
            made[dims][opcode] += 1
    # 2 arrays a layer of either kind, each handed in (to the program, and
    # on through the interpreted kernel's own loop) and written once: the
    # step's entry into the ring, the closed chunk's into the table
    assert set(made) == {"4,32,4,16", "4,40,4,16"}, made
    for dims in made:
        assert set(made[dims]) == {
            "parameter", "get-tuple-element", "dynamic-update-slice",
            "scatter"}, made
        assert made[dims]["scatter"] == 2 * 2
        # the program's own and the shared kernel function's two
        assert made[dims]["parameter"] == 2 * 2 + 2


def test_keye_window_hands_its_kernel_the_row_where_it_lies(lowered):
    """The fixture's row is 104 positions x (2 key/value heads x 16
    numbers), keys and values, in each of 3 layers, and a window is 8
    tokens.  Every operation of the window's kernel, interpreted here
    (the mask and the count of equal keys among them), is booked to the
    attention itself; the row's two arrays are written once and handed
    over as they lie: nothing of their size is reshaped heads-apart,
    padded, copied or transposed on the way (tests/test_tpu_compile.py
    holds the compiled window to what it keeps of the window x the row)."""
    from benchmark import program_parts

    raw, text = lowered["keye prefill_window"][2:]
    module = program_parts.messages()["HloModule"].FromString(raw)
    path = {ins.name: ins.metadata.op_name
            for comp in module.computations for ins in comp.instructions}
    parts = program_parts.module_parts(raw)
    kernel = [n for n, p in path.items() if "selected_attention" in p]
    assert len(kernel) > 100
    assert {parts[n] for n in kernel} == {("attention_core", "own")}
    assert not any("dsa.select" in path[n] for n in kernel)

    made = {}         # opcode -> count, of the row's size
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?(\S+) = (\w+)\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if m is None:
            continue
        name, _, dims, opcode = m.groups()
        assert dims not in ("104,2,16", "1,104,2,16", "2,104,16"), line
        if dims in ("1,104,32", "104,32") and "selected_attention" \
                not in path.get(name.lstrip("%"), ""):
            made[opcode] = made.get(opcode, 0) + 1
    # 2 arrays a layer: handed in, written, the row cut out of its slot
    assert set(made) <= {
        "parameter", "dynamic-update-slice", "reshape", "bitcast",
        "get-tuple-element", "tuple"}, made
    assert made["dynamic-update-slice"] == 2 * 3


def test_the_window_program_has_its_name(lowered):
    from tpu_pipelines.trainer import train_loop as train_loop_module
    train_loop = sys.modules[train_loop_module.__module__]

    assert lowered["bert train window"][0] == "jit_train_window"
    assert "jit_" + lowered["window function"] \
        == train_loop.WINDOW_PROGRAM_NAME == "jit_train_window"


def test_one_vocabulary_in_one_place():
    """Every scope the program opens is a word of ``DEVICE_PARTS`` or one
    of the older, finer scopes, which stand letter for letter; the
    benchmark's copy of the vocabulary is the program's."""
    from benchmark import program_parts
    from tpu_pipelines.observability import trace

    assert program_parts.PARTS == trace.DEVICE_PARTS
    assert len(set(trace.DEVICE_PARTS)) == len(trace.DEVICE_PARTS)
    root = os.path.join(os.path.dirname(__file__), "..", "tpu_pipelines")
    found = {}
    for sub in ("models", "trainer", "serving"):
        for name in sorted(os.listdir(os.path.join(root, sub))):
            if name.endswith(".py"):
                with open(os.path.join(root, sub, name)) as f:
                    for scope in re.findall(
                            r'named_scope\(\s*"([^"]+)"', f.read()):
                        found.setdefault(scope, set()).add(name)
    assert set(found) <= set(trace.DEVICE_PARTS) | OLDER_SCOPES, found
    assert OLDER_SCOPES <= set(found)
    # the part every word is opened in somewhere
    assert set(trace.DEVICE_PARTS) <= set(found)
    for name in ("transformer.py", "bert.py", "t5.py", "evabyte.py",
                 "pangu_moe.py", "command_a.py", "xing.py", "keye.py",
                 "train_loop.py",
                 "generative.py"):
        assert any(name in files for files in found.values()), name


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    table = lower_all(pytest.MonkeyPatch())
    for key in PROGRAMS:
        print(f'    "{key}":\n        "{table[key][1]}",')
