"""Compile the main path's kernels and the BERT-base train step for a TPU
v5e that is DESCRIBED, not attached (on-chip-measurement guide, section 2).

What interpret-mode tests cannot see — a tiling the chip's compiler
refuses, a kernel that needs more fast memory than it may use, a program
that does not fit 16 GiB, a collective the compiler moved — shows here at
no chip time.  A compile is not a run: results and times come from
``chip_smoke.py`` on the chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU library, and under
``pytest -n`` every worker imports this file while only the worker that is
given it runs its tests.  All such tests stay in THIS file for the same
reason.  On the CPU backend the autotune table is keyed ``cpu`` and misses,
so the kernels are handed the blocks the committed table holds for the
described chip's ``device_kind`` — what a run there would pick.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

HBM_BYTES = 16 * 1024**3   # one v5e chip
V5E_KIND = "TPU v5 lite"   # jax's device_kind for v5e: the table's key


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip (it warns and recompiles).
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from tpu_pipelines.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(), devices=list(topo.devices))


def _table_blocks(op, b, h, l, d, causal):
    """(block_q, block_k) the committed table holds for the v5e, or None
    (the kernel then takes its defaults, as a run on the chip would)."""
    from tpu_pipelines.ops import autotune

    key = autotune.make_key(
        op, b, h, l, d, "bfloat16", causal, device_kind=V5E_KIND
    )
    entry = autotune._lookup_entry(autotune.key_id(key), V5E_KIND)
    return None if entry is None else (entry["block_q"], entry["block_k"])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    m = compiled.memory_analysis()
    need = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert need < HBM_BYTES, m
    return m


# ------------------------------------------------------------------ kernels


FLASH_CASES = [
    # (id, batch, seq, heads, head_dim, causal, masked)
    ("workhorse_2048", 8, 2048, 12, 64, False, False),
    ("long_8192", 8, 8192, 12, 64, False, False),
    ("causal_2048", 8, 2048, 12, 64, True, False),
    ("bert_masked_128", 256, 128, 12, 64, False, True),
]


@pytest.mark.parametrize(
    "b,l,h,d,causal,masked",
    [c[1:] for c in FLASH_CASES], ids=[c[0] for c in FLASH_CASES],
)
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, b, l, h, d, causal, masked):
    from tpu_pipelines.ops.flash_attention import flash_attention

    fwd = _table_blocks("flash_fwd", b, h, l, d, causal)
    bwd = _table_blocks("flash_bwd", b, h, l, d, causal)
    if l == 8192:
        assert fwd == bwd == (256, 256)  # the table's pick is what compiles
    kw = {}
    if fwd:
        kw.update(block_q=fwd[0], block_k=fwd[1])
    if bwd:
        kw.update(bwd_block_q=bwd[0], bwd_block_k=bwd[1])

    def loss(q, k, v, mask):
        out = flash_attention(
            q, k, v, causal=causal, kv_mask=mask if masked else None,
            interpret=False, **kw,
        )
        return out.astype(jnp.float32).sum()

    qkv = _sds((b, l, h, d), jnp.bfloat16, one_chip)
    mask = _sds((b, l), jnp.int32, one_chip)
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv, mask
    )
    assert "tpu_custom_call" in lowered.as_text()
    _fits(lowered.compile())


def test_flash_decode_compiles_for_v5e(one_chip):
    from tpu_pipelines.ops.flash_attention import flash_decode_attention

    b, l, h, d = 8, 2048, 8, 64
    blocks = _table_blocks("flash_decode", b, h, l, d, False)
    kw = {"block_k": blocks[1]} if blocks else {}
    q = _sds((b, 1, h, d), jnp.bfloat16, one_chip)
    kv = _sds((b, l, h, d), jnp.bfloat16, one_chip)
    mask = _sds((b, l), jnp.int32, one_chip)
    lowered = jax.jit(
        lambda q, k, v, m: flash_decode_attention(
            q, k, v, kv_mask=m, interpret=False, **kw
        )
    ).lower(q, kv, kv, mask)
    assert "tpu_custom_call" in lowered.as_text()
    _fits(lowered.compile())


def test_flash_training_memory_beats_dense_at_long_seq(one_chip):
    """At L=2048 the flash fwd+bwd path must need less live memory than
    dense (which materializes [b,h,L,L] scores in both passes) — read from
    the TPU compiler's own memory analysis."""
    from tpu_pipelines.ops.flash_attention import flash_attention
    from tpu_pipelines.parallel.ring_attention import dense_attention

    x = _sds((2, 2048, 4, 64), jnp.float32, one_chip)

    def temp(fn):
        g = jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2)
        )
        m = jax.jit(g).lower(x, x, x).compile().memory_analysis()
        return m.temp_size_in_bytes

    flash = temp(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False)
    )
    dense = temp(lambda q, k, v: dense_attention(q, k, v, causal=True))
    assert flash < dense / 2, (flash, dense)


# ------------------------------------------------------- BERT-base train step


BERT_BATCH, BERT_SEQ = 256, 128


def _bert_step(mesh=None, dp_mode=""):
    """The step the Trainer builds for examples/bert at bert-base width:
    bert_trainer_module's loss, adamw, the ``rbg`` training key, state
    donated — from shapes only.  ``dp_mode`` routes the gradient exchange
    through ``_make_dp_forward_backward`` exactly as ``train_loop`` does."""
    from tpu_pipelines.models.bert import DEFAULT_HPARAMS, build_bert_model
    from tpu_pipelines.parallel.partition import fsdp_param_partition
    from tpu_pipelines.trainer.train_loop import (
        TrainState,
        _make_dp_forward_backward,
    )

    hp = {**DEFAULT_HPARAMS, "max_len": BERT_SEQ}
    assert hp["vocab_size"] == 30528 and hp["d_model"] == 768
    model = build_bert_model(hp)
    optimizer = optax.adamw(2e-5)
    batch = {
        "input_ids": np.zeros((BERT_BATCH, BERT_SEQ), np.int32),
        "attention_mask": np.ones((BERT_BATCH, BERT_SEQ), np.int32),
        "label": np.zeros((BERT_BATCH,), np.int32),
    }

    def features(b):
        return {k: v for k, v in b.items() if k != "label"}

    def loss_fn(params, b, rng):
        logits = model.apply(
            {"params": params}, features(b),
            deterministic=False, rngs={"dropout": rng},
        )
        labels = jnp.asarray(b["label"], jnp.int32)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()
        return loss, {"accuracy": jnp.mean(jnp.argmax(logits, -1) == labels)}

    def make_state():
        rng = jax.random.key(0, impl="rbg")
        params = model.init(rng, features(batch))["params"]
        return TrainState.create(params, optimizer, rng)

    state = jax.eval_shape(make_state)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(state.params)
    )
    assert 105e6 < n_params < 115e6, n_params  # bert-base: the proof of width

    fsdp_specs = None
    dp_fb = None
    if dp_mode:
        if dp_mode == "fsdp":
            fsdp_specs = fsdp_param_partition(state.params, mesh)
        dp_fb = _make_dp_forward_backward(
            loss_fn, mesh, dp_mode, buckets=2,
            grad_blocks=mesh.shape["data"], fsdp_specs=fsdp_specs,
        )

    def step_fn(st, b):
        step_rng = jax.random.fold_in(st.rng, st.step)
        if dp_fb is not None:
            loss, metrics, grads, _ = dp_fb(st.params, None, b, step_rng)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(st.params, b, step_rng)
        updates, opt_state = optimizer.update(grads, st.opt_state, st.params)
        return st.replace(
            step=st.step + 1,
            params=optax.apply_updates(st.params, updates),
            opt_state=opt_state,
        ), {"loss": loss, **metrics}

    return step_fn, state, batch, fsdp_specs


def test_bert_base_train_step_fits_one_v5e(one_chip):
    step_fn, state, batch, _ = _bert_step()
    state_abs = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), state
    )
    batch_abs = {
        k: _sds(v.shape, v.dtype, one_chip) for k, v in batch.items()
    }
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        state_abs, batch_abs
    ).compile()
    m = _fits(compiled)
    # params + two adamw moments, f32: the resident state the step updates
    # in place (donated, so it is aliased and not counted twice).
    assert m.argument_size_in_bytes > 3 * 4 * 105e6
    assert m.alias_size_in_bytes > 3 * 4 * 105e6


def _window_text(mesh4, dp_mode):
    """Compiled text of a 2-step scan window of the BERT-base step on the
    described 2x2, sharded the way ``train_loop`` shards it."""
    step_fn, state, batch, fsdp_specs = _bert_step(mesh4, dp_mode)
    rep = NamedSharding(mesh4, P())
    if fsdp_specs is not None:
        from tpu_pipelines.trainer.train_loop import _opt_state_sharding

        p_shard = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh4, spec), fsdp_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        state_shard = state.replace(
            step=rep, rng=rep, params=p_shard,
            opt_state=_opt_state_sharding(
                state.opt_state, state.params, p_shard, mesh4
            ),
        )
    else:
        state_shard = jax.tree.map(lambda _: rep, state)
    state_abs = jax.tree.map(
        lambda x, s: _sds(x.shape, x.dtype, s), state, state_shard
    )
    win_abs = {
        k: _sds(
            (2,) + v.shape, v.dtype,
            NamedSharding(mesh4, P(None, "data", *[None] * (v.ndim - 1))),
        )
        for k, v in batch.items()
    }
    compiled = jax.jit(
        lambda st, bats: jax.lax.scan(step_fn, st, bats),
        donate_argnums=(0,),
    ).lower(state_abs, win_abs).compile()
    return compiled, compiled.as_text()


def _scan_body(text):
    """The while-body computation that holds the backward's matmuls."""
    bodies = [
        body for header, body in _hlo_computations(text)
        if "fusion(" in body and re.search(r"all-(reduce|gather)", body)
    ]
    assert bodies, "no computation with collectives in the window"
    return max(bodies, key=len)


def _hlo_computations(text):
    blocks, cur, header = [], [], None
    for line in text.splitlines():
        if header is None:
            if line.rstrip().endswith("{"):
                header, cur = line, []
        elif line.startswith("}"):
            blocks.append((header, "\n".join(cur)))
            header = None
        else:
            cur.append(line)
    return blocks


def _kept(text):
    """The result shapes of the instructions outside fused computations:
    what the program keeps in memory between two operations (inside a
    fusion a shape is a value on its way through the registers)."""
    fused = set(re.findall(r"fusion\(.*calls=%([\w.\-]+)", text))
    shapes = []
    for header, body in _hlo_computations(text):
        name = re.match(r"(?:ENTRY )?%?([\w.\-]+)", header).group(1)
        if name not in fused:
            shapes += re.findall(
                r"^\s+(?:ROOT )?%[\w.\-]+ = (.*?) [\w\-]+\(", body, re.M)
    return shapes


def test_bucketed_allreduce_stays_inside_backward_on_v5e_2x2(mesh4):
    """What tests/test_multichip_window.py cannot decide on a toy (whose
    few bytes any compiler merges into one all-reduce): at BERT-base size
    the TPU compiler keeps SEVERAL gradient all-reduces inside the scan
    body, placed between the backward's fusions — not one collective
    hoisted to the window boundary.  Whether they hide behind compute is a
    time, and only a trace on four chips can say."""
    compiled, text = _window_text(mesh4, "psum_bucketed")
    _fits(compiled)
    assert "while(" in text or "while (" in text
    body = _scan_body(text).splitlines()
    reduces = [
        i for i, l in enumerate(body)
        if re.search(r"all-reduce(-start)?\(", l)
    ]
    # >= the 2 requested buckets (the compiler may split them further).
    assert len(reduces) >= 2, reduces
    between = body[reduces[0]:reduces[-1]]
    assert sum(" fusion(" in l for l in between) >= 10
    # Replicated DP: every device holds all of params + both moments.
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 3 * 4 * 105e6


def test_fsdp_window_shards_state_on_v5e_2x2(mesh4):
    """fsdp on the described 2x2: per-device resident state is about a
    quarter of the replicated step's, and the scan body gathers params and
    reduce-scatters grads."""
    compiled, text = _window_text(mesh4, "fsdp")
    m = _fits(compiled)
    full = 3 * 4 * 110e6
    assert m.argument_size_in_bytes < 0.3 * full, m
    body = _scan_body(text)
    assert "all-gather" in body
    assert "reduce-scatter" in body or "all-reduce" in body


# ------------------------------------------- the decode step's K/V write


_SHAPE = r"[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?"


def _entry_layouts(text):
    """``(parameters, results, {result index: parameter number})`` of a
    compiled module: each side's shapes with their layouts, in order, and
    the input-output aliases, all from the module's first line."""
    head = text[:text.index("\n")]
    opening = "entry_computation_layout={("
    layout = head[head.index(opening) + len(opening):]
    params, results = layout.split(")->", 1)
    results = re.split(r"\}, [a-z_]+=", results)[0]   # the next attribute
    aliases = {
        int(out): int(param) for out, param in re.findall(
            r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", head)
    }
    return re.findall(_SHAPE, params), re.findall(_SHAPE, results), aliases


DECODE_WRITE_CASES = [
    # (id, rows, positions): the whole arena, a bucket inside it, and the
    # two buckets the paced cell spends its time in (PERF.md §5)
    ("step_64x256", 64, 256),
    ("step_32x128", 32, 128),
    ("step_16x128", 16, 128),
    ("step_16x256", 16, 256),
]


ARENA = dict(rows=64, positions=256, enc_len=128, heads=16, head_dim=64)


@pytest.fixture(scope="module")
def t5_large_engine_shapes():
    """``(fns, params, state)``: T5-large's decode contract at two layers,
    and the shapes of its parameters and of a 64 x 256 engine arena."""
    from tpu_pipelines.models.t5 import (
        build_t5_model, make_continuous_decode_fns,
    )

    rows, enc_len = ARENA["rows"], ARENA["enc_len"]
    model = build_t5_model(dict(
        vocab_size=32128, d_model=1024, n_layers=2, n_heads=ARENA["heads"],
        head_dim=ARENA["head_dim"], d_ff=4096, dropout_rate=0.0,
    ))
    fns = make_continuous_decode_fns(
        model, max_decode_len=ARENA["positions"], eos_id=32200,
        max_input_len=enc_len,
    )
    inputs = jnp.zeros((rows, enc_len), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), {
            "inputs": inputs, "targets": inputs[:, :4],
            "input_mask": inputs,
        })["params"]
    )
    cache, encoded, _ = jax.eval_shape(
        lambda p: fns.prefill(p, inputs, inputs), params
    )
    state = (
        cache, jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows,), bool), encoded, inputs,
    )
    return fns, params, state


@pytest.mark.parametrize(
    "b,kv", [c[1:] for c in DECODE_WRITE_CASES],
    ids=[c[0] for c in DECODE_WRITE_CASES],
)
def test_decode_kv_write_keeps_the_cache_where_it_lies_on_v5e(
    one_chip, t5_large_engine_shapes, b, kv
):
    """The engine's own step program at T5-large widths (two
    decoder layers, arena 64 x 256 donated): the chip keeps a
    ``bf16[b, kv, 16, 64]`` cache leaf position-minor, and the write of
    the step's K/V must leave it so.  A per-row scatter made the compiler
    re-lay the whole bucket out and back, four copies a layer (PERF.md §6,
    PR 28); a row-wise ``dynamic_update_slice`` still does in every bucket
    smaller than the arena."""
    from types import SimpleNamespace

    from tpu_pipelines.serving import generative as gen

    fns, params, state = t5_large_engine_shapes
    rows, positions = ARENA["rows"], ARENA["positions"]
    heads, head_dim = ARENA["heads"], ARENA["head_dim"]
    on_chip = lambda tree: jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), tree
    )
    program = gen.GenerativeEngine._build_step(
        SimpleNamespace(pad_id=0), b, kv, fns
    )
    compiled = program.lower(on_chip(params), on_chip(state)).compile()
    _fits(compiled)
    text = compiled.as_text()

    # Every self-attention K/V leaf of the arena is aliased to a result
    # that lies as the parameter lies.
    arena_leaf = f"bf16[{rows},{positions},{heads},{head_dim}]"
    param_layouts, result_layouts, aliases = _entry_layouts(text)
    leaves = [
        i for i, s in enumerate(param_layouts) if s.startswith(arena_leaf)
    ]
    assert len(leaves) == 2 * 2                     # K and V, two layers
    result_of = {param: out for out, param in aliases.items()}
    for i in leaves:
        assert i in result_of, f"cache parameter {i} is not aliased"
        assert result_layouts[result_of[i]] == param_layouts[i]

    # Nothing of the bucket's shape is copied, transposed or scattered
    # into, and nothing of that shape lies any other way than the arena.
    bucket = re.escape(f"bf16[{b},{kv},{heads},{head_dim}]")
    moved = re.findall(
        rf"= {bucket}\S* (?:copy|transpose|scatter)\(.*", text
    )
    assert not moved, (len(moved), moved[:2])
    lies = lambda shape: shape[shape.index("{") + 1:].split(":")[0]
    arena_lies = {lies(param_layouts[i]) for i in leaves}
    assert len(arena_lies) == 1
    assert set(re.findall(bucket + r"\{([\d,]+)", text)) == arena_lies


@pytest.mark.parametrize("tokens", [1, 128, 256])
def test_grouped_expert_products_compile_for_v5e(one_chip, tokens):
    """The Pallas grouped product of models/pangu_moe.py at the published
    widths (16 experts held, 7680 -> 2048 -> 7680, 8 choices a token) with
    the tiles the model hands it: a tiling the chip's compiler refuses, or
    one that wants more fast memory than a kernel may use, shows here.  On
    the CPU backend the model takes XLA's grouped product, so the kernel
    is compiled by its own name."""
    from tpu_pipelines.models import pangu_moe as pm

    m = -(-tokens * 8 // pm.ROW_TILE) * pm.ROW_TILE
    sizes = _sds((16,), jnp.int32, one_chip)

    def both(rows, w_in, hidden, w_out, sizes):
        return (pm.megablox(rows, w_in, sizes, pm.TILE_IN),
                pm.megablox(hidden, w_out, sizes, pm.TILE_OUT))

    compiled = jax.jit(both).lower(
        _sds((m, 7680), jnp.bfloat16, one_chip),
        _sds((16, 7680, 2048), jnp.bfloat16, one_chip),
        _sds((m, 2048), jnp.bfloat16, one_chip),
        _sds((16, 2048, 7680), jnp.bfloat16, one_chip), sizes,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 ** 2


def test_latent_row_write_keeps_the_cache_where_it_lies_on_v5e(
        one_chip, monkeypatch):
    """The engine's own step program for the latent-cache contract
    (models/pangu_moe.py) at the published widths, two layers (one dense,
    one with its 16 experts), the whole 128 x 2,560 arena handed over in
    place and donated: the chip keeps ``bf16[128, 2560, 576]``
    position-minor, and the 128 row-wise ``dynamic_update_slice`` of a
    step leave it so.  ``.at[rows, pos].set`` compiled to two copies of
    the whole array a layer (PERF.md §6, PR 31).

    Each layer's attention over the latents is ONE Pallas kernel
    (ops/flash_attention.py ``latent_decode_attention``) that is handed
    the array with its positions last: where it lies, so the view is a
    bitcast and nothing of the array's size is copied or transposed (a
    kernel over ``[slots, positions, width]`` blocks cost a copy of the
    whole array a layer; PERF.md §6, PR 41).  No float32 array of 128
    heads' scores over the positions is left in the program.  The program
    picks its kernels by the backend, which is the CPU here: the test
    tells it the chip's."""
    from types import SimpleNamespace

    from tpu_pipelines.models import pangu_moe as pm
    from tpu_pipelines.serving import generative as gen

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, positions = 128, 2560
    model = pm.build_pangu_moe_model(dict(
        vocab_size=19200, n_layers=2, n_dense_layers=1, experts_held=16,
        n_mtp=0))
    fns = pm.make_continuous_decode_fns(
        model, max_decode_len=1536, eos_id=19200, max_input_len=1024)
    assert fns.cache_positions == positions
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": jnp.zeros((1, 8), jnp.int32)})["params"])
    state = (
        jax.eval_shape(lambda: fns.blank_cache(rows)),
        jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows,), bool), jnp.zeros((rows, 0), jnp.float32),
        jnp.zeros((rows, 1024), jnp.int32),
    )
    on_chip = lambda tree: jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    program = gen.GenerativeEngine._build_step(
        SimpleNamespace(pad_id=0), rows, positions, fns)
    compiled = program.lower(on_chip(params), on_chip(state)).compile()
    _fits(compiled)
    text = compiled.as_text()
    leaf = f"bf16[{rows},{positions},576]"
    param_layouts, result_layouts, aliases = _entry_layouts(text)
    leaves = [i for i, s in enumerate(param_layouts) if s.startswith(leaf)]
    assert len(leaves) == 2
    result_of = {param: out for out, param in aliases.items()}
    for i in leaves:
        assert result_layouts[result_of[i]] == param_layouts[i]
    moved = re.findall(
        rf"= {re.escape(leaf)}\S* (?:copy|transpose|scatter)\(.*", text)
    assert not moved, (len(moved), moved[:2])
    assert not re.findall(
        rf"= bf16\[{rows},576,{positions}\]\S* (?:copy|transpose)\(.*", text)
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*", text)
    assert sum("latent_decode_attention" in k for k in kernels) == 2
    assert len(kernels) == 2 + 3       # and the expert layer's three products
    assert f"f32[{rows},128,{positions}]" not in text
    # the step's tally rides behind its tokens: one result of 128 + 16
    assert f"s32[{rows + 16}]" in text


def test_xing_step_and_window_compile_at_the_cells_shapes_on_v5e(
        one_chip, monkeypatch):
    """The engine's step program for models/xing.py at the cell's shapes
    (32 rows, 32 heads, 18,432 positions, all 64 experts held), two layers
    (one dense, one with experts), and the prefill window of 1,024 tokens
    over a span of 16,384.  The latent kernel of PR 41, written at 128
    heads x 2,560 positions, is handed ``bf16[32, 18432, 576]`` where it
    lies, one kernel a layer; the stream mixing leaves the arena alone;
    the window attends in blocks (``LatentAttention.blocked``, one
    ``grouped_attention`` a layer at 640 columns), so no float32 array of
    32 heads' scores over the span is left in either program; the grouped
    products take the tiles of ``TILES`` for 3,584 x 1,024."""
    from types import SimpleNamespace

    from tpu_pipelines.models import pangu_moe as pm, xing
    from tpu_pipelines.serving import generative as gen

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, positions, window = 32, 18432, 1024
    model = xing.build_xing_model(dict(
        n_layers=2, n_dense_layers=1, n_mtp=0))
    assert (3584, 1024) in pm.TILES and (1024, 3584) in pm.TILES
    fns = xing.make_continuous_decode_fns(
        model, max_decode_len=2048, eos_id=131072, max_input_len=16384,
        prefill_window_len=window)
    assert fns.cache_positions == positions
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": jnp.zeros((1, 8), jnp.int32)})["params"])
    state = (
        jax.eval_shape(lambda: fns.blank_cache(rows)),
        jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows,), bool), jnp.zeros((rows, 0), jnp.float32),
        jnp.zeros((rows, 16384), jnp.int32),
    )
    on_chip = lambda tree: jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    program = gen.GenerativeEngine._build_step(
        SimpleNamespace(pad_id=0), rows, positions, fns)
    compiled = program.lower(on_chip(params), on_chip(state)).compile()
    _fits(compiled)
    text = compiled.as_text()
    leaf = f"bf16[{rows},{positions},576]"
    moved = re.findall(
        rf"= {re.escape(leaf)}\S* (?:copy|transpose|scatter)\(.*", text)
    assert not moved, (len(moved), moved[:2])
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*", text)
    assert sum("latent_decode_attention" in k for k in kernels) == 2
    assert len(kernels) == 2 + 3       # and the expert layer's three products
    assert f"f32[{rows},32,{positions}]" not in text
    # the step's tally rides behind its tokens: one result of 32 + 64
    assert f"s32[{rows + 64}]" in text

    row = jax.eval_shape(lambda: fns.blank_cache(1))
    i32 = _sds((), jnp.int32, one_chip)
    compiled = jax.jit(fns.prefill_window, donate_argnums=1).lower(
        on_chip(params), on_chip(row), _sds((1, window), jnp.int32, one_chip),
        i32, i32).compile()
    _fits(compiled)
    text = compiled.as_text()
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*", text)
    assert sum("grouped_attention" in k for k in kernels) == 2
    assert f"f32[1,32,{window},16384]" not in text
    assert f"f32[32,{window},16384]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 ** 3


def test_keye_step_and_window_compile_at_the_cells_shapes_on_v5e(
        one_chip, monkeypatch):
    """The engine's step program for models/keye.py at the cell's shapes
    (16 rows, 22,528 positions, 32 query heads over 4 key/value heads, an
    indexer of 16 x 64 that selects 2,048, all 128 experts held), two
    layers, and the prefill window of 512 tokens over a row of 22,528
    positions, both inside 16 GiB.
    The step writes its three arrays a layer row by row where they lie and
    FETCHES the selected entries: nothing of an array's size is copied,
    transposed or scattered (with the key/value heads before the positions
    the compiler copied both arrays heads-innermost for every gather and
    back), the gathered entries are ``[16, 4, 2048, 128]`` and go to ONE
    ``grouped_decode_attention`` a layer (8 query heads a key/value head),
    and the top-k is exact (no ``ApproxTopK``).  The window keeps no
    float32 scores of 32 heads over the row and no index products of 16
    heads over it: the index products run in blocks of keys, and each
    layer's mask, tie count and attention are ONE Pallas kernel
    (ops/flash_attention.py ``selected_attention``) beside the three
    grouped products of its expert layer, handed the row's keys and values
    where they lie.  Of the row's size the program keeps the float32 index
    scores and their ``uint32`` order keys and nothing else: no scores of
    a block of 1,024 keys, no running count, no mask."""
    from types import SimpleNamespace

    from tpu_pipelines.models import keye
    from tpu_pipelines.serving import generative as gen

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, positions, window = 16, 22528, 512
    model = keye.build_keye_model(dict(n_layers=2))
    fns = keye.make_continuous_decode_fns(
        model, max_decode_len=2048, eos_id=151936, max_input_len=20480,
        prefill_window_len=window)
    assert fns.cache_positions == positions
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": jnp.zeros((1, 8), jnp.int32)})["params"])
    state = (
        jax.eval_shape(lambda: fns.blank_cache(rows)),
        jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows,), bool), jnp.zeros((rows, 0), jnp.float32),
        jnp.zeros((rows, 20480), jnp.int32),
    )
    on_chip = lambda tree: jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    program = gen.GenerativeEngine._build_step(
        SimpleNamespace(pad_id=0), rows, positions, fns)
    compiled = program.lower(on_chip(params), on_chip(state)).compile()
    _fits(compiled)
    text = compiled.as_text()
    for leaf in (f"bf16[{rows},{positions},512]",
                 f"bf16[{rows},{positions},64]"):
        moved = re.findall(
            rf"= {re.escape(leaf)}\S* (?:copy|transpose|scatter)\(.*", text)
        assert not moved, (len(moved), moved[:2])
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*", text)
    assert sum("grouped_decode_attention" in k for k in kernels) == 2
    assert len(kernels) == 2 + 2 * 3   # and each expert layer's three products
    assert f"bf16[{rows},4,2048,128]" in text           # what was fetched
    assert "ApproxTopK" not in text and "approx_top_k" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20
    # the step's tally rides behind its tokens: one result of 16 + 2 x 128
    assert f"s32[{rows + 256}]" in text

    row = jax.eval_shape(lambda: fns.blank_cache(1))
    i32 = _sds((), jnp.int32, one_chip)
    compiled = jax.jit(fns.prefill_window, donate_argnums=1).lower(
        on_chip(params), on_chip(row), _sds((1, window), jnp.int32, one_chip),
        i32, i32).compile()
    _fits(compiled)
    text = compiled.as_text()
    for whole in (f"f32[4,8,{window},{positions}]",
                  f"f32[32,{window},{positions}]",
                  f"f32[{window},16,{positions}]",
                  f"f32[16,{window},{positions}]"):
        assert whole not in text, whole
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*", text)
    assert sum("selected_attention" in k for k in kernels) == 2
    assert len(kernels) == 2 + 2 * 3   # and each expert layer's three products
    kept = "\n".join(_kept(text))
    for gone in (f"f32[4,8,{window},1024]", f"s32[{window},{positions}]",
                 f"s32[{window},176,128]", f"pred[{window},{positions}]",
                 f"pred[{window},176,128]"):
        assert gone not in kept, gone
    assert f"u32[{window},{positions}]" in kept       # the order keys
    # a row's keys and values: [22528, 4 x 128] as the cache holds them
    moved = re.findall(
        rf"= bf16\[(?:1,)?{positions},(?:512|4,128)\]\S* "
        r"(?:copy|transpose)\(.*", text)
    assert not moved, (len(moved), moved[:2])
    # 62.2 MB at PR 45 (144.0 MB with the masked attention in plain XLA)
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 ** 3
    assert compiled.memory_analysis().temp_size_in_bytes < 100 * 10 ** 6


SELECTED_CASES = [
    # (id, kv, g, l, n, d, start, dtype): the cell's shapes at its first
    # window and at its last (``start`` traced, as the window program
    # hands it, and a plain number); queries and positions that no block
    # divides; one query head a key/value head, float32
    ("cell_first", 4, 8, 512, 22528, 128, None, jnp.bfloat16),
    ("cell_start_0", 4, 8, 512, 22528, 128, 0, jnp.bfloat16),
    ("cell_start_19968", 4, 8, 512, 22528, 128, 19968, jnp.bfloat16),
    ("ragged_300_of_1000", 4, 8, 300, 1000, 128, None, jnp.bfloat16),
    ("g_1_float32", 2, 1, 512, 2048, 128, None, jnp.float32),
]


@pytest.mark.parametrize(
    "kv,g,l,n,d,start,dtype", [c[1:] for c in SELECTED_CASES],
    ids=[c[0] for c in SELECTED_CASES])
def test_selected_attention_kernel_compiles_for_v5e(
        one_chip, monkeypatch, kv, g, l, n, d, start, dtype):
    """``selected_attention`` alone: the chip's compiler takes its blocks
    (one head's 512 positions of the row where it lies, the queries'
    512 x 512 order keys, the mask and the prefix count built in VMEM)
    and its share of the fast memory; the row's keys and values, 128 lanes
    a head side by side, are handed over as they are."""
    import importlib

    fa = importlib.import_module("tpu_pipelines.ops.flash_attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    row = _sds((n, kv * d), dtype, one_chip)
    args = [_sds((kv, g, l, d), dtype, one_chip), row, row,
            _sds((l, n), jnp.uint32, one_chip),
            _sds((l,), jnp.uint32, one_chip),
            _sds((l,), jnp.int32, one_chip)]
    if start is None:
        call = fa.selected_attention
        args.append(_sds((), jnp.int32, one_chip))
    else:
        call = lambda *a: fa.selected_attention(*a, start)
    compiled = jax.jit(call).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 ** 2
    name = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    if n % 512 == 0:
        assert not re.findall(
            rf"= {name}\[{n},{kv * d}\]\S* (?:copy|transpose)\(", text)


LATENT_CASES = [
    # (id, rows, heads, r, rope, slots, positions, dtype): the cell's
    # shapes; the fixtures' (one key block reaching past the array's
    # end); positions that no block divides
    ("cell", 128, 128, 512, 64, 128, 2560, jnp.bfloat16),
    ("fixture", 2, 4, 16, 8, 4, 160, jnp.float32),
    ("ragged_1000", 8, 16, 128, 64, 8, 1000, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "rows,heads,r,rope,slots,positions,dtype",
    [c[1:] for c in LATENT_CASES], ids=[c[0] for c in LATENT_CASES])
def test_latent_decode_kernel_reads_the_cache_where_it_lies_on_v5e(
        one_chip, monkeypatch, rows, heads, r, rope, slots, positions, dtype):
    """``latent_decode_attention`` alone: the chip's compiler takes its
    blocks (a key block is ``[r + rope, 512]`` of an array it keeps
    position-minor), and the view it is handed, positions last, is a
    bitcast of the array: no copy or transpose of it."""
    import importlib

    fa = importlib.import_module("tpu_pipelines.ops.flash_attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    width = r + rope
    compiled = jax.jit(lambda q, cache, pos: fa.latent_decode_attention(
        q, cache, pos, positions, scale=width ** -0.5, r=r)).lower(
            _sds((rows, heads, width), dtype, one_chip),
            _sds((slots, positions, width), dtype, one_chip),
            _sds((rows,), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    name = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    param_layouts, _, _ = _entry_layouts(text)
    assert param_layouts[1].startswith(
        f"{name}[{slots},{positions},{width}]{{1,2,0")
    moved = re.findall(
        rf"= {name}\[{slots},(?:{positions},{width}|{width},{positions})\]"
        r"\S* (?:copy|transpose)\(.*", text)
    assert not moved, moved[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 ** 2


GROUPED_DECODE_CASES = [
    # (id, rows, kv, g, d, slots, entries, klen, dtype): the cell's ring
    # and its array by position; the fixtures' ring (a small part of one
    # key block) and their array under a bucket that ends inside it;
    # entries that no block divides
    ("cell_ring", 32, 8, 16, 128, 32, 4096, 4096, jnp.bfloat16),
    ("cell_full", 32, 8, 16, 128, 32, 18432, 18432, jnp.bfloat16),
    ("fixture_ring", 2, 2, 4, 16, 4, 16, 16, jnp.float32),
    ("fixture_full", 2, 2, 4, 16, 4, 104, 64, jnp.bfloat16),
    ("ragged_1000", 8, 8, 8, 128, 8, 1000, 1000, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "rows,kv,g,d,slots,entries,klen,dtype",
    [c[1:] for c in GROUPED_DECODE_CASES],
    ids=[c[0] for c in GROUPED_DECODE_CASES])
def test_grouped_decode_kernel_compiles_for_v5e(
        one_chip, monkeypatch, rows, kv, g, d, slots, entries, klen, dtype):
    """``grouped_decode_attention`` alone: the chip's compiler takes its
    blocks (every key/value head's 512 entries of keys and of values a
    step, or the fewest lanes that hold the array) and its share of the
    fast memory, and at the cell's shapes the arrays, rows of 128 lying
    row-major behind the heads, are handed over as they are."""
    import importlib

    fa = importlib.import_module("tpu_pipelines.ops.flash_attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = _sds((slots, kv, entries, d), dtype, one_chip)
    compiled = jax.jit(lambda q, k, v, depth: fa.grouped_decode_attention(
        q, k, v, depth, klen)).lower(
            _sds((rows, kv, g, d), dtype, one_chip), cache, cache,
            _sds((rows,), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 ** 2
    if d == 128:
        assert not re.findall(r" (?:copy|transpose)\(", text)


RING_TABLE_CASES = [
    # (id, rows, heads, d, slots, ring, table, dtype): the cell's ring
    # and table; entries that no block divides; float32; the fixtures'
    ("cell", 8, 32, 128, 9, 2048, 896, jnp.bfloat16),
    ("ragged", 8, 16, 128, 8, 1000, 300, jnp.bfloat16),
    ("float32", 4, 8, 128, 4, 512, 128, jnp.float32),
    ("fixture", 2, 4, 16, 4, 32, 40, jnp.float32),
]


@pytest.mark.parametrize(
    "rows,heads,d,slots,ring,table,dtype",
    [c[1:] for c in RING_TABLE_CASES], ids=[c[0] for c in RING_TABLE_CASES])
def test_ring_table_decode_kernel_compiles_for_v5e(
        one_chip, monkeypatch, rows, heads, d, slots, ring, table, dtype):
    """``ring_table_decode_attention`` alone: the chip's compiler takes its
    blocks (128 entries of a ring or of a table, every head of an entry
    at once, read as the rows of one product) within the fast memory a
    kernel has without asking, and at the cell's shapes the four arrays,
    a position's heads side by side, are handed over as they are."""
    import importlib

    fa = importlib.import_module("tpu_pipelines.ops.flash_attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    a_ring = _sds((slots, ring, heads, d), dtype, one_chip)
    a_table = _sds((slots, table, heads, d), dtype, one_chip)
    depth = _sds((rows,), jnp.int32, one_chip)
    compiled = jax.jit(lambda *a: fa.ring_table_decode_attention(
        *a, scale=d ** -0.5)).lower(
            _sds((rows, heads, d), dtype, one_chip), a_ring, a_ring,
            a_table, a_table, depth, depth).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 ** 2
    if d == 128:
        assert not re.findall(r" (?:copy|transpose)\(", text)


def test_evabyte_step_keeps_ring_and_table_where_they_lie_on_v5e(
        one_chip, monkeypatch):
    """The engine's own step program for the contract of
    models/evabyte.py at the published widths, two layers, the whole
    8-slot arena (rings of 2,048, tables of 896 entries) handed over in
    place and donated: every cache array comes back where it lay, each
    layer's attention is ONE Pallas kernel
    (ops/flash_attention.py ``ring_table_decode_attention``) that is
    handed the layer's four arrays where they lie, nothing of an array's
    size is copied or transposed, and no float32 scores over a ring or a
    table are left in the program (ISSUE 46).  The program picks its
    kernels by the backend, which is the CPU here: the test tells it the
    chip's."""
    from types import SimpleNamespace

    from tpu_pipelines.models import evabyte as ev
    from tpu_pipelines.serving import generative as gen

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, layers = 8, 2
    model = ev.build_evabyte_model(dict(n_layers=layers))
    fns = ev.make_continuous_decode_fns(
        model, max_decode_len=1024, eos_id=320, max_input_len=12288)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": jnp.zeros((1, 8), jnp.int32)})["params"])
    state = (
        jax.eval_shape(lambda: fns.blank_cache(rows)),
        jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows,), bool), jnp.zeros((rows, 0), jnp.float32),
        jnp.zeros((rows, 12288), jnp.int32),
    )
    on_chip = lambda tree: jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    program = gen.GenerativeEngine._build_step(
        SimpleNamespace(pad_id=0), rows, 13312, fns)
    compiled = program.lower(on_chip(params), on_chip(state)).compile()
    m = _fits(compiled)
    # one q, k or v kernel transposed (33.5 MB: ROADMAP S9) and no ring
    assert m.temp_size_in_bytes < 64 * 1024 ** 2
    text = compiled.as_text()
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*", text)
    assert len(kernels) == layers
    assert all("ring_table_decode_attention" in k for k in kernels)
    assert not re.findall(rf"f32\[{rows},32,1,(?:2048|896)\]", text)
    param_layouts, result_layouts, aliases = _entry_layouts(text)
    result_of = {param: out for out, param in aliases.items()}
    for leaf in (f"bf16[{rows},2048,32,128]", f"bf16[{rows},896,32,128]"):
        leaves = [
            i for i, s in enumerate(param_layouts) if s.startswith(leaf)]
        assert len(leaves) == 2 * layers
        for i in leaves:
            assert result_layouts[result_of[i]] == param_layouts[i]
        moved = re.findall(
            rf"= {re.escape(leaf)}\S* (?:copy|transpose)\(.*", text)
        assert not moved, (len(moved), moved[:2])


@pytest.mark.parametrize("tokens", [32, 512])
def test_grouped_expert_products_at_4096_compile_for_v5e(one_chip, tokens):
    """The same kernel at Command A+'s expert shape (16 experts held,
    4,096 -> 4,096 -> 4,096) with the tile the model's table hands it for
    that shape: a decode step's 32 rows x 8 choices and a prefill window's
    512 x 8.  (32, 2048, 2048) ran out of fast memory on the chip
    (PERF.md section 6, PR 35)."""
    from tpu_pipelines.models import pangu_moe as pm

    tile = pm.TILES[(4096, 4096)]
    m = -(-tokens * 8 // pm.ROW_TILE) * pm.ROW_TILE
    compiled = jax.jit(
        lambda rows, w, sizes: pm.megablox(rows, w, sizes, tile)).lower(
        _sds((m, 4096), jnp.bfloat16, one_chip),
        _sds((16, 4096, 4096), jnp.bfloat16, one_chip),
        _sds((16,), jnp.int32, one_chip),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 ** 2


def test_window_and_full_step_keeps_caches_and_weights_where_they_lie_on_v5e(
        one_chip, monkeypatch):
    """The engine's own step program for the contract of
    models/command_a.py at the published widths, one period of layers
    (three rings and a full array a slot) with its 16 experts, the whole
    32-slot arena of 18,432 positions handed over in place and donated:
    every cache array comes back where it lay, the 256 row-wise writes
    leave the key/value-heads-before-entries layout alone, and no
    projection's weights are copied into another layout (without the
    barrier behind the q, k and v products the compiler copied Wq, Wk and
    Wv at every step: PERF.md section 6, PR 35).  Each layer's attention
    is ONE Pallas kernel (ops/flash_attention.py
    ``grouped_decode_attention``) that is handed the layer's two arrays
    where they lie, beside the three grouped products of its expert layer,
    and no float32 scores over a ring or over the array's positions are
    left in the program.  The program picks its kernels by the backend,
    which is the CPU here: the test tells it the chip's."""
    from types import SimpleNamespace

    from tpu_pipelines.models import command_a as ca
    from tpu_pipelines.serving import generative as gen

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, positions = 32, 18432
    model = ca.build_command_a_model(dict(
        vocab_size=32768, n_layers=4, experts_held=16))
    fns = ca.make_continuous_decode_fns(
        model, max_decode_len=2048, eos_id=32768, max_input_len=16384)
    assert fns.cache_positions == positions
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": jnp.zeros((1, 8), jnp.int32)})["params"])
    state = (
        jax.eval_shape(lambda: fns.blank_cache(rows)),
        jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows,), bool), jnp.zeros((rows, 0), jnp.float32),
        jnp.zeros((rows, 16384), jnp.int32),
    )
    on_chip = lambda tree: jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    program = gen.GenerativeEngine._build_step(
        SimpleNamespace(pad_id=0), rows, positions, fns)
    compiled = program.lower(on_chip(params), on_chip(state)).compile()
    m = _fits(compiled)
    assert m.temp_size_in_bytes < 64 * 1024 ** 2
    text = compiled.as_text()
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*", text)
    assert sum("grouped_decode_attention" in k for k in kernels) == 4
    assert len(kernels) == 4 + 12      # and a layer's three grouped products
    assert not re.findall(rf"f32\[{rows},8,16,(?:{positions}|4096)\]", text)
    param_layouts, result_layouts, aliases = _entry_layouts(text)
    result_of = {param: out for out, param in aliases.items()}
    for leaf, count in ((f"bf16[{rows},8,{positions},128]", 2),
                        (f"bf16[{rows},8,4096,128]", 6)):
        leaves = [
            i for i, s in enumerate(param_layouts) if s.startswith(leaf)]
        assert len(leaves) == count
        for i in leaves:
            assert result_layouts[result_of[i]] == param_layouts[i]
        moved = re.findall(
            rf"= {re.escape(leaf)}\S* (?:copy|transpose|scatter)\(.*", text)
        assert not moved, (len(moved), moved[:2])
    # no weight is re-laid out: Wq and Wo, Wk and Wv, the shared experts
    for weight in ("bf16[4096,16384]", "bf16[16384,4096]",
                   "bf16[4096,1024]", "bf16[1024,4096]"):
        moved = re.findall(
            rf"= {re.escape(weight)}\S* (?:copy|transpose)\(.*", text)
        assert not moved, (weight, len(moved), moved[:1])
    # the step's tally rides behind its tokens: one result of 32 + 4 x 16
    assert f"s32[{rows + 64}]" in text


def test_prefill_window_holds_its_scores_in_the_kernel_on_v5e(
        one_chip, monkeypatch):
    """The engine's window program for the same contract at the cell's
    shapes: 512 positions of one prompt (8 key/value heads x 16 query
    heads of 128) against the row being built, three rings of 4,096 and a
    by-position array of 18,432.  Each layer's attention is ONE Pallas
    kernel (ops/flash_attention.py ``grouped_attention``) beside the three
    grouped products of its expert layer: the chip's compiler takes its
    tiles and its share of the fast memory, no float32 block of scores
    (8 key/value heads x 16 x 256 queries x a block of keys: the loop body
    of the ``jnp`` blocks that the kernel took the place of) is left in
    the program, and the row's arrays are donated, not copied.  The
    program picks its kernels by the backend, which is the CPU here: the
    test tells it the chip's."""
    from tpu_pipelines.models import command_a as ca
    from tpu_pipelines.serving import generative as gen

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    positions, p = 18432, 512
    model = ca.build_command_a_model(dict(
        vocab_size=32768, n_layers=4, experts_held=16))
    fns = ca.make_continuous_decode_fns(
        model, max_decode_len=2048, eos_id=32768, max_input_len=16384,
        prefill_window_len=p)
    assert fns.cache_positions == positions
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"inputs": jnp.zeros((1, 8), jnp.int32)})["params"])

    def prefill_window(params, row_cache, tokens, n_valid, index):
        row_cache, logits = fns.prefill_window(
            params, row_cache, tokens, n_valid, index)
        return row_cache, jnp.argmax(logits[0], -1).astype(jnp.int32)

    on_chip = lambda tree: jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    scalar = _sds((), jnp.int32, one_chip)
    compiled = gen._jit_program(prefill_window).lower(
        on_chip(params), on_chip(jax.eval_shape(lambda: fns.blank_cache(1))),
        _sds((1, p), jnp.int32, one_chip), scalar, scalar).compile()
    m = _fits(compiled)
    assert m.temp_size_in_bytes < 256 * 1024 ** 2
    text = compiled.as_text()
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*", text)
    assert sum("grouped_attention" in k for k in kernels) == 4
    assert len(kernels) == 4 + 12      # and a layer's three grouped products
    # what is left in float32 over 8 key/value heads x thousands of rows
    # is a head's 128 numbers wide, not a block of keys
    assert set(re.findall(r"f32\[8,\d{4,},(\d{3,})\]", text)) <= {"128"}
    assert not re.findall(r"f32\[8,16,\d{3,},(?:[5-9]\d\d|\d{4,})\]", text)
    # no loop under an attention's scope (the grouped products keep theirs)
    assert not re.findall(r" while\(.*attn\.(?:window|full)", text)
    param_layouts, result_layouts, aliases = _entry_layouts(text)
    result_of = {param: out for out, param in aliases.items()}
    for leaf, count in ((f"bf16[1,8,{positions},128]", 2),
                        ("bf16[1,8,4096,128]", 6)):
        leaves = [
            i for i, s in enumerate(param_layouts) if s.startswith(leaf)]
        assert len(leaves) == count
        for i in leaves:
            assert result_layouts[result_of[i]] == param_layouts[i]
        moved = re.findall(
            rf"= {re.escape(leaf)}\S* (?:copy|transpose|scatter)\(.*", text)
        assert not moved, (len(moved), moved[:2])
