#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process that imports JAX once and starts
no child that needs a device:

  default (one chip):
    a. pipeline  the BERT fine-tune DAG (examples/bert) at bert-base width
                 through ``LocalDagRunner().run`` with a Pusher, so a
                 versioned payload exists;
    b. serve     a ``ModelServer`` on the pushed directory answering HTTP
                 predicts for raw text rows, against
                 ``load_exported_model(...).predict`` on the same rows;
    c. kernels   ``flash_attention`` fwd+bwd and ``flash_decode_attention``
                 compiled for the device, against ``dense_attention``;
    d. generate  T5-small through ``GenerativeEngine``, each emitted token
                 checked against a plain teacher-forced forward pass.
  --chips 4 (ONLY the multi-chip path and what it is compared with):
    the BERT-base train step on a ``{"data": 4}`` mesh under dp and under
    fsdp against the one-device step (same seed, same batches), and a
    four-replica ``ServingFleet`` whose replicas each hold their params on
    their own device.

Data and weights are made from ``--seed``.  Everything is written under
``--out`` (default ``chip_smoke_out/`` beside this file) and the compile
cache (utils/compile_cache.py), which stays ON.

The LAST line of stdout is one JSON object.  ``{"ok": true, "device":
{...}}`` is printed only when the device is a TPU, the size is ``full`` and
every phase passed (exit 0).  Exit codes: 1 a phase failed; 2 no TPU — a
full-size run is refused, nothing runs; 3 a ``--size tiny`` rehearsal whose
phases all passed (never "ok": a rehearsal is not a chip run).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import logging
import os
import shutil
import sys
import time
import traceback
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

SIZES = {
    # bert-base width; the vocabulary is PINNED (the example would otherwise
    # size the embedding from the few thousand words the tokenizer learns).
    "full": {
        "bert": {"vocab_size": 30528, "d_model": 768, "n_layers": 12,
                 "n_heads": 12, "d_ff": 3072, "max_len": 128,
                 "num_classes": 2, "batch_size": 256, "learning_rate": 1e-4},
        "seq_len": 128, "train_steps": 48, "rows": 2048, "words": 6000,
        "sentence_words": (40, 100),
        "t5": {},                      # models/t5.py DEFAULT_HPARAMS
        "t5_requests": 6, "t5_new_tokens": 12,
        "flash": [
            # (name, batch, seq, heads, head_dim, causal, masked, ref rows)
            ("flash 2048", 8, 2048, 12, 64, False, False, None),
            ("flash 8192", 8, 8192, 12, 64, False, False, (1, 2)),
            ("flash causal 2048", 8, 2048, 12, 64, True, False, None),
            ("flash masked 128", 256, 128, 12, 64, False, True, None),
        ],
        "decode": (8, 2048, 8, 64),
        "mesh_steps": 4,
    },
    "tiny": {
        "bert": {"vocab_size": 512, "d_model": 32, "n_layers": 2,
                 "n_heads": 2, "d_ff": 64, "max_len": 16, "num_classes": 2,
                 "batch_size": 16, "learning_rate": 1e-2,
                 "dropout_rate": 0.0},
        "seq_len": 16, "train_steps": 40, "rows": 192, "words": 120,
        "sentence_words": (5, 12),
        "t5": {"vocab_size": 128, "d_model": 32, "n_layers": 2,
               "n_heads": 2, "head_dim": 16, "d_ff": 64},
        "t5_requests": 3, "t5_new_tokens": 6,
        "flash": [
            ("flash 64", 2, 64, 2, 16, False, False, None),
            ("flash 128 sliced ref", 2, 128, 2, 16, False, False, (1, 1)),
            ("flash causal 64", 2, 64, 2, 16, True, False, None),
            ("flash masked 32", 4, 32, 2, 16, False, True, None),
        ],
        "decode": (2, 64, 2, 16),
        "mesh_steps": 4,
    },
}

# bf16 inputs on the MXU: kernel and reference each round every matmul to
# bf16, in different orders.  Errors are normalised by the reference's
# largest magnitude, so the bound means something for small outputs too.
KERNEL_TOL = 4e-2
# Served logits vs load_exported_model(...).predict.  The model computes in
# bf16 (8 bits of mantissa, 4e-3 relative) and the server answers from
# padded buckets of 1, 2 and 4 rows where the reference runs one batch of
# 6 — XLA tiles them differently, so they agree to bf16 rounding, not to
# the bit (first chip run, PR 21: 3.2e-3 on logits of order 1).
SERVE_TOL = 2e-2
# An emitted token's teacher-forced logit may trail the plain forward
# pass's best by this share of that position's logit spread (cached
# single-step decode vs a full pass round bf16 in different orders).
GENERATE_TOL = 5e-2
# dp / fsdp loss against the one-device loss at every logged step.
MESH_LOSS_TOL = 2e-2


def say(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------- set-up


def _write_reviews(path: str, size: dict, seed: int) -> None:
    """A seeded two-class text set: each class draws most of its words from
    its own half of a random vocabulary, so the label is learnable from the
    bag of words in a few tens of steps and the tokenizer learns thousands
    of distinct terms (token ids span the embedding, not 16 rows of it)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    words = sorted({
        "".join(rng.choice(letters, size=rng.integers(4, 10)))
        for _ in range(size["words"] * 2)
    })[: size["words"]]
    half = len(words) // 2
    banks = (words[:half], words[half:])
    lo, hi = size["sentence_words"]
    rows = ["text,label"]
    for _ in range(size["rows"]):
        label = int(rng.integers(2))
        n = int(rng.integers(lo, hi + 1))
        own = rng.random(n) < 0.85
        text = " ".join(
            banks[label if o else 1 - label][rng.integers(half)] for o in own
        )
        rows.append(f'"{text}",{label}')
    with open(path, "w") as f:
        f.write("\n".join(rows) + "\n")


PREPROCESSING = '''"""Written by chip_smoke.py: examples/bert/bert_preprocessing.py at the
smoke's sequence length (the example's module pads to 64)."""

MAX_LEN = {max_len}
VOCAB_SIZE = {vocab_size}


def preprocessing_fn(inputs, tft):
    ids = tft.tokenize(inputs["text"], max_len=MAX_LEN, vocab_size=VOCAB_SIZE)
    return {{
        "input_ids": ids,
        "attention_mask": tft.greater(ids, 0),
        "label": tft.cast(inputs["label"], "int32"),
    }}
'''


class _LossTap(logging.Handler):
    """Collects the (step, metrics) pairs train_loop logs at log_every."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.rows = []

    def emit(self, record):
        if record.msg == "step %d: %s" and isinstance(record.args, tuple):
            step, metrics = record.args
            self.rows.append((int(step), dict(metrics)))


@contextlib.contextmanager
def _tap_losses():
    tap = _LossTap()
    logger = logging.getLogger("tpu_pipelines.trainer")
    prev = logger.level
    logger.addHandler(tap)
    logger.setLevel(logging.INFO)
    try:
        yield tap
    finally:
        logger.removeHandler(tap)
        logger.setLevel(prev)


def _compile_seconds() -> dict:
    from tpu_pipelines.observability.metrics import default_registry

    counter = default_registry().counter(
        "train_compile_seconds_total", labels=("when",)
    )
    return {w: counter.labels(w).get() for w in ("warmup", "steady", "admin")}


def _native_cores() -> dict:
    """Build + load the three native cores; a failed make is an error here,
    not a quiet fall to the Python paths."""
    from tpu_pipelines.data import native_record
    from tpu_pipelines.metadata import native_store
    from tpu_pipelines.transform import native_tokenizer

    loaded = {
        "libtppmeta": native_store._load_library() is not None,
        "libtpptok": native_tokenizer._load_library() is not None,
        "libtpprec": native_record._load_library() is not None,
    }
    missing = [k for k, ok in loaded.items() if not ok]
    if missing:
        raise RuntimeError(f"native cores failed to build/load: {missing}")
    return loaded


# ------------------------------------------------------- phase a: pipeline


def phase_pipeline(size: dict, out: str, seed: int) -> dict:
    import numpy as np

    from tpu_pipelines.components import (
        CsvExampleGen,
        Evaluator,
        Pusher,
        SchemaGen,
        StatisticsGen,
        Trainer,
        Transform,
    )
    from tpu_pipelines.dsl.pipeline import Pipeline
    from tpu_pipelines.metadata import MetadataStore
    from tpu_pipelines.orchestration import LocalDagRunner
    from tpu_pipelines.trainer.export import exported_params_abstract

    bert_dir = os.path.join(HERE, "examples", "bert")
    base = os.path.join(out, "pipeline")
    os.makedirs(base)
    csv = os.path.join(base, "reviews.csv")
    _write_reviews(csv, size, seed)
    prep = os.path.join(base, "bert_preprocessing.py")
    with open(prep, "w") as f:
        f.write(PREPROCESSING.format(
            max_len=size["seq_len"], vocab_size=size["bert"]["vocab_size"],
        ))
    push_dir = os.path.join(base, "serving", "bert")

    hp = dict(size["bert"])
    gen = CsvExampleGen(input_path=csv)
    stats = StatisticsGen(examples=gen.outputs["examples"])
    schema = SchemaGen(statistics=stats.outputs["statistics"])
    transform = Transform(
        examples=gen.outputs["examples"],
        schema=schema.outputs["schema"],
        module_file=prep,
        materialize_on_device=True,
    )
    trainer = Trainer(
        examples=transform.outputs["transformed_examples"],
        transform_graph=transform.outputs["transform_graph"],
        module_file=os.path.join(bert_dir, "bert_trainer_module.py"),
        train_steps=size["train_steps"],
        hyperparameters=hp,
    )
    evaluator = Evaluator(
        examples=transform.outputs["transformed_examples"],
        model=trainer.outputs["model"],
        label_key="label",
        problem="multiclass",
        batch_size=int(hp["batch_size"]),
    )
    pusher = Pusher(
        model=trainer.outputs["model"],
        blessing=evaluator.outputs["blessing"],
        push_destination=push_dir,
    )
    md_path = os.path.join(base, "metadata.sqlite")
    pipeline = Pipeline(
        "chip-smoke-bert",
        [gen, stats, schema, transform, trainer, evaluator, pusher],
        pipeline_root=os.path.join(base, "root"),
        metadata_path=md_path,
    )

    compile_before = _compile_seconds()
    with _tap_losses() as tap:
        result = LocalDagRunner().run(pipeline)
    for node_id, nr in result.nodes.items():
        say(f"  node {node_id}: {nr.status} {nr.wall_clock_s:.1f}s"
            + (f" — {nr.error[-300:]}" if nr.error else ""))
    if not result.succeeded or any(
        nr.status != "COMPLETE" for nr in result.nodes.values()
    ):
        raise RuntimeError("the DAG did not complete (all nodes, uncached)")

    store = MetadataStore(md_path)
    try:
        props = {
            n: store.get_execution(result.nodes[n].execution_id).properties
            for n in ("Transform", "Trainer", "Pusher")
        }
    finally:
        store.close()
    on_device = props["Transform"]["materialize_on_device"]
    say(f"  Transform materialised on the device: {on_device}")
    if not on_device:
        raise RuntimeError("Transform did not materialise through the jit")

    model_uri = result.outputs_of("Trainer", "model")[0].uri
    shapes = exported_params_abstract(model_uri)
    import jax

    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)
    )
    embed = shapes["encoder"]["embed"]["embedding"].shape
    say(f"  parameters: {n_params:,} ({n_params / 1e6:.1f} M); token "
        f"embedding {tuple(embed)}")
    if embed[0] != hp["vocab_size"] or embed[1] != hp["d_model"]:
        raise RuntimeError(f"embedding {embed} is not the pinned width")
    if hp["d_model"] == 768 and not 105e6 < n_params < 115e6:
        raise RuntimeError(f"{n_params} parameters is not bert-base")

    compile_s = {
        k: round(v - compile_before[k], 2)
        for k, v in _compile_seconds().items()
    }
    tr = props["Trainer"]
    say(f"  Trainer: {tr['examples_per_sec']:.1f} examples/s "
        f"({tr['examples_per_sec_per_chip']:.1f} per chip), "
        f"{tr['steps_completed']} steps, compile seconds {compile_s}")
    say(f"  Trainer goodput {tr['goodput']} ({tr['goodput_source']}); "
        "badput shares of the job's wall-clock: "
        f"{ {k[7:]: v for k, v in tr.items() if k.startswith('badput_')} }")
    losses = [(s, m["loss"]) for s, m in tap.rows]
    if len(losses) < 2:
        raise RuntimeError(f"train loop logged {len(losses)} loss points")
    (s0, first), (s1, last) = losses[0], losses[-1]
    # One batch's loss is noisy: "falls" is judged on the mean of the last
    # quarter of the logged points against the mean of the first quarter.
    q = max(1, len(losses) // 4)
    head = float(np.mean([l for _, l in losses[:q]]))
    tail = float(np.mean([l for _, l in losses[-q:]]))
    say(f"  loss: step {s0} {first:.4f} -> step {s1} {last:.4f}; mean of the "
        f"first {q} logged points {head:.4f} -> of the last {q} {tail:.4f} "
        f"(final_loss {tr['final_loss']:.4f})")
    if not all(np.isfinite(l) for _, l in losses):
        raise RuntimeError(f"non-finite loss in {losses}")
    if not tail < head:
        raise RuntimeError(f"loss did not fall: {losses}")
    if not (os.path.isdir(push_dir) and os.listdir(push_dir)):
        raise RuntimeError(f"nothing pushed under {push_dir}")
    say(f"  pushed versions: {sorted(os.listdir(push_dir))}")
    return {"push_dir": push_dir, "csv": csv, "n_params": n_params,
            "compile_seconds": compile_s}


# ---------------------------------------------------------- phase b: serve


def _http_json(url: str, payload=None, timeout: float = 600.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, method="GET" if data is None else "POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.load(r)


def phase_serve(push_dir: str, csv: str) -> dict:
    import numpy as np

    from tpu_pipelines.serving import aot
    from tpu_pipelines.serving.server import ModelServer, latest_version_dir
    from tpu_pipelines.trainer.export import load_exported_model

    with open(csv) as f:
        lines = f.read().splitlines()[1:7]
    rows = []
    for line in lines:
        text, label = line.rsplit(",", 1)
        rows.append({"text": text.strip('"'), "label": int(label)})

    max_batch = 4
    server = ModelServer(
        "bert", push_dir, batching=True, max_batch_size=max_batch,
    )
    try:
        # The swap gate's ahead-of-time warm-up, on the model this server
        # serves: every padded bucket compiled (or read from the cache)
        # before a request arrives.
        warm = aot.warm_loaded(
            server._current_loaded(),
            {k: np.asarray([r[k] for r in rows[:1]]) for k in rows[0]},
            max_batch, raw=True,
        )
        say(f"  AOT warm-up: {warm}")
        if warm["fallback_warm"] or warm["load_failed"]:
            raise RuntimeError(f"AOT warm-up degraded: {warm}")
        port = server.start(port=0)
        base = f"http://127.0.0.1:{port}"
        served = []
        for chunk in (rows[:1], rows[1:3], rows[3:6]):
            reply = _http_json(
                f"{base}/v1/models/bert:predict", {"instances": chunk}
            )
            served.extend(reply["predictions"])
        health = _http_json(f"{base}/healthz")
        say(f"  /healthz: {health}")
        if not health.get("healthy"):
            raise RuntimeError(f"server unhealthy: {health}")
        dispatch = server._current_loaded().aot
        say(f"  requests answered: 3 ({len(served)} rows); served by AOT "
            f"executables, jit fallbacks after warm-up: {dispatch.fallbacks}")
        if dispatch.fallbacks:
            raise RuntimeError("a request compiled after the warm-up")
    finally:
        server.stop()

    served = np.asarray(served, np.float32)
    reference = load_exported_model(latest_version_dir(push_dir))
    want = np.asarray(reference.predict(
        {k: np.asarray([r[k] for r in rows]) for k in rows[0]}
    ), np.float32)
    err = float(np.max(np.abs(served - want)))
    say(f"  served logits {served.shape} vs load_exported_model().predict: "
        f"max abs diff {err:.3g} (tolerance {SERVE_TOL})")
    if served.shape != want.shape or not np.all(np.isfinite(served)):
        raise RuntimeError(f"served {served.shape} vs reference {want.shape}")
    if err > SERVE_TOL:
        raise RuntimeError("served logits differ from the loaded model's")
    return {"aot": warm, "max_abs_diff": err}


# -------------------------------------------------------- phase c: kernels


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))


def phase_kernels(size: dict, seed: int, on_tpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_pipelines.ops.flash_attention import (
        flash_attention,
        flash_decode_attention,
    )
    from tpu_pipelines.parallel.ring_attention import dense_attention

    interpret = not on_tpu      # compiled for the device whenever it is a TPU
    worst = 0.0
    for name, b, l, h, d, causal, masked, ref_rows in size["flash"]:
        keys = jax.random.split(jax.random.key(seed), 4)
        q, k, v, w = (
            jax.random.normal(kk, (b, l, h, d), jnp.bfloat16) for kk in keys
        )
        mask = None
        if masked:
            lengths = np.linspace(l // 4, l, b).astype(np.int32)
            mask = jnp.asarray(
                (np.arange(l)[None, :] < lengths[:, None]).astype(np.int32)
            )

        def loss(fn, q, k, v, w, mask):
            out = fn(q, k, v, causal=causal, kv_mask=mask)
            return (out.astype(jnp.float32) * w.astype(jnp.float32)).sum(), out

        flash = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(
                lambda *a, **kw: flash_attention(
                    *a, interpret=interpret, **kw
                ), q, k, v, w, mask,
            ), argnums=(0, 1, 2), has_aux=True,
        ))
        lowered = flash.lower(q, k, v).as_text()
        custom_call = "tpu_custom_call" in lowered
        (_, out), grads = flash(q, k, v)
        # Reference: dense attention on the chip.  Where [b,h,L,L] scores
        # cannot fit, on the first rows/heads only — batch rows and heads
        # are independent, so the slice's answer is the whole's.
        rb, rh = ref_rows or (b, h)
        cut = lambda x: x[:rb, :, :rh]  # noqa: E731
        (_, ref_out), ref_grads = jax.jit(jax.value_and_grad(
            lambda q, k, v, w, mask: loss(
                dense_attention, q, k, v, w, mask
            ), argnums=(0, 1, 2), has_aux=True,
        ))(cut(q), cut(k), cut(v), cut(w), None if mask is None else mask[:rb])
        errs = [_rel_err(cut(out), ref_out)] + [
            _rel_err(cut(g), rg) for g, rg in zip(grads, ref_grads)
        ]
        say(f"  {name} [{b},{l},{h},{d}] bf16: max err / max|ref| out "
            f"{errs[0]:.3g}, dq {errs[1]:.3g}, dk {errs[2]:.3g}, dv "
            f"{errs[3]:.3g}; tpu_custom_call in lowered text: {custom_call}"
            + (f"; reference on [{rb},{l},{rh},{d}]" if ref_rows else ""))
        if on_tpu and not custom_call:
            raise RuntimeError(f"{name}: no Pallas kernel in the program")
        if not all(np.isfinite(e) and e <= KERNEL_TOL for e in errs):
            raise RuntimeError(f"{name}: error {errs} over {KERNEL_TOL}")
        worst = max(worst, *errs)

    b, l, h, d = size["decode"]
    keys = jax.random.split(jax.random.key(seed + 1), 3)
    q = jax.random.normal(keys[0], (b, 1, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, l, h, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, l, h, d), jnp.bfloat16)
    pos = np.linspace(l // 8, l - 1, b).astype(np.int32)
    mask = jnp.asarray((np.arange(l)[None, :] <= pos[:, None]).astype(np.int32))
    decode = jax.jit(lambda q, k, v, m: flash_decode_attention(
        q, k, v, kv_mask=m, interpret=interpret
    ))
    custom_call = "tpu_custom_call" in decode.lower(q, k, v, mask).as_text()
    err = _rel_err(
        decode(q, k, v, mask),
        jax.jit(lambda q, k, v, m: dense_attention(q, k, v, kv_mask=m))(
            q, k, v, mask
        ),
    )
    say(f"  flash_decode q [{b},1,{h},{d}] kv [{b},{l},{h},{d}] bf16: max "
        f"err / max|ref| {err:.3g}; tpu_custom_call in lowered text: "
        f"{custom_call}")
    if on_tpu and not custom_call:
        raise RuntimeError("flash_decode: no Pallas kernel in the program")
    if not (np.isfinite(err) and err <= KERNEL_TOL):
        raise RuntimeError(f"flash_decode: error {err} over {KERNEL_TOL}")
    say(f"  tolerance {KERNEL_TOL} of the reference's largest magnitude; "
        f"worst {max(worst, err):.3g}")
    return {"worst_rel_err": max(worst, err)}


# ------------------------------------------------------- phase d: generate


def phase_generate(size: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_pipelines.models.t5 import (
        DEFAULT_HPARAMS,
        build_t5_model,
        make_continuous_decode_fns,
    )
    from tpu_pipelines.serving.generative import GenerativeEngine

    hp = {**DEFAULT_HPARAMS, **size["t5"], "dropout_rate": 0.0}
    model = build_t5_model(hp)
    max_in, max_new = 16, size["t5_new_tokens"]
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(2, hp["vocab_size"], size=int(n)).astype(np.int32)
        for n in rng.integers(4, max_in + 1, size=size["t5_requests"])
    ]
    sample = {
        "inputs": np.ones((1, max_in), np.int32),
        "targets": np.ones((1, max_new), np.int32),
    }
    params = model.init(jax.random.key(seed), sample)["params"]
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    )
    say(f"  T5 d_model {hp['d_model']}, {hp['n_layers']}+{hp['n_layers']} "
        f"layers, vocab {hp['vocab_size']}: {n_params / 1e6:.1f} M parameters")

    fns = make_continuous_decode_fns(
        model, max_decode_len=max_new + 4, max_input_len=max_in
    )
    engine = GenerativeEngine(fns, params, max_batch_size=4, page_size=8)
    t0 = time.monotonic()
    try:
        engine.warm()
        warm_s = time.monotonic() - t0
        handles = [
            engine.submit_nowait(p, max_new_tokens=max_new) for p in prompts
        ]
        streams = [np.asarray(h.wait(600.0)) for h in handles]
        compiles_after_warm = engine.compiles_after_warm
    finally:
        engine.close()
    total = sum(len(s) for s in streams)
    say(f"  engine warm-up {warm_s:.1f}s; {len(prompts)} requests -> {total} "
        f"tokens ({[len(s) for s in streams]}); decode compiles after "
        f"warm-up: {compiles_after_warm}")
    if compiles_after_warm:
        raise RuntimeError("a decode step compiled mid-traffic")

    # Reference: ONE plain forward pass per request over its emitted stream
    # (teacher forcing, no KV cache, no engine).  Every emitted token must
    # be that pass's best token at its position, to within rounding.
    apply = jax.jit(lambda p, b: model.apply({"params": p}, b))
    worst, exact = 0.0, 0
    for prompt, stream in zip(prompts, streams):
        if not (1 <= len(stream) <= max_new) or stream.min() < 0 \
                or stream.max() >= hp["vocab_size"]:
            raise RuntimeError(f"bad token stream {stream}")
        logits = np.asarray(apply(params, {
            "inputs": prompt[None, :], "targets": stream[None, :],
        }), np.float32)[0]
        if not np.all(np.isfinite(logits)):
            raise RuntimeError("non-finite reference logits")
        for t, tok in enumerate(stream):
            spread = float(logits[t].max() - logits[t].min())
            gap = float(logits[t].max() - logits[t, tok]) / spread
            worst = max(worst, gap)
            exact += int(np.argmax(logits[t]) == tok)
    say(f"  vs a teacher-forced forward pass: {exact}/{total} tokens are its "
        f"argmax; worst shortfall {worst:.3g} of the logit spread "
        f"(tolerance {GENERATE_TOL})")
    if worst > GENERATE_TOL:
        raise RuntimeError("the engine emitted a token the model does not rank top")
    return {"tokens": total, "worst_gap": worst}


# ------------------------------------------------------ --chips 4: the mesh


def phase_mesh(size: dict, out: str, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpu_pipelines.models.bert import DEFAULT_HPARAMS, build_bert_model
    from tpu_pipelines.parallel.mesh import MeshConfig, make_mesh
    from tpu_pipelines.trainer import TrainLoopConfig, export_model, train_loop

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devices)}")
    # Dropout off: the three runs are then the same function of the same
    # seed and batches, and differ only in where the arithmetic happens.
    hp = {**DEFAULT_HPARAMS, **size["bert"], "dropout_rate": 0.0}
    batch, seq = int(hp["batch_size"]), size["seq_len"]
    steps = size["mesh_steps"]
    model = build_bert_model(hp)
    rng = np.random.default_rng(seed)
    ids = rng.integers(4, hp["vocab_size"], size=(steps, batch, seq))
    data = [{
        "input_ids": ids[i].astype(np.int32),
        "attention_mask": np.ones((batch, seq), np.int32),
        "label": (ids[i][:, 0] % 2).astype(np.int32),
    } for i in range(steps)]

    def features(b):
        return {k: v for k, v in b.items() if k != "label"}

    def loss_fn(params, b, step_rng):
        logits = model.apply({"params": params}, features(b))
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(b["label"], jnp.int32)
        ).mean()
        return loss, {}

    def run(name, *, mesh=None, mesh_config=None, dp=None):
        with _tap_losses() as tap:
            params, result = train_loop(
                loss_fn=loss_fn,
                init_params_fn=lambda r, b: model.init(r, features(b))["params"],
                optimizer=optax.adamw(float(hp["learning_rate"])),
                train_iter=iter(data),
                config=TrainLoopConfig(
                    train_steps=steps, batch_size=batch, log_every=1,
                    window_steps=2, seed=seed, dp_collective=dp,
                    mesh_config=mesh_config,
                ),
                mesh=mesh,
            )
        losses = [m["loss"] for _, m in tap.rows]
        leaves = jax.tree_util.tree_leaves(params)
        total = sum(x.nbytes for x in leaves)
        per_device = sum(x.addressable_shards[0].data.nbytes for x in leaves)
        on = sorted({d.id for x in leaves for d in x.devices()})
        say(f"  {name}: losses {[round(l, 4) for l in losses]}; parameter "
            f"bytes per device {per_device:,} of {total:,} "
            f"({per_device / total:.3f}); params on devices {on}; "
            f"dp_collective {result.dp_collective or 'implicit'!r}")
        if len(losses) != steps or not np.all(np.isfinite(losses)):
            raise RuntimeError(f"{name}: losses {losses}")
        return params, losses, per_device / total, on

    _, one, _, _ = run(
        "one device", mesh=make_mesh(MeshConfig(), devices=devices[:1])
    )
    four = MeshConfig(data=4)   # make_mesh builds it over jax.devices()
    dp_params, dp, dp_share, dp_on = run(
        "dp   {'data': 4}", mesh_config=four, dp="psum_bucketed"
    )
    _, fsdp, fsdp_share, fsdp_on = run(
        "fsdp {'data': 4}", mesh_config=four, dp="fsdp"
    )
    for name, losses in (("dp", dp), ("fsdp", fsdp)):
        err = max(abs(a - b) for a, b in zip(losses, one))
        say(f"  {name} vs one device: max |loss diff| {err:.3g} "
            f"(tolerance {MESH_LOSS_TOL})")
        if err > MESH_LOSS_TOL:
            raise RuntimeError(f"{name} loss departs from the one-device step")
    if len(dp_on) != 4 or len(fsdp_on) != 4:
        raise RuntimeError("the mesh did not span four devices")
    if abs(dp_share - 1.0) > 1e-6 or not 0.24 <= fsdp_share <= 0.27:
        raise RuntimeError(
            f"per-device parameter share: dp {dp_share}, fsdp {fsdp_share}"
        )

    # A pushed version (what Pusher leaves: <destination>/<version>/) of the
    # dp run's weights, then one fleet with a replica on each chip.
    from tpu_pipelines.serving.fleet import ServingFleet
    from tpu_pipelines.trainer.export import load_exported_model

    push_dir = os.path.join(out, "mesh", "serving", "bert")
    export_model(
        serving_model_dir=os.path.join(push_dir, "1"),
        params=jax.device_get(dp_params),
        module_file=os.path.join(
            HERE, "examples", "bert", "bert_trainer_module.py"
        ),
        hyperparameters=hp,
    )
    del dp_params
    request = features({k: v[:4] for k, v in data[0].items()})
    want = np.asarray(
        load_exported_model(os.path.join(push_dir, "1")).predict(request),
        np.float32,
    )
    fleet = ServingFleet("bert", push_dir, replicas=4, max_batch_size=4)
    try:
        fleet.load_version(os.path.join(push_dir, "1"))
        loaded = fleet.active_loaded()
        homes = []
        for replica in fleet.pool.replicas:
            got = np.asarray(replica.submit(request, 4, timeout_s=600.0))
            err = float(np.max(np.abs(got - want)))
            where = sorted({
                d.id
                for x in jax.tree_util.tree_leaves(
                    loaded.params_on(replica.device)
                )
                for d in x.devices()
            })
            in_use = (replica.device.memory_stats() or {}).get("bytes_in_use")
            say(f"  replica {replica.name} on device {replica.device.id}: "
                f"its params live on {where}; logits vs the loaded model "
                f"max abs diff {err:.3g}; device bytes_in_use {in_use}")
            if where != [replica.device.id] or err > SERVE_TOL:
                raise RuntimeError(f"replica {replica.name} is misplaced")
            homes.append(replica.device.id)
        routed = np.asarray(fleet.submit(request, 4), np.float32)
        if float(np.max(np.abs(routed - want))) > SERVE_TOL:
            raise RuntimeError("routed fleet answer differs")
    finally:
        fleet.close()
    say(f"  four replicas on devices {homes}")
    if len(set(homes)) != 4:
        raise RuntimeError(f"replicas share devices: {homes}")

    # Ordered mode's contract on real chips: the same step gives the same
    # bits on 4, 2 and 1 devices at one fixed block count.  At matmul sizes
    # the MXU runs (this MLP) it held on four chips; for sub-tile toys,
    # whose dots the compiler turns into fused reductions, it does not
    # (PERF.md, PR 21) and is not asserted.
    def mlp_loss(params, b, step_rng):
        pred = jnp.tanh(b["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - b["y"]) ** 2), {}

    d_in, d_h, rows = (256, 512, 256) if hp["d_model"] == 768 else (8, 16, 64)
    trng = np.random.default_rng(seed)
    mlp_data = [{"x": trng.normal(size=(rows, d_in)).astype(np.float32),
                 "y": trng.normal(size=(rows, 1)).astype(np.float32)}
                for _ in range(4)]
    w1 = trng.normal(size=(d_in, d_h)).astype(np.float32) * 0.3
    w2 = trng.normal(size=(d_h, 1)).astype(np.float32) * 0.3
    finals = []
    for n in (4, 2, 1):
        params, _ = train_loop(
            loss_fn=mlp_loss,
            init_params_fn=lambda r, b: {
                "w1": jnp.asarray(w1), "w2": jnp.asarray(w2),
            },
            optimizer=optax.sgd(0.1),
            train_iter=iter(mlp_data),
            config=TrainLoopConfig(
                train_steps=4, batch_size=rows, log_every=0, window_steps=2,
                prng_impl=None, dp_collective="ordered", dp_grad_blocks=4,
            ),
            mesh=make_mesh(MeshConfig(), devices=devices[:n]),
        )
        finals.append([np.asarray(x) for x in jax.tree_util.tree_leaves(params)])
    bitwise = all(
        np.array_equal(a, b)
        for other in finals[1:] for a, b in zip(finals[0], other)
    )
    say(f"  ordered mode, MLP {d_in}x{d_h} batch {rows}, 4 vs 2 vs 1 devices: "
        f"bitwise equal params: {bitwise}")
    if not bitwise:
        raise RuntimeError("ordered mode is not mesh-size invariant here")
    return {"fsdp_share": fsdp_share, "replica_devices": homes}


# ------------------------------------------------------------------- main


def _device_report():
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--size", choices=sorted(SIZES), default="full")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"))
    args = parser.parse_args(argv)
    size = SIZES[args.size]

    def finish(ok: bool, device, code: int, **extra) -> int:
        print(json.dumps({"ok": ok, "device": device, **extra}), flush=True)
        return code

    try:
        sys.path.insert(0, HERE)
        import tpu_pipelines  # noqa: F401 — nothing of the repo here = fail
        device = _device_report()
    except Exception as e:  # noqa: BLE001 — no repo, no jax, no backend
        traceback.print_exc()
        return finish(False, None, 2, error=f"{type(e).__name__}: {e}")
    on_tpu = device["platform"] == "tpu"
    say(f"chip_smoke: size {args.size}, chips {args.chips}, seed {args.seed}, "
        f"device {device}")
    if not on_tpu and args.size == "full":
        say("chip_smoke: no TPU found — a full-size run is a chip run or "
            "nothing (rehearse with --size tiny)")
        return finish(False, device, 2, error="no TPU")
    if device["count"] != args.chips:
        return finish(
            False, device, 2,
            error=f"--chips {args.chips} but jax sees {device['count']}",
        )

    from tpu_pipelines.utils.compile_cache import (
        cache_root,
        maybe_enable_compile_cache,
    )

    cache_on = maybe_enable_compile_cache()
    import jax

    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    say(f"chip_smoke: compile cache on: {cache_on}, root {cache_root()}, "
        f"xla dir {jax.config.jax_compilation_cache_dir}")

    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    state: dict = {}
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(size, args.out, args.seed))]
    else:
        phases = [
            ("native", _native_cores),
            ("pipeline", lambda: phase_pipeline(size, args.out, args.seed)),
            ("serve", lambda: phase_serve(
                state["pipeline"]["push_dir"], state["pipeline"]["csv"])),
            ("kernels", lambda: phase_kernels(size, args.seed, on_tpu)),
            ("generate", lambda: phase_generate(size, args.seed)),
        ]
    t_all = time.monotonic()
    for name, fn in phases:
        say(f"phase {name}:")
        t0 = time.monotonic()
        try:
            state[name] = fn()
        except Exception as e:  # noqa: BLE001 — any phase failure fails the run
            traceback.print_exc()
            say(f"phase {name}: FAILED after {time.monotonic() - t0:.1f}s")
            return finish(
                False, device, 1, failed_phase=name,
                error=f"{type(e).__name__}: {e}"[:500],
            )
        if name == "native":
            say(f"  native cores loaded: {state[name]}")
        say(f"phase {name}: ok {time.monotonic() - t0:.1f}s")
        gc.collect()   # drop the phase's device buffers before the next
    say(f"chip_smoke: all phases passed in {time.monotonic() - t_all:.1f}s; "
        f"xla persistent cache hits {cache_events['hits']}, misses "
        f"{cache_events['misses']}")
    if not on_tpu or args.size != "full":
        say("chip_smoke: a rehearsal, not a chip run — not ok")
        return finish(False, device, 3, rehearsal="all phases passed")
    return finish(True, device, 0)


if __name__ == "__main__":
    sys.exit(main())
