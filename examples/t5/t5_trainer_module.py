"""T5 seq2seq trainer module (BASELINE config 4: the JAX run_fn config).

Teacher-forced cross-entropy on tokenized (inputs, targets) pairs from
t5_preprocessing.py; loss is masked to non-pad target positions.
"""

import jax.numpy as jnp
import optax

from tpu_pipelines.data.input_pipeline import (
    BatchIterator,
    InputConfig,
    per_host_input_config,
)
from tpu_pipelines.models.t5 import DEFAULT_HPARAMS, build_t5_model
from tpu_pipelines.parallel.mesh import MeshConfig
from tpu_pipelines.trainer import (
    TrainLoopConfig, export_model, train_loop, warm_start_init,
)


def build_model(hyperparameters):
    return build_t5_model(hyperparameters)


def make_generate_step(model, hyperparameters):
    """Export hook (trainer/export.py): jitted beam-search decoding over
    transformed feature batches — the BulkInferrer predict_method="generate"
    path.  Returns ``fn(params, batch)`` so the loader passes params as a jit
    argument (never baked into the compiled program as constants).  Decode
    length/beam ride the exported hyperparameters."""
    from tpu_pipelines.models.t5 import make_beam_generate

    # End-of-sequence is the tokenizer's [SEP] (id 3): tft.tokenize emits
    # "[CLS] ... [SEP]" with SPECIAL_TOKENS [PAD]=0 [UNK]=1 [CLS]=2 [SEP]=3
    # (transform/graph.py), so trained targets terminate with 3 — NOT the
    # upstream-T5 convention of eos=1, which here is [UNK].
    gen = make_beam_generate(
        model,
        beam_size=int(hyperparameters.get("beam_size", 4)),
        max_decode_len=int(hyperparameters.get("max_decode_len", 32)),
        eos_id=int(hyperparameters.get("eos_id", 3)),
    )

    def fn(params, batch):
        mask = (
            jnp.asarray(batch["input_mask"], jnp.int32)
            if "input_mask" in batch else None
        )
        tokens, _score = gen(
            params, jnp.asarray(batch["inputs"], jnp.int32), mask
        )
        return tokens

    return fn


def make_decode_fns(model, hyperparameters):
    """Export hook (trainer/export.py): the ``DecodeContract``
    (models/decode_contract.py) that opts this payload into the
    generative fleet model type (serving/generative.py).  Same eos/pad
    conventions as make_generate_step above."""
    from tpu_pipelines.models.t5 import make_continuous_decode_fns

    return make_continuous_decode_fns(
        model,
        max_decode_len=int(hyperparameters.get("max_decode_len", 32)),
        eos_id=int(hyperparameters.get("eos_id", 3)),
        max_input_len=int(hyperparameters.get("max_input_len", 64)),
    )


def apply_fn(model, params, batch):
    return model.apply({"params": params}, {
        "inputs": jnp.asarray(batch["inputs"], jnp.int32),
        "targets": jnp.asarray(batch["targets"], jnp.int32),
        "input_mask": jnp.asarray(batch["input_mask"], jnp.int32)
        if "input_mask" in batch else None,
    })


def run_fn(fn_args):
    hp = {**DEFAULT_HPARAMS, **fn_args.hyperparameters}
    if "vocab_size" not in fn_args.hyperparameters and fn_args.transform_graph_uri:
        from tpu_pipelines.transform.graph import TransformGraph

        sizes = TransformGraph.load(
            fn_args.transform_graph_uri
        ).tokenizer_vocab_sizes()
        if sizes:
            hp["vocab_size"] = -(-max(sizes.values()) // 64) * 64
    model = build_t5_model(hp)
    batch_size = int(hp["batch_size"])

    train_iter = BatchIterator(
        fn_args.train_examples_uri, "train",
        # Multi-host DP: each process reads only its own shard of the
        # train split (whole files over a sharded artifact) instead
        # of every host decoding every row.  No-op single-process.
        per_host_input_config(InputConfig(batch_size=batch_size, shuffle=True, seed=0)),
    )

    def eval_iter_fn():
        return BatchIterator(
            fn_args.eval_examples_uri, "eval",
            InputConfig(batch_size=batch_size, shuffle=False, num_epochs=1,
                        drop_remainder=True),
        )

    def loss_fn(params, batch, rng):
        logits = model.apply(
            {"params": params}, batch,
            deterministic=False, rngs={"dropout": rng},
        )
        targets = jnp.asarray(batch["targets"], jnp.int32)
        mask = jnp.asarray(
            batch.get("target_mask", targets > 0), jnp.float32
        )
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        )
        loss = (per_tok * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return loss, {}

    def init_params_fn(rng, sample_batch):
        return model.init(rng, sample_batch)["params"]

    mesh_cfg = MeshConfig(**fn_args.mesh_config) if fn_args.mesh_config else None
    params, result = train_loop(
        loss_fn=loss_fn,
        init_params_fn=warm_start_init(fn_args, init_params_fn),
        optimizer=optax.adam(hp["learning_rate"]),
        train_iter=train_iter,
        eval_iter_fn=eval_iter_fn,
        config=TrainLoopConfig(
            train_steps=fn_args.train_steps,
            batch_size=batch_size,
            eval_steps=fn_args.eval_steps,
            checkpoint_every=max(1, fn_args.train_steps // 4),
            log_every=max(1, fn_args.train_steps // 10),
            mesh_config=mesh_cfg,
        ),
        checkpoint_dir=fn_args.model_run_dir,
    )

    export_model(
        serving_model_dir=fn_args.serving_model_dir,
        params=params,
        module_file=__file__,
        hyperparameters=hp,
        transform_graph_uri=fn_args.transform_graph_uri,
        extra_spec={"label": "targets"},
    )
    return result
