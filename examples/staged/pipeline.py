"""Pipeline-parallel training pipeline: ImportExampleGen -> Trainer(dp×pp)
-> Evaluator.

The Trainer trains the staged transformer classifier (models/staged.py)
over a ``{"data": D, "pipe": S}`` mesh — GPipe microbatching through the
ordinary component layer.  Defaults fit the 8-device CPU test mesh
(dp2×pp4); env knobs: STAGED_TRAIN_STEPS, STAGED_DATA, STAGED_PIPE.
Synthetic token data (label = first token mod num_classes) is generated on
first run so the pipeline works out of the box.
"""

import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _ensure_data(base: str) -> str:
    path = os.path.join(base, "staged_synthetic.npz")
    if not os.path.exists(path):
        os.makedirs(base, exist_ok=True)
        rng = np.random.default_rng(0)
        n, seq_len, vocab, classes = 4096, 16, 64, 4
        tokens = rng.integers(2, vocab, size=(n, seq_len))
        np.savez(
            path,
            tokens=tokens.astype(np.int64),
            label=(tokens[:, 0] % classes).astype(np.int64),
        )
    return path


def create_pipeline(base_dir: str = ""):
    from tpu_pipelines.components import Evaluator, ImportExampleGen, Trainer
    from tpu_pipelines.dsl.pipeline import Pipeline

    base = base_dir or os.environ.get(
        "TPP_PIPELINE_HOME", os.path.join(HERE, "_run")
    )
    import jax

    data = int(os.environ.get("STAGED_DATA", "2"))
    pipe = int(os.environ.get("STAGED_PIPE", "4"))
    if jax.device_count() < data * pipe:
        # Single-chip fallback (e.g. a one-chip TPU host): plain DP,
        # sequential stages — same network, no pipeline schedule.
        data, pipe = -1, 1

    gen = ImportExampleGen(input_path=_ensure_data(base))
    trainer = Trainer(
        examples=gen.outputs["examples"],
        module_file=os.path.join(HERE, "staged_trainer_module.py"),
        train_steps=int(os.environ.get("STAGED_TRAIN_STEPS", "60")),
        hyperparameters={"batch_size": 32},
        mesh={"data": data, "pipe": pipe},
    )
    evaluator = Evaluator(
        examples=gen.outputs["examples"],
        model=trainer.outputs["model"],
        label_key="label",
        problem="multiclass",
        batch_size=64,
    )
    return Pipeline(
        "staged-pp", [gen, trainer, evaluator],
        pipeline_root=os.path.join(base, "root"),
        metadata_path=os.path.join(base, "metadata.sqlite"),
    )
