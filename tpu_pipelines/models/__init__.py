"""Model zoo: flax models for the reference workloads (BASELINE configs).

Present:
  - taxi: Chicago-Taxi wide-and-deep DNN (config 0)
  - mnist: Keras-CNN-equivalent convnet (config 1)
  - resnet: ResNet-18/34/50/101/152, NHWC bfloat16 (config 2)
  - bert: BERT-base encoder + classifier/MLM heads (config 3)
  - t5: T5-small encoder-decoder seq2seq (config 4)
  - evabyte: byte-level decoder-only LM on EVA chunked linear attention
    (served through serving/generative.py; window ring + chunk table)
  - pangu_moe: decoder-only LM on latent attention and top-k routed experts
    with a shared one, as one chip's share of the experts (served through
    serving/generative.py; a latent cache by position)
  - command_a: decoder-only LM on window and full attention mixed 3 : 1
    over grouped-query heads, a parallel block and sigmoid-routed experts
    beside averaged shared ones (``build_command_a_model``; served through
    serving/generative.py; rings and by-position arrays in one arena)
  - xing: decoder-only LM whose residual path is four streams mixed by
    Sinkhorn-normalised matrices (mHC) around pangu_moe's latent attention
    (under YaRN) and routed experts (with a selection bias)
    (``tpu_pipelines.models.xing.build_xing_model``; served through
    serving/generative.py under pangu_moe's contract)
  - keye: decoder-only LM on grouped-query attention over the positions a
    learned indexer selects (an index cache beside the key/value cache, a
    decode step that fetches only what was selected), a three-section
    rotary code and softmax-routed experts without a shared one
    (``build_keye_model``; served through serving/generative.py; two kinds
    of cache at the same positions)
  - decode_contract: ``DecodeContract`` and ``CacheKind``, what each of
    the served models above hands serving/generative.py
  - transformer: shared sharded blocks (TP over 'model', ring-attention SP
    over 'seq') used by bert/t5

Tabular models (taxi) take a dict of (transformed) feature arrays; array-input
models (mnist, resnet) define an ``apply_fn`` hook in their trainer module file
so the serving/export path can adapt the feature dict (see trainer/export.py).
"""


def __getattr__(name):
    # The builder a configuration file names, without importing flax with
    # the package.
    if name == "build_command_a_model":
        from tpu_pipelines.models.command_a import build_command_a_model

        return build_command_a_model
    if name == "build_keye_model":
        from tpu_pipelines.models.keye import build_keye_model

        return build_keye_model
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
