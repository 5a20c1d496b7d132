"""BERT-base encoder + heads (BASELINE config 3: BERT-base fine-tune).

The reference fine-tunes BERT-base through TFX Transform (tokenization) +
Trainer (SURVEY.md §0 configs[3]).  Here: the encoder is built from the
sharded transformer blocks (models/transformer.py) — post-LN as in the
original BERT — with a classification head for fine-tuning and an MLM head
for pretraining-style objectives.  Tokenization stays host-side in the
Transform component (SURVEY.md §7 hard part 5); the model consumes
``input_ids`` / ``token_type_ids`` / an attention mask.

Parallelism: batch over mesh ``data``; optional TP over ``model`` via
``bert_partition_rules``; optional ring-attention SP over ``seq`` for long
sequences (attn_impl="ring").
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

from tpu_pipelines.models.transformer import (
    TRANSFORMER_PARTITION_RULES,
    TransformerBlock,
)


class BertEncoder(nn.Module):
    vocab_size: int = 30522
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_len: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16
    attn_impl: str = "dense"
    mesh: Optional[Mesh] = None
    # > 0 makes every other layer (odd i — the Switch convention) a
    # mixture-of-experts MLP with this many experts, expert-parallel over
    # the mesh ``expert`` axis.
    moe_experts: int = 0

    @nn.compact
    def __call__(
        self,
        input_ids,
        *,
        token_type_ids=None,
        attention_mask=None,
        deterministic: bool = True,
    ):
        ids = jnp.asarray(input_ids, jnp.int32)
        b, l = ids.shape
        with jax.named_scope("embed_head"):
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         name="embed")(ids)
            x = x + nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                             name="pos_embed")(jnp.arange(l)[None, :])
            types = (jnp.zeros_like(ids) if token_type_ids is None
                     else jnp.asarray(token_type_ids, jnp.int32))
            x = x + nn.Embed(self.type_vocab_size, self.d_model,
                             dtype=self.dtype, name="type_embed")(types)
        with jax.named_scope("norm"):
            x = nn.LayerNorm(dtype=self.dtype, name="embed_norm")(x)
        if self.dropout_rate:
            with jax.named_scope("dropout"):
                x = nn.Dropout(self.dropout_rate)(
                    x, deterministic=deterministic)
        for i in range(self.n_layers):
            x = TransformerBlock(
                n_heads=self.n_heads,
                head_dim=self.d_model // self.n_heads,
                d_ff=self.d_ff,
                dropout_rate=self.dropout_rate,
                dtype=self.dtype,
                attn_impl=self.attn_impl,
                mesh=self.mesh,
                causal=False,
                prenorm=False,          # original BERT is post-LN
                moe_experts=self.moe_experts if i % 2 == 1 else 0,
                name=f"layer_{i}",
            )(x, kv_mask=attention_mask, deterministic=deterministic)
        return x


class BertClassifier(nn.Module):
    """[CLS]-pooled sequence classification (the fine-tune workload)."""

    encoder: BertEncoder
    num_classes: int = 2
    dropout_rate: float = 0.1

    @nn.compact
    def __call__(self, batch: Dict[str, Any], *, deterministic: bool = True):
        x = self.encoder(
            batch["input_ids"],
            token_type_ids=batch.get("token_type_ids"),
            attention_mask=batch.get("attention_mask"),
            deterministic=deterministic,
        )
        with jax.named_scope("embed_head"):
            pooled = nn.tanh(
                nn.Dense(x.shape[-1], dtype=jnp.float32, name="pooler")(
                    x[:, 0].astype(jnp.float32)
                )
            )
        if self.dropout_rate:
            with jax.named_scope("dropout"):
                pooled = nn.Dropout(self.dropout_rate)(
                    pooled, deterministic=deterministic
                )
        with jax.named_scope("embed_head"):
            return nn.Dense(
                self.num_classes, dtype=jnp.float32, name="head"
            )(pooled)


class BertMLMHead(nn.Module):
    """Masked-LM logits over the vocab (pretraining-style objective)."""

    encoder: BertEncoder

    @nn.compact
    def __call__(self, batch: Dict[str, Any], *, deterministic: bool = True):
        x = self.encoder(
            batch["input_ids"],
            token_type_ids=batch.get("token_type_ids"),
            attention_mask=batch.get("attention_mask"),
            deterministic=deterministic,
        )
        with jax.named_scope("embed_head"):
            x = nn.gelu(
                nn.Dense(x.shape[-1], dtype=x.dtype, name="mlm_dense")(x))
        with jax.named_scope("norm"):
            x = nn.LayerNorm(dtype=x.dtype, name="mlm_norm")(x)
        with jax.named_scope("embed_head"):
            return nn.Dense(
                self.encoder.vocab_size, dtype=jnp.float32, name="mlm_head"
            )(x)


DEFAULT_HPARAMS = {
    # bert-base-uncased geometry, vocab padded 30522 → 30528 (divisible by
    # 64) so the TP embedding/MLM-head rules shard cleanly on any mesh —
    # the standard Megatron-style vocab padding.
    "vocab_size": 30528,
    "d_model": 768,
    "n_layers": 12,
    "n_heads": 12,
    "d_ff": 3072,
    "max_len": 512,
    "type_vocab_size": 2,
    "dropout_rate": 0.1,
    "num_classes": 2,
    "attn_impl": "auto",
    "moe_experts": 0,
    "learning_rate": 3e-5,
    "batch_size": 64,
    "head": "classifier",     # or "mlm"
}


def build_bert_model(hparams: Dict, mesh: Optional[Mesh] = None):
    hp = {**DEFAULT_HPARAMS, **(hparams or {})}
    encoder = BertEncoder(
        vocab_size=int(hp["vocab_size"]),
        d_model=int(hp["d_model"]),
        n_layers=int(hp["n_layers"]),
        n_heads=int(hp["n_heads"]),
        d_ff=int(hp["d_ff"]),
        max_len=int(hp["max_len"]),
        type_vocab_size=int(hp["type_vocab_size"]),
        dropout_rate=float(hp["dropout_rate"]),
        attn_impl=str(hp["attn_impl"]),
        mesh=mesh,
        moe_experts=int(hp.get("moe_experts", 0)),
    )
    if hp["head"] == "mlm":
        return BertMLMHead(encoder=encoder)
    return BertClassifier(
        encoder=encoder,
        num_classes=int(hp["num_classes"]),
        dropout_rate=float(hp["dropout_rate"]),
    )


def bert_partition_rules():
    """TP rules for the train loop's ``param_partition`` (first match wins)."""
    return list(TRANSFORMER_PARTITION_RULES) + [
        (r"mlm_head/kernel", P(None, "model")),
    ]
