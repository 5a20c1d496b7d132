"""Plain reference for the BERT sequence classifier and its fine-tune step.

Follows Devlin et al. 2018 (post-LN encoder, [CLS] pooler with tanh, a
linear head, softmax cross-entropy) with AdamW as ``optax.adamw`` states
it.  Departures, each shared with the program so that the two compute the
same function: GELU in its tanh form (the paper's erf form differs by up to
1e-3), LayerNorm epsilon 1e-6 (the released checkpoints use 1e-12),
vocabulary padded to 30,528, dropout on the embeddings, on each
sub-layer's output and on the pooled vector but not on the attention
probabilities.

Dropout: a step with dropout can only be followed with the program's own
masks, so ``dropout_masks`` restates, with jax and flax alone, the two
rules by which the program comes to them: the trainer's step key and
flax's key for a module path.  Nothing of the program is imported.

Parameters are a flat dict; the per-layer leaves are stacked on a leading
layer axis under names that start with ``layers.`` and the encoder is a
``lax.scan`` over them, which keeps the compile short.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference import common as C

_LAYER_LEAVES = {
    "attn/query/kernel": "layers.q_w", "attn/query/bias": "layers.q_b",
    "attn/key/kernel": "layers.k_w", "attn/key/bias": "layers.k_b",
    "attn/value/kernel": "layers.v_w", "attn/value/bias": "layers.v_b",
    "attn/out/kernel": "layers.o_w", "attn/out/bias": "layers.o_b",
    "attn_norm/scale": "layers.attn_ln_g", "attn_norm/bias": "layers.attn_ln_b",
    "mlp/wi/kernel": "layers.wi_w", "mlp/wi/bias": "layers.wi_b",
    "mlp/wo/kernel": "layers.wo_w", "mlp/wo/bias": "layers.wo_b",
    "mlp_norm/scale": "layers.mlp_ln_g", "mlp_norm/bias": "layers.mlp_ln_b",
}
_TOP_LEAVES = {
    "encoder/embed/embedding": "word_emb",
    "encoder/pos_embed/embedding": "pos_emb",
    "encoder/type_embed/embedding": "type_emb",
    "encoder/embed_norm/scale": "emb_ln_g",
    "encoder/embed_norm/bias": "emb_ln_b",
    "pooler/kernel": "pooler_w", "pooler/bias": "pooler_b",
    "head/kernel": "head_w", "head/bias": "head_b",
}


def leaf_names(n_layers: int) -> Dict[str, str]:
    """``{path of a leaf in the served tree: name of its norm here}``."""
    names = dict(_TOP_LEAVES)
    for i in range(n_layers):
        for path, name in _LAYER_LEAVES.items():
            names[f"encoder/layer_{i}/{path}"] = f"{name}[{i}]"
    return names


def from_served_tree(flat: Dict[str, jax.Array], n_layers: int) -> Dict:
    """The reference's layout from ``{leaf path: array}`` of the weights
    the benchmark made for the program."""
    out = {name: flat[path] for path, name in _TOP_LEAVES.items()}
    for path, name in _LAYER_LEAVES.items():
        out[name] = jnp.stack([
            flat[f"encoder/layer_{i}/{path}"] for i in range(n_layers)
        ])
    return {k: v.astype(jnp.float32) for k, v in out.items()}


def dropout_masks(spec: Dict, step: int, batch: int, seq: int,
                  d_model: int, n_layers: int) -> Dict[str, jax.Array]:
    """The keep-masks of step ``step`` (from 0), batch axis first, under
    the names ``logits`` looks for in its batch.

    Two rules are restated.  The trainer's (``trainer/train_loop.py``):
    the run's key is ``key(seed, impl)``, the state keeps the first half
    of its split, and a step's key is that with the step folded in.
    flax's: a ``nn.Dropout`` takes the key with the path of module names
    down to itself and the count 1 folded in (``LazyRng``), and keeps an
    element where ``bernoulli(key, 1 - rate)`` says so."""
    from flax.core.scope import LazyRng

    rng, _ = jax.random.split(
        jax.random.key(int(spec["train_seed"]), impl=spec["prng_impl"]))
    step_rng = jax.random.fold_in(rng, step)
    keep = 1.0 - float(spec["rate"])

    def mask(shape, *path):
        key = LazyRng.create(step_rng, *path, 1).as_jax_rng()
        return jax.random.bernoulli(key, keep, shape)

    wide = (batch, seq, d_model)
    per_layer = lambda sub: jnp.stack([
        mask(wide, "encoder", f"layer_{i}", sub, "Dropout_0")
        for i in range(n_layers)
    ], axis=1)
    return {
        "drop.embed": mask(wide, "encoder", "Dropout_0"),
        "drop.attn": per_layer("attn"),
        "drop.mlp": per_layer("mlp"),
        "drop.pooled": mask((batch, d_model), "Dropout_0"),
    }


def logits(params: Dict, batch: Dict, mode: str = "f32",
           dropout_rate: float = 0.0):
    """``batch`` carries the keep-masks under ``drop.*`` where
    ``dropout_rate`` is not 0."""
    ids = batch["input_ids"]
    mask = batch["attention_mask"]
    seq = ids.shape[1]
    drop = lambda x, keep: (
        jnp.where(keep, x / (1.0 - dropout_rate), 0.0)
        if dropout_rate else x)
    x = (
        params["word_emb"][ids]
        + params["pos_emb"][jnp.arange(seq)][None]
        + params["type_emb"][jnp.zeros_like(ids)]
    )
    x = C.layer_norm(x, params["emb_ln_g"], params["emb_ln_b"])
    x = drop(x, batch.get("drop.embed"))

    def layer(x, p_and_keep):
        p, keep_attn, keep_mlp = p_and_keep
        proj = lambda w, b: C.weight_product(
            "bld,dhk->blhk", x, p[w], mode, (2,), (0,)) + p[b]
        a = C.attention(
            proj("layers.q_w", "layers.q_b"),
            proj("layers.k_w", "layers.k_b"),
            proj("layers.v_w", "layers.v_b"),
            key_mask=mask,
        )
        a = C.weight_product(
            "blhk,hkd->bld", a, p["layers.o_w"], mode, (2, 3), (0, 1)
        ) + p["layers.o_b"]
        a = drop(a, keep_attn)
        x = C.layer_norm(x + a, p["layers.attn_ln_g"], p["layers.attn_ln_b"])
        h = C.gelu_tanh(C.weight_product(
            "bld,df->blf", x, p["layers.wi_w"], mode, (2,), (0,)
        ) + p["layers.wi_b"])
        m = C.weight_product(
            "blf,fd->bld", h, p["layers.wo_w"], mode, (2,), (0,)
        ) + p["layers.wo_b"]
        m = drop(m, keep_mlp)
        x = C.layer_norm(x + m, p["layers.mlp_ln_g"], p["layers.mlp_ln_b"])
        return x, None

    stacked = {k: v for k, v in params.items() if k.startswith("layers.")}
    n_layers = stacked["layers.q_w"].shape[0]
    keeps = [
        jnp.moveaxis(batch[k], 1, 0) if dropout_rate
        else jnp.zeros((n_layers,), jnp.bool_)
        for k in ("drop.attn", "drop.mlp")
    ]
    x, _ = jax.lax.scan(layer, x, (stacked, *keeps))
    pooled = jnp.tanh(C.weight_product(
        "bd,de->be", x[:, 0], params["pooler_w"], mode, (1,), (0,)
    ) + params["pooler_b"])
    pooled = drop(pooled, batch.get("drop.pooled"))
    return C.weight_product(
        "bd,dc->bc", pooled, params["head_w"], mode, (1,), (0,)
    ) + params["head_b"]


def summed_loss(params: Dict, batch: Dict, mode: str = "f32",
                dropout_rate: float = 0.0):
    lg = logits(params, batch, mode, dropout_rate)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)
    return -jnp.sum(picked)


def loss_and_grads(
    params: Dict, batch: Dict, mode: str = "f32", rows_per_block: int = 32,
    dropout_rate: float = 0.0,
) -> Tuple[jax.Array, Dict]:
    """Mean loss over the batch and its gradients, accumulated over
    blocks of rows so that float32 activations fit beside the weights."""
    n = batch["input_ids"].shape[0]
    if n % rows_per_block:
        raise ValueError(f"{n} rows do not split into {rows_per_block}")
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((n // rows_per_block, rows_per_block)
                            + a.shape[1:]),
        batch,
    )

    def one(carry, block):
        loss, grads = jax.value_and_grad(summed_loss)(
            params, block, mode, dropout_rate)
        acc_l, acc_g = carry
        return (acc_l + loss,
                jax.tree_util.tree_map(jnp.add, acc_g, grads)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(one, zero, blocks)
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def adamw_step(params, grads, m, v, t: int, *, lr: float, b1=0.9, b2=0.999,
               eps=1e-8, weight_decay=1e-4):
    """One update of ``optax.adamw(lr)`` with its default constants."""
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(
        lambda a, g: b2 * a + (1 - b2) * jnp.square(g), v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (
            (a / c1) / (jnp.sqrt(b / c2) + eps) + weight_decay * p),
        params, m, v,
    )
    return new, m, v


def follow_steps(
    params: Dict, batches: List[Dict], *, lr: float, mode: str = "f32",
    rows_per_block: int = 32, dropout: Dict = None,
) -> Dict:
    """Drive the plain step over ``batches``: each step's loss, the norm
    and the projection (``common.project``) of every leaf of the first
    gradient, and the norm of the parameters' change after the last step.
    ``dropout`` is ``{rate, train_seed, prng_impl}`` or nothing."""
    rate = float(dropout["rate"]) if dropout else 0.0
    grad_fn = jax.jit(
        lambda p, b: loss_and_grads(p, b, mode, rows_per_block, rate))
    n_layers, d_model = params["layers.q_w"].shape[:2]
    masks_fn = jax.jit(
        lambda step, rows, seq: dropout_masks(
            dropout, step, rows, seq, d_model, n_layers),
        static_argnums=(1, 2))
    step_fn = jax.jit(
        lambda p, g, m, v, t: adamw_step(p, g, m, v, t, lr=lr),
        static_argnums=(4,),
    )
    start = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad, first_proj = [], None, None
    for t, batch in enumerate(batches, start=1):
        batch = {k: jnp.asarray(a) for k, a in batch.items()}
        if rate:
            batch.update(masks_fn(t - 1, *batch["input_ids"].shape))
        loss, grads = grad_fn(params, batch)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = C.leaf_norms(grads)
            first_proj = C.leaf_projections(grads)
        params, m, v = step_fn(params, grads, m, v, t)
    change = C.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, params, start))
    return {"losses": losses, "first_grad_norms": first_grad,
            "first_grad_projections": first_proj, "change_norms": change}
