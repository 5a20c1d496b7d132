"""Shared arithmetic of the plain references.

Straightforward ``jax.numpy`` in float32 with matmul precision ``highest``;
no kernels, no cache, no batching tricks.  Nothing here imports the program.

``mode`` selects the precision of the products with a weight matrix:
``"f32"`` is the reference; ``"bf16"``, ``"int8"`` and ``"fp8"`` exist only
for the control, which must come out as not correct: the reference put in
the program's place and computed in a lower precision than the
configuration states.  ``int8`` and ``fp8`` (e4m3) round both operands with
one scale per row of the contraction (per token for activations, per output
column for weights) and let the gradient pass straight through the rounding.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e9


def _round_int8(x, axes: Sequence[int]):
    scale = jnp.max(jnp.abs(x), axis=tuple(axes), keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _round_fp8(x, axes: Sequence[int]):
    scale = jnp.max(jnp.abs(x), axis=tuple(axes), keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def weight_product(eq: str, x, w, mode: str, x_axes, w_axes):
    """``einsum(eq, x, w)`` where ``w`` is a weight; ``x_axes`` and
    ``w_axes`` are the contracted axes of each operand."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "f32":
        return jnp.einsum(eq, x, w, precision=HIGHEST)
    if mode == "bf16":
        return jnp.einsum(
            eq, x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    if mode in ("int8", "fp8"):
        rnd = _round_int8 if mode == "int8" else _round_fp8
        return jnp.einsum(
            eq, rnd(x, x_axes), rnd(w, w_axes), precision=HIGHEST,
        )
    raise ValueError(f"unknown precision mode {mode!r}")


def product(eq: str, a, b):
    return jnp.einsum(
        eq, a.astype(jnp.float32), b.astype(jnp.float32), precision=HIGHEST
    )


def layer_norm(x, gain, bias, eps: float = 1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def rms_norm(x, gain, eps: float = 1e-6):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * gain


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)
    ))


def attention(q, k, v, *, key_mask=None, bias=None, causal=False):
    """q [b, lq, h, d], k and v [b, lk, h, d] -> [b, lq, h, d]."""
    scores = product("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    if bias is not None:
        scores = scores + bias
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        allowed = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
        scores = jnp.where(allowed, scores, NEG_INF)
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, :] > 0, scores, NEG_INF)
    return product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def sign_pattern(n: int):
    """A fixed pattern of +1 and -1 over ``n`` elements, from an integer
    hash of the element's index: the direction on which a gradient leaf is
    projected, the same for the program's probe and the reference."""
    idx = jnp.arange(n, dtype=jnp.uint32)
    bit = ((idx * jnp.uint32(2654435761)) >> 15) & jnp.uint32(1)
    return jnp.where(bit == 1, 1.0, -1.0).astype(jnp.float32)


def project(x):
    """Sum of a leaf's elements under ``sign_pattern``."""
    flat = x.astype(jnp.float32).reshape(-1)
    return jnp.sum(flat * sign_pattern(flat.shape[0]))


def leaf_projections(tree) -> dict:
    """``project`` of every leaf, one per layer for a stacked leaf."""
    out = {}
    for name, x in tree.items():
        if name.startswith("layers."):
            for i in range(x.shape[0]):
                out[f"{name}[{i}]"] = float(project(x[i]))
        else:
            out[name] = float(project(x))
    return out


def leaf_norms(tree) -> dict:
    """``{name or name[i]: L2 norm}``; a leaf stacked over layers gives one
    norm per layer."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        if name.startswith("layers."):
            sq = jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1)
            for i, s in enumerate(jnp.sqrt(sq).tolist()):
                out[f"{name}[{i}]"] = s
        else:
            out[name] = float(jnp.sqrt(jnp.sum(jnp.square(x))))
    return out
