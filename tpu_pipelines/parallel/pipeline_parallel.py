"""Pipeline parallelism: GPipe-style microbatched stages over mesh ``pipe``.

The fifth parallelism axis (with data/model/seq/expert).  A network of S
identical-signature stages — e.g. groups of transformer layers — runs with
stage s's parameters resident only on pipe-device s; microbatches stream
through the pipeline, each device computing its stage every tick and
handing activations to the next stage with a single ``ppermute`` over ICI.

TPU-first mechanics (the scaling-book recipe):
  - per-stage parameters are STACKED on a leading stage dim and sharded
    ``P("pipe", ...)`` — each device holds 1/S of the model;
  - the schedule is one ``lax.scan`` over M + S - 1 ticks inside
    ``shard_map``; tick t has device s computing microbatch t - s (the
    GPipe fill/steady/drain diagonal), so the whole pipeline is ONE jitted
    computation, differentiable end-to-end (``ppermute`` is linear; its
    transpose is the reverse permute, giving the backward pipeline for
    free);
  - bubble fraction is the usual (S - 1) / (M + S - 1) — callers pick
    ``num_microbatches`` >> S to amortize.

Constraints: every stage must preserve the activation shape/dtype
(transformer blocks do), and the stage function must be identical across
stages (parameters differ, code does not) — the SPMD requirement that
makes one traced program serve every pipe device.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# stage_fn(stage_params, activation [mb, ...]) -> activation [mb, ...]
StageFn = Callable[[Any, jax.Array], jax.Array]


def gpipe(
    stage_fn: StageFn,
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pipe",
    batch_axis: str = "data",
) -> jax.Array:
    """Apply S pipelined stages to ``x`` as if run sequentially.

    ``stage_params``: pytree whose leaves carry a leading stage dim of size
    S = ``mesh.shape[axis]``, sharded ``P(axis, ...)``.  ``x``: the full
    batch ``[batch, ...]``; it is split into ``num_microbatches`` equal
    microbatches along dim 0.  Returns ``stage_S-1(... stage_0(x))``.

    Call inside ``jit``.  S == 1 degrades to a plain scan over nothing —
    the stage applies once per microbatch with the single param slice.
    """
    s = mesh.shape[axis]
    m = num_microbatches
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible by microbatches {m}")
    mb = b // m
    micro = x.reshape(m, mb, *x.shape[1:])

    if s == 1:
        params0 = jax.tree_util.tree_map(lambda p: p[0], stage_params)
        return jax.vmap(lambda xm: stage_fn(params0, xm))(micro).reshape(
            b, *x.shape[1:]
        )

    perm = [(i, i + 1) for i in range(s - 1)]   # non-cyclic shift forward

    def local_fn(params, micro):
        # params: this device's [1, ...] stage slice; micro replicated.
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis)
        ticks = m + s - 1

        def tick(carry, t):
            act, outs = carry
            # Stage 0 ingests microbatch t during the fill/steady phase
            # (clamped index; the drain-phase value is masked out of the
            # recorded outputs anyway); later stages consume the activation
            # handed to them last tick.
            inj = jax.lax.dynamic_index_in_dim(
                micro, jnp.clip(t, 0, m - 1), keepdims=False
            )
            x_in = jnp.where(idx == 0, inj, act)
            y = stage_fn(params, x_in)
            # The last stage finishes microbatch t - (s - 1) at tick t.
            out_t = jnp.clip(t - (s - 1), 0, m - 1)
            recorded = jax.lax.dynamic_update_index_in_dim(
                outs, y, out_t, 0
            )
            outs = jnp.where((t >= s - 1) & (idx == s - 1), recorded, outs)
            act_next = jax.lax.ppermute(y, axis, perm)
            return (act_next, outs), None

        act0 = jnp.zeros(micro.shape[1:], micro.dtype)
        outs0 = jnp.zeros_like(micro)
        (_, outs), _ = jax.lax.scan(
            tick, (act0, outs0), jnp.arange(ticks)
        )
        # Add a stage axis so out_specs can place each device's buffer;
        # only the last stage's holds real outputs.
        return outs[None]

    stage_spec = jax.tree_util.tree_map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stage_params
    )
    # Microbatch ROWS shard over `data`, so PP composes with DP: each
    # data-axis column pipelines its own 1/dp slice of every microbatch
    # instead of redundantly recomputing the full batch.  (Requires the
    # microbatch size to divide by the data axis, like any DP batch.)
    dp = mesh.shape.get(batch_axis, 1)
    if mb % dp:
        raise ValueError(
            f"microbatch size {mb} not divisible by mesh axis "
            f"{batch_axis}={dp}"
        )
    micro_spec = P(None, batch_axis)
    stacked = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(stage_spec, micro_spec),
        out_specs=P(axis, None, batch_axis),
        check_vma=False,
    )(stage_params, micro)
    return stacked[-1].reshape(b, *x.shape[1:])
