"""FSDP-sharded params, grad accumulation, and ring wiring (ISSUE 18).

The multi-chip window (ISSUE 15) with the explicit collectives extended to
the memory axis.  Contracts pinned here:

  * sharded memory model — ``dp_collective="fsdp"`` keeps exactly 1/N of
    every parameter (and optimizer slot) resident per device; a model
    whose FULL f32 params exceed a documented per-device budget trains on
    the 8-device mesh because the working set is the shard plus ONE
    layer's gather, never the whole tree;
  * overlappable collectives — the compiled window carries one distinct
    all-gather per parameter leaf on the forward and one reduce-scatter
    per leaf on the backward (the AD transpose of the tiled gather),
    inside the scan's while body interleaved with the matmuls;
  * numeric parity — fsdp on N devices matches the unsharded single-chip
    trajectory to float tolerance (same math, resharded);
  * grad accumulation — the inner ``lax.scan`` over interleaved
    micro-batches composes with every collective mode; for ``ordered``
    it stays BITWISE invariant to the mesh size (and equals the unrolled
    micro-step loop to float rounding), and for ``psum_bucketed`` the
    exchange volume per outer step is invariant to the accumulation
    depth;
  * model_state — BatchNorm-style collections thread micro-batch to
    micro-batch through the window under every mode;
  * elastic resume — an fsdp run interrupted mid-window resumes on a
    survivor mesh with exact replay accounting;
  * ring wiring — ``attn_impl="auto"`` routes self-attention to ring on
    a populated ``seq`` axis at long context, and
    ``long_context_batch_partition`` derives the matching input sharding.
"""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_pipelines.parallel.mesh import MeshConfig, make_mesh
from tpu_pipelines.parallel.partition import fsdp_param_partition
from tpu_pipelines.trainer import TrainLoopConfig, train_loop
from tpu_pipelines.trainer.train_loop import _make_dp_forward_backward

pytestmark = pytest.mark.multichip

BATCH = 64
D = 128       # layer width: every leaf dim divides the 8-device data axis
LAYERS = 4
# The documented per-device budget the memory-model test asserts against:
# full f32 params (264,704 B for this model) do NOT fit, while the fsdp
# working set — the 1/8 shard plus one layer's gather — does.
DEVICE_BUDGET_BYTES = 160_000


def _mesh(n_devices: int):
    return make_mesh(MeshConfig(), devices=jax.devices()[:n_devices])


def _batches(n, batch=BATCH, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.normal(size=(batch, D)).astype(np.float32)
        y = np.tanh(x[:, :1] * 0.3).astype(np.float32)
        out.append({"x": x, "y": y})
    return out


def _loss_fn(params, b, rng):
    h = b["x"]
    for i in range(LAYERS):
        h = jnp.tanh(h @ params["layers"][f"w_{i}"] + params["layers"][f"b_{i}"])
    pred = h @ params["head"]
    return jnp.mean((pred - b["y"]) ** 2), {"pred_mean": jnp.mean(pred)}


def _init_fn(rng, b):
    r = np.random.default_rng(7)
    layers = {}
    for i in range(LAYERS):
        layers[f"w_{i}"] = jnp.asarray(
            r.normal(size=(D, D)).astype(np.float32) * 0.05
        )
        layers[f"b_{i}"] = jnp.zeros((D,), jnp.float32)
    return {
        "layers": layers,
        "head": jnp.asarray(r.normal(size=(D, 1)).astype(np.float32) * 0.05),
    }


def _sloss_fn(params, mstate, b, rng):
    loss, metrics = _loss_fn(params, b, rng)
    new_ms = {
        "running": 0.9 * mstate["running"] + 0.1 * metrics["pred_mean"],
        "count": mstate["count"] + 1,
    }
    return loss, (metrics, new_ms)


def _sinit_fn(rng, b):
    return _init_fn(rng, b), {
        "running": jnp.zeros(()), "count": jnp.zeros((), jnp.int32),
    }


def _run(n_devices, *, dp="fsdp", steps=8, window=4, state=False,
         batches=None, ckpt="", checkpoint_every=0, optimizer=None, **kw):
    # Trajectory-parity tests pass plain SGD: adam's sqrt(v) normalization
    # turns ulp-scale reduction-order differences in near-zero grads into
    # macroscopic drift over a few steps, which would test the optimizer's
    # chaos, not the collective's math.
    params, result = train_loop(
        loss_fn=_sloss_fn if state else _loss_fn,
        init_params_fn=_sinit_fn if state else _init_fn,
        optimizer=optimizer or optax.adam(0.05),
        train_iter=iter(batches if batches is not None else _batches(steps)),
        config=TrainLoopConfig(
            train_steps=steps, batch_size=BATCH, log_every=0,
            window_steps=window, prng_impl=None, dp_collective=dp,
            checkpoint_every=checkpoint_every, **kw,
        ),
        mesh=_mesh(n_devices),
        checkpoint_dir=ckpt,
        has_model_state=state,
    )
    return params, result


def _np_leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _param_bytes(tree):
    return sum(v.size * v.dtype.itemsize for v in _np_leaves(tree))


def _hlo_computations(text: str):
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


# ------------------------------------------------------- numeric parity


def test_fsdp_matches_unsharded_single_chip():
    """fsdp on 8 devices lands on the unsharded single-chip trajectory to
    float tolerance — sharding moves bytes, not math — and records its
    mode on the result."""
    sgd = lambda: optax.sgd(0.1)
    p8, r8 = _run(8, dp="fsdp", optimizer=sgd())
    p1, r1 = _run(1, dp=None, optimizer=sgd())
    assert r8.dp_collective == "fsdp"
    assert r8.steps_completed == r1.steps_completed == 8
    for a, b in zip(_np_leaves(p8), _np_leaves(p1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- memory model


def test_fsdp_trains_model_beyond_single_device_budget():
    """The ISSUE 18 acceptance model: full f32 params exceed the
    documented per-device budget, yet the fsdp run completes on the
    8-device mesh because residency is params/N plus one layer's gather.
    The returned params stay sharded: per-device persistent bytes are
    EXACTLY total/8."""
    params, result = _run(8, dp="fsdp")
    assert result.steps_completed == 8

    total = _param_bytes(params)
    assert total > DEVICE_BUDGET_BYTES, (
        "fixture model must overflow the documented budget unsharded"
    )
    # One transformer-block-equivalent layer: w_i + b_i, gathered full.
    layer_bytes = D * D * 4 + D * 4
    shard_resident = sum(
        v.addressable_shards[0].data.nbytes
        for v in jax.tree_util.tree_leaves(params)
    )
    assert shard_resident * 8 == total  # every leaf sharded, exactly 1/N
    assert shard_resident + layer_bytes < DEVICE_BUDGET_BYTES

    # The derived default partition shards every leaf of THIS model over
    # the data axis (all dims divide 8).
    specs = fsdp_param_partition(params, _mesh(8))
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P)
    )
    assert all(s == P("data") for s in leaves)


def test_fsdp_compiled_window_memory_and_overlap():
    """Compiled evidence: the window program carries one all-gather per
    param leaf (forward) and one reduce-scatter per leaf (the AD
    transpose of the tiled gather) INSIDE the scan's while body, sharing
    a computation with the matmuls; and the per-device argument footprint
    (sharded params + adam slots + batch) stays well under the full
    parameter bytes a replicated mode would pin."""
    mesh = _mesh(8)
    params = _init_fn(None, None)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    specs = fsdp_param_partition(params, mesh)
    fb = _make_dp_forward_backward(
        _loss_fn, mesh, "fsdp", buckets=2, grad_blocks=8, fsdp_specs=specs
    )
    opt = optax.adam(0.05)
    p_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )
    params_s = jax.tree_util.tree_map(jax.device_put, params, p_shard)

    def step(carry, batch):
        p, o = carry
        loss, _metrics, grads, _ = fb(p, None, batch, jax.random.key(0))
        updates, o = opt.update(grads, o, p)
        return (optax.apply_updates(p, updates), o), loss

    bshard = {k: NamedSharding(mesh, P(None, "data")) for k in ("x", "y")}
    stack = {
        k: jax.device_put(np.stack([b[k] for b in _batches(4)]), bshard[k])
        for k in ("x", "y")
    }
    win = jax.jit(
        lambda c, b: jax.lax.scan(step, c, b),
        in_shardings=((p_shard, None), bshard),
    )
    compiled = win.lower((params_s, opt.init(params_s)), stack).compile()
    text = compiled.as_text()

    assert "while(" in text or "while (" in text
    gather_blocks = [
        (h, b) for h, b in _hlo_computations(text) if "all-gather(" in b
    ]
    scatter_blocks = [
        (h, b) for h, b in _hlo_computations(text) if "reduce-scatter(" in b
    ]
    assert gather_blocks and scatter_blocks
    # One distinct collective per leaf, each overlappable with compute.
    assert text.count("all-gather(") >= n_leaves
    assert text.count("reduce-scatter(") >= n_leaves
    assert any("dot(" in b for _, b in gather_blocks)
    assert any("dot(" in b for _, b in scatter_blocks)

    # Per-device steady-state arguments (param shards + both adam slots +
    # the batch slice) undercut even the bare full-param bytes.
    arg_bytes = compiled.memory_analysis().argument_size_in_bytes
    assert arg_bytes < _param_bytes(params)


# ------------------------------------------------------- grad accumulation


def test_ordered_accum_inner_scan_matches_unrolled_bitwise():
    """Ordered mode through accumulation.  The contract is mesh-size
    invariance: accum=2 gives BITWISE the same gradients on 8, 4 and 1
    devices at one fixed block count, at the function level and through
    the full loop.  Against the hand-unrolled two micro calls (same
    interleaved rows, same fold_in rng, same accumulate-then-scale order)
    the inner lax.scan agrees to float rounding only: they are different
    programs, and a compiler is free to fuse a scan body differently from
    a stand-alone call (the installed one does, by an ulp)."""
    params = _init_fn(None, None)
    batch = _batches(1)[0]
    key = jax.random.key(3)
    kw = dict(buckets=2, grad_blocks=8)

    def fb_on(n, accum):
        return _make_dp_forward_backward(
            _loss_fn, _mesh(n), "ordered", accum=accum, **kw
        )

    loss2, metrics2, grads2, _ = fb_on(8, 2)(params, None, batch, key)
    for n in (4, 1):
        loss_n, _, grads_n, _ = fb_on(n, 2)(params, None, batch, key)
        for a, b in zip(_np_leaves(grads2), _np_leaves(grads_n)):
            assert np.array_equal(a, b), n
        assert np.array_equal(np.asarray(loss2), np.asarray(loss_n)), n

    # Unrolled reference: the global batch whose contiguous per-device
    # split is exactly micro i's interleaved LOCAL rows.
    def global_micro(i):
        return {
            k: np.concatenate([c[i::2] for c in np.split(v, 8)])
            for k, v in batch.items()
        }

    fb1 = fb_on(8, 1)
    micro = [
        fb1(params, None, global_micro(i), jax.random.fold_in(key, i))
        for i in range(2)
    ]
    ref_grads = jax.tree_util.tree_map(
        lambda a, b: (a + b) * (1.0 / 2), micro[0][2], micro[1][2]
    )
    ref_loss = (micro[0][0] + micro[1][0]) * (1.0 / 2)
    for a, b in zip(_np_leaves(grads2), _np_leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(loss2), np.asarray(ref_loss), rtol=1e-6
    )

    # And the full-loop consequence: the ordered bitwise mesh-size
    # invariance survives accumulation (same fixed block count).
    pa, _ = _run(8, dp="ordered", grad_accum_steps=2, dp_grad_blocks=8)
    pb, _ = _run(4, dp="ordered", grad_accum_steps=2, dp_grad_blocks=8)
    for a, b in zip(_np_leaves(pa), _np_leaves(pb)):
        assert np.array_equal(a, b)


def test_psum_accum_exchange_volume_invariant():
    """psum_bucketed accumulates LOCAL grads across micro-steps and
    exchanges ONCE per outer step: the compiled all-reduce count does not
    grow with accumulation depth."""
    mesh = _mesh(8)
    params = _init_fn(None, None)
    batch = _batches(1)[0]
    bshard = {k: NamedSharding(mesh, P("data")) for k in ("x", "y")}

    def count_allreduce(accum):
        fb = _make_dp_forward_backward(
            _loss_fn, mesh, "psum_bucketed",
            buckets=2, grad_blocks=8, accum=accum,
        )
        f = jax.jit(
            lambda p, b: fb(p, None, b, jax.random.key(0)),
            in_shardings=(None, bshard),
        )
        staged = {k: jax.device_put(v, bshard[k]) for k, v in batch.items()}
        return f.lower(params, staged).compile().as_text().count("all-reduce(")

    assert count_allreduce(4) == count_allreduce(1)


def test_grad_accum_composes_with_every_mode():
    """No mode refuses grad_accum_steps>1 any more, and the accumulated
    gradient equals the single-micro-batch gradient of the same global
    batch to float tolerance under every mode (mean of micro means ==
    full mean, different summation order)."""
    mesh = _mesh(8)
    params = _init_fn(None, None)
    batch = _batches(1)[0]
    key = jax.random.key(0)
    base = None
    for dp in ("psum_bucketed", "ordered", "fsdp"):
        kw = dict(buckets=2, grad_blocks=8)
        if dp == "fsdp":
            kw["fsdp_specs"] = fsdp_param_partition(params, mesh)
        g = {
            a: _make_dp_forward_backward(_loss_fn, mesh, dp, accum=a, **kw)(
                params, None, batch, key
            )[2]
            for a in (1, 2)
        }
        for a, b in zip(_np_leaves(g[1]), _np_leaves(g[2])):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
        # All modes agree on the same mean gradient too.
        if base is None:
            base = g[1]
        else:
            for a, b in zip(_np_leaves(base), _np_leaves(g[1])):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# ------------------------------------------------------- model_state


def test_model_state_threads_through_window_all_modes():
    """has_model_state no longer raises under any explicit mode: the
    collection threads micro-batch to micro-batch inside the window, the
    counter advances once per micro-step, and ordered mode keeps its
    mesh-size bitwise invariance with state in play."""
    for dp in ("psum_bucketed", "ordered", "fsdp"):
        kw = {"dp_grad_blocks": 8} if dp == "ordered" else {}
        (params, ms), result = _run(
            8, dp=dp, state=True, grad_accum_steps=2, **kw
        )
        assert result.steps_completed == 8
        # 8 outer steps x 2 micro-steps of threaded updates.
        assert int(ms["count"]) == 16
        assert float(np.abs(np.asarray(ms["running"]))) > 0

    (p8, s8), _ = _run(8, dp="ordered", state=True, dp_grad_blocks=8)
    (p4, s4), _ = _run(4, dp="ordered", state=True, dp_grad_blocks=8)
    for a, b in zip(_np_leaves(p8), _np_leaves(p4)):
        assert np.array_equal(a, b)  # the param contract stays bitwise
    assert int(s8["count"]) == int(s4["count"])
    # The EMA leaf is reduced in the same block order, but XLA may fuse
    # 0.9*r + 0.1*m into an FMA at one vmap width and not the other — the
    # state collection carries a documented 1-ulp mesh-size tolerance.
    np.testing.assert_allclose(
        np.asarray(s8["running"]), np.asarray(s4["running"]), rtol=1e-6
    )


# ------------------------------------------------------- elastic resume


def test_fsdp_elastic_resume_mid_window(tmp_path):
    """Lose a host mid-window under fsdp: resume from the last durable
    window on the survivor mesh, replay accounting exact, and the final
    params match an uninterrupted single-chip run to float tolerance
    (fsdp re-shards over the new axis size; no bitwise claim)."""
    ckpt = str(tmp_path / "ckpts")
    data = _batches(16)
    sgd = lambda: optax.sgd(0.1)

    _, ra = _run(
        8, dp="fsdp", steps=16, batches=data[:10],
        ckpt=ckpt, checkpoint_every=4, optimizer=sgd(),
    )
    assert ra.steps_completed == 10
    assert ra.replayed_steps == 0

    import orbax.checkpoint as ocp

    step10 = os.path.join(os.path.abspath(ckpt), "10")
    assert os.path.isdir(step10)
    shutil.rmtree(step10)
    assert ocp.CheckpointManager(ckpt).latest_step() == 8

    pb, rb = _run(
        4, dp="fsdp", steps=16, batches=data[8:],
        ckpt=ckpt, checkpoint_every=4, optimizer=sgd(),
    )
    assert rb.resumed_from_step == 8
    assert rb.steps_completed == 16
    assert rb.replayed_steps == 2
    executed = ra.steps_completed + (rb.steps_completed - rb.resumed_from_step)
    assert executed - rb.replayed_steps == 16

    pc, rc = _run(1, dp=None, steps=16, batches=data, optimizer=sgd())
    assert rc.steps_completed == 16
    for a, b in zip(_np_leaves(pb), _np_leaves(pc)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------- capability errors


def test_fsdp_capability_errors():
    """fsdp refusals are capability-accurate: a foreign mesh axis in the
    partition names the data-axis-only contract, an indivisible rule
    surfaces the validate_partition findings BEFORE compilation, and
    batch_partition points back at the implicit mode."""
    with pytest.raises(ValueError, match="'data' axis"):
        _run(8, dp="fsdp", param_partition={
            "layers": {f"{k}_{i}": P() for i in range(LAYERS)
                       for k in ("w", "b")} | {"w_0": P("model")},
            "head": P(),
        })
    with pytest.raises(ValueError, match="not divisible"):
        _run(8, dp="fsdp", param_partition={
            "layers": {f"{k}_{i}": P() for i in range(LAYERS)
                       for k in ("w", "b")},
            "head": P(None, "data"),  # head dim 1 cannot shard 8 ways
        })
    with pytest.raises(ValueError, match="implicit"):
        _run(8, dp="fsdp", batch_partition={"x": P("data", "seq")})


# ------------------------------------------------------- ring wiring


def _seq_mesh(n_seq):
    devs = np.array(jax.devices()[:n_seq]).reshape(1, 1, n_seq, 1, 1)
    return Mesh(devs, ("data", "model", "seq", "expert", "pipe"))


def test_attn_auto_routes_ring_on_seq_mesh(monkeypatch):
    """choose_attn_impl step 0: a populated seq axis routes long-context
    self-attention to ring; short sequences, cross-attention, and
    seq-axis-free meshes keep the measured dense/flash rule.  The floor
    is env-tunable."""
    from tpu_pipelines.models.transformer import RING_MIN_SEQ, choose_attn_impl

    mesh = _seq_mesh(8)
    assert choose_attn_impl(8, 12, RING_MIN_SEQ, RING_MIN_SEQ, mesh=mesh) == "ring"
    assert choose_attn_impl(8, 12, 128, 128, mesh=mesh) != "ring"
    # Cross-attention (seq_q != seq_kv) never rings.
    assert choose_attn_impl(8, 12, 4096, 1024, mesh=mesh) != "ring"
    # No populated seq axis -> the gate never fires.
    assert choose_attn_impl(8, 12, 4096, 4096, mesh=_mesh(8)) != "ring"
    monkeypatch.setenv("TPP_RING_MIN_SEQ", "64")
    assert choose_attn_impl(8, 12, 128, 128, mesh=mesh) == "ring"


def test_long_context_batch_partition_selects_token_features():
    """The helper shards token-shaped features over (data, seq) for the
    infeed, leaves per-example scalars on the default layout, and no-ops
    on a seq-free mesh."""
    from tpu_pipelines.parallel.ring_attention import (
        long_context_batch_partition,
    )

    batch = {
        "tokens": np.zeros((8, 4096), np.int32),
        "mask": np.zeros((8, 4096), np.float32),
        "labels": np.zeros((8,), np.int32),
        "short": np.zeros((8, 3), np.float32),  # dim 1 < seq axis
    }
    bp = long_context_batch_partition(batch, _seq_mesh(8))
    assert bp == {"tokens": P("data", "seq"), "mask": P("data", "seq")}
    assert long_context_batch_partition(batch, _mesh(8)) == {}


def test_ring_window_end_to_end_with_sequence_sharded_infeed():
    """Ring attention inside the windowed train step: inputs staged
    pre-sharded over (data, seq) via long_context_batch_partition, the
    loss runs ring_attention over the populated seq axis, and the run
    matches a dense-attention replica of the same model."""
    from tpu_pipelines.parallel.ring_attention import (
        dense_attention,
        long_context_batch_partition,
        ring_attention,
    )

    devs = np.array(jax.devices()[:8]).reshape(2, 1, 4, 1, 1)
    mesh = Mesh(devs, ("data", "model", "seq", "expert", "pipe"))
    B, S, H, Dh = 4, 32, 2, 4

    def batches(n):
        r = np.random.default_rng(5)
        return [
            {
                "x": r.normal(size=(B, S, H * Dh)).astype(np.float32),
                "y": r.normal(size=(B, S, 1)).astype(np.float32),
            }
            for _ in range(n)
        ]

    def init_fn(rng, b):
        r = np.random.default_rng(11)
        return {
            "qkv": jnp.asarray(
                r.normal(size=(H * Dh, 3 * H * Dh)).astype(np.float32) * 0.2
            ),
            "out": jnp.asarray(
                r.normal(size=(H * Dh, 1)).astype(np.float32) * 0.2
            ),
        }

    def make_loss(attn):
        def loss_fn(params, b, rng):
            qkv = b["x"] @ params["qkv"]
            q, k, v = [
                t.reshape(*t.shape[:2], H, Dh)
                for t in jnp.split(qkv, 3, axis=-1)
            ]
            o = attn(q, k, v).reshape(*q.shape[:2], H * Dh)
            pred = o @ params["out"]
            return jnp.mean((pred - b["y"]) ** 2), {}
        return loss_fn

    bp = long_context_batch_partition(batches(1)[0], mesh)
    assert bp == {"x": P("data", "seq"), "y": P("data", "seq")}

    def run(attn, bp):
        return train_loop(
            loss_fn=make_loss(attn),
            init_params_fn=init_fn,
            optimizer=optax.adam(0.05),
            train_iter=iter(batches(4)),
            config=TrainLoopConfig(
                train_steps=4, batch_size=B, log_every=0, window_steps=2,
                prng_impl=None, batch_partition=bp,
            ),
            mesh=mesh,
        )

    p_ring, r_ring = run(
        lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=True), bp
    )
    p_dense, _ = run(
        lambda q, k, v: dense_attention(q, k, v, causal=True), {}
    )
    assert r_ring.steps_completed == 4
    for a, b in zip(_np_leaves(p_ring), _np_leaves(p_dense)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
