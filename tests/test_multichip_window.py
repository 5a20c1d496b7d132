"""Multi-chip windowed training (ISSUE 15) on the virtual 8-device mesh.

The PR 8 device-resident window, threaded through data-parallel multi-chip
execution.  Contracts pinned here:

  * trajectory identity — with ``dp_collective="ordered"`` (fixed global
    gradient blocks, all-gathered and summed in block order) the windowed
    multi-chip run reproduces the single-chip param trajectory BITWISE at
    equal global batch: the reduction structure is chosen independently of
    the mesh, so the data-axis size cannot perturb the math;
  * collective placement — with ``dp_collective="psum_bucketed"`` the
    compiled window HLO carries the gradient all-reduce INSIDE the scan's
    while body with the backward compute, instead of one collective
    serialized at the window boundary (bucket separation at a real size:
    tests/test_tpu_compile.py);
  * elastic resume — losing a host mid-window resumes from the last
    durable window on the survivor mesh, stays on the same (ordered-mode)
    trajectory, and reports the replayed span so no example is counted as
    fresh progress twice;
  * per-host infeed — ``per_host_input_config`` +
    ``assigned_shard_files`` give every simulated host a disjoint,
    complete shard of the split, re-derivable after a host is lost;
  * short-tail padding — ``shard_batch`` pads indivisible batches to the
    data axis with a validity mask; divisible batches take the exact
    pre-padding path (no mask, bitwise-identical placement).
"""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_pipelines.parallel.mesh import (
    VALID_MASK_KEY,
    MeshConfig,
    make_mesh,
    masked_mean,
    shard_batch,
)
from tpu_pipelines.trainer import TrainLoopConfig, train_loop

pytestmark = pytest.mark.multichip

BATCH = 64
G = 8  # fixed global gradient-block count, shared by every mesh size


def _mesh(n_devices: int):
    return make_mesh(MeshConfig(), devices=jax.devices()[:n_devices])


def _batches(n, batch=BATCH, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.normal(size=(batch, 4)).astype(np.float32)
        y = (x @ np.array([3.0, -2.0, 1.0, 0.5], np.float32) + 1.0).astype(
            np.float32
        )
        out.append({"x": x, "y": y})
    return out


def _loss_fn(params, b, rng):
    pred = jnp.tanh(b["x"] @ params["w1"]) @ params["w2"]
    loss = jnp.mean((pred - b["y"]) ** 2)
    return loss, {"w_norm": jnp.sum(params["w1"] ** 2)}


def _init_fn(rng, b):
    r = np.random.default_rng(7)
    return {
        "w1": jnp.asarray(r.normal(size=(4, 8)).astype(np.float32) * 0.3),
        "w2": jnp.asarray(r.normal(size=(8, 1)).astype(np.float32) * 0.3),
    }


def _run(n_devices, *, dp="ordered", steps=16, window=4, log_every=4,
         batches=None, ckpt="", checkpoint_every=0, buckets=2):
    hist = []
    params, result = train_loop(
        loss_fn=_loss_fn,
        init_params_fn=_init_fn,
        optimizer=optax.adam(0.05),
        train_iter=iter(batches if batches is not None else _batches(steps)),
        config=TrainLoopConfig(
            train_steps=steps, batch_size=BATCH, log_every=log_every,
            window_steps=window, prng_impl=None,
            dp_collective=dp, dp_grad_blocks=G, collective_buckets=buckets,
            checkpoint_every=checkpoint_every,
        ),
        mesh=_mesh(n_devices),
        checkpoint_dir=ckpt,
        metrics_cb=lambda s, m: hist.append((s, m["loss"], m["w_norm"])),
    )
    return params, result, hist


def _leaves_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


# ------------------------------------------------- trajectory identity


def test_windowed_multichip_matches_single_chip_bitwise():
    """Ordered mode: 8-device windowed == 1-device windowed, bitwise, at
    equal global batch — params AND the reconstructed per-step loss
    series.  The fixed block count (not the mesh) owns the reduction
    order, so the data-axis size cannot perturb a single ulp."""
    p8, r8, h8 = _run(8)
    p1, r1, h1 = _run(1)
    assert r8.steps_completed == r1.steps_completed == 16
    assert r8.dp_collective == r1.dp_collective == "ordered"
    assert _leaves_equal(p8, p1)
    assert h8 == h1 and len(h8) == 4
    assert r8.final_metrics == r1.final_metrics

    # A mid-size survivor mesh sits on the same trajectory too.
    p4, _, h4 = _run(4)
    assert _leaves_equal(p8, p4)
    assert h4 == h8


def test_windowed_equals_per_step_on_the_mesh():
    """The window is a pure dispatch optimization on the mesh as well:
    same step_fn scanned, so window 4 == window 1 bitwise (the PR 8
    contract, now under the explicit multi-chip collective)."""
    pw, _, hw = _run(8, window=4)
    pp, _, hp = _run(8, window=1)
    assert _leaves_equal(pw, pp)
    assert hw == hp


def test_psum_bucketed_runs_close_to_ordered():
    """The perf-path collective (chunked psum) matches ordered mode to
    float tolerance (same math, different reduction order) and records
    its mode on the result."""
    po, _, _ = _run(8, dp="ordered")
    pb, rb, _ = _run(8, dp="psum_bucketed")
    assert rb.dp_collective == "psum_bucketed"
    for a, b in zip(
        jax.tree_util.tree_leaves(po), jax.tree_util.tree_leaves(pb)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


# ------------------------------------------------- collective overlap


def _hlo_computations(text: str):
    """Split HLO text into (header, body) computation blocks."""
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


def test_collective_overlap_hlo_bucketed_inside_scan_body():
    """Compiled evidence that psum_bucketed's gradient exchange lives
    INSIDE the scan's while-body, in the same computation as the
    backward's dots — not hoisted to the window boundary.  This toy's
    gradients are a few hundred bytes, and the compiler merges so few
    bytes into ONE all-reduce (the installed CPU compiler and the TPU
    compiler both do); that the buckets stay separate and interleaved
    with the backward at a real size is asserted on the TPU compiler's
    output for BERT-base in tests/test_tpu_compile.py."""
    from tpu_pipelines.trainer.train_loop import _make_dp_forward_backward

    mesh = _mesh(8)
    fb = _make_dp_forward_backward(
        _loss_fn, mesh, "psum_bucketed", buckets=2, grad_blocks=8
    )
    opt = optax.adam(0.05)
    params = _init_fn(None, None)

    def step(carry, batch):
        params, opt_state = carry
        loss, _metrics, grads, _ = fb(
            params, None, batch, jax.random.key(0)
        )
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), loss

    from jax.sharding import NamedSharding, PartitionSpec as P

    bshard = {
        k: NamedSharding(mesh, P(None, "data"))
        for k in ("x", "y")
    }
    stack_host = {
        k: np.stack([b[k] for b in _batches(4)]) for k in ("x", "y")
    }
    stack = {k: jax.device_put(v, bshard[k]) for k, v in stack_host.items()}
    win = jax.jit(
        lambda c, b: jax.lax.scan(step, c, b), in_shardings=(None, bshard)
    )
    text = win.lower((params, opt.init(params)), stack).compile().as_text()

    assert "while(" in text or "while (" in text, "scan must compile to while"
    blocks = _hlo_computations(text)
    with_collectives = [(h, b) for h, b in blocks if "all-reduce(" in b]
    assert with_collectives, "no all-reduce in the compiled window"
    # Every all-reduce shares its computation with backward compute
    # (dots): the exchange is part of the step, inside the loop.
    assert all("dot(" in b for _, b in with_collectives), text[:2000]
    # ...and that computation is a while body, not the entry computation.
    entry = [b for h, b in blocks if h.lstrip().startswith("ENTRY")]
    assert entry and all("all-reduce(" not in b for b in entry)
    # Both gradient leaves and the loss went through the exchange (merged
    # or not): the all-reduce operands carry w1, w2 and a scalar.
    operands = " ".join(
        line for _, b in with_collectives for line in b.splitlines()
        if "all-reduce(" in line
    )
    for shape in ("f32[4,8]", "f32[8,1]", "f32[]"):
        assert shape in operands, (shape, operands[:500])


# ------------------------------------------------- elastic resume


def test_elastic_resume_mid_window_no_double_count(tmp_path):
    """Lose a host mid-window: resume on the survivor mesh from the last
    durable window, land bitwise on the uninterrupted single-chip
    trajectory, and report the replayed span so goodput accounting never
    counts a replayed example as fresh progress."""
    ckpt = str(tmp_path / "ckpts")
    data = _batches(16)

    # Run A on the full 8-device mesh; the input dies at step 10, two
    # steps into the third window (durable checkpoints at 4 and 8).
    _, ra, _ = _run(
        8, batches=data[:10], ckpt=ckpt, checkpoint_every=4, log_every=0,
    )
    assert ra.steps_completed == 10
    assert ra.replayed_steps == 0

    import orbax.checkpoint as ocp

    # The loop's exit path fenced a final save at step 10; the simulated
    # KILL means that save never became durable (orbax step dirs are
    # atomic — an interrupted save leaves nothing).  Drop it to recreate
    # the killed host's on-disk state: durable windows end at step 8,
    # executed progress (the window_progress marker) reads 10.
    step10 = os.path.join(os.path.abspath(ckpt), "10")
    assert os.path.isdir(step10)
    shutil.rmtree(step10)
    assert ocp.CheckpointManager(ckpt).latest_step() == 8

    # Run B re-forms the mesh with the 4 surviving devices and resumes.
    # Same global batch, same fixed block count: ordered mode keeps the
    # survivor mesh on the exact trajectory.
    pb, rb, _ = _run(
        4, batches=data[8:], ckpt=ckpt, checkpoint_every=4, log_every=0,
    )
    assert rb.resumed_from_step == 8
    assert rb.steps_completed == 16
    # The replayed span: steps 9..10 executed before the kill, lost with
    # the non-durable window, re-executed after resume.
    assert rb.replayed_steps == 2

    # No double counting: unique steps == 16.  Run A executed 1..10, run
    # B executed 9..16; the overlap is exactly the reported replay.
    executed = ra.steps_completed + (rb.steps_completed - rb.resumed_from_step)
    assert executed - rb.replayed_steps == 16

    # Bitwise identity with an uninterrupted single-chip run at equal
    # global batch: run A's 8-device prefix + run B's 4-device suffix land
    # exactly where one chip would have.
    assert ocp.CheckpointManager(ckpt).latest_step() == 16
    pc, rc, _ = _run(1, batches=data, log_every=0)
    assert rc.steps_completed == 16
    assert _leaves_equal(pb, pc)


# ------------------------------------------------- per-host infeed


def test_per_host_infeed_disjoint_complete_and_rederivable(tmp_path):
    """Each simulated host reads a disjoint shard of the split via whole
    shard files; the union is the split; and after losing a host the
    assignment re-derives to full coverage for the survivors."""
    from tpu_pipelines.data import examples_io
    from tpu_pipelines.data.input_pipeline import (
        BatchIterator,
        InputConfig,
        assigned_shard_files,
        per_host_input_config,
    )

    import pyarrow as pa

    uri = str(tmp_path / "examples")
    n_rows = 64
    rows = pa.table({
        "row": np.arange(n_rows, dtype=np.int64),
        "x": np.random.default_rng(0).normal(size=n_rows).astype(np.float32),
    })
    examples_io.write_split(uri, "train", rows, num_shards=4)
    shard_rows = examples_io.shard_row_counts(uri, "train")
    assert len(shard_rows) == 4

    base = InputConfig(
        batch_size=8, shuffle=False, num_epochs=1, drop_remainder=False
    )

    def host_rows(index, count):
        cfg = per_host_input_config(
            base, process_index=index, process_count=count
        )
        it = BatchIterator(uri, "train", cfg)
        return [int(r) for b in it for r in b["row"]], cfg

    rows0, cfg0 = host_rows(0, 2)
    rows1, cfg1 = host_rows(1, 2)
    # File-granular: whole shard files, no host decodes dropped rows.
    assert assigned_shard_files(shard_rows, cfg0) == [0, 2]
    assert assigned_shard_files(shard_rows, cfg1) == [1, 3]
    assert set(rows0) & set(rows1) == set()
    assert sorted(rows0 + rows1) == list(range(n_rows))

    # Host 1 dies: the surviving host re-derives to the full split.
    survivor_rows, cfg_s = host_rows(0, 1)
    assert cfg_s.num_shards == 1  # helper no-ops at one process
    assert sorted(survivor_rows) == list(range(n_rows))

    # An explicitly-sharded config is the caller's business: unchanged.
    pinned = InputConfig(batch_size=8, shard_index=1, num_shards=3)
    assert per_host_input_config(
        pinned, process_index=0, process_count=2
    ) is pinned


def test_survivor_topology_rederives_full_coverage(tmp_path):
    """Losing hosts re-forms the process topology densely (relative order
    kept, process-0 duties to the lowest survivor) and the re-derived
    per-host assignments cover every shard file again, disjointly."""
    from tpu_pipelines.data.input_pipeline import (
        InputConfig,
        assigned_shard_files,
        per_host_input_config,
    )
    from tpu_pipelines.parallel.distributed import survivor_configs

    remapped = survivor_configs(4, lost_process_ids=[1])
    assert [(old, cfg.process_id, cfg.num_processes)
            for old, cfg in remapped] == [(0, 0, 3), (2, 1, 3), (3, 2, 3)]

    # Re-derived shard assignment over 6 shard files: disjoint + complete
    # across the three survivors.
    shard_rows = [10] * 6
    base = InputConfig(batch_size=2)
    taken = []
    for _old, cfg in remapped:
        icfg = per_host_input_config(
            base, process_index=cfg.process_id,
            process_count=cfg.num_processes,
        )
        taken.append(assigned_shard_files(shard_rows, icfg))
    flat = [i for files in taken for i in files]
    assert sorted(flat) == list(range(6))
    assert len(set(flat)) == len(flat)

    with pytest.raises(ValueError, match="nothing to re-form"):
        survivor_configs(2, lost_process_ids=[0, 1])
    with pytest.raises(ValueError, match="not in 0"):
        survivor_configs(2, lost_process_ids=[5])


# ------------------------------------------------- short-tail padding


def test_shard_batch_pads_tail_with_mask():
    mesh = _mesh(8)

    # Divisible batch: the exact pre-padding path — no mask key, values
    # round-trip bitwise.
    full = {"x": np.arange(32, dtype=np.float32).reshape(16, 2),
            "y": np.ones(16, np.float32)}
    placed = shard_batch(full, mesh)
    assert VALID_MASK_KEY not in placed
    assert np.array_equal(np.asarray(placed["x"]), full["x"])

    # Indivisible tail: padded up to the data axis with a validity mask.
    tail = {"x": np.arange(24, dtype=np.float32).reshape(12, 2),
            "y": np.ones(12, np.float32)}
    padded = shard_batch(tail, mesh)
    assert VALID_MASK_KEY in padded
    mask = np.asarray(padded[VALID_MASK_KEY])
    assert padded["x"].shape[0] == 16 and mask.shape == (16,)
    assert mask[:12].all() and not mask[12:].any()
    assert np.array_equal(np.asarray(padded["x"])[:12], tail["x"])
    assert not np.asarray(padded["x"])[12:].any()

    # Loss/metrics ignore padded rows: weighting per-row values by the
    # mask equals the unpadded computation.
    per_row = np.asarray(padded["x"]).sum(axis=1)
    want = float(np.mean(tail["x"].sum(axis=1)))
    got = float(masked_mean(jnp.asarray(per_row), jnp.asarray(mask)))
    np.testing.assert_allclose(got, want, rtol=1e-6)

    # No mask (the divisible case) is literally jnp.mean — bitwise.
    vals = jnp.asarray(np.random.default_rng(1).normal(size=(16,)).astype(
        np.float32
    ))
    assert np.array_equal(
        np.asarray(masked_mean(vals)), np.asarray(jnp.mean(vals))
    )


# ------------------------------------------------- config plumbing


def test_dp_collective_validation_and_env(monkeypatch):
    from tpu_pipelines.trainer.train_loop import _effective_dp_collective

    def run_cfg(**kw):
        return train_loop(
            loss_fn=_loss_fn,
            init_params_fn=_init_fn,
            optimizer=optax.adam(0.05),
            train_iter=iter(_batches(4)),
            config=TrainLoopConfig(
                train_steps=4, batch_size=BATCH, log_every=0,
                window_steps=2, prng_impl=None, **kw,
            ),
            mesh=_mesh(8),
        )

    with pytest.raises(ValueError, match="expected one of"):
        run_cfg(dp_collective="ring")
    with pytest.raises(ValueError, match="dp_grad_blocks"):
        run_cfg(dp_collective="ordered", dp_grad_blocks=5)
    # Capability-accurate routing (ISSUE 18): features that used to be a
    # blanket refusal now either compose or name the mode that serves them.
    _, result = run_cfg(dp_collective="ordered", grad_accum_steps=2)
    assert result.steps_completed == 4  # grad_accum composes with all modes
    from jax.sharding import PartitionSpec as P

    with pytest.raises(ValueError, match="fsdp"):
        run_cfg(
            dp_collective="ordered",
            param_partition={"w1": P("data"), "w2": P()},
        )
    with pytest.raises(ValueError, match="implicit"):
        run_cfg(
            dp_collective="psum_bucketed",
            batch_partition={"x": P("data", "seq")},
        )

    # Env rung: TPP_DP_COLLECTIVE applies when config leaves it unset...
    monkeypatch.setenv("TPP_DP_COLLECTIVE", "ordered")
    assert _effective_dp_collective(TrainLoopConfig(train_steps=1)) == "ordered"
    # ...and explicit config (incl. "auto" = implicit GSPMD) wins.
    assert _effective_dp_collective(
        TrainLoopConfig(train_steps=1, dp_collective="auto")
    ) == ""
    monkeypatch.delenv("TPP_DP_COLLECTIVE")
    _, result = run_cfg(dp_collective="ordered")
    assert result.dp_collective == "ordered"
    assert result.steps_completed == 4
