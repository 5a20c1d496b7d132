"""The ``train_loop`` driver run end to end at a tiny size on the CPU
(``--rehearse``), its lower-precision control, and a run with its timed path
broken.  Nothing here is a device number: these runs check paths, arguments,
the result line and that ``correct`` can come out false.
"""

import numpy as np
import pytest

from benchmark import harness
from rehearsal import check_contracts_line, read_result, run_cell


@pytest.mark.parametrize("workload,trace", [
    ("tiny-bert.finetune", 0),
    ("tiny-bert.finetune-dp4", 1),     # a train_loop block: mesh, exchange
    ("tiny-bert.finetune-ckpt", 0),    # a train_loop block: a save per 4 steps
])
def test_rehearsal_ends_in_the_contracts_line(capsys, workload, trace):
    check_contracts_line(capsys, workload, trace)


@pytest.mark.parametrize("block", [
    {"window_steps": 2, "checkpoint_evry": 4},   # not a field
    {"window_steps": 2, "batch_size": 16},       # the driver's own
    {"prng_impl": "rbg"},                        # no window_steps
])
def test_a_train_loop_block_is_checked_against_the_dataclass(
        capsys, monkeypatch, block):
    from benchmark import traffic

    load = traffic.load
    monkeypatch.setattr(
        traffic, "load", lambda path: dict(load(path), train_loop=block))
    with pytest.raises(KeyError, match="train_loop"):
        run_cell(capsys, "tiny-bert.finetune", "--rehearse", "--trace", "0")


def test_no_chip_is_an_error_and_prints_noread_result(capsys):
    code, out = run_cell(capsys, "tiny-bert.finetune", "--trace", "0")
    assert code != 0
    assert out.strip() == ""


def test_train_control_in_lower_precision_is_not_correct(capsys):
    """The reference computed in fp8, put in the program's place, fails a
    limit of the cell; the program's own run passes them."""
    code, out = run_cell(
        capsys, "tiny-bert.finetune", "--rehearse", "--control",
        "--trace", "0", seed=2 ** 31 + 5)
    assert code == 0 and read_result(out)["correct"] is True
    assert "control[fp8] correct: False" in out, out


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    import optax

    monkeypatch.setattr(
        optax, "apply_updates", lambda params, updates: params)
    code, out = run_cell(
        capsys, "tiny-bert.finetune", "--rehearse", "--trace", "0")
    assert code == 0
    assert read_result(out)["correct"] is False
    assert "change_norm_gap.worst_leaf" in out


def test_checks_need_every_number_inside_its_limit(capsys):
    checks = harness.Checks()
    assert checks.ok is False           # nothing compared is not correct
    checks.at_most("a", 0.1, 0.2)
    assert checks.ok is True
    checks.at_most("b", float("nan"), 0.2)
    assert checks.ok is False
    capsys.readouterr()


def _masks(seed, step):
    from benchmark.reference import bert

    spec = {"rate": 0.1, "train_seed": seed, "prng_impl": "rbg"}
    return bert.dropout_masks(spec, step, 4, 8, 16, 2)


def test_reference_masks_are_a_function_of_seed_step_and_site():
    a, b = _masks(7, 0), _masks(7, 0)
    assert set(a) == {"drop.embed", "drop.attn", "drop.mlp", "drop.pooled"}
    assert a["drop.attn"].shape == (4, 2, 8, 16)      # batch axis first
    assert a["drop.pooled"].shape == (4, 16)
    for k in a:
        assert np.array_equal(a[k], b[k]), k
        assert 0.8 < float(np.mean(a[k])) < 0.97, k   # keeps about 90 %
    other_step, other_seed = _masks(7, 1), _masks(8, 0)
    for k in ("drop.embed", "drop.attn", "drop.mlp"):
        assert not np.array_equal(a[k], other_step[k]), k
        assert not np.array_equal(a[k], other_seed[k]), k
    assert not np.array_equal(a["drop.attn"], a["drop.mlp"])
    assert not np.array_equal(a["drop.attn"][:, 0], a["drop.attn"][:, 1])


def test_a_step_under_other_masks_than_the_references_is_not_correct(
        capsys, monkeypatch):
    """Dropout is in the timed step and in the comparison: masks drawn
    from another key than the program's put the first gradient far out."""
    from benchmark.reference import bert

    masks = bert.dropout_masks
    monkeypatch.setattr(
        bert, "dropout_masks",
        lambda spec, *a: masks(
            dict(spec, train_seed=spec["train_seed"] + 1), *a))
    code, out = run_cell(
        capsys, "tiny-bert.finetune", "--rehearse", "--trace", "0")
    assert code == 0
    assert read_result(out)["correct"] is False
    assert "first_grad_error.rms_leaf" in out
