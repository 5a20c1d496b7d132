"""One process per chip (utils/chip.py): a process that holds an accelerator
neither forks a pool nor starts a child that would need the device."""

import pytest

from tpu_pipelines.utils.chip import held_accelerator


def _square(x):
    return x * x


def test_cpu_backend_is_not_a_held_accelerator():
    import jax

    jax.devices()  # backend initialised — and it is the CPU
    assert held_accelerator() is None


def test_map_shards_rides_threads_once_the_chip_is_held(monkeypatch):
    from tpu_pipelines.data import shard_plan

    monkeypatch.delenv(shard_plan.ENV_POOL, raising=False)
    free = shard_plan.map_shards_resilient(_square, [1, 2, 3], workers=2)
    assert free.pool == "process" and free.results == [1, 4, 9]

    monkeypatch.setattr(shard_plan, "held_accelerator", lambda: "tpu")
    held = shard_plan.map_shards_resilient(_square, [1, 2, 3], workers=2)
    assert held.pool == "thread" and held.results == [1, 4, 9]
    assert held.pool_replacements == 0


def test_tuner_subprocess_modes_refuse_under_a_held_chip(monkeypatch):
    from tpu_pipelines.components import tuner

    monkeypatch.setattr(tuner, "held_accelerator", lambda: "tpu")
    with pytest.raises(RuntimeError, match="chip belongs to one process"):
        tuner._run_trial_subprocess(0, {"lr": 0.1}, "unused.py", None)
