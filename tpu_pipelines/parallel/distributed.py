"""Multi-host bootstrap: the TF_CONFIG / TFJob-operator equivalent.

The reference forms its worker mesh from ``TF_CONFIG`` injected by the
training operator (SURVEY.md §2b TFJob row, §5 comm backend).  The TPU-native
equivalent is JAX's coordination service: every process calls
``jax.distributed.initialize(coordinator, num_processes, process_id)`` and
XLA then sees one global device set; collectives ride ICI within a host's
slice and DCN across hosts — no NCCL, no user-level comms library.

The cluster runner (orchestration/cluster_runner.py) injects the TPP_* env
vars below into each JobSet worker pod; ``maybe_initialize_from_env`` is
called by the node entrypoint before any JAX computation.  Locally, tests
spawn N subprocesses with the same env vars over localhost (gloo CPU
collectives) — multi-host semantics without a cluster (SURVEY.md §4).
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional

log = logging.getLogger("tpu_pipelines.distributed")

ENV_COORDINATOR = "TPP_COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "TPP_NUM_PROCESSES"
ENV_PROCESS_ID = "TPP_PROCESS_ID"
# JobSet injects the worker index here; used when TPP_PROCESS_ID is absent.
ENV_JOB_COMPLETION_INDEX = "JOB_COMPLETION_INDEX"
DEFAULT_PORT = 8476


@dataclasses.dataclass
class DistributedConfig:
    coordinator_address: str
    num_processes: int
    process_id: int

    @classmethod
    def from_env(cls, env=os.environ) -> Optional["DistributedConfig"]:
        """None when the env describes a single-process run."""
        n = int(env.get(ENV_NUM_PROCESSES, "1"))
        if n <= 1:
            return None
        coordinator = env.get(ENV_COORDINATOR, "")
        if not coordinator:
            raise ValueError(
                f"{ENV_NUM_PROCESSES}={n} but {ENV_COORDINATOR} is unset"
            )
        pid_s = env.get(ENV_PROCESS_ID, env.get(ENV_JOB_COMPLETION_INDEX))
        if pid_s is None:
            raise ValueError(
                f"{ENV_NUM_PROCESSES}={n} but neither {ENV_PROCESS_ID} nor "
                f"{ENV_JOB_COMPLETION_INDEX} is set"
            )
        return cls(coordinator, n, int(pid_s))

    def env_vars(self) -> dict:
        return {
            ENV_COORDINATOR: self.coordinator_address,
            ENV_NUM_PROCESSES: str(self.num_processes),
            ENV_PROCESS_ID: str(self.process_id),
        }


def survivor_configs(
    num_processes: int,
    lost_process_ids,
    coordinator_address: str = "",
) -> list:
    """Re-form the process topology after losing hosts: the elastic-resume
    bootstrap (docs/TUTORIAL.md §7).

    jax's coordination service cannot shrink in place — the driver
    restarts the job on the survivors with a re-derived topology.  This is
    that derivation: survivors keep their RELATIVE order but are
    re-indexed densely 0..n-1 (process 0 duties — metadata writes,
    TensorBoard — fall to the lowest surviving rank), and the coordinator
    moves to the new process 0's address unless one is passed explicitly.
    Each surviving worker then resumes from the last durable window with
    a per-host shard assignment re-derived from the NEW (index, count)
    (``per_host_input_config`` / ``assigned_shard_files``), so the
    surviving hosts cover the whole dataset again with no overlap.

    Returns ``[(old_process_id, DistributedConfig), ...]`` in new-rank
    order; raises when nothing survives.
    """
    lost = {int(p) for p in lost_process_ids}
    bad = lost - set(range(num_processes))
    if bad:
        raise ValueError(
            f"lost process ids {sorted(bad)} not in 0..{num_processes - 1}"
        )
    survivors = [p for p in range(num_processes) if p not in lost]
    if not survivors:
        raise ValueError(
            f"all {num_processes} processes lost: nothing to re-form"
        )
    return [
        (
            old_id,
            DistributedConfig(
                coordinator_address=coordinator_address,
                num_processes=len(survivors),
                process_id=new_id,
            ),
        )
        for new_id, old_id in enumerate(survivors)
    ]


def local_process_id(env=os.environ) -> int:
    """This host's process id in a multi-host run; 0 for single-process.

    Reads only the TPP_*/JobSet env vars — safe to call from code that must
    not import jax (e.g. the metadata-plane parts of the local runner).
    """
    cfg = DistributedConfig.from_env(env)
    return 0 if cfg is None else cfg.process_id


def maybe_initialize_from_env(
    *, cpu_devices_per_process: int = 0, env=os.environ
) -> Optional[DistributedConfig]:
    """Join the coordination service if the env asks for it; else no-op.

    Must run before any JAX backend is touched.  ``cpu_devices_per_process``
    > 0 switches to the CPU/gloo simulation path (tests, dry runs): each
    process contributes that many virtual CPU devices to the global mesh.
    """
    cfg = DistributedConfig.from_env(env)
    if cfg is None:
        return None
    import jax

    if cpu_devices_per_process:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.config.update("jax_num_cpu_devices", cpu_devices_per_process)
    jax.distributed.initialize(
        coordinator_address=cfg.coordinator_address,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
    )
    if jax.process_count() != cfg.num_processes:
        raise RuntimeError(
            f"distributed init: expected {cfg.num_processes} processes, "
            f"backend reports {jax.process_count()}"
        )
    log.info(
        "joined coordination service %s as process %d/%d; %d global devices",
        cfg.coordinator_address, cfg.process_id, cfg.num_processes,
        len(jax.devices()),
    )
    return cfg
