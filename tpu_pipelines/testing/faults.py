"""Fault-injection harness: prove the runner's failure semantics.

PR 1 *claims* fail-fast drain, no orphans, clean retry slates; the resume
layer claims crash-safe adoption and fencing.  This module makes those
claims testable by injecting the exact failure modes a preemptible TPU
fleet produces, at the exact runner phase where they occur:

  ==================== =====================================================
  kind                 fires at
  ==================== =====================================================
  RAISE                inside the executor attempt (transient executor bug)
  HANG                 inside the executor attempt; blocks on the runner's
                       cancel event (stuck ``urlopen``, deadlocked
                       collective) — released by the deadline watchdog, so
                       a hang test leaves no orphan thread behind
  CRASH_BEFORE_PUBLISH after the executor succeeded, before the publisher's
                       store write (RUNNING execution + written payload
                       dirs left behind — the state a resume must fence)
  CRASH_AFTER_PUBLISH  right after the COMPLETE publish landed (the state a
                       resume must adopt as-is)
  KILL_ORCHESTRATOR    at node dispatch, in the scheduler thread (pod
                       eviction / OOM / Ctrl-C mid-run)
  TRANSIENT_EXECUTOR_  inside the executor attempt: an explicitly-
  ERROR                classified TransientError, ``times`` times, then
                       clean — the classified-retry-with-backoff bait
  STORE_CONTENTION     inside a store write transaction (key
                       ``STORE_KEY``): transient StoreUnavailableError,
                       ``times`` times — multi-writer SQLITE_BUSY shape
  ==================== =====================================================

The crash kinds raise :class:`SimulatedCrash` — a ``BaseException`` so no
``except Exception`` along the way can swallow it, mimicking a process
death: the metadata store is left exactly as a SIGKILL would leave it
(committed rows only, nothing finalized).  Each fault fires ONCE per plan,
so the node runs clean on resume.

Usage::

    plan = FaultPlan({"Trainer": NodeFault(CRASH_BEFORE_PUBLISH)})
    with plan.activate():
        with pytest.raises(SimulatedCrash):
            LocalDagRunner().run(pipeline)
    LocalDagRunner().run(pipeline, resume_from="latest")

The runner's hook calls cost one module-global read when no plan is
active; production runs never pay more than that.
"""

from __future__ import annotations

import dataclasses
import threading
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

RAISE = "raise"
HANG = "hang"
CRASH_BEFORE_PUBLISH = "crash_before_publish"
CRASH_AFTER_PUBLISH = "crash_after_publish"
KILL_ORCHESTRATOR = "kill_orchestrator"
# Robustness-layer kinds (ISSUE 7): the failure modes the unified
# fault-tolerance layer must absorb rather than surface.
TRANSIENT_EXECUTOR_ERROR = "transient_executor_error"  # classified-retry bait
STORE_CONTENTION = "store_contention"      # transient StoreUnavailableError
# Fleet-supervision kinds (ISSUE 17): the per-replica failure modes the
# ReplicaSupervisor/failover layer must absorb (plan key ``REPLICA_KEY``).
KILL_REPLICA = "kill_replica"      # latched death until rebuild (generation)
WEDGE_PREDICT = "wedge_predict"    # predict parks, queue age grows
DEVICE_ERROR = "device_error"      # transient device fault, `times` times

# Sentinel plan keys for faults that are not tied to a pipeline node.
STORE_KEY = "__store__"
REPLICA_KEY = "__replica__"

# kind -> the runner phase whose hook triggers it.
_KIND_TO_POINT = {
    RAISE: "in_executor",
    HANG: "in_executor",
    TRANSIENT_EXECUTOR_ERROR: "in_executor",
    CRASH_BEFORE_PUBLISH: "before_publish",
    CRASH_AFTER_PUBLISH: "after_publish",
    KILL_ORCHESTRATOR: "at_dispatch",
    STORE_CONTENTION: "store_op",
    KILL_REPLICA: "replica_predict",
    WEDGE_PREDICT: "replica_predict",
    DEVICE_ERROR: "replica_predict",
}


class SimulatedCrash(BaseException):
    """Stand-in for orchestrator/process death at a precise runner phase.

    BaseException on purpose: a real SIGKILL is not catchable, so no
    ``except Exception`` in an executor, worker, or retry loop may
    convert this into an ordinary node failure.
    """

    def __init__(self, node_id: str, point: str):
        super().__init__(f"simulated crash at {point} of node {node_id!r}")
        self.node_id = node_id
        self.point = point


class InjectedFault(RuntimeError):
    """The exception RAISE/HANG faults surface inside the executor."""


@dataclasses.dataclass
class NodeFault:
    kind: str
    message: str = "injected fault"
    # HANG safety ceiling: the hang waits on the runner's cancel event and
    # gives up after this long regardless, so a missing/misconfigured
    # watchdog can never wedge a test run forever.
    max_hang_s: float = 60.0
    # How many times the fault fires before going inert (RAISE /
    # TRANSIENT_EXECUTOR_ERROR / STORE_CONTENTION: fail N attempts, then
    # succeed — the shape a classified retry policy must absorb).
    times: int = 1
    # KILL_REPLICA: fire once the Nth request has arrived (so the hammer
    # is demonstrably in flight when the kill happens).
    after: int = 1
    # Replica-fault targeting (KILL_REPLICA / WEDGE_PREDICT /
    # DEVICE_ERROR): which replica name the fault applies to; "" = the
    # first replica the fault observes (then latched to it).
    replica: str = ""
    # WEDGE_PREDICT release valve: tests set() it to un-wedge early;
    # otherwise the wedge parks for max_hang_s.
    release: threading.Event = dataclasses.field(
        default_factory=threading.Event, compare=False
    )

    def __post_init__(self):
        if self.kind not in _KIND_TO_POINT:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; "
                f"expected one of {sorted(_KIND_TO_POINT)}"
            )
        if self.times < 1:
            raise ValueError(f"times must be >= 1, got {self.times}")


class FaultPlan:
    """Per-node faults, each fired at most once (so resumed runs succeed).

    ``log`` records ``(node_id, event)`` tuples — tests assert on it to
    prove e.g. that a hang was released by the watchdog's cancel event
    rather than by its own safety ceiling (no orphan threads).
    """

    def __init__(self, faults: Dict[str, NodeFault]):
        self.faults = dict(faults)
        self._fired: Dict[str, int] = {}
        self._replica_calls = 0   # replica_predict arrivals (KILL_REPLICA)
        # KILL_REPLICA latch: replica name -> the generation that died.
        # Every call from that (replica, generation) fails; the rebuild
        # bumps the generation, so the rebuilt incarnation runs clean.
        self._killed: Dict[str, int] = {}
        self._lock = threading.Lock()
        self.log: List[Tuple[str, str]] = []

    def _take(self, node_id: str, point: str) -> Optional[NodeFault]:
        """Claim one firing of the fault keyed by ``node_id`` at runner
        phase ``point``; None once its ``times`` budget is spent."""
        fault = self.faults.get(node_id)
        if fault is None or _KIND_TO_POINT[fault.kind] != point:
            return None
        with self._lock:
            fired = self._fired.get(node_id, 0)
            if fired >= fault.times:
                return None
            self._fired[node_id] = fired + 1
        return fault

    def record(self, node_id: str, event: str) -> None:
        with self._lock:
            self.log.append((node_id, event))

    @contextmanager
    def activate(self):
        """Install this plan for the duration of the block (test-only)."""
        global _ACTIVE
        prev = _ACTIVE
        _ACTIVE = self
        try:
            yield self
        finally:
            _ACTIVE = prev


_ACTIVE: Optional[FaultPlan] = None


# ------------------------------------------------------------ runner hooks


def at_dispatch(node_id: str) -> None:
    """Scheduler thread, before the node's driver phase runs."""
    plan = _ACTIVE
    if plan is None:
        return
    fault = plan._take(node_id, "at_dispatch")
    if fault is not None:
        plan.record(node_id, "kill_orchestrator")
        raise SimulatedCrash(node_id, "at_dispatch")


def in_executor(
    node_id: str, cancel_event: Optional[threading.Event]
) -> None:
    """Worker thread, inside the executor attempt (before the real fn)."""
    plan = _ACTIVE
    if plan is None:
        return
    fault = plan._take(node_id, "in_executor")
    if fault is None:
        return
    if fault.kind == TRANSIENT_EXECUTOR_ERROR:
        # Explicitly-classified transient failure: the robustness layer's
        # RetryPolicy must absorb `times` of these and then succeed.
        from tpu_pipelines.robustness.errors import TransientError

        plan.record(node_id, "transient_executor_error")
        raise TransientError(fault.message)
    if fault.kind == RAISE:
        plan.record(node_id, "raise")
        raise InjectedFault(fault.message)
    # HANG: cooperative stuck-executor — parks until the deadline
    # watchdog's cancel event (or the safety ceiling) releases it.
    plan.record(node_id, "hang_start")
    event = cancel_event or threading.Event()
    released = event.wait(fault.max_hang_s)
    plan.record(node_id, "hang_released" if released else "hang_ceiling")
    raise InjectedFault(
        f"{fault.message} (hang "
        f"{'cancelled by watchdog' if released else 'hit safety ceiling'})"
    )


def before_publish(node_id: str) -> None:
    """Worker thread, executor succeeded, publisher not yet written."""
    plan = _ACTIVE
    if plan is None:
        return
    if plan._take(node_id, "before_publish") is not None:
        plan.record(node_id, "crash_before_publish")
        raise SimulatedCrash(node_id, "before_publish")


def after_publish(node_id: str) -> None:
    """Worker thread, COMPLETE publish committed."""
    plan = _ACTIVE
    if plan is None:
        return
    if plan._take(node_id, "after_publish") is not None:
        plan.record(node_id, "crash_after_publish")
        raise SimulatedCrash(node_id, "after_publish")


def store_op(op: str) -> None:
    """Inside a MetadataStore write transaction, before the commit.

    STORE_CONTENTION (plan key ``STORE_KEY``): raises a transient
    ``StoreUnavailableError`` ``times`` times — the shape a contended
    multi-writer store produces (SQLITE_BUSY under N concurrent
    publishers) — which the store-level publish retry must absorb.
    """
    plan = _ACTIVE
    if plan is None:
        return
    fault = plan._take(STORE_KEY, "store_op")
    if fault is None:
        return
    plan.record(STORE_KEY, f"store_contention:{op}")
    from tpu_pipelines.metadata.store import StoreUnavailableError

    raise StoreUnavailableError(fault.message)


def replica_predict(replica_name: str, generation: int = 0) -> None:
    """Per call on a fleet replica's hot paths (batched predict, the
    supervisor heartbeat, the generative engine's worker loop), keyed by
    ``REPLICA_KEY``.

    KILL_REPLICA: after the ``after``-th call fleet-wide, the targeted
    replica's CURRENT generation is latched dead — every subsequent call
    from that (replica, generation) raises, exactly like a device that
    fell off the bus.  A rebuild bumps the generation, so the rebuilt
    incarnation runs clean: the recovery proof needs the death to be
    *persistent until healed*, not a one-shot blip.

    WEDGE_PREDICT: ``times`` calls park on the fault's ``release`` event
    (bounded by ``max_hang_s``) — the wedged-device shape the
    supervisor's queue-age probe must catch.

    DEVICE_ERROR: ``times`` calls raise a transient device-runtime error
    (the transfer-failure shape ``classify_error`` marks retriable), so
    request failover engages without any replica being declared dead.
    """
    plan = _ACTIVE
    if plan is None:
        return
    fault = plan.faults.get(REPLICA_KEY)
    if fault is None or _KIND_TO_POINT.get(fault.kind) != "replica_predict":
        return
    if fault.kind == KILL_REPLICA:
        with plan._lock:
            latched = plan._killed.get(replica_name)
            if latched is not None:
                if latched == generation:
                    pass  # still the dead incarnation: fall through, raise
                else:
                    return  # rebuilt: the new generation runs clean
            else:
                if fault.replica and fault.replica != replica_name:
                    return
                plan._replica_calls += 1
                if plan._replica_calls < max(1, fault.after):
                    return
                if plan._fired.get(REPLICA_KEY, 0) >= 1:
                    return  # only one replica dies per plan
                plan._fired[REPLICA_KEY] = 1
                plan._killed[replica_name] = generation
                plan.log.append(
                    (REPLICA_KEY, f"kill_replica:{replica_name}")
                )
        raise InjectedFault(f"{fault.message} (replica {replica_name} dead)")
    if fault.replica and fault.replica != replica_name:
        return
    claimed = plan._take(REPLICA_KEY, "replica_predict")
    if claimed is None:
        return
    if fault.kind == WEDGE_PREDICT:
        plan.record(REPLICA_KEY, f"wedge_predict:{replica_name}")
        released = fault.release.wait(fault.max_hang_s)
        plan.record(
            REPLICA_KEY, "wedge_released" if released else "wedge_ceiling"
        )
        raise InjectedFault(f"{fault.message} (predict wedged)")
    plan.record(REPLICA_KEY, f"device_error:{replica_name}")
    raise RuntimeError(
        f"{fault.message}: failed to transfer buffer to device "
        f"(injected device error on replica {replica_name})"
    )
