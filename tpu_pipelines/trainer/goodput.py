"""Goodput/badput accounting via ``ml_goodput_measurement`` (SURVEY.md §5).

The reference delegates goodput to the substrate; the TPU stack's canonical
tool is Google's ``ml_goodput_measurement``, whose recorder/calculator pair
normally rides Google Cloud Logging.  Here the logger is duck-typed onto an
in-process entry list (optionally mirrored to a JSONL next to the
checkpoints), so the real badput algebra — TPU init, training prep,
sync/async data loading, program startup, checkpoint save/restore, wasted
progress — runs with zero GCP dependency and works in air-gapped tests.

``GoodputTracker`` is the train-loop-facing wrapper: every record method is
a no-op when the library is unavailable, and ``summary()`` returns {} so the
loop's own host-input-wait proxy remains the fallback.
"""

from __future__ import annotations

import datetime
import json
import logging
import os
import time
from typing import Any, Dict, List, Optional

log = logging.getLogger("tpu_pipelines.trainer")


def _now() -> datetime.datetime:
    return datetime.datetime.now(datetime.timezone.utc)


class LocalEntryLogger:
    """Duck-types ``ml_goodput_measurement``'s ``_CloudLogger`` interface
    (``write_cloud_logging_entry`` / ``read_cloud_logging_entries``) over an
    in-memory list, optionally mirrored to a JSONL file for post-hoc
    inspection (`model_run/goodput_log.jsonl`).

    Mirror failures (a full or read-only disk) never break training, and
    no longer latch the mirror off forever: every failure is counted in
    the metrics registry (``goodput_mirror_failures_total``), writes are
    suppressed for ``mirror_retry_backoff_s``, then retried ONCE — a
    transient ENOSPC recovers, a genuinely dead path disables the mirror
    after its second strike.
    """

    def __init__(
        self,
        job_name: str,
        jsonl_path: str = "",
        mirror_retry_backoff_s: float = 30.0,
    ):
        self.job_name = job_name
        self.job_start_time = None  # attribute the real logger also exposes
        self._entries: List[Dict[str, Any]] = []
        self._jsonl_path = jsonl_path
        self._mirror_retry_backoff_s = mirror_retry_backoff_s
        self._mirror_retry_at: Optional[float] = None  # monotonic
        self._mirror_dead = False
        from tpu_pipelines.observability.metrics import default_registry

        self._m_mirror_failures = default_registry().counter(
            "goodput_mirror_failures_total",
            "Goodput JSONL mirror write failures (OSError).",
        )

    def write_cloud_logging_entry(self, entry) -> None:
        if entry is None or entry.get("job_name") != self.job_name:
            return
        self._entries.append(entry)
        if not self._jsonl_path or self._mirror_dead:
            return
        if (
            self._mirror_retry_at is not None
            and time.monotonic() < self._mirror_retry_at
        ):
            return  # backing off; the entry stays in-memory only
        try:
            parent = os.path.dirname(self._jsonl_path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            with open(self._jsonl_path, "a") as f:
                f.write(json.dumps(entry, default=str) + "\n")
        except OSError as e:
            self._m_mirror_failures.inc()
            if self._mirror_retry_at is None:
                # First strike this episode: back off, then retry once.
                self._mirror_retry_at = (
                    time.monotonic() + self._mirror_retry_backoff_s
                )
                log.warning(
                    "goodput jsonl mirror failed (%s); retrying once "
                    "after %gs", e, self._mirror_retry_backoff_s,
                )
            else:
                # The post-backoff retry also failed: the path is dead.
                self._mirror_dead = True
                log.warning(
                    "goodput jsonl mirror disabled after retry: %s", e
                )
        else:
            # A success closes the failure episode: a future failure gets
            # its own backoff + single retry.
            self._mirror_retry_at = None

    def read_cloud_logging_entries(
        self, start_time=None, end_time=None, last_entry_info=None
    ):
        """``(payloads, (last_timestamp, last_id))`` — the installed
        calculator's contract.  Ids are list positions, so a calculator
        that kept ``last_entry_info`` from an earlier read gets only what
        was written since; the time window is not needed on top of that
        (entries are never retained across jobs here)."""
        del start_time, end_time
        first = 0
        if last_entry_info is not None and last_entry_info[1] is not None:
            first = int(last_entry_info[1]) + 1
        entries = self._entries[first:]
        if not entries:
            return [], (None, None)
        return list(entries), (_now(), str(first + len(entries) - 1))


class GoodputTracker:
    """Recorder facade for the train loop; disabled ⇒ every call no-ops."""

    def __init__(self, job_name: str = "train", jsonl_path: str = ""):
        self.job_name = job_name
        self._recorder = None
        self._goodput_mod = None
        try:
            from ml_goodput_measurement.src import goodput as goodput_mod

            self._logger = LocalEntryLogger(job_name, jsonl_path)
        except ImportError as e:
            log.info("ml_goodput_measurement unavailable (%s); using proxy", e)
            return
        # Outside the ImportError guard on purpose: a constructor that no
        # longer matches the installed library must fail loudly, not
        # downgrade every run to the proxy path in silence.
        self._recorder = goodput_mod.GoodputRecorder(
            job_name, "local", logging_enabled=True,
            cloud_logger=self._logger,
        )
        self._goodput_mod = goodput_mod

    @property
    def enabled(self) -> bool:
        return self._recorder is not None

    # ---- recording (thin pass-throughs; timestamps default to now-UTC)

    def job_start(self):
        if self._recorder:
            self._recorder.record_job_start_time(_now())

    def job_end(self):
        if self._recorder:
            self._recorder.record_job_end_time(_now())

    def tpu_init_start(self):
        if self._recorder:
            self._recorder.record_tpu_init_start_time(_now())

    def tpu_init_end(self):
        if self._recorder:
            self._recorder.record_tpu_init_end_time(_now())

    def training_prep_start(self):
        if self._recorder:
            self._recorder.record_training_preparation_start_time(_now())

    def training_prep_end(self):
        if self._recorder:
            self._recorder.record_training_preparation_end_time(_now())

    def data_loading_start(self):
        if self._recorder:
            self._recorder.record_data_loading_start_time(_now())

    def data_loading_end(self):
        if self._recorder:
            self._recorder.record_data_loading_end_time(_now())

    def step_start(self, step: int):
        if self._recorder:
            self._recorder.record_step_start_time(step, _now())

    # ---- summary

    def summary(self) -> Dict[str, Any]:
        """{"goodput": fraction, "badput": {kind: fraction}, "last_step": n}
        or {} when disabled / nothing recorded / calculator error."""
        if not self._recorder:
            return {}
        try:
            calc = self._goodput_mod.GoodputCalculator(
                self.job_name, "local", cloud_logger=self._logger
            )
            goodput_pct, badput, last_step = calc.get_job_goodput(
                include_badput_breakdown=True
            )
        except Exception as e:  # noqa: BLE001
            log.warning("goodput calculation failed: %s", e)
            return {}
        breakdown: Dict[str, float] = {}
        for kind, pct in badput.items():
            name = getattr(kind, "name", str(kind)).lower()
            if isinstance(pct, dict):  # CUSTOM_BADPUT_EVENTS sub-breakdown
                pct = sum(pct.values())
            if pct:
                breakdown[name] = round(float(pct) / 100.0, 4)
        return {
            "goodput": round(float(goodput_pct) / 100.0, 4),
            "badput": breakdown,
            "last_step": int(last_step),
        }
