"""Reduction of ``train_loop``'s ``window_breakdown`` events.

Each event closes one window of the trainer's loop: the clock was read
after a device-to-host read of that window's metrics, so ``window_s`` is
host time that the device's work bounds.  A window counts only when it
lies wholly inside the measured span.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from benchmark import stats


def windows_inside(
    events: Sequence[Dict], start: float, end: float
) -> List[Dict]:
    """Events whose window began at or after ``start`` and ended at or
    before ``end``.  ``at`` is the host clock when the event was received,
    which is the window's end."""
    return [
        e for e in events
        if e["at"] <= end and e["at"] - e["window_s"] >= start
    ]


def reduce_windows(
    windows: Sequence[Dict], *, batch_size: int, chips: int
) -> Dict[str, float]:
    if not windows:
        raise ValueError("no whole window inside the measured span")
    wall = sum(w["window_s"] for w in windows)
    steps = sum(w["window_steps"] for w in windows)
    # The trainer clocks the infeed wait and the device span of a window and
    # books the remainder as host, so the three sum to ``window_s``; how it
    # splits the device span into compute and collective is an estimate
    # and is not read.
    device = [
        (w["window_s"] - w["infeed_wait"] - w["host"]) / w["window_steps"]
        for w in windows
    ]
    return {
        "windows": len(windows),
        "steps": steps,
        "wall_s": wall,
        "examples_per_s_per_chip": steps * batch_size / wall / chips,
        "steps_per_s": steps / wall,
        "infeed_wait_share": 100.0 * sum(
            w["infeed_wait"] for w in windows) / wall,
        "host_share": 100.0 * sum(w["host"] for w in windows) / wall,
        "step_ms": 1e3 * stats.median(device),
    }
