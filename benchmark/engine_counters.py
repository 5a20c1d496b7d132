"""The engine's own account of its time, read from the metrics registry.

``serving.generative`` books every phase of its worker thread (``idle``,
``admit``, ``prefill``, ``insert``, ``step``, ``emit``, ``retire``) as self
seconds and occurrences, and each request's queue wait and time to first
token as histograms whose sums and counts are exact.  The driver's marks at
the window's ends hold only tokens and steps, so the readers cannot take a
window's deltas: they take the process's totals at the end of the run.  A
benchmark run has one engine per process and ``engine.warm()`` calls no
hook, so those totals are the run's whole traffic: the settling seconds,
the measured window and the drain, all the same mix.  (A phase is booked
when it ends, so the idle wait that spans warm-up is there too; no reader
takes ``idle``.)  The rehearsal tests run several cells in one pytest
process, where the totals carry earlier runs as well: nothing there is a
reading, and the tests ask only for finite values.

A program without these series (any commit before they were added) gives
``None`` everywhere, and the metric is left out of the line.
"""

from __future__ import annotations

from typing import Dict, Optional

SECONDS = "serving_decode_engine_seconds_total"
OCCURRENCES = "serving_decode_engine_phase_total"
QUEUE_WAIT = "serving_decode_queue_wait_seconds"
TTFT = "serving_decode_ttft_seconds"


def _series(family: str, registry=None) -> Optional[Dict]:
    if registry is None:
        from tpu_pipelines.observability.metrics import default_registry

        registry = default_registry()
    metric = registry.snapshot().get(family)
    if metric is None or not metric["series"]:
        return None
    return metric


def by_label(family: str, label: str,
             registry=None) -> Optional[Dict[str, float]]:
    """``{value of label: total}`` of a labelled counter, summed over its
    other labels (the replicas); ``None`` where the program has no such
    counter."""
    metric = _series(family, registry)
    if metric is None:
        return None
    at = list(metric["labels"]).index(label)
    totals: Dict[str, float] = {}
    for key, value in metric["series"].items():
        totals[key[at]] = totals.get(key[at], 0.0) + float(value)
    return totals


def by_phase(family: str, registry=None) -> Optional[Dict[str, float]]:
    """``{phase: total}`` of one of the two per-phase counters."""
    return by_label(family, "phase", registry)


def mean_ms(family: str, registry=None) -> Optional[float]:
    """Sum over count of a histogram, in milliseconds."""
    metric = _series(family, registry)
    if metric is None:
        return None
    total = sum(s["sum"] for s in metric["series"].values())
    count = sum(s["count"] for s in metric["series"].values())
    return 1e3 * total / count if count else None
