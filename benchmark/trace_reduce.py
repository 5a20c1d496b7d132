"""Reduction of a profiler trace to device busy time, idle gaps and the
operations that took most time.

The arithmetic works on plain ``(name, start_s, duration_s)`` tuples so
that it can be checked on a hand-built list; ``load``, ``read_device_events``
and ``read_host_mark`` alone know the ``.xplane.pb`` layout.
"""

from __future__ import annotations

import bisect
import glob
import itertools
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start, duration (seconds)
Span = Tuple[str, float, float]           # label, start, end (seconds)

# Lines of a TPU plane that hold one event per executed operation.  The
# "XLA Modules" line holds one event per executed program, "Steps" is the
# profiler's own grouping; neither is device work of its own.
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


_INDEX = re.compile(r"\.\d+$")
_RESULT = re.compile(r"\(*([a-z0-9]+\[[0-9,]*\])")


def op_kind(name: str) -> str:
    """The kind of a device operation from its event name.  The TPU's
    trace names an operation by its whole HLO line (``%fusion.12 = ...``);
    the kind is the result's name without its index and the type of its
    (first) result, so that the instances of one fusion in every layer
    and step add up and a matrix product shows by its shape."""
    head, _, rest = name.partition(" = ")
    kind = _INDEX.sub("", head.strip().lstrip("%")) or "unnamed"
    shape = _RESULT.match(rest)
    return (kind + (" " + shape.group(1) if shape else ""))[:64]


def merge_intervals(
    intervals: Iterable[Tuple[float, float]]
) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_seconds(
    events: Sequence[Event], start: float, end: float
) -> float:
    """Seconds of ``[start, end]`` in which at least one event ran."""
    clipped = (
        (max(s, start), min(s + d, end)) for _, s, d in events
    )
    return sum(b - a for a, b in merge_intervals(clipped))


def idle_gaps(
    events: Sequence[Event], start: float, end: float
) -> List[Tuple[float, float]]:
    """The intervals of ``[start, end]`` in which no event ran."""
    gaps = []
    at = start
    for a, b in merge_intervals(
        (max(s, start), min(s + d, end)) for _, s, d in events
    ):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if end > at:
        gaps.append((at, end))
    return gaps


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Time per operation name, counting an operation that encloses others
    (a loop, a call) only for the part its children do not cover."""
    totals: Dict[str, float] = {}
    stack: List[List] = []        # [name, end, self_seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(0.0, own)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


def top_operations(
    events: Sequence[Event], n: int = 10
) -> List[List]:
    ranked = sorted(self_times(events).items(), key=lambda kv: -kv[1])
    return [[name, secs] for name, secs in ranked[:n]]


def label_gaps(
    gaps: Sequence[Tuple[float, float]], host_spans: Sequence[Span],
    n: int = 10, unlabelled: str = "host, no span",
) -> List[List]:
    """Total idle seconds by what the host was doing: each gap is split
    over the host spans that overlap it, the rest goes to ``unlabelled``.
    Returns the ``n`` largest labels.

    The spans are sorted by their start once, and each gap looks only at
    those that can overlap it: the ones that start before its end, from
    the first whose end, or that of a span starting before it, lies past
    the gap's start.  A traced serve window holds some 10**6 gaps and
    10**3 spans, and every gap against every span took minutes."""
    order = sorted(range(len(host_spans)), key=lambda i: host_spans[i][1])
    starts = [host_spans[i][1] for i in order]
    ends_so_far = list(itertools.accumulate(
        (host_spans[i][2] for i in order), max))
    totals: Dict[str, float] = {}
    for a, b in gaps:
        covered = 0.0
        first = bisect.bisect_right(ends_so_far, a)
        last = bisect.bisect_left(starts, b)
        # in the order the spans were given, so that the sums are the
        # same to the last bit as a loop over all of them
        for i in sorted(order[first:last]):
            label, s, e = host_spans[i]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                totals[label] = totals.get(label, 0.0) + overlap
                covered += overlap
        rest = (b - a) - covered
        if rest > 0:
            totals[unlabelled] = totals.get(unlabelled, 0.0) + rest
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[label, secs] for label, secs in ranked[:n]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    """One ``.xplane.pb``, parsed once for every reader below."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def read_device_events(
    data, rehearse: bool = False
) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane name: {line name: [(name, start_s, duration_s)]}}`` for the
    device planes of a loaded trace.  Times are seconds on the
    profiler's own clock, which starts near the start of the trace.

    A rehearsal on the CPU has no device plane; there the XLA client's
    host threads stand in for one, so that the traced path can be tested.
    Nothing read that way is a device number."""
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        if rehearse and plane.name == "/host:CPU":
            planes["/rehearsal:CPU"] = {OP_LINE: [
                (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                for line in plane.lines if line.name.startswith("tf_XLA")
                for ev in line.events
            ]}
            continue
        if not plane.name.startswith("/device:"):
            continue
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            if line.name not in (OP_LINE, MODULE_LINE):
                continue
            kind = op_kind if line.name == OP_LINE else (lambda n: n)
            lines[line.name] = [
                (kind(ev.name), ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                for ev in line.events
            ]
        planes[plane.name] = lines
    return planes


def read_host_mark(data, name: str):
    """Start, in seconds on the trace's clock, of the first host event
    called ``name``; ``None`` if the trace holds none."""
    starts = [
        ev.start_ns * 1e-9
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events if ev.name == name
    ]
    return min(starts) if starts else None


def reduce_trace(
    planes: Dict[str, Dict[str, List[Event]]],
    host_spans: Sequence[Span] = (),
    host_clock_offset: float = 0.0,
) -> Dict:
    """Busy seconds averaged over the device planes that ran operations,
    the traced window, the top operations and the labelled idle gaps of
    the busiest plane.  ``host_spans`` are on the host's clock;
    ``host_clock_offset`` is what to add to a trace time to get it."""
    per_plane = []
    for name, lines in planes.items():
        ops = lines.get(OP_LINE, [])
        if ops:
            per_plane.append((name, ops))
    if not per_plane:
        raise ValueError(
            "the trace holds no device operation: planes "
            f"{ {p: sorted(l) for p, l in planes.items()} }"
        )
    start = min(s for _, ops in per_plane for _, s, _ in ops)
    end = max(s + d for _, ops in per_plane for _, s, d in ops)
    busy = [busy_seconds(ops, start, end) for _, ops in per_plane]
    lead = max(range(len(busy)), key=lambda i: busy[i])
    lead_ops = per_plane[lead][1]
    gaps = [
        (a + host_clock_offset, b + host_clock_offset)
        for a, b in idle_gaps(lead_ops, start, end)
    ]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": end - start,
        "start_s": start,
        "end_s": end,
        "device_ops": top_operations(lead_ops),
        "idle_gaps": label_gaps(gaps, host_spans),
        "modules": planes[per_plane[lead][0]].get(MODULE_LINE, []),
    }
