"""The device's idle time by what the engine's worker thread was doing.

    python3 -m benchmark.host_plane <cell>

reads the newest ``.xplane.pb`` under ``.cache/benchmark_out/<cell>/trace``
(what a ``--trace 1`` run of a serve cell leaves).  The engine writes its
phases into that trace as ``engine.*`` annotations on its worker thread's
line of the host plane, on the clock of the device's operations: the idle
gaps of the busiest device plane are labelled with them as they stand,
no offset between two clocks to be worked out.  Nested spans are cut to
their innermost part first, so each moment has one label.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Sequence

from benchmark import harness, manifest, trace_reduce
from benchmark.trace_reduce import Span

PREFIX = "engine."


def engine_spans(data) -> List[Span]:
    """``(name, start_s, end_s)`` of the ``engine.*`` events of the host
    line that holds most of them (the engine's worker thread)."""
    lines = [
        [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
         for ev in line.events if ev.name.startswith(PREFIX)]
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines
    ]
    return sorted(max(lines, key=len, default=[]), key=lambda s: s[1])


def innermost(spans: Sequence[Span]) -> List[Span]:
    """Spans that nest by time, cut so that none overlaps another: a parent
    keeps only what its children leave of it."""
    out: List[Span] = []
    open_: List[List] = []                # [name, end, covered up to]

    def close(upto: float) -> None:
        while open_ and open_[-1][1] <= upto:
            name, end, at = open_.pop()
            if end > at:
                out.append((name, at, end))
            if open_:
                open_[-1][2] = max(open_[-1][2], end)

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(start)
        if open_:
            if start > open_[-1][2]:
                out.append((open_[-1][0], open_[-1][2], start))
            open_[-1][2] = start
        open_.append([name, end, start])
    close(float("inf"))
    return sorted(out, key=lambda s: s[1])


def report(path: str, rehearse: bool = False) -> dict:
    data = trace_reduce.load(path)
    leaves = innermost(engine_spans(data))
    reduced = trace_reduce.reduce_trace(
        trace_reduce.read_device_events(data, rehearse), leaves, 0.0)
    start, end = reduced["start_s"], reduced["end_s"]
    covered = sum(
        max(0.0, min(e, end) - max(s, start)) for _, s, e in leaves)
    by_span = reduced["idle_gaps"]        # at most eight names and the rest
    idle = sum(seconds for _, seconds in by_span)
    return {
        "trace": path,
        "leaf_spans": len(leaves),
        "device_window_s": end - start,
        "device_idle_s": idle,
        "leaf_spans_cover_share": covered / (end - start),
        "idle_by_leaf_span": by_span,
        "idle_without_span_share": (
            dict(map(tuple, by_span)).get("host, no span", 0.0) / idle
            if idle else 0.0),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell")
    p.add_argument("--rehearse", action="store_true",
                   help="a CPU rehearsal's trace (tests only)")
    args = p.parse_args(argv)
    trace_dir = os.path.join(
        harness.out_dir(manifest.ROOT, args.cell), "trace")
    out = report(trace_reduce.find_xplane(trace_dir), args.rehearse)
    print(f"{args.cell}: device idle {out['device_idle_s']:.4f} s of "
          f"{out['device_window_s']:.4f} s; {out['leaf_spans']} leaf spans "
          f"cover {100 * out['leaf_spans_cover_share']:.2f} % of it")
    for label, seconds in out["idle_by_leaf_span"]:
        print(f"  {label:<18} {seconds:.4f} s "
              f"{100 * seconds / out['device_idle_s']:5.1f} %")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
