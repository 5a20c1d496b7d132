"""What every driver shares: the device check, the compile cache, the
profiler around a sub-window, and the result line."""

from __future__ import annotations

import glob
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional, Sequence

from benchmark import trace_reduce

CLOCK_MARK = "benchmark_clock_mark"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def say(*parts: Any) -> None:
    """A line for the reader, ahead of the result line."""
    print(*parts, flush=True)


def stage(ctx, what: str) -> None:
    """Where set-up's seconds go: one line per stage reached."""
    say(f"stage {what}: {time.perf_counter() - ctx.t_process_start:.1f}s "
        "after process start")


def configure_jax(root: str) -> Dict[str, int]:
    """Point JAX's persistent compilation cache at a fixed directory of
    the checkout, whatever the environment says, and count its hits and
    misses.  The program's own ``maybe_enable_compile_cache`` keeps a
    directory that is already set.  Must run before the first compile."""
    import jax

    cache_dir = os.path.join(root, ".cache", "xla")
    os.makedirs(cache_dir, exist_ok=True)
    # JAX writes an entry and then its access time.  A process ended
    # between the two leaves an entry without one, and every later write
    # to the directory then fails on it: each run would compile anew.
    for entry in glob.glob(os.path.join(cache_dir, "*-cache")):
        if not os.path.exists(entry[:-len("-cache")] + "-atime"):
            os.remove(entry)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # The directory is the benchmark's own; a machine-wide cap (the chip
    # tool's machine sets 192 MiB) would evict a cell's programs before
    # its next run asks for them.
    jax.config.update("jax_compilation_cache_max_size", 4 * 1024 ** 3)
    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def find_devices(chips: int, rehearse: bool) -> List[Any]:
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" and not rehearse:
        raise NoChip("JAX found no accelerator (platform cpu)")
    if len(devices) < chips:
        raise NoChip(
            f"the cell asks for {chips} chips, JAX found {len(devices)}"
        )
    return list(devices[:chips])


def memory_peak_bytes(devices: Sequence[Any]) -> int:
    """Peak on the fullest chip.  The TPU runtime books what programs
    allocate (weights, state, arenas, batches) under ``bytes_in_use`` and
    the scratch memory of the compiled programs themselves (activations,
    temporaries) under ``bytes_reserved``; the chip holds both.  The two
    peaks need not fall together, so their sum is an upper end of the
    true peak; each is printed beside it."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        say(f"memory on device {d.id}: peak_bytes_in_use {in_use} + "
            f"peak_bytes_reserved {reserved} = {in_use + reserved} "
            f"(bytes_limit {stats.get('bytes_limit')})")
        peak = max(peak, in_use + reserved)
    return peak


def device_block(devices: Sequence[Any], peak_bytes: int) -> Dict[str, Any]:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak_bytes),
    }


class DeviceTrace:
    """``jax.profiler`` around a part of the measured window, started and
    stopped by the driver on the thread that drives the device."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "trace")
        self.mark_host_s: Optional[float] = None
        self.started = False
        self.stopped = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.started = True
        self.mark_host_s = time.perf_counter()
        with jax.profiler.TraceAnnotation(CLOCK_MARK):
            pass

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.stopped = True

    def reduce(self, host_spans, rehearse: bool = False) -> Dict:
        """Busy time, window, top operations and labelled idle gaps."""
        data = trace_reduce.load(trace_reduce.find_xplane(self.dir))
        planes = trace_reduce.read_device_events(data, rehearse)
        offset = 0.0
        mark = trace_reduce.read_host_mark(data, CLOCK_MARK)
        if mark is not None and self.mark_host_s is not None:
            offset = self.mark_host_s - mark
        else:
            host_spans = ()
        out = trace_reduce.reduce_trace(planes, host_spans, offset)
        out["host_clock_offset"] = offset
        return out


def check_option_keys(what: str, keys, known, drivers_own) -> None:
    """A block of the program's options in a data file (``engine``,
    ``train_loop``) may name what the program takes and the driver does
    not set itself; anything else is an error."""
    for key in keys:
        if key in drivers_own or key not in known:
            raise KeyError(
                f"{what} option {key!r} is not one a file may set; "
                f"the program takes {sorted(set(known) - set(drivers_own))}")


def out_dir(root: str, workload: str) -> str:
    """Where a run leaves what is too long for its output: under the
    checkout's ``.cache/``, which the repo's ``.gitignore`` already lists."""
    path = os.path.join(root, ".cache", "benchmark_out", workload)
    os.makedirs(path, exist_ok=True)
    return path


def result_line(
    *, correct: bool, attempted: int, failed: int,
    metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
    breakdown: Optional[Dict] = None, checks: Sequence[Dict] = (),
) -> str:
    """``checks``: every number compared beside its limit, under a key of
    its own that comes last in the line."""
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {
        r["check"]: {
            # a value that is no number fails its check; JSON has no NaN
            "value": r["value"] if r["value"] == r["value"] else None,
            "limit": r["limit"]}
        for r in checks}
    return json.dumps(line)


def check_line(row: Dict[str, Any]) -> str:
    return (f"check {row['check']}: {row['value']!r} (limit "
            f"{row['limit']!r}) {'ok' if row['ok'] else 'NOT CORRECT'}")


class Checks:
    """Every number compared, beside its limit; ``ok`` is their verdict."""

    def __init__(self) -> None:
        self.rows: List[Dict[str, Any]] = []

    def at_most(self, name: str, value: float, limit: float) -> None:
        ok = bool(value == value and value <= limit)
        self.rows.append(
            {"check": name, "value": value, "limit": limit, "ok": ok})
        say(check_line(self.rows[-1]))

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)
