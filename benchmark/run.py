"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, in a
traced run, ``breakdown``).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics.  Without an accelerator, or
with fewer chips than the cell asks for, the run prints no result and exits
with a code other than 0.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # The script's own directory leaves the path: its modules are imported
    # as ``benchmark.<name>`` from the root, like everything else here.
    sys.path[0] = ROOT

from benchmark import harness, manifest, traffic  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument(
        "--rehearse", action="store_true",
        help="accept the CPU (tests only): the device is printed as cpu "
             "and no number of such a run is a device number")
    p.add_argument(
        "--control", action="store_true",
        help="also compute the lower-precision control's numbers and "
             "print them beside the limits; changes no result")
    p.add_argument(
        "--manifest-root", default=ROOT,
        help="directory that holds BENCHMARK.json and its benchmark/ "
             "configs and traffic (the tests' fixture has its own)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    root = os.path.abspath(args.manifest_root)
    spec = manifest.load(root)
    cell = manifest.cell(spec, args.workload)
    config = manifest.load_config(spec, cell["config"], root)
    mix = traffic.load(manifest.traffic_path(cell["traffic"], root))

    try:
        devices = harness.find_devices(int(cell["chips"]), args.rehearse)
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    # A rehearsal on the CPU keeps no compiled program: CPU entries would
    # sit in the checkout's cache beside the chip's.
    cache_counts = (
        {"hits": 0, "misses": 0} if args.rehearse
        else harness.configure_jax(ROOT))

    driver = importlib.import_module("benchmark.drivers." + config["driver"])
    ctx = SimpleNamespace(
        cell=cell, config=config, traffic=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        rehearse=args.rehearse, control=args.control, devices=devices,
        t_process_start=T_PROCESS_START, root=ROOT,
        out_dir=harness.out_dir(ROOT, args.workload),
    )
    out = driver.run(ctx)
    harness.say(f"compile cache: {cache_counts['hits']} hits, "
                f"{cache_counts['misses']} misses")

    device = harness.device_block(devices, out["memory_peak_bytes"])
    breakdown = None
    metrics = {}
    if args.trace:
        traced = out["facts"].get("trace")
        if traced is None:
            raise RuntimeError("a traced run produced no trace")
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        breakdown = {"device_ops": traced["device_ops"],
                     "idle_gaps": traced["idle_gaps"]}
        for m in manifest.metrics_for(spec, "per_layer", args.workload):
            reader = manifest.load_layer_metric(m["name"])
            value = reader.read(out["facts"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in manifest.metrics_for(spec, "end_to_end", args.workload):
            metrics[m["name"]] = {
                "value": out["end_to_end"][m["name"]], "unit": m["unit"]}
    print(harness.result_line(
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], metrics=metrics, device=device,
        breakdown=breakdown, checks=out["checks"],
    ), flush=True)
    # The numbers compared, each beside its limit, end standard error too.
    for row in out["checks"]:
        print(harness.check_line(row), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
