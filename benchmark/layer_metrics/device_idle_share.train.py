"""Share of the traced span (whole windows of the train loop) in which no
operation ran on the device: 1 - busy / window, from the benchmark's own
reduction of the profiler's trace."""

LAYER = "device"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts.get("trace")
    if trace is None or "train_windows" not in facts:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
