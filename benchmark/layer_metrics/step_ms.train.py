"""Median over the measured windows of a window's device span (dispatch to
the device-to-host read of its metrics) over its steps."""

LAYER = "model step"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "program_span"


def read(facts):
    windows = facts.get("train_windows")
    return None if windows is None else windows["step_ms"]
