"""Share of the measured windows' wall time spent in the trainer's own host
work between windows (metric reconstruction, publishing, watchdogs):
``window_breakdown.host`` over ``window_s``."""

LAYER = "trainer loop"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "program_span"


def read(facts):
    windows = facts.get("train_windows")
    return None if windows is None else windows["host_share"]
