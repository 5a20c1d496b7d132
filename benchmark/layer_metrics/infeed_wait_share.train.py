"""Share of the measured windows' wall time that the trainer's loop waited
for the next staged window: ``window_breakdown.infeed_wait`` over
``window_s``, summed over the whole windows of the run."""

LAYER = "data plane"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "program_span"


def read(facts):
    windows = facts.get("train_windows")
    return None if windows is None else windows["infeed_wait_share"]
