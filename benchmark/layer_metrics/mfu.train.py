"""Model FLOP/s utilization: the model FLOPs of one step (benchmark/work.py,
forward and backward matmuls only) times the measured steps per second,
over the chips times the published bf16 peak.  A share of a peak, not a
kernel's roofline share."""

LAYER = "model step"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "program_span"


def read(facts):
    windows, peaks = facts.get("train_windows"), facts.get("peaks")
    if windows is None or peaks is None:
        return None
    achieved = facts["train_flops_per_step"] * windows["steps_per_s"]
    return 100.0 * achieved / (facts["chips"] * peaks["bf16_flops_per_s"])
