"""Share of the train window program's device time that goes to the layer norms
(``norm``), forward and backward: the self seconds of the program's
operations booked to that part over those of all its operations, from the
run's own trace (benchmark/program_parts.py has the rule). A share is a map
of the program, not a goal. Returns nothing in another kind of cell, in an
untraced run and on the CPU. On a commit before the scopes flax's own module
names ``mlp`` and ``norm`` are the only words, and the share is of what they
hold."""

LAYER = "model step"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"

# trainer/train_loop.py WINDOW_PROGRAM_NAME
PROGRAM = "jit_train_window"
PART = "norm"


def read(facts):
    from benchmark import program_parts

    table = program_parts.for_cell(facts, "train_windows")
    return program_parts.share(table, (PROGRAM,), (PART,))
