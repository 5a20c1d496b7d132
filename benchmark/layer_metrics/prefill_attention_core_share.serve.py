"""Share of the prefill programs' device time that goes to the attention's
scores, mask, softmax and weighted sum (``attention_core``; in a windowed
contract the blocked kernel and the chunk pooling): the self seconds of the
operations booked there over those of all the programs' operations, every
bucket together, from the run's own trace (benchmark/program_parts.py has
the rule). A share is a map of the program, not a goal. Returns nothing in
another kind of cell, in an untraced run and on the CPU. On a commit before
the scopes flax's own module names ``mlp`` and ``norm`` are the only words,
and the share is of what they hold."""

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES, WINDOW_PROGRAM_NAME
PROGRAMS = ("jit_prefill", "jit_prefill_window")
PARTS = ("attention_core",)


def read(facts):
    from benchmark import program_parts

    table = program_parts.for_cell(facts, "serve_steps")
    return program_parts.share(table, PROGRAMS, PARTS)
