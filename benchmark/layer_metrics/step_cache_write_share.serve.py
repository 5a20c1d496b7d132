"""Share of the decode step program's device time that goes to writing: the
step's own write into its cache (``cache_write``) and the engine's bucket
cut out of the arena and set back, with the re-layout the compiler makes of
it (``arena``): the self seconds of the operations booked there over those
of all the programs' operations, every bucket together, from the run's own
trace (benchmark/program_parts.py has the rule). A share is a map of the
program, not a goal. Returns nothing in another kind of cell, in an untraced
run and on the CPU. On a commit before the scopes flax's own module names
``mlp`` and ``norm`` are the only words, and the share is of what they hold."""

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES, WINDOW_PROGRAM_NAME
PROGRAMS = ("jit_run",)
PARTS = ("cache_write", "arena")


def read(facts):
    from benchmark import program_parts

    table = program_parts.for_cell(facts, "serve_steps")
    return program_parts.share(table, PROGRAMS, PARTS)
