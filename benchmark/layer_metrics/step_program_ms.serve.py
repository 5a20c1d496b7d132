"""Mean device time of one decode step PROGRAM: the events of ``jit_run``
on the trace's "XLA Modules" line, every bucket together, seconds over
events.  ``decode_step_ms.serve`` beside it is the step's period as the
engine's thread sees it (since PR 30 it holds the work queued ahead of
the step and the admission turns behind its dispatch); this is the
program alone, the time the ``*_decode_hbm_share`` readers divide by.
Returns nothing in another kind of cell, in an untraced run and on the
CPU."""

LAYER = "kernels / device"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
PROGRAM = "jit_run"


def read(facts):
    from benchmark import program_parts

    table = program_parts.for_cell(facts, "serve_steps")
    return program_parts.mean_ms(table, PROGRAM)
