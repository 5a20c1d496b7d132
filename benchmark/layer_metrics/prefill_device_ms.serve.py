"""Mean device time of the prefill program: the ``jit_prefill`` events on the
trace's "XLA Modules" line, over the traced last seconds of the window.
Beside ``prefill_ms.serve`` it says how much of the engine thread's wait for
a prefill is the prefill itself.  Returns nothing where the trace has no
such line (a CPU rehearsal) or no prefill ran under it."""

LAYER = "kernels / device"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
PREFILL = "jit_prefill"


def read(facts):
    trace = facts.get("trace")
    if trace is None or "serve_steps" not in facts:
        return None
    runs = [d for name, _, d in trace["modules"] if name.startswith(PREFILL)]
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
