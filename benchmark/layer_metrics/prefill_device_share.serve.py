"""Share of the device's busy time that goes to prompts: the ``jit_prefill``
and ``jit_prefill_window`` events on the trace's "XLA Modules" line over the
trace's busy seconds.  The rest is decode steps and the programs that move
rows of the arena.  Returns nothing where the trace has no such line (a CPU
rehearsal)."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES and WINDOW_PROGRAM_NAME; the first
# is a prefix of the second
PREFILL = "jit_prefill"


def read(facts):
    trace = facts.get("trace")
    if trace is None or "serve_steps" not in facts:
        return None
    if not trace["modules"] or not trace["busy_s"]:
        return None
    prefill = sum(
        d for name, _, d in trace["modules"] if name.startswith(PREFILL))
    return 100.0 * prefill / trace["busy_s"]
