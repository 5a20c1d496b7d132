"""Share of the device's busy time that goes to the three programs which
only move rows of the arena: the ``jit_insert``, ``jit_move`` and
``jit_clear`` events on the trace's "XLA Modules" line over the trace's busy
seconds.  Returns nothing where the trace has no such line (a CPU
rehearsal)."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
ADMIN = ("jit_insert", "jit_move", "jit_clear")


def read(facts):
    trace = facts.get("trace")
    if trace is None or "serve_steps" not in facts:
        return None
    if not trace["modules"] or not trace["busy_s"]:
        return None
    admin = sum(d for name, _, d in trace["modules"] if name.startswith(ADMIN))
    return 100.0 * admin / trace["busy_s"]
