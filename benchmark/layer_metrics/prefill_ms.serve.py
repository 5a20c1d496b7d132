"""Prompt processing as the engine thread waits for it: self seconds of the
``prefill`` phase (dispatch of the prefill program up to the host's read of
its first token, so whatever was queued on the device ahead of it is in
there) over its occurrences.  Totals of the whole run, not of the window:
see benchmark/engine_counters.py."""

LAYER = "model step"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(facts, registry=None):
    from benchmark import engine_counters

    if "serve_steps" not in facts:
        return None
    seconds = engine_counters.by_phase(engine_counters.SECONDS, registry)
    count = engine_counters.by_phase(engine_counters.OCCURRENCES, registry)
    if seconds is None or count is None or not count.get("prefill"):
        return None
    return 1e3 * seconds.get("prefill", 0.0) / count["prefill"]
