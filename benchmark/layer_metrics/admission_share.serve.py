"""Share of the engine thread's working time that goes to taking requests
in and out of the batch: self seconds of ``admit`` + ``prefill`` +
``prefill.window`` (what a contract that prefills by windows books in place
of ``prefill``) + ``insert`` + ``retire`` over those of every phase but
``idle`` (``serving_decode_engine_seconds_total``).  The rest is ``step`` and
``emit``.
Totals of the whole run, not of the window: see benchmark/engine_counters.py."""

LAYER = "engine scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"

ADMISSION = ("admit", "prefill", "prefill.window", "insert", "retire")


def read(facts, registry=None):
    from benchmark import engine_counters

    if "serve_steps" not in facts:
        return None
    seconds = engine_counters.by_phase(engine_counters.SECONDS, registry)
    if seconds is None:
        return None
    working = sum(s for phase, s in seconds.items() if phase != "idle")
    if not working:
        return None
    return 100.0 * sum(seconds.get(p, 0.0) for p in ADMISSION) / working
