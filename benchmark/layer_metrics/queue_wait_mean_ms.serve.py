"""Mean time a request waits in the engine's queue: submit to the admission
turn that takes it (``serving_decode_queue_wait_seconds``, sum over count).
Totals of the whole run, not of the window: see benchmark/engine_counters.py.
Open-loop cells only: in a closed loop the wait is Little's law over the
callers and says nothing of the engine."""

LAYER = "engine scheduler"
UNIT = "ms"
MOVES = "serve_ms_per_token_p95"
SOURCE = "program_counter"


def read(facts, registry=None):
    from benchmark import engine_counters

    if "serve_steps" not in facts:
        return None
    return engine_counters.mean_ms(engine_counters.QUEUE_WAIT, registry)
