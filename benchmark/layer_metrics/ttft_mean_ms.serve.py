"""Mean time from a request's submit to its first token on the host: queue
wait plus prefill (``serving_decode_ttft_seconds``, sum over count).  Totals
of the whole run, not of the window: see benchmark/engine_counters.py.
Open-loop cells only, like the queue wait."""

LAYER = "engine scheduler"
UNIT = "ms"
MOVES = "serve_ms_per_token_p95"
SOURCE = "program_counter"


def read(facts, registry=None):
    from benchmark import engine_counters

    if "serve_steps" not in facts:
        return None
    return engine_counters.mean_ms(engine_counters.TTFT, registry)
