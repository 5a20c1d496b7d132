"""Mean live rows per decode step over the engine's slots: the window's delta
of ``serving_decode_tokens_total`` over its delta of
``serving_decode_steps_total``, over ``max_batch_size``."""

LAYER = "engine scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(facts):
    steps = facts.get("serve_steps")
    if steps is None or not steps["counter_steps"]:
        return None
    per_step = steps["counter_tokens"] / steps["counter_steps"]
    return 100.0 * per_step / steps["max_batch_size"]
