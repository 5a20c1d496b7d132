"""How uneven the routing is over the experts this chip holds: per decode
step, the fullest held expert's assignments over the mean of the held
experts (expert layers averaged), averaged over the run's steps
(``serving_decode_expert_load_ratio_sum`` over ``..._count``, totals of the
whole run: see benchmark/engine_counters.py).  1 is an even spread; the
grouped product's time follows the fullest expert's tile, and in the
deployment the step waits for the fullest chip.  Returns nothing where the
program has no such counters or no step made an assignment (any contract
without held experts)."""

LAYER = "model step"
UNIT = "ratio"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"

SUM = "serving_decode_expert_load_ratio_sum"
COUNT = "serving_decode_expert_load_ratio_count"


def read(facts, registry=None):
    from benchmark import engine_counters

    if "serve_steps" not in facts:
        return None
    total = engine_counters._series(SUM, registry)
    count = engine_counters._series(COUNT, registry)
    if total is None or count is None:
        return None
    n = sum(count["series"].values())
    return sum(total["series"].values()) / n if n else None
