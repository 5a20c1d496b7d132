"""Share of the HBM roofline that the decode step reaches under a contract
with a window ring and a chunk table (EvaByte): the bytes one step must read
(benchmark/work_evabyte.py: the blocks' weights and head 0 at their stored
width, plus the ring and chunk entries that are valid for the live rows,
``serving_decode_cache_read_bytes_total`` over ``serving_decode_steps_total``,
both totals of the whole run: see benchmark/engine_counters.py) over the
published bytes per second, over the step program's mean device time in the
trace.  It asks for its own two kinds of cache, ``window`` and ``chunk``:
another contract's latents, or a ring beside a full array, are not its to
price with EvaByte's weight arithmetic.  Returns nothing where the program
keeps no such account (any other contract, any commit before the counter)
or the trace names no step program."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
STEP = "jit_run"
CACHE_READ = "serving_decode_cache_read_bytes_total"
KINDS = ("window", "chunk")        # models/evabyte.py cache_kinds
STEPS = "serving_decode_steps_total"


def read(facts, registry=None):
    from benchmark import engine_counters, work_evabyte

    trace, peaks = facts.get("trace"), facts.get("peaks")
    model = facts.get("serve_model")
    if None in (trace, peaks, model) or "serve_steps" not in facts:
        return None
    cache = engine_counters.by_label(CACHE_READ, "kind", registry)
    steps = engine_counters._series(STEPS, registry)
    runs = [d for name, _, d in trace["modules"] if name.startswith(STEP)]
    if cache is None or set(cache) != set(KINDS) or steps is None \
            or not runs:
        return None
    n_steps = sum(steps["series"].values())
    if not n_steps:
        return None
    shape = {k: model[k] for k in (
        "d_model", "d_ff", "n_layers", "n_heads", "head_dim", "vocab_size",
        "weight_itemsize")}
    per_step = work_evabyte.decode_weight_bytes(**shape) \
        + sum(cache.values()) / n_steps
    least_s = per_step / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(runs) / len(runs))
