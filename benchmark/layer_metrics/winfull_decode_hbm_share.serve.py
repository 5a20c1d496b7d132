"""Share of the HBM roofline that the decode step reaches under a contract
whose layers keep window rings and full caches side by side (Command A+):
the bytes one step must read (benchmark/work_command_a.py: the weights it
touches at their stored width, with only the held experts that a live row
chose, ``serving_decode_experts_touched_total``; plus the ring and
full-cache entries that are valid for the live rows,
``serving_decode_cache_read_bytes_total{kind="window"|"full"}``; each over
``serving_decode_steps_total``, totals of the whole run: see
benchmark/engine_counters.py) over the published bytes per second, over
the step program's mean device time in the trace.  Returns nothing where
the program keeps no such account (any other contract, any commit before
the kinds) or the trace names no step program."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
STEP = "jit_run"
CACHE_READ = "serving_decode_cache_read_bytes_total"
CACHE_SPAN = "serving_decode_cache_span_bytes_total"
KINDS = ("window", "full")
STEPS = "serving_decode_steps_total"
TOUCHED = "serving_decode_experts_touched_total"
CONFIG = "command-a-plus-05-2026"


def by_kind(family, registry=None):
    """``{kind: the run's total}`` of a series labelled by kind of cache,
    or None unless the program counts exactly the two kinds of this
    contract."""
    from benchmark import engine_counters

    totals = engine_counters.by_label(family, "kind", registry)
    return totals if totals and set(totals) == set(KINDS) else None


def total(family, registry=None):
    from benchmark import engine_counters

    series = engine_counters._series(family, registry)
    return None if series is None else sum(series["series"].values())


def hparams(model):
    """The configuration's ``hparams``, where the cell's model is that
    configuration's (the driver's facts carry six of its sizes)."""
    from benchmark import manifest

    hp = manifest.load_config(manifest.load(), CONFIG)["hparams"]
    same = all(model.get(k) == hp[k] for k in (
        "d_model", "d_ff", "n_layers", "n_heads", "vocab_size"))
    return hp if same else None


def read(facts, registry=None):
    from benchmark import work_command_a

    trace, peaks = facts.get("trace"), facts.get("peaks")
    model = facts.get("serve_model")
    if None in (trace, peaks, model) or "serve_steps" not in facts:
        return None
    cache = by_kind(CACHE_READ, registry)
    n_steps, touched = total(STEPS, registry), total(TOUCHED, registry)
    runs = [d for name, _, d in trace["modules"] if name.startswith(STEP)]
    if cache is None or not n_steps or touched is None or not runs:
        return None
    hp = hparams(model)
    if hp is None:
        return None
    per_step = work_command_a.decode_weight_bytes(
        hp, model["weight_itemsize"], touched / n_steps) \
        + sum(cache.values()) / n_steps
    least_s = per_step / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(runs) / len(runs))
