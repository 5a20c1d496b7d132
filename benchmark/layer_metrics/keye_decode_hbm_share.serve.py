"""Share of the HBM roofline that the decode step reaches for
Keye-VL-2.0-30B-A3B, the whole step: the bytes one step must move
(benchmark/work_keye.py: the weights every token reads, the matrices of
the experts that the live rows TOUCHED,
``serving_decode_experts_touched_total``, the index keys that are valid
for the live rows, ``serving_decode_cache_read_bytes_total{kind="index"}``,
and of the keys and values only the entries the rows SELECTED and fetched,
``serving_decode_cache_span_bytes_total{kind="kv"}``: whole blocks of the
gathered entries, which is the ``min(t + 1, index_topk)`` selected wherever
a row is ``index_topk`` deep, as every row of the cell is, and at most a
block a row more under that; all over ``serving_decode_steps_total``,
totals of the whole run) over the published bytes per second, over the step
program's mean device time in the trace.  A step that read every valid key
and value would move more and is not what the model asks for, so the share
cannot rise by reading them.  Returns nothing where the program keeps no
such account (any other contract), the cell's model is another
configuration's, or the trace names no step program."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
STEP = "jit_run"
CACHE_READ = "serving_decode_cache_read_bytes_total"
CACHE_SPAN = "serving_decode_cache_span_bytes_total"
KINDS = ("kv", "index")
STEPS = "serving_decode_steps_total"
TOUCHED = "serving_decode_experts_touched_total"
CONFIG = "keye-vl-2.0-30b-a3b"


def by_kind(family, registry=None):
    """``{kind: the run's total}`` of a series labelled by kind of cache,
    or None unless the program counts exactly the two kinds of this
    contract."""
    from benchmark import engine_counters

    totals = engine_counters.by_label(family, "kind", registry)
    return totals if totals and set(totals) == set(KINDS) else None


def hparams(model):
    """The configuration's ``hparams``, where the cell's model is that
    configuration's (the driver's facts carry six of its sizes) and the
    manifest has it."""
    from benchmark import manifest

    try:
        hp = manifest.load_config(manifest.load(), CONFIG)["hparams"]
    except KeyError:
        return None
    same = all(model.get(k) == hp[k] for k in (
        "d_model", "d_ff", "n_layers", "n_heads", "vocab_size"))
    return hp if same else None


def totals(registry=None):
    """``(valid index bytes, fetched kv bytes, experts touched, steps)`` of
    the whole run, or None where the program lacks one of them."""
    from benchmark import engine_counters

    valid = by_kind(CACHE_READ, registry)
    span = by_kind(CACHE_SPAN, registry)
    touched = engine_counters._series(TOUCHED, registry)
    steps = engine_counters._series(STEPS, registry)
    if valid is None or span is None or touched is None or steps is None:
        return None
    n_steps = sum(steps["series"].values())
    if not n_steps:
        return None
    return (valid["index"], span["kv"], sum(touched["series"].values()),
            n_steps)


def read(facts, registry=None):
    from benchmark import work_keye

    trace, peaks = facts.get("trace"), facts.get("peaks")
    model = facts.get("serve_model")
    if None in (trace, peaks, model) or "serve_steps" not in facts:
        return None
    runs = [d for name, _, d in trace["modules"] if name.startswith(STEP)]
    run = totals(registry)
    hp = hparams(model)
    if run is None or hp is None or not runs:
        return None
    index, selected, touched, n_steps = run
    per_step = work_keye.decode_step_bytes(
        hp, model["weight_itemsize"], touched / n_steps, index / n_steps,
        selected / n_steps)
    least_s = per_step / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(runs) / len(runs))
