"""Share of the HBM roofline that the decode step reaches under a contract
with a latent cache (openPangu-Ultra-MoE): the bytes one step must read
(benchmark/work_pangu_moe.py: the weights held at their stored width, plus
the latent rows that are valid for the live rows,
``serving_decode_cache_read_bytes_total{kind="latent"}`` over
``serving_decode_steps_total``, both totals of the whole run: see
benchmark/engine_counters.py) over the published bytes per second, over
the step program's mean device time in the trace.  Returns nothing where
the program keeps no such account (any other contract, any commit before
the kind) or the trace names no step program."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
STEP = "jit_run"
CACHE_READ = "serving_decode_cache_read_bytes_total"
KIND = "latent"
STEPS = "serving_decode_steps_total"
CONFIG = "openpangu-ultra-moe-718b"


def latent_bytes(registry=None):
    """The run's total of latent bytes read, or None where the program
    counts none (another contract)."""
    from benchmark import engine_counters

    totals = engine_counters.by_label(CACHE_READ, "kind", registry)
    return (totals or {}).get(KIND) or None


def hparams(model):
    """The configuration's ``hparams``, where the cell's model is that
    configuration's (the driver's facts carry six of its sizes)."""
    from benchmark import manifest

    hp = manifest.load_config(manifest.load(), CONFIG)["hparams"]
    same = all(model.get(k) == hp[k] for k in (
        "d_model", "d_ff", "n_layers", "n_heads", "vocab_size"))
    return hp if same else None


def read(facts, registry=None):
    from benchmark import engine_counters, work_pangu_moe

    trace, peaks = facts.get("trace"), facts.get("peaks")
    model = facts.get("serve_model")
    if None in (trace, peaks, model) or "serve_steps" not in facts:
        return None
    latents = latent_bytes(registry)
    steps = engine_counters._series(STEPS, registry)
    runs = [d for name, _, d in trace["modules"] if name.startswith(STEP)]
    if latents is None or steps is None or not runs:
        return None
    n_steps = sum(steps["series"].values())
    hp = hparams(model)
    if not n_steps or hp is None:
        return None
    per_step = work_pangu_moe.decode_weight_bytes(
        hp, model["weight_itemsize"]) + latents / n_steps
    least_s = per_step / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(runs) / len(runs))
