"""Share of the HBM roofline that the decode step reaches for
Xing4.0-29B-A4B, the whole step: the bytes one step must move
(benchmark/work_xing.py: the weights every token reads, the matrices of
the experts that the live rows TOUCHED,
``serving_decode_experts_touched_total``, the latent rows that are valid,
``serving_decode_cache_read_bytes_total{kind="latent"}``, both over
``serving_decode_steps_total``, totals of the whole run, and the streams
of the step's rows through the mixing, the window's tokens a step) over
the published bytes per second, over the step program's mean device time
in the trace.  A step that touches fewer experts must read less, so the
share cannot pass 100 % by skipping idle ones.  Returns nothing where the
program keeps no such account, the cell's model is another
configuration's, or the trace names no step program."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
STEP = "jit_run"
CACHE_READ = "serving_decode_cache_read_bytes_total"
KIND = "latent"
STEPS = "serving_decode_steps_total"
TOUCHED = "serving_decode_experts_touched_total"
CONFIG = "xing4.0-29b-a4b"


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
    """``(latent bytes, experts touched, steps)`` of the whole run, or
    None where the program lacks one of the three."""
    from benchmark import engine_counters

    latents = (engine_counters.by_label(
        CACHE_READ, "kind", registry) or {}).get(KIND)
    touched = engine_counters._series(TOUCHED, registry)
    steps = engine_counters._series(STEPS, registry)
    if not latents or touched is None or steps is None:
        return None
    n_steps = sum(steps["series"].values())
    if not n_steps:
        return None
    return latents, sum(touched["series"].values()), n_steps


def read(facts, registry=None):
    from benchmark import work_xing

    trace, peaks = facts.get("trace"), facts.get("peaks")
    model, steps = facts.get("serve_model"), facts.get("serve_steps")
    if None in (trace, peaks, model, steps):
        return None
    runs = [d for name, _, d in trace["modules"] if name.startswith(STEP)]
    run = totals(registry)
    hp = hparams(model)
    if run is None or hp is None or not runs \
            or not steps.get("counter_steps"):
        return None
    latents, touched, n_steps = run
    per_step = work_xing.decode_step_bytes(
        hp, model["weight_itemsize"], touched / n_steps, latents / n_steps,
        steps["counter_tokens"] / steps["counter_steps"])
    least_s = per_step / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(runs) / len(runs))
