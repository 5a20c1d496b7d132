"""Share of the chip's peak bf16 FLOP/s that the prefill-window program
reaches: the model FLOPs of a window holding the run's mean count of prompt
tokens (benchmark/work_evabyte.py; ``serving_decode_prefill_tokens_total``
over ``serving_decode_prefill_windows_total``, totals of the whole run: see
benchmark/engine_counters.py) over the program's mean device time in the
trace, over the published peak.  A prompt's last window is computed whole
and counted by its own tokens, so the share errs low.  Returns nothing where
the program has no such counters or the trace names no such program."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py WINDOW_PROGRAM_NAME
WINDOW = "jit_prefill_window"
TOKENS = "serving_decode_prefill_tokens_total"
WINDOWS = "serving_decode_prefill_windows_total"


def read(facts, registry=None):
    from benchmark import engine_counters, work_evabyte

    trace, peaks = facts.get("trace"), facts.get("peaks")
    model = facts.get("serve_model")
    if None in (trace, peaks, model) or "serve_steps" not in facts:
        return None
    tokens = engine_counters._series(TOKENS, registry)
    windows = engine_counters._series(WINDOWS, registry)
    runs = [d for name, _, d in trace["modules"] if name.startswith(WINDOW)]
    if tokens is None or windows is None or not runs:
        return None
    n_windows = sum(windows["series"].values())
    if not n_windows:
        return None
    flops = work_evabyte.prefill_window_flops(
        tokens=sum(tokens["series"].values()) / n_windows,
        **{k: model[k] for k in (
            "d_model", "d_ff", "n_layers", "n_heads", "head_dim")})
    achieved = flops / (sum(runs) / len(runs))
    return 100.0 * achieved / peaks["bf16_flops_per_s"]
