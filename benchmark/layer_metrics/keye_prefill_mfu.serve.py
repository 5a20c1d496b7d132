"""Share of the chip's peak bf16 FLOP/s that the prefill-window program
reaches for Keye-VL-2.0-30B-A3B: the model FLOPs of a window holding the
run's mean count of prompt tokens (benchmark/work_keye.py: the products,
the indexer's scores and attention over the selected keys;
``serving_decode_prefill_tokens_total`` over
``serving_decode_prefill_windows_total``, totals of the whole run) over the
program's mean device time in the trace, over the published peak.  A
prompt's last window is computed whole and counted by its own tokens, and
a window's reads of EARLIER windows' positions are not counted (the reader
knows a window's tokens, not its index), so the share errs low; a window
also reads every weight held (10.7 ms at the published bandwidth), which
bounds it from above.  Returns nothing where the program keeps no such
account, the cell's model is another configuration's, or the trace names no
such program."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py WINDOW_PROGRAM_NAME
WINDOW = "jit_prefill_window"
TOKENS = "serving_decode_prefill_tokens_total"
WINDOWS = "serving_decode_prefill_windows_total"


def read(facts, registry=None):
    from benchmark import engine_counters, manifest, work_keye

    trace, peaks = facts.get("trace"), facts.get("peaks")
    model = facts.get("serve_model")
    if None in (trace, peaks, model) or "serve_steps" not in facts:
        return None
    decode = manifest.load_layer_metric("keye_decode_hbm_share.serve")
    tokens = engine_counters._series(TOKENS, registry)
    windows = engine_counters._series(WINDOWS, registry)
    runs = [d for name, _, d in trace["modules"] if name.startswith(WINDOW)]
    if decode.totals(registry) is None or tokens is None \
            or windows is None or not runs:
        return None
    n_windows = sum(windows["series"].values())
    hp = decode.hparams(model)
    if not n_windows or hp is None:
        return None
    flops = work_keye.prefill_window_flops(
        hp, sum(tokens["series"].values()) / n_windows)
    achieved = flops / (sum(runs) / len(runs))
    return 100.0 * achieved / peaks["bf16_flops_per_s"]
