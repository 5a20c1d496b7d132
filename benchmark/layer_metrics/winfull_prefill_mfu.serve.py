"""Share of the chip's peak bf16 FLOP/s that the prefill-window program
reaches under the window-and-full contract (Command A+): the model FLOPs of
a window holding the run's mean count of prompt tokens
(benchmark/work_command_a.py; ``serving_decode_prefill_tokens_total`` over
``serving_decode_prefill_windows_total``, totals of the whole run) over the
program's mean device time in the trace, over the published peak.  A
prompt's last window is computed whole and counted by its own tokens, and
a window's products with the keys of earlier windows are not counted, so
the share errs low; a window also reads every weight held (11.6 ms at the
published bandwidth), which bounds this share from above at 512 tokens.
Returns nothing where the program counts no window and full bytes (another
contract) or the trace names no such program."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py WINDOW_PROGRAM_NAME
WINDOW = "jit_prefill_window"
TOKENS = "serving_decode_prefill_tokens_total"
WINDOWS = "serving_decode_prefill_windows_total"


def read(facts, registry=None):
    from benchmark import manifest, work_command_a

    trace, peaks = facts.get("trace"), facts.get("peaks")
    model = facts.get("serve_model")
    if None in (trace, peaks, model) or "serve_steps" not in facts:
        return None
    decode = manifest.load_layer_metric("winfull_decode_hbm_share.serve")
    tokens = decode.total(TOKENS, registry)
    n_windows = decode.total(WINDOWS, registry)
    runs = [d for name, _, d in trace["modules"] if name.startswith(WINDOW)]
    if decode.by_kind(decode.CACHE_READ, registry) is None \
            or not n_windows or not runs:
        return None
    hp = decode.hparams(model)
    if hp is None:
        return None
    flops = work_command_a.prefill_window_flops(hp, tokens / n_windows)
    achieved = flops / (sum(runs) / len(runs))
    return 100.0 * achieved / peaks["bf16_flops_per_s"]
