"""Share of the HBM roofline that the decode step program reaches: the bytes
one step must read (decoder and embedding weights at their stored width,
plus the self- and cross-attention K/V of the live rows at their filled
lengths, averaged over the window's steps; benchmark/work.py) over the
published bytes per second, over the step program's mean device time in
the trace.  Returns nothing where the trace names no step program."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# The engine jits its step as ``run``; every (batch, KV) bucket program
# carries that name on the trace's "XLA Modules" line.
STEP = "jit_run"


def read(facts):
    from benchmark import work

    trace, steps = facts.get("trace"), facts.get("serve_steps")
    peaks, model = facts.get("peaks"), facts.get("serve_model")
    requests = facts.get("serve_requests")
    if None in (trace, steps, peaks, model, requests):
        return None
    runs = [d for name, _, d in trace["modules"] if name.startswith(STEP)]
    if not runs or not steps["counter_steps"]:
        return None
    shape = {k: model[k] for k in ("n_layers", "n_heads", "head_dim")}
    per_step = work.t5_decoder_weight_bytes(
        d_model=model["d_model"], d_ff=model["d_ff"],
        vocab_size=model["vocab_size"],
        weight_itemsize=model["weight_itemsize"], **shape,
    ) + work.t5_decode_kv_bytes(
        self_positions=requests["self_positions_read"],
        cross_positions=requests["cross_positions_read"],
        kv_itemsize=model["kv_itemsize"], **shape,
    ) / steps["counter_steps"]
    least_s = per_step / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(runs) / len(runs))
