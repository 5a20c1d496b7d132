"""Share of its roofline that the Pallas grouped product (``megablox.gmm``,
models/pangu_moe.py ``grouped_product``) reaches inside the decode step of
the window-and-full contract (Command A+): per step, the least time the
chip could take for the three products of every layer
(benchmark/work_command_a.py ``grouped_product_work``: the matrices of the
held experts that a live row chose, ``serving_decode_experts_touched_total``,
and 2 k n operations an assignment,
``serving_decode_expert_assignments_total``, both over
``serving_decode_steps_total``, totals of the whole run), over the
kernel's device time a step: the durations of the trace's ``gmm`` events
that lie inside a ``jit_run`` program, over the programs counted.  At two
assignments an expert the bound is the bytes.  The trace is read again
from the run's own directory, because the driver's facts keep only the
ten largest operations.  Returns nothing where the program counts no
touched experts (another contract, an earlier commit), or the trace holds
no step program or no such kernel (the CPU, where XLA's own grouped
product runs)."""

import bisect
import glob
import os

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

KERNEL = "gmm"
ASSIGNMENTS = "serving_decode_expert_assignments_total"


def newest_trace():
    """The device events of the newest trace under the checkout's
    ``.cache/benchmark_out``: ``(operations, programs)`` of the plane that
    ran most, or None."""
    from benchmark import manifest, trace_reduce

    found = glob.glob(os.path.join(
        manifest.ROOT, ".cache", "benchmark_out", "*", "trace", "**",
        "*.xplane.pb"), recursive=True)
    if not found:
        return None
    planes = trace_reduce.read_device_events(
        trace_reduce.load(max(found, key=os.path.getmtime)))
    lines = max(
        planes.values(), default=None,
        key=lambda l: len(l.get(trace_reduce.OP_LINE, ())))
    if not lines:
        return None
    return (lines.get(trace_reduce.OP_LINE, []),
            lines.get(trace_reduce.MODULE_LINE, []))


def kernel_seconds_a_step(ops, modules, step="jit_run"):
    """Seconds of ``KERNEL`` events inside ``step`` programs, over the
    programs; None where there is none of either."""
    spans = sorted(
        (s, s + d) for name, s, d in modules if name.startswith(step))
    starts = [a for a, _ in spans]

    def inside(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < spans[i][1]

    seconds = sum(
        d for name, s, d in ops if name.startswith(KERNEL) and inside(s))
    return seconds / len(spans) if spans and seconds else None


def read(facts, registry=None, events=None):
    from benchmark import manifest, work_command_a

    trace, peaks = facts.get("trace"), facts.get("peaks")
    model = facts.get("serve_model")
    if None in (trace, peaks, model) or "serve_steps" not in facts:
        return None
    decode = manifest.load_layer_metric("winfull_decode_hbm_share.serve")
    n_steps = decode.total(decode.STEPS, registry)
    touched = decode.total(decode.TOUCHED, registry)
    assigned = decode.total(ASSIGNMENTS, registry)
    hp = decode.hparams(model)
    if not n_steps or not touched or assigned is None or hp is None:
        return None
    events = events or newest_trace()
    took = events and kernel_seconds_a_step(*events, step=decode.STEP)
    if not took:
        return None
    d, f = hp["d_model"], hp["d_expert"]
    least = sum(
        work_command_a.roofline_seconds(work_command_a.grouped_product_work(
            k, n, touched / n_steps, assigned / n_steps,
            model["weight_itemsize"]), peaks)
        for k, n in ((d, f), (d, f), (f, d)))
    return 100.0 * least / took
