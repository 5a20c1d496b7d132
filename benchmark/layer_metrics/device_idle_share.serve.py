"""Share of the traced span (the last seconds of the window, under steady
traffic) in which no operation ran on the device: 1 - busy / window."""

LAYER = "device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts.get("trace")
    if trace is None or "serve_steps" not in facts:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
