"""Mean time of one decode step as the engine clocks it (dispatch to the
device-to-host read of the step's tokens), over the untraced steps of the
window."""

LAYER = "model step"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(facts):
    steps = facts.get("serve_steps")
    if steps is None or not steps["count"]:
        return None
    return 1e3 * steps["seconds"] / steps["count"]
