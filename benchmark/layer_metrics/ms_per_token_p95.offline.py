"""Per request of the closed loop: time from when it was sent to its last
token, over the tokens it emitted; 95th percentile over the requests sent
in the window, a failed or unfinished request counting as the worst.  Above
capacity the queue is always full, so this tail swings with the smallest
change and is recorded here, not judged end to end."""

LAYER = "engine scheduler"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def read(facts):
    requests = facts.get("serve_requests")
    return None if requests is None else requests["ms_per_token_p95"]
