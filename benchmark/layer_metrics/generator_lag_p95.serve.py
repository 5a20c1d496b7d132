"""How late the benchmark's own generator sent each request against its due
time, 95th percentile over the requests due in the window.  A starved
generator must not be read as a fast server."""

LAYER = "load generator"
UNIT = "ms"
MOVES = "serve_ms_per_token_p95"
SOURCE = "host_clock"


def read(facts):
    requests = facts.get("serve_requests")
    return None if requests is None else requests["generator_lag_p95_ms"]
