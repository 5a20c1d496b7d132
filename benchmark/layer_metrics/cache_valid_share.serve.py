"""Share of the cache bytes in the decode steps' buckets that were valid:
the ring and full-cache entries the live rows hold
(``serving_decode_cache_read_bytes_total{kind}``) over the bytes the arrays
of those kinds span in each step's ``(rows, positions)`` bucket
(``serving_decode_cache_span_bytes_total{kind}``), totals of the whole run
(benchmark/engine_counters.py).  What one queue of short and long rows
costs while every slot owns whole arrays at their largest size: a step
that reads its arrays whole moves 100 / this share times the bytes it
needs.  Returns nothing where the program counts no span (any contract
that states none, any commit before the series)."""

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(facts, registry=None):
    from benchmark import manifest

    if "serve_steps" not in facts:
        return None
    decode = manifest.load_layer_metric("winfull_decode_hbm_share.serve")
    valid = decode.by_kind(decode.CACHE_READ, registry)
    span = decode.by_kind(decode.CACHE_SPAN, registry)
    if valid is None or span is None or not sum(span.values()):
        return None
    return 100.0 * sum(valid.values()) / sum(span.values())
