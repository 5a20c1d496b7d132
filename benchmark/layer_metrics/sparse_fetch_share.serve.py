"""What the decode steps fetched of their caches over what a dense step
would have: the bytes of keys and values the rows selected and fetched
plus the bytes of index keys scored to select them
(``serving_decode_cache_span_bytes_total{kind="kv"}`` and ``{kind="index"}``
together) over the keys and values that were valid for the same rows
(``serving_decode_cache_read_bytes_total{kind="kv"}``), totals of the whole
run (benchmark/engine_counters.py).  Under 100 % the selection reads less
than attention over every cached position would; it falls as rows grow
deeper than ``index_topk``.  Returns nothing where the program counts other
kinds of cache (any other contract) or no span."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(facts, registry=None):
    from benchmark import manifest

    if "serve_steps" not in facts:
        return None
    decode = manifest.load_layer_metric("keye_decode_hbm_share.serve")
    valid = decode.by_kind(decode.CACHE_READ, registry)
    span = decode.by_kind(decode.CACHE_SPAN, registry)
    if valid is None or span is None or not valid["kv"]:
        return None
    return 100.0 * (span["kv"] + span["index"]) / valid["kv"]
