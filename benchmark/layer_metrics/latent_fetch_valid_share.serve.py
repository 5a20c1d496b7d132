"""Share of the latent rows that the decode steps' attention kernel
fetched that were valid: the rows the live rows hold
(``serving_decode_cache_read_bytes_total{kind="latent"}``) over the key
blocks handed to them, whole blocks up to the one that holds a row's
position (``serving_decode_cache_span_bytes_total{kind="latent"}``),
totals of the whole run (benchmark/engine_counters.py).  How much of the
kernel's reads and products a smaller key block would save: it moves
100 / this share times the bytes it needs.  Returns nothing where the
program counts no span for the kind (any other contract, any commit
before the kernel)."""

LAYER = "kernels / device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"

CACHE_READ = "serving_decode_cache_read_bytes_total"
CACHE_SPAN = "serving_decode_cache_span_bytes_total"
KIND = "latent"


def read(facts, registry=None):
    from benchmark import engine_counters

    if "serve_steps" not in facts:
        return None
    valid, span = (
        (engine_counters.by_label(family, "kind", registry) or {}).get(KIND)
        for family in (CACHE_READ, CACHE_SPAN))
    if not valid or not span:
        return None
    return 100.0 * valid / span
