"""Share of the decode step program's device time that goes to choosing
and fetching what a row attends over (learned sparse attention,
models/keye.py): the self seconds of the operations whose scope path holds
``dsa.index`` (the indexer's projections, the index key's norm and
rotation, the index scores of a row's cached keys), ``dsa.select`` (the
exact top-k) or ``dsa.gather`` (the fetch of the selected keys and values
by position) over those of all the step programs' operations, from the
run's own trace, by ``step_stream_mix_share.serve``'s ``shares`` with these
scopes.  The three lie INSIDE a word of benchmark/program_parts.py
(``attention_proj``, ``attention_core``), so this share is a part of those
and not an eleventh beside them; the attention over the fetched entries is
not in it.  What the selection costs beside the bytes it saves: a map of
the program, not a goal.  Returns nothing in another kind of cell, in an
untraced run, on the CPU, and where no operation of the step holds such a
scope (any other model, any commit before the scopes)."""

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
PROGRAMS = ("jit_run",)
SCOPES = ("dsa.index", "dsa.select", "dsa.gather")


def read(facts):
    from benchmark import manifest, program_parts

    if facts.get("trace") is None or "serve_steps" not in facts:
        return None
    path = program_parts.newest_path()
    mix = manifest.load_layer_metric("step_stream_mix_share.serve")
    found = mix.shares(path, PROGRAMS, SCOPES) if path else None
    if not found or not found[0] or not found[1]:
        return None
    return 100.0 * found[0] / found[1]
