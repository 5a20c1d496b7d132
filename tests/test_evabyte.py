"""EvaByte (models/evabyte.py) against its plain reference
(benchmark/reference/evabyte.py) on seeded weights, at a small size on the
CPU: the whole-sequence forward, prefill by window and then decode through
the two kinds of cache, and the same through a real ``GenerativeEngine``.

Size: 2 layers, d_model 64, 4 heads of 16, window 32, chunk 4, context
160, 2 prediction heads; weights from benchmark/weights.py, with the
spreads of ``phi``, ``mu`` and the query/key kernels raised so that the
attention is peaked and the chunk summaries carry weight: a fault in
either kind of cache then moves the logits by far more than a tolerance.

Tolerances.  The program in float32 and the reference compute the same
function in another order of summation (windows and blocks of queries
against one full mask), so their logits differ by float32 rounding:
observed 1e-5 at a logit spread of 1; ``F32_TOL`` 2e-4 leaves a decade
for other seeds and is 1/5000 of a logit's spread.  In bfloat16 (the
served precision) products round to 8 bits: over 48 decoded positions the
root mean square of the logits' error was 0.027 of their spread;
``BF16_TOL`` 0.05 is twice that.  Leaving the chunk table out moves a
step's logits by 0.14 (root mean square), the ring by 1.4: the last test
but two holds both over twice ``BF16_TOL``, 700 times ``F32_TOL``.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.generative

VOCAB, WINDOW, CHUNK = 320, 32, 4
HP = dict(
    vocab_size=VOCAB, d_model=64, n_layers=2, n_heads=4, head_dim=16,
    d_ff=96, window_size=WINDOW, chunk_size=CHUNK, num_pred_heads=2,
)
RULES = {
    "embed/embedding": 0.1, "q_proj/kernel": 0.3, "k_proj/kernel": 0.3,
    "phi": 0.5, "mu": 0.5, "kernel": "fan_in", "scale": 0.02,
}
SHAPE = dict(window=WINDOW, chunk=CHUNK)
F32_TOL, BF16_TOL = 2e-4, 0.05
rms = lambda e: float(np.sqrt(np.mean(np.square(e))))
MAX_IN, MAX_OUT = 96, 64


def build(dtype="float32", seed=7):
    import jax

    from benchmark import weights
    from tpu_pipelines.models import evabyte

    model = evabyte.build_evabyte_model(
        {**HP, "compute_dtype": dtype, "param_dtype": dtype})
    sample = {"inputs": np.ones((1, MAX_IN), np.int32),
              "targets": np.ones((1, 8), np.int32)}
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), sample)["params"])
    return model, weights.make_weights(shapes, RULES, seed)


@pytest.fixture(scope="module")
def f32():
    return build()


def reference_params(params):
    from benchmark import weights
    from benchmark.reference import evabyte as ref

    return ref.from_served_tree(weights.flat_leaves(params), HP["n_layers"])


def reference_logits(params, tokens):
    """Every head's logits [len, heads, vocab] for one sequence."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import evabyte as ref

    rp = reference_params(params)
    n = len(tokens)
    padded = np.zeros(-(-n // CHUNK) * CHUNK, np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(rp, jnp.asarray(padded), **SHAPE)
        out = ref.head_logits(rp, h)
    return np.asarray(out)[:n].reshape(n, HP["num_pred_heads"], VOCAB)


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        2, VOCAB, size=n).astype(np.int32)


def decode_fns(model):
    from tpu_pipelines.models.evabyte import make_continuous_decode_fns

    return make_continuous_decode_fns(
        model, max_decode_len=MAX_OUT, eos_id=VOCAB, max_input_len=MAX_IN)


def through_the_cache(model, params, fns, tokens, n_new):
    """Greedy decode of one sequence through the contract's own programs:
    prefill by window, then single steps.  -> (tokens [n_new], head-0
    logits [n_new, vocab])."""
    import jax
    import jax.numpy as jnp

    window = jax.jit(fns.prefill_window)
    step = jax.jit(fns.step, static_argnums=6)
    cache = fns.blank_cache(1)
    for m in range(-(-len(tokens) // WINDOW)):
        part = tokens[m * WINDOW:(m + 1) * WINDOW]
        padded = np.zeros((1, WINDOW), np.int32)
        padded[0, :len(part)] = part
        cache, logits = window(
            params, cache, padded, np.int32(len(part)), np.int32(m))
    out, rows = [], []
    pos = len(tokens)
    none = jnp.zeros((1, 0))
    while True:
        rows.append(np.asarray(logits[0]))
        out.append(int(np.argmax(rows[-1])))
        if len(out) == n_new:
            return np.asarray(out, np.int32), np.stack(rows)
        cache, logits = step(
            params, cache, np.asarray(out[-1:], np.int32),
            np.asarray([pos], np.int32), none, none, MAX_OUT)
        pos += 1


# (prompt length, new tokens): ends mid-chunk and mid-window and decodes
# over two roll-overs (61 -> 125 crosses 64 and 96); ends on a window's
# last position; ends on a chunk's; one byte.
CASES = [(61, 64), (64, 40), (44, 30), (1, 40)]


@pytest.mark.parametrize("n", [100, 32, 7, 160])
def test_forward_matches_the_reference_for_every_head(f32, n):
    """The program's whole-sequence pass (window by window) against the
    reference's one full mask, all prediction heads."""
    model, params = f32
    tokens = prompt(n, n)
    got = np.asarray(model.apply({"params": params}, {"inputs": tokens[None]}))
    want = reference_logits(params, tokens)
    assert got.shape == (1, n, HP["num_pred_heads"], VOCAB)
    assert np.abs(got[0] - want).max() < F32_TOL
    assert want.std() > 0.5          # the logits are not all alike


@pytest.mark.parametrize("n_prompt,n_new", CASES)
def test_prefill_by_window_then_decode_matches_the_reference(
        f32, n_prompt, n_new):
    """Prefill a window at a time, then decode through ring and chunk
    table: each step's logits against the reference's full pass over the
    prompt with the served tokens behind it."""
    model, params = f32
    tokens = prompt(n_prompt, n_prompt)
    served, logits = through_the_cache(
        model, params, decode_fns(model), tokens, n_new)
    whole = np.concatenate([tokens, served])
    want = reference_logits(params, whole)[n_prompt - 1:-1, 0]
    assert np.abs(logits - want).max() < F32_TOL
    assert len(set(served.tolist())) > 3     # not one token over and over


def test_served_precision_stays_near_the_reference():
    """bfloat16 weights, products and cache, as served."""
    model, params = build("bfloat16")
    tokens = prompt(3, 61)
    served, logits = through_the_cache(
        model, params, decode_fns(model), tokens, 48)
    want = reference_logits(params, np.concatenate([tokens, served]))
    want = want[60:-1, 0]
    assert rms(logits - want) < BF16_TOL * want.std()


@pytest.mark.parametrize("dropped", ["chunk", "window"])
def test_the_tolerance_would_notice_a_cache_left_out(f32, dropped):
    """The comparison has teeth: with the chunk table (or the ring)
    zeroed after prefill, the next step's logits miss the reference by
    more than twice the bfloat16 tolerance."""
    import jax

    model, params = f32
    fns = decode_fns(model)
    tokens = prompt(5, 90)
    cache = fns.blank_cache(1)
    for m in range(3):
        part = np.zeros((1, WINDOW), np.int32)
        n = min(WINDOW, 90 - m * WINDOW)
        part[0, :n] = tokens[m * WINDOW:m * WINDOW + n]
        cache, logits = jax.jit(fns.prefill_window)(
            params, cache, part, np.int32(n), np.int32(m))
    first = int(np.argmax(logits[0]))
    broken = jax.tree_util.tree_map_with_path(
        lambda p, x: x * 0 if fns.cache_kind_of(p) == dropped else x, cache)
    none = np.zeros((1, 0), np.float32)
    args = (np.asarray([first], np.int32), np.asarray([90], np.int32),
            none, none, MAX_OUT)
    want = reference_logits(params, np.append(tokens, first))[-1, 0]
    _, good = fns.step(params, cache, *args)
    _, bad = fns.step(params, broken, *args)
    assert np.abs(np.asarray(good[0]) - want).max() < F32_TOL
    assert rms(np.asarray(bad[0]) - want) > 2 * BF16_TOL * want.std()


@pytest.fixture(scope="module")
def engine_run(f32):
    """A real engine, 4 slots, chunked prefill on: ten requests whose
    prompts are 1 to 3 windows long, offered in two bursts."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = f32
    reg = MetricsRegistry()
    engine = GenerativeEngine(
        decode_fns(model), params, max_batch_size=4, prefill_chunk_pages=1,
        registry=reg)
    lengths = [61, 64, 44, 1, 96, 33, 17, 80, 95, 50]
    budgets = [64, 40, 30, 40, 12, 9, 25, 31, 8, 20]
    prompts = [prompt(100 + i, n) for i, n in enumerate(lengths)]
    try:
        engine.warm()
        handles = []
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            handles.append(engine.submit_nowait(p, max_new_tokens=m))
            if i == 4:
                handles[0].wait(120.0)
        outs = [h.wait(120.0) for h in handles]
    finally:
        engine.close()
    return engine, reg, prompts, budgets, outs


@pytest.mark.parametrize("i", range(10))
def test_engine_serves_what_the_reference_would(f32, engine_run, i):
    """Through the scheduler, the arena, insert, move and clear: every
    served token is the reference's best at its position, or within the
    tolerance of it, and the stream is the one the same row gives alone
    (a row's logits do not depend on who shares its batch)."""
    model, params = f32
    engine, _, prompts, budgets, outs = engine_run
    served = np.asarray(outs[i])
    assert len(served) == budgets[i]
    n = len(prompts[i])
    want = reference_logits(
        params, np.concatenate([prompts[i], served]))[n - 1:-1, 0]
    picked = want[np.arange(len(served)), served]
    assert (want.max(-1) - picked).max() < F32_TOL
    alone, _ = through_the_cache(
        model, params, decode_fns(model), prompts[i], budgets[i])
    assert served.tolist() == alone.tolist()


def test_engine_prefill_cost_follows_the_prompts_own_windows(engine_run):
    engine, reg, prompts, budgets, _ = engine_run
    get = lambda name, *lab: reg.get(name).labels("0", *lab).get()
    windows = sum(-(-len(p) // WINDOW) for p in prompts)
    assert get("serving_decode_prefill_windows_total") == windows == 21
    assert get("serving_decode_prefill_tokens_total") == sum(
        len(p) for p in prompts)
    assert get("serving_decode_engine_phase_total", "prefill.window") \
        == windows
    assert get("serving_decode_engine_phase_total", "prefill") == 0
    assert get("serving_decode_engine_phase_total", "insert") == 10
    assert engine.compiles_after_warm == 0
    # a step at position t: (t + 1) % 4 == 0 stores a summary, t % 32 == 0
    # begins a window
    fed = [
        t for p, m in zip(prompts, budgets)
        for t in range(len(p), len(p) + m - 1)]
    assert get("serving_decode_chunk_summaries_total") == sum(
        (t + 1) % CHUNK == 0 for t in fed)
    assert get("serving_decode_window_rollovers_total") == sum(
        t % WINDOW == 0 for t in fed)
    entry = 2 * 2 * 4 * 16 * 4          # k and v, layers, heads, dim, f32
    assert get("serving_decode_cache_read_bytes_total", "window") == sum(
        t % WINDOW + 1 for t in fed) * entry
    assert get("serving_decode_cache_read_bytes_total", "chunk") == sum(
        t // WINDOW * (WINDOW // CHUNK) for t in fed) * entry


def test_the_contract_states_what_the_engine_may_not_guess(f32):
    import jax

    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = f32
    fns = decode_fns(model)
    cache = fns.blank_cache(3)
    kinds = {
        fns.cache_kind_of(p) for p, _ in
        jax.tree_util.tree_flatten_with_path(cache)[0]}
    assert kinds == set(fns.cache_kinds) == {"window", "chunk"}
    assert not any(k.by_position for k in fns.cache_kinds.values())
    assert all(k.in_place for k in fns.cache_kinds.values())
    assert all(k.written for k in fns.cache_kinds.values())
    shapes = {x.shape for x in jax.tree_util.tree_leaves(cache)}
    # whole windows over 96 + 64 positions: 5, so 40 chunks
    assert shapes == {(3, WINDOW, 4, 16), (3, 160 // CHUNK, 4, 16)}
    assert int(fns.first_decode_pos(np.array([[1, 1, 1, 0, 0]]))) == 3
    assert fns.prefill is None
    with pytest.raises(ValueError, match="prefilled by window"):
        GenerativeEngine(fns, params, prefix_cache_entries=2)


def test_who_sees_what_in_a_hand_sized_case():
    """Window 4, chunk 2, 10 positions.  Windows {0..3} {4..7} {8, 9};
    chunks {0,1} {2,3} | {4,5} {6,7} | {8,9}."""
    from benchmark.reference.evabyte import visible

    exact, summary = visible(np.arange(10), 10, 4, 2)
    exact, summary = np.asarray(exact), np.asarray(summary)
    sees = lambda row: np.flatnonzero(row).tolist()
    assert [sees(r) for r in exact] == [
        [0], [0, 1], [0, 1, 2], [0, 1, 2, 3],
        [4], [4, 5], [4, 5, 6], [4, 5, 6, 7],
        [8], [8, 9]]
    assert [sees(r) for r in summary] == [
        [], [], [], [],                      # no window is complete yet
        [0, 1], [0, 1], [0, 1], [0, 1],      # the first window's chunks
        [0, 1, 2, 3], [0, 1, 2, 3]]          # never its own window's
