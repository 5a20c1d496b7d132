"""Command A+ (models/command_a.py) against its plain reference
(benchmark/reference/command_a.py) on seeded weights, at a small size on
the CPU: the whole-sequence forward, prefill by window and then decode
through the two kinds of cache with the ring wrapped both times, the
grouped-query mapping, the interleaved rotation, what a full and a window
layer may see, the expert layer's shares against the uncut layer, the
grouped product at the chip's tiles, and the same through a real
``GenerativeEngine`` with short and long rows in one batch.

Size: 8 layers (two periods of three window layers and a full one),
d_model 64, 8 query heads over 2 key/value heads of 16, window 16, 16
experts of width 32 with 4 a token, 4 of them held (4 shares), 2 shared
experts; prefill windows of 8, context 64 + 40 (up to 6.5 windows: the
ring wraps while a prompt is prefilled AND while it decodes); weights from
benchmark/weights.py with the spreads of the router and of the query / key
projections raised, so that the four chosen are not a matter of rounding
and attention is peaked: a fault in a ring, in the full cache or in the
routed sum then moves the logits by far more than a tolerance.

Tolerances.  The program in float32 and the reference compute the same
function in another order of summation (blocks of keys under an online
softmax, a ring, rows sorted by expert against a masked loop), so their
logits differ by float32 rounding: observed 3.3e-6 at a logit spread of
0.4; ``F32_TOL`` 5e-5 leaves a decade for other seeds.  In bfloat16 (the
served precision) the root mean square of the logits' error over 40
decoded positions was 0.031 to 0.052 of their spread on three seeds;
``BF16_TOL`` 0.12 is over twice that.  The other head mapping moves the
logits by 1.3 of their spread, a rotated full layer by 0.57, leaving the
routed part out by 0.56: each test holds its fault over ``2 * BF16_TOL``.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.generative

VOCAB, WINDOW, PREFILL, HELD, EXPERTS, TOP_K = 96, 16, 8, 4, 16, 4
HP = dict(
    vocab_size=VOCAB, d_model=64, n_layers=8, n_heads=8, n_kv_heads=2,
    head_dim=16, window_size=WINDOW, full_every=4, d_expert=32,
    n_experts=EXPERTS, experts_held=HELD, expert_offset=0,
    experts_per_token=TOP_K, n_shared_experts=2,
)
# what the reference cannot read off the weights (its SIZES, at this size)
SIZES = dict(window=WINDOW, head_dim=16, top_k=TOP_K, n_shared=2)
RULES = {
    "embed/embedding": 0.05, "router": 0.5, "q_proj/kernel": 0.2,
    "k_proj/kernel": 0.2, "experts_gate": 0.125, "experts_up": 0.125,
    "experts_down": 0.177, "scale": "around_one", "kernel": "fan_in",
}
F32_TOL, BF16_TOL = 5e-5, 0.12
rms = lambda e: float(np.sqrt(np.mean(np.square(e))))
MAX_IN, MAX_OUT = 64, 40
ENTRY = 2 * 2 * 16           # numbers a cached position holds in a layer


def build(dtype="float32", seed=7, **over):
    import jax

    from benchmark import weights
    from tpu_pipelines.models import command_a

    model = command_a.build_command_a_model(
        {**HP, **over, "compute_dtype": dtype, "param_dtype": dtype})
    sample = {"inputs": np.ones((1, MAX_IN), np.int32)}
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), sample)["params"])
    return model, weights.make_weights(shapes, RULES, seed)


@pytest.fixture(scope="module")
def f32():
    return build()


def reference_params(params):
    from benchmark import weights
    from benchmark.reference import command_a as ref

    flat = weights.flat_leaves(params)
    return ref.from_served_tree(flat, ref.n_layers_of(flat))


REFERENCE_PASS = {}


def reference_logits(params, tokens, **sizes):
    """[len, vocab] for one sequence.  The pass is causal, so the sequence
    is padded to a multiple of 32 and the pass compiled once a length."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import command_a as ref

    sizes = {**SIZES, **sizes}
    key = tuple(sorted(sizes.items()))
    if key not in REFERENCE_PASS:
        REFERENCE_PASS[key] = jax.jit(lambda rp, tokens: ref.head_logits(
            rp, ref.hidden(rp, tokens, **sizes), **sizes))
    n = len(tokens)
    padded = np.zeros((-(-n // 32) * 32,), np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(REFERENCE_PASS[key](
            reference_params(params), jnp.asarray(padded)))[:n]


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        2, VOCAB, size=n).astype(np.int32)


DECODE_FNS = {}


def decode_fns(model, **over):
    """The contract of ``model``, made once for each set of keywords, with
    its window and step programs (``fns.jitted``) compiled once too."""
    import jax

    from tpu_pipelines.models.command_a import make_continuous_decode_fns

    key = (id(model), tuple(sorted(over.items())))
    if key not in DECODE_FNS:
        fns = make_continuous_decode_fns(model, **{**dict(
            max_decode_len=MAX_OUT, eos_id=VOCAB, max_input_len=MAX_IN,
            prefill_window_len=PREFILL), **over})
        fns.jitted = (
            jax.jit(fns.prefill_window),
            jax.jit(fns.step, static_argnums=6))
        DECODE_FNS[key] = (model, fns)      # the model kept: its id is the key
    return DECODE_FNS[key][1]


def prefill(fns, params, tokens, cache=None):
    """A prompt's windows into a row (blank unless given) -> (cache, last
    logits)."""
    window, p = fns.jitted[0], fns.prefill_window_len
    cache = fns.blank_cache(1) if cache is None else cache
    for m in range(-(-len(tokens) // p)):
        part = tokens[m * p:(m + 1) * p]
        padded = np.zeros((1, p), np.int32)
        padded[0, :len(part)] = part
        cache, logits = window(
            params, cache, padded, np.int32(len(part)), np.int32(m))
    return cache, logits


def through_the_cache(params, fns, tokens, n_new, cache=None):
    """Greedy decode of one sequence through the contract's own programs:
    prefill by window, then single steps.  -> (tokens [n_new], logits
    [n_new, vocab])."""
    import jax.numpy as jnp

    step = fns.jitted[1]
    cache, logits = prefill(fns, params, tokens, cache)
    out, rows = [], []
    pos = len(tokens)
    none = jnp.zeros((1, 0))
    while True:
        rows.append(np.asarray(logits[0]))
        out.append(int(np.argmax(rows[-1])))
        if len(out) == n_new:
            return np.asarray(out, np.int32), np.stack(rows)
        cache, logits, _ = step(
            params, cache, np.asarray(out[-1:], np.int32),
            np.asarray([pos], np.int32), none, none, fns.cache_positions)
        pos += 1


# (prompt length, new tokens): ends inside a prefill window, 2.3 rings deep
# and wraps again while decoding; the longest prompt (4 rings) and the
# longest answer; one token (the ring fills while decoding, then wraps);
# ends on a ring's last position; shorter than the ring throughout.
CASES = [(37, 40), (64, 40), (1, 30), (48, 20), (5, 6)]


@pytest.mark.parametrize("n", [50, 16, 7, 104])
def test_forward_matches_the_reference(f32, n):
    model, params = f32
    tokens = prompt(n, n)
    got = np.asarray(model.apply({"params": params}, {"inputs": tokens[None]}))
    want = reference_logits(params, tokens)
    assert got.shape == (1, n, VOCAB)
    assert np.abs(got[0] - want).max() < F32_TOL
    assert want.std() > 0.2          # the logits are not all alike


@pytest.mark.parametrize("n_prompt,n_new", CASES)
def test_prefill_by_window_then_decode_matches_the_reference(
        f32, n_prompt, n_new):
    """Windows written into three rings and a by-position array a period,
    then steps over both: each step's logits against the reference's one
    full pass over the prompt with the served tokens behind it."""
    model, params = f32
    tokens = prompt(n_prompt, n_prompt)
    served, logits = through_the_cache(
        params, decode_fns(model), tokens, n_new)
    whole = np.concatenate([tokens, served])
    want = reference_logits(params, whole)[n_prompt - 1:-1]
    assert np.abs(logits - want).max() < F32_TOL
    # not one token over and over
    assert len(set(served.tolist())) > min(3, n_new // 4)


def test_a_used_row_serves_what_a_blank_one_does(f32):
    """The engine prefills every prompt into ONE row and never clears it:
    a short prompt after a long one finds the long one's keys in the
    rings and past its own end in the full arrays, and must not see
    them."""
    model, params = f32
    fns = decode_fns(model)
    used, _ = prefill(fns, params, prompt(1, 64))
    served, logits = through_the_cache(
        params, fns, prompt(2, 11), 12, cache=used)
    alone, alone_logits = through_the_cache(params, fns, prompt(2, 11), 12)
    assert served.tolist() == alone.tolist()
    assert np.array_equal(logits, alone_logits)


def test_served_precision_stays_near_the_reference():
    """bfloat16 weights, products and cache, as served."""
    model, params = build("bfloat16")
    tokens = prompt(3, 37)
    served, logits = through_the_cache(
        params, decode_fns(model), tokens, 40)
    want = reference_logits(params, np.concatenate([tokens, served]))
    want = want[36:-1]
    assert rms(logits - want) < BF16_TOL * want.std()


# ------------------------------------------- heads, rotation, what is seen


def test_query_head_i_reads_key_value_head_i_over_g(f32):
    """8 query heads over 2 key/value heads: head ``i`` reads ``i // 4``.
    Under the other convention (``i % 2``) the same weights are another
    model: the reference with its query heads re-ordered that way, which
    is what that convention would compute, is far from the program."""
    import jax

    model, params = f32
    tokens = prompt(13, 50)
    got = np.asarray(
        model.apply({"params": params}, {"inputs": tokens[None]}))[0]
    assert np.abs(got - reference_logits(params, tokens)).max() < F32_TOL
    # head i of the other convention is head (i % 2) * 4 + i // 2 of this
    order = np.asarray([(i % 2) * 4 + i // 2 for i in range(8)])
    columns = (order[:, None] * 16 + np.arange(16)[None]).reshape(-1)

    def other(path, x):
        at = "/".join(str(getattr(k, "key", k)) for k in path)
        if at.endswith("q_proj/kernel"):
            return x[:, columns]
        return x[columns] if at.endswith("o_proj/kernel") else x

    wrong = reference_logits(
        jax.tree_util.tree_map_with_path(other, params), tokens)
    assert rms(got - wrong) > 2 * BF16_TOL * got.std()


def test_rotation_is_by_interleaved_pairs():
    """``(x_2j, x_2j+1)`` as the complex number ``x_2j + i x_2j+1`` times
    ``exp(i t theta ** (-2j / d))``; the split-halves code of
    models/evabyte.py on the same numbers is another rotation."""
    from tpu_pipelines.models.command_a import rope_interleaved
    from tpu_pipelines.models.evabyte import rope

    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 9, 3, 16)).astype(np.float32)
    pos = np.stack([np.arange(9), 40 + np.arange(9)])
    got = np.asarray(rope_interleaved(x, pos, 50000.0))
    z = x[..., 0::2] + 1j * x[..., 1::2]
    angle = pos[..., None, None] * 50000.0 ** (-np.arange(0, 16, 2) / 16)
    want = z * np.exp(1j * angle)
    assert np.abs(got[..., 0::2] - want.real).max() < 1e-5
    assert np.abs(got[..., 1::2] - want.imag).max() < 1e-5
    assert np.abs(got[0, 0] - x[0, 0]).max() == 0          # position 0
    halves = np.asarray(rope(x, pos, 50000.0))
    assert rms(got - halves) > 0.5 * x.std()


def test_a_full_layer_carries_no_positions_and_a_window_layer_does(f32):
    """The same inputs at other positions: a full layer's queries and
    keys do not move, a window layer's do."""
    from tpu_pipelines.models import command_a

    model, params = f32
    x = np.random.default_rng(8).normal(size=(1, 12, 64)).astype(np.float32)
    here, there = np.arange(12)[None], 70 + np.arange(12)[None]
    for layer, full in ((3, True), (2, False)):
        attn = command_a.GroupedAttention(model.cfg, full)
        p = {"params": params[f"layer_{layer}"]["attn"]}
        q0, k0, v0 = attn.apply(p, x, here, method="project")
        q1, k1, v1 = attn.apply(p, x, there, method="project")
        assert np.array_equal(v0, v1)
        assert np.array_equal(q0, q1) == np.array_equal(k0, k1) == full
    # a rotated full layer would be another model
    tokens = prompt(17, 50)
    got = np.asarray(
        model.apply({"params": params}, {"inputs": tokens[None]}))[0]
    rotated = reference_logits(params, tokens, full_every=100)
    assert rms(got - rotated) > 2 * BF16_TOL * got.std()


def test_a_window_layer_sees_sixteen_keys_and_not_the_seventeenth():
    """One window layer alone: the logits at ``t`` move with the token at
    ``t - 15`` and not with the token at ``t - 16``; through the whole
    pass, and through the ring after it has wrapped."""
    model, params = build(n_layers=1)
    fns = decode_fns(model)
    tokens = prompt(23, 40)
    t = 39

    def last(tokens):
        whole = np.asarray(model.apply(
            {"params": params}, {"inputs": tokens[None]}))[0, t]
        _, ring = through_the_cache(params, fns, tokens, 1)
        assert np.abs(whole - ring[0]).max() < F32_TOL
        return whole

    base = last(tokens)
    for back, seen in ((WINDOW - 1, True), (WINDOW, False)):
        other = tokens.copy()
        other[t - back] = (other[t - back] + 1 - 2) % (VOCAB - 2) + 2
        moved = np.abs(last(other) - base).max()
        assert (moved > 1e-3) == seen, (back, moved)
    # one full layer alone sees all of them
    model, params = build(n_layers=1, full_every=1)
    other = tokens.copy()
    other[0] = (other[0] + 1 - 2) % (VOCAB - 2) + 2
    apply = lambda x: np.asarray(
        model.apply({"params": params}, {"inputs": x[None]}))[0, t]
    assert np.abs(apply(other) - apply(tokens)).max() > 1e-3


# ------------------------------------------------------- the expert layer


def expert_layer(cfg_over, layer_params, x):
    from tpu_pipelines.models import command_a, pangu_moe

    cfg = command_a.build_command_a_model(
        {**HP, **cfg_over, "compute_dtype": "float32",
         "param_dtype": "float32"}).cfg
    return pangu_moe.RoutedExperts(cfg).apply({"params": layer_params}, x)


@pytest.fixture(scope="module")
def whole_layer():
    """One expert layer with all 16 experts held, and 24 tokens."""
    _, params = build(experts_held=EXPERTS)
    x = np.random.default_rng(11).normal(size=(24, 64)).astype(np.float32)
    return params["layer_1"]["ffn"], x


def _flat(tree, at=()):
    out = {}
    for k, v in tree.items():
        out.update(_flat(v, at + (k,)) if isinstance(v, dict)
                   else {at + (k,): v})
    return out


def reference_layer(layer, x, what="experts"):
    import jax

    from benchmark.reference import command_a as ref

    flat = {"ffn/" + "/".join(k): v for k, v in _flat(layer).items()}
    with jax.default_matmul_precision("highest"):
        if what == "shared":
            return np.asarray(ref.shared_experts(flat, "ffn", x, "f32", 2))
        return np.asarray(ref.experts(
            flat, "ffn", x, "f32", {**ref.SIZES, **SIZES}))


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        whole_layer):
    """Four chips of 4 experts each: what every share gives for its own
    experts, the shared experts' mean counted once, is the uncut
    reference's layer; and every token's four choices are computed by
    somebody."""
    layer, x = whole_layer
    shared = reference_layer(layer, x, "shared")
    total, chosen = shared.copy(), 0
    for share in range(EXPERTS // HELD):
        cut = slice(share * HELD, (share + 1) * HELD)
        part = {**layer, **{
            k: layer[k][cut]
            for k in ("experts_gate", "experts_up", "experts_down")}}
        y, picked = expert_layer({"expert_offset": share * HELD}, part, x)
        assert picked.shape == (24, HELD)
        chosen += int(np.asarray(picked).sum())
        total += np.asarray(y) - shared
    want = reference_layer(layer, x)
    assert np.abs(total - want).max() < F32_TOL
    assert chosen == 24 * TOP_K
    # a share alone is not the layer
    assert rms(np.asarray(y) - want) > 0.1 * want.std()


def test_the_shared_experts_are_averaged_and_the_weights_sum_to_one(
        whole_layer):
    """The mean of the two shared experts, not their sum (openPangu's
    layer sums its one), and eight weights that add up to 1 with no
    further factor."""
    import jax

    from benchmark.reference import command_a as ref

    layer, x = whole_layer
    no_routed = {**layer, "experts_down": layer["experts_down"] * 0}
    y, _ = expert_layer({"experts_held": EXPERTS}, no_routed, x)
    shared = reference_layer(layer, x, "shared")
    assert np.abs(np.asarray(y) - shared).max() < F32_TOL
    summed, _ = expert_layer(
        {"experts_held": EXPERTS, "shared_average": 0}, no_routed, x)
    assert np.abs(np.asarray(summed) - 2 * shared).max() < F32_TOL
    with jax.default_matmul_precision("highest"):
        weight = np.asarray(ref.routing(
            {"ffn/router": layer["router"]}, "ffn", x, "f32", TOP_K))
    assert ((weight > 0).sum(-1) == TOP_K).all()
    assert np.abs(weight.sum(-1) - 1).max() < 1e-6


def test_the_chips_grouped_product_is_xlas_at_the_new_tiles():
    """``grouped_product`` at Command A+'s expert shape (4,096 x 4,096)
    with the tile the model's table hands the kernel for it: the Pallas
    ``megablox`` kernel (what the chip runs; here through the interpreter)
    against XLA's ``ragged_dot`` (what this backend runs), rows sorted by
    group, a group left empty, rows behind the last group."""
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models import pangu_moe

    tile = pangu_moe.TILES[(4096, 4096)]
    assert tile != pangu_moe.TILE_IN and 4096 % tile[0] == 4096 % tile[1] == 0
    key = jax.random.key(3)
    rows = jax.random.normal(key, (64, 4096), jnp.float32).astype(
        jnp.bfloat16)
    weights = (jax.random.normal(
        jax.random.fold_in(key, 1), (3, 4096, 4096), jnp.float32
    ) / 64).astype(jnp.bfloat16)
    sizes = jnp.asarray([30, 0, 11], jnp.int32)
    want = jax.lax.ragged_dot(
        rows, weights, sizes, preferred_element_type=jnp.float32)
    got = pangu_moe.megablox(rows, weights, sizes, tile, interpret=True)
    filled = int(sizes.sum())
    assert np.abs(np.asarray(got - want)[:filled]).max() < 2e-2
    assert np.asarray(want)[:filled].std() > 0.5
    assert pangu_moe.grouped_product(
        rows, weights, sizes, tile).shape == (64, 4096)


def test_the_tolerance_would_notice_the_routed_part_left_out(f32):
    import jax

    model, params = f32
    tokens = prompt(9, 50)
    want = reference_logits(params, tokens)
    broken = jax.tree_util.tree_map_with_path(
        lambda p, x: x * 0 if "experts_down" in str(p[-1]) else x, params)
    got = model.apply({"params": broken}, {"inputs": tokens[None]})
    assert rms(np.asarray(got[0]) - want) > 2 * BF16_TOL * want.std()


# ------------------------------------------------------------- the engine


LENGTHS = [64, 9, 37, 1, 61, 12, 48, 5, 33, 17]
BUDGETS = [12, 40, 30, 25, 8, 14, 20, 9, 31, 6]


@pytest.fixture(scope="module")
def engine_run(f32):
    """A real engine, 4 slots, chunked prefill on: ten requests, short
    and long prompts (0.06 to 4 rings) in one queue, offered in two
    bursts, so that short rows take the slots and the prefill row that
    long ones left."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = f32
    reg = MetricsRegistry()
    engine = GenerativeEngine(
        decode_fns(model), params, max_batch_size=4, prefill_chunk_pages=1,
        registry=reg)
    prompts = [prompt(100 + i, n) for i, n in enumerate(LENGTHS)]
    try:
        engine.warm()
        handles = []
        for i, (p, m) in enumerate(zip(prompts, BUDGETS)):
            handles.append(engine.submit_nowait(p, max_new_tokens=m))
            if i == 4:
                handles[0].wait(120.0)
        outs = [h.wait(120.0) for h in handles]
    finally:
        engine.close()
    return engine, reg, prompts, outs


@pytest.mark.parametrize("i", range(10))
def test_engine_serves_what_the_reference_would(f32, engine_run, i):
    """Through the scheduler, the arena of two kinds, insert, move and
    clear: every served token is the reference's best at its position,
    or within the tolerance of it, and the stream is the one the same
    row gives alone from a blank cache (a short row in a slot, and
    behind a prefill row, that a long one used: stale ring entries and
    stale positions stay unseen)."""
    model, params = f32
    _, _, prompts, outs = engine_run
    served = np.asarray(outs[i])
    assert len(served) == BUDGETS[i]
    n = len(prompts[i])
    want = reference_logits(
        params, np.concatenate([prompts[i], served]))[n - 1:-1]
    picked = want[np.arange(len(served)), served]
    assert (want.max(-1) - picked).max() < F32_TOL
    alone, _ = through_the_cache(
        params, decode_fns(model), prompts[i], BUDGETS[i])
    assert served.tolist() == alone.tolist()


def test_engine_counts_both_kinds_of_cache_and_the_experts(engine_run):
    engine, reg, prompts, _ = engine_run
    get = lambda name, *lab: reg.get(name).labels("0", *lab).get()
    windows = sum(-(-len(p) // PREFILL) for p in prompts)
    assert get("serving_decode_prefill_windows_total") == windows
    assert get("serving_decode_prefill_tokens_total") == sum(LENGTHS)
    assert get("serving_decode_engine_phase_total", "insert") == 10
    assert engine.compiles_after_warm == 0
    # a step at position t reads min(t + 1, 16) entries of each of the 6
    # rings and t + 1 of each of the 2 full arrays
    fed = [
        t for p, m in zip(prompts, BUDGETS)
        for t in range(len(p), len(p) + m - 1)]
    read = lambda kind: get("serving_decode_cache_read_bytes_total", kind)
    assert read("window") == sum(
        min(t + 1, WINDOW) for t in fed) * 6 * ENTRY * 4
    assert read("full") == sum(t + 1 for t in fed) * 2 * ENTRY * 4
    # the span is what the step's kernel fetches for the live rows, whole
    # key blocks to a row's depth: at this size one block a row, which
    # holds the ring's 16 entries or the array's 104 positions
    span = lambda kind: get("serving_decode_cache_span_bytes_total", kind)
    steps = get("serving_decode_steps_total")
    assert span("window") == len(fed) * 6 * WINDOW * ENTRY * 4
    assert span("full") == len(fed) * 2 * (MAX_IN + MAX_OUT) * ENTRY * 4
    assert span("window") >= steps * 6 * WINDOW * ENTRY * 4
    assert read("window") < span("window") and read("full") < span("full")
    assert get("serving_decode_window_rollovers_total") == sum(
        t % WINDOW == 0 for t in fed)
    # 4 of 16 experts held, 4 choices a token, 8 layers: 8 a token on
    # average
    picked = get("serving_decode_expert_assignments_total")
    assert 0.5 * 8 * len(fed) < picked < 1.5 * 8 * len(fed)
    touched = get("serving_decode_experts_touched_total")
    assert 0 < touched <= min(picked, steps * 8 * HELD)
    count = get("serving_decode_expert_load_ratio_count")
    assert 0 < count <= steps
    assert 1.0 <= get("serving_decode_expert_load_ratio_sum") / count <= HELD


def test_kv_buckets_cut_the_full_arrays_and_not_the_rings(f32):
    """``page_size`` 16: the step of a row that holds a prompt of 40 and
    ``held`` tokens runs in a bucket of at least ``40 + held`` positions
    of the full arrays, whatever the rings hold, and the streams are the
    same under any bucket.  The bucket bounds the kernel's grid; what a
    row fetches is whole key blocks to its depth under either bucket, at
    this size one block that holds all 104 positions."""
    from tpu_pipelines.observability.metrics import MetricsRegistry
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = f32
    reg = MetricsRegistry()
    fns = decode_fns(model)
    engine = GenerativeEngine(
        fns, params, max_batch_size=2, page_size=16, registry=reg)
    assert engine.kv_buckets == [16, 32, 64, MAX_IN + MAX_OUT]
    seen = []
    inner = engine._step_for
    tokens = prompt(5, 40)
    try:
        engine.warm()
        engine._step_for = lambda b, kv: (
            seen.append((kv, 40 + engine._slots[0].held)), inner(b, kv))[1]
        served = engine.submit(tokens, max_new_tokens=30)
    finally:
        engine.close()
    assert seen and all(kv >= depth for kv, depth in seen)
    assert {kv for kv, _ in seen} == {64, MAX_IN + MAX_OUT}
    alone, _ = through_the_cache(params, fns, tokens, 30)
    assert np.asarray(served).tolist() == alone.tolist()
    assert engine.compiles_after_warm == 0
    span = reg.get("serving_decode_cache_span_bytes_total")
    assert span.labels("0", "full").get() == 29 * 2 * 104 * ENTRY * 4


def test_the_contract_states_what_the_engine_may_not_guess(f32):
    import jax

    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = f32
    fns = decode_fns(model)
    cache = fns.blank_cache(3)
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    kinds = {fns.cache_kind_of(p) for p, _ in flat}
    assert kinds == set(fns.cache_kinds) == {"window", "full"}
    window, full = fns.cache_kinds["window"], fns.cache_kinds["full"]
    assert not window.by_position and full.by_position
    assert all(k.written and k.in_place for k in (window, full))
    shapes = {fns.cache_kind_of(p): x.shape for p, x in flat}
    assert shapes == {"window": (3, 2, WINDOW, 16),
                      "full": (3, 2, MAX_IN + MAX_OUT, 16)}
    by_kind = [fns.cache_kind_of(p) for p, _ in flat]
    assert by_kind.count("window") == 12 and by_kind.count("full") == 4
    assert fns.cache_positions == MAX_IN + MAX_OUT
    assert fns.step_tally_len == 8 * HELD
    assert int(fns.first_decode_pos(np.array([[1, 1, 1, 0, 0]]))) == 3
    assert fns.prefill is None
    account = fns.step_account([3, 40], [0] * 31 + [2], (2, 104))
    assert account["cache_entries"] == {
        "window": 6 * (4 + 16), "full": 2 * (4 + 41)}
    # one key block a row: the ring's 16 entries, the array's 104 positions
    assert account["cache_span_bytes"] == {
        "window": 6 * 2 * 16 * ENTRY * 4, "full": 2 * 2 * 104 * ENTRY * 4}
    assert account["experts_touched"] == 1
    with pytest.raises(ValueError, match="prefilled by window"):
        GenerativeEngine(fns, params, prefix_cache_entries=2)
    with pytest.raises(ValueError, match="divide window_size"):
        decode_fns(model, prefill_window_len=6)
    with pytest.raises(ValueError, match="inside the router"):
        build(expert_offset=14)
