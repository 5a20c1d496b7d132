"""Keye-VL-2.0-30B-A3B's language model (models/keye.py) against its plain
reference (benchmark/reference/keye.py) on seeded weights, at a small size
on the CPU: the whole-sequence forward, prefill by window and then decode
through the two kinds of cache, positions of three unequal components, what
the indexer selects (exactly, equal scores to the lower position), the
step's gathered form against the window's masked form, and the same through
a real ``GenerativeEngine`` with short and long rows in one batch.

Size: 3 layers, d_model 64, 8 query heads over 2 key/value heads of 16
(rotary sections 2 + 3 + 3 pairs), an indexer of 4 heads of 8 that selects
12 positions, 8 experts of width 32 with 2 a token and no shared one;
prefill windows of 8, context 64 + 40, so a row is up to 8.7 times as deep
as what it attends over.  Weights from benchmark/weights.py with the
spreads of the indexer's, the query's and the key's projections and of the
router raised, so that the 12 selected are not a matter of rounding, differ
from query to query and hold much of the attention's weight: a fault in
the selection, in either cache or in the fetch moves the logits by far
more than a tolerance.

Tolerances.  The program in float32 and the reference compute the same
function in another order of summation (a threshold found by bisection and
a mask over blocks of keys, or a top-k and a gather, against one sort;
rows sorted by expert against a masked loop), so their logits differ by
float32 rounding: observed 5e-6 at a logit spread of 1.0; ``F32_TOL`` 1e-4
leaves a decade for other seeds.  A selection that differed by ONE
position would move the logits by 1e-2 and more (one of 12 keys), so
``F32_TOL`` also holds the two selections equal.  In bfloat16 (the served
precision) the selections do differ at the margin, as the routers' top-2
do, and one of 12 keys or one of 2 experts is much of a token: the root
mean square of the logits' error over 40 decoded positions was 0.28, 0.31
and 0.34 of their spread on three seeds (7, 8, 9); ``BF16_TOL`` 0.45 is a
third over the largest.  Attention over every position instead of the 12
selected moves the logits by 1.03, 1.13 and 1.17 of their spread on the
same seeds (the served program lies 0.94 to 1.15 from that dense
reference), the other head mapping by as much: each test holds its fault
over ``FAULT`` 0.6, a third over the tolerance.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.generative

VOCAB, PREFILL, EXPERTS, TOP_K, TOPK = 96, 8, 8, 2, 12
SECTIONS = (2, 3, 3)
HP = dict(
    vocab_size=VOCAB, d_model=64, n_layers=3, n_heads=8, n_kv_heads=2,
    head_dim=16, mrope_section=list(SECTIONS), index_heads=4, index_dim=8,
    index_topk=TOPK, d_expert=32, n_experts=EXPERTS, experts_held=EXPERTS,
    expert_offset=0, experts_per_token=TOP_K,
)
# what the reference cannot read off the weights (its SIZES, at this size)
SIZES = dict(top_k=TOP_K, index_topk=TOPK, sections=SECTIONS)
RULES = {
    "embed/embedding": 0.3, "router": 0.5, "q_proj/kernel": 0.3,
    "k_proj/kernel": 0.3, "index_q/kernel": 0.4, "index_k/kernel": 0.4,
    "index_w/kernel": 0.3, "experts_gate": 0.125, "experts_up": 0.125,
    "experts_down": 0.177, "scale": "around_one", "head": "fan_in",
    "kernel": "fan_in",
}
F32_TOL, BF16_TOL, FAULT = 1e-4, 0.45, 0.6
rms = lambda e: float(np.sqrt(np.mean(np.square(e))))
MAX_IN, MAX_OUT = 64, 40
POSITIONS = MAX_IN + MAX_OUT
KV_ENTRY, INDEX_ENTRY = 2 * 2 * 16, 8      # numbers a position a layer


def build(dtype="float32", seed=7, **over):
    import jax

    from benchmark import weights
    from tpu_pipelines.models import keye

    model = keye.build_keye_model(
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
    from benchmark.reference import keye as ref

    flat = weights.flat_leaves(params)
    return ref.from_served_tree(flat, ref.n_layers_of(flat))


REFERENCE_PASS = {}


def reference_logits(params, tokens, positions=None, **sizes):
    """[len, vocab] for one sequence.  The pass is causal, so the sequence
    is padded to a multiple of 32 and the pass compiled once a length."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import keye as ref

    sizes = {**SIZES, **sizes}
    key = tuple(sorted(sizes.items()))
    if key not in REFERENCE_PASS:
        REFERENCE_PASS[key] = jax.jit(
            lambda rp, tokens, pos: ref.head_logits(
                rp, ref.hidden(rp, tokens, "f32", pos, **sizes)))
    n = len(tokens)
    total = -(-n // 32) * 32
    padded = np.zeros((total,), np.int32)
    padded[:n] = tokens
    pos = np.broadcast_to(np.arange(total), (3, total)).copy()
    if positions is not None:
        pos[:, :n] = positions
    with jax.default_matmul_precision("highest"):
        return np.asarray(REFERENCE_PASS[key](
            reference_params(params), jnp.asarray(padded),
            jnp.asarray(pos)))[:n]


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        2, VOCAB, size=n).astype(np.int32)


DECODE_FNS = {}


def decode_fns(model, **over):
    """The contract of ``model``, made once for each set of keywords, with
    its window and step programs (``fns.jitted``) compiled once too."""
    import jax

    from tpu_pipelines.models.keye import make_continuous_decode_fns

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


# (prompt length, new tokens): ends inside a prefill window, 3 times the
# selection deep; the longest prompt and the longest answer; one token (the
# row passes the selection's size while decoding); ends on a window's last
# position; no deeper than the selection throughout.
CASES = [(37, 40), (64, 40), (1, 30), (48, 20), (5, 6)]


@pytest.mark.parametrize("n", [50, 12, 7, 104])
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
    """Windows written into the keys', the values' and the index keys'
    arrays, each query under its own mask, then steps that select, fetch
    and attend: each step's LOGITS against the reference's one full pass
    over the prompt with the served tokens behind it."""
    model, params = f32
    tokens = prompt(n_prompt, n_prompt)
    served, logits = through_the_cache(
        params, decode_fns(model), tokens, n_new)
    whole = np.concatenate([tokens, served])
    want = reference_logits(params, whole)[n_prompt - 1:-1]
    assert np.abs(logits - want).max() < F32_TOL
    # not one token over and over
    assert len(set(served.tolist())) > min(3, n_new // 4)


def test_rows_at_different_depths_in_one_step(f32):
    """Three rows of one step, 3, 30 and 70 deep (under the selection's
    size, over it, far over it), each against the reference and against
    itself alone."""
    import jax.numpy as jnp

    model, params = f32
    fns = decode_fns(model)
    step = fns.jitted[1]
    depths = [3, 30, 70]
    rows = [prompt(40 + i, n + 1) for i, n in enumerate(depths)]
    filled = [prefill(fns, params, r[:-1])[0] for r in rows]
    import jax

    arena = jax.tree.map(
        lambda blank, *parts: blank.at[:3].set(jnp.concatenate(parts, 0)),
        fns.blank_cache(4), *filled)
    tok = np.asarray([r[-1] for r in rows] + [0], np.int32)
    pos = np.asarray(depths + [0], np.int32)
    none = jnp.zeros((4, 0))
    _, logits, picked = step(
        params, arena, tok, pos, none, none, fns.cache_positions)
    assert picked.shape == (4, 3 * EXPERTS)
    assert np.asarray(picked)[:3].sum(-1).tolist() == [3 * TOP_K] * 3
    for i, r in enumerate(rows):
        want = reference_logits(params, r)[-1]
        assert np.abs(np.asarray(logits[i]) - want).max() < F32_TOL
        _, alone, _ = step(
            params, filled[i], tok[i:i + 1], pos[i:i + 1], none[:1],
            none[:1], fns.cache_positions)
        assert np.abs(np.asarray(alone[0] - logits[i])).max() < 1e-5


def test_a_used_row_serves_what_a_blank_one_does(f32):
    """The engine prefills every prompt into ONE row and never clears it:
    a short prompt after a long one finds the long one's keys and index
    keys past its own end, and must neither select nor see them."""
    model, params = f32
    fns = decode_fns(model)
    used, _ = prefill(fns, params, prompt(1, 64))
    served, logits = through_the_cache(
        params, fns, prompt(2, 11), 12, cache=used)
    alone, alone_logits = through_the_cache(params, fns, prompt(2, 11), 12)
    assert served.tolist() == alone.tolist()
    assert np.array_equal(logits, alone_logits)


def test_served_precision_stays_near_the_reference():
    """bfloat16 weights, products and caches, as served."""
    model, params = build("bfloat16")
    tokens = prompt(3, 37)
    served, logits = through_the_cache(
        params, decode_fns(model), tokens, 40)
    want = reference_logits(params, np.concatenate([tokens, served]))
    want = want[36:-1]
    assert rms(logits - want) < BF16_TOL * want.std()


# ------------------------------------------------- positions, heads, rotary


def test_three_unequal_position_components_match_the_reference(f32):
    """An image's patches inside a text: the temporal component stands
    while height and width count the grid, then the text goes on.  The
    whole-sequence pass takes ``[3, b, l]``; the sections decide which
    pairs turn by which component, and the indexer turns by the temporal
    one alone."""
    model, params = f32
    n = 50
    tokens = prompt(77, n)
    t = np.concatenate([np.arange(10), np.full(24, 10), 16 + np.arange(16)])
    h = np.concatenate([np.arange(10), 10 + np.arange(24) // 6,
                        16 + np.arange(16)])
    w = np.concatenate([np.arange(10), 10 + np.arange(24) % 6,
                        16 + np.arange(16)])
    pos = np.stack([t, h, w])
    got = np.asarray(model.apply(
        {"params": params},
        {"inputs": tokens[None], "positions": pos[:, None]}))[0]
    want = reference_logits(params, tokens, positions=pos)
    assert np.abs(got - want).max() < F32_TOL
    # the components matter, each of them
    text = reference_logits(params, tokens)
    assert rms(want - text) > FAULT * want.std()
    for swapped in (pos[[0, 2, 1]], pos[[1, 0, 2]]):
        other = reference_logits(params, tokens, positions=swapped)
        assert rms(want - other) > 0.05 * want.std()


def test_rotation_is_by_split_halves_in_contiguous_sections():
    """Pair ``j`` is ``(x_j, x_{j + d/2})`` and turns by ``p_c(j) theta **
    (-j / (d/2))``, ``c`` = 0 for the first 2 pairs, 1 for the next 3, 2
    for the last 3."""
    from tpu_pipelines.models.keye import rope_sections, three

    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 9, 3, 16)).astype(np.float32)
    pos = rng.integers(0, 50, size=(3, 2, 9))
    got = np.asarray(rope_sections(x, pos, 1e4, SECTIONS))
    which = np.repeat(np.arange(3), SECTIONS)
    z = x[..., :8] + 1j * x[..., 8:]
    angle = np.moveaxis(pos, 0, -1)[..., which] * 1e4 ** (-np.arange(8) / 8)
    want = z * np.exp(1j * angle[:, :, None, :])
    assert np.abs(got[..., :8] - want.real).max() < 1e-4
    assert np.abs(got[..., 8:] - want.imag).max() < 1e-4
    # without sections: the temporal component for every pair
    plain = np.asarray(rope_sections(x, pos, 1e4))
    same = np.asarray(rope_sections(x, three(pos[0]), 1e4, SECTIONS))
    assert np.abs(plain - same).max() < 1e-6


def test_query_head_i_reads_key_value_head_i_over_g(f32):
    """8 query heads over 2 key/value heads: head ``i`` reads ``i // 4``.
    The reference with its query heads re-ordered to the other convention
    is far from the program."""
    import jax

    model, params = f32
    tokens = prompt(13, 50)
    got = np.asarray(
        model.apply({"params": params}, {"inputs": tokens[None]}))[0]
    order = np.asarray([(i % 2) * 4 + i // 2 for i in range(8)])
    columns = (order[:, None] * 16 + np.arange(16)[None]).reshape(-1)

    def other(path, x):
        at = "/".join(str(getattr(k, "key", k)) for k in path)
        if at.endswith("q_proj/kernel"):
            return x[:, columns]
        return x[columns] if at.endswith("o_proj/kernel") else x

    wrong = reference_logits(
        jax.tree_util.tree_map_with_path(other, params), tokens)
    assert rms(got - wrong) > FAULT * got.std()


# ------------------------------------------------------------ the selection


def stable_topk(scores, t, topk):
    """numpy: per row the ``min(topk, t + 1)`` keys ``s <= t`` with the
    largest scores, equal scores to the lower index."""
    out = np.zeros(scores.shape, bool)
    for i, row in enumerate(scores):
        seen = row[:t[i] + 1]
        order = np.argsort(-seen, kind="stable")[:topk]
        out[i, order] = True
    return out


def test_the_mask_is_the_exact_top_k_with_ties_to_the_lower_position():
    """``selected`` (a threshold by bisection over the scores' bits, then
    a cut by position among equal scores) against a stable sort: random
    scores, scores of a few distinct values (many ties at the threshold),
    both zeros, infinities, negative numbers, all scores equal."""
    import jax

    from tpu_pipelines.models.keye import selected

    rng = np.random.default_rng(0)
    n = 90
    t = np.asarray([0, 3, 11, 12, 13, 40, 89, 60], np.int32)
    few = rng.integers(-2, 3, size=(8, n)).astype(np.float32)
    few[few == 0] = np.where(rng.random((few == 0).sum()) < 0.5, 0.0, -0.0)
    cases = {
        "random": rng.normal(size=(8, n)).astype(np.float32),
        "few values": few,
        "all equal": np.zeros((8, n), np.float32),
        "huge": (rng.normal(size=(8, n)) * 1e30).astype(np.float32),
        "infinite": np.where(
            rng.random((8, n)) < 0.3, np.inf, few).astype(np.float32),
        "small": (rng.normal(size=(8, n)) * 1e-30).astype(np.float32),
    }
    pick = jax.jit(selected, static_argnums=2)
    for name, scores in cases.items():
        for topk in (12, 1, 200):
            got = np.asarray(pick(scores, t, topk))
            want = stable_topk(scores, t, topk)
            assert np.array_equal(got, want), (name, topk)
            assert got.sum(1).tolist() == np.minimum(topk, t + 1).tolist()
    # all equal: the lowest positions
    got = np.asarray(pick(cases["all equal"], t, 12))
    assert got[6, :12].all() and not got[6, 12:].any()


def test_the_step_chooses_what_the_mask_holds_and_ties_go_low(f32):
    """``SparseAttention.choose`` (``jax.lax.top_k`` over a row's index
    scores) returns the positions that ``selected`` marks for the same
    scores, the valid ones first; with an indexer whose scores are all
    equal (its query projection zeroed) both take the LOWEST positions,
    in the program and in the reference alike."""
    import jax

    from tpu_pipelines.models import keye

    model, params = f32
    attn = keye.SparseAttention(model.cfg)
    layer = {"params": params["layer_1"]["attn"]}
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1, 60, 64)).astype(np.float32)
    pos = keye.three(np.arange(60)[None])
    qi, ki, w = attn.apply(layer, x, pos, method="index")
    index = np.asarray(attn.apply(layer, qi[0], ki[0], w[0], method="scores"))
    for t in (5, 11, 12, 30, 59):
        chosen = np.asarray(attn.apply(
            layer, qi[:, t], ki, w[:, t], np.asarray([t], np.int32), 60,
            method="choose"))[0]
        n = min(t + 1, TOPK)
        mask = np.asarray(keye.selected(
            index[t:t + 1], np.asarray([t], np.int32), TOPK))[0]
        assert sorted(chosen[:n].tolist()) == np.flatnonzero(mask).tolist()
        assert (np.diff(index[t][chosen[:n]]) <= 0).all()    # best first
    # an indexer that says nothing: the first 12 positions, every layer
    blind = jax.tree_util.tree_map_with_path(
        lambda p, a: a * 0 if "index_q" in str(p) else a, params)
    tokens = prompt(31, 50)
    got = np.asarray(
        model.apply({"params": blind}, {"inputs": tokens[None]}))[0]
    want = reference_logits(blind, tokens)
    assert np.abs(got - want).max() < F32_TOL
    served, logits = through_the_cache(blind, decode_fns(model), tokens, 8)
    want = reference_logits(blind, np.concatenate([tokens, served]))[49:-1]
    assert np.abs(logits - want).max() < F32_TOL
    # the last position's logits do not move with a token past the first
    # 12 (nor with itself: position 49 is not among them), and do with one
    # inside them
    base = got[-1]
    for at, seen in ((20, False), (3, True)):
        other = tokens.copy()
        other[at] = (other[at] + 1 - 2) % (VOCAB - 2) + 2
        moved = np.abs(np.asarray(model.apply(
            {"params": blind}, {"inputs": other[None]}))[0, -1] - base).max()
        assert (moved > 1e-3) == seen, (at, moved)


def test_the_gathered_step_equals_the_masked_window(f32):
    """The same position served both ways: as the last query of a prefill
    window (a mask over the row's array by threshold) and as a decode
    step (a top-k, a gather of the 12 entries, the kernel over them)."""
    import jax.numpy as jnp

    model, params = f32
    fns = decode_fns(model)
    none = jnp.zeros((1, 0))
    for n in (61, 30, 9):
        tokens = prompt(n, n)
        _, masked = prefill(fns, params, tokens)
        cache, _ = prefill(fns, params, tokens[:-1])
        _, gathered, _ = fns.jitted[1](
            params, cache, tokens[-1:], np.asarray([n - 1], np.int32),
            none, none, fns.cache_positions)
        assert np.abs(np.asarray(masked - gathered)).max() < 1e-5


def test_a_row_no_deeper_than_the_selection_attends_over_everything(f32):
    """Up to 12 positions the selection is every position: the reference
    with the selection switched off gives the same logits there and other
    ones behind, and the program follows the one that selects."""
    model, params = f32
    tokens = prompt(19, 60)
    got = np.asarray(
        model.apply({"params": params}, {"inputs": tokens[None]}))[0]
    dense = reference_logits(params, tokens, select=False)
    assert np.abs(got[:TOPK] - dense[:TOPK]).max() < F32_TOL
    assert np.abs(got[TOPK + 4:] - dense[TOPK + 4:]).max() > 1e-2


def test_switching_the_selection_off_is_caught(f32):
    """THE test that a program which ignored its indexer would fail: the
    reference over all positions differs from the reference over the
    selected ones by far more than the served precision's tolerance, in
    the whole pass and through the cache, so neither tolerance of this
    file passes a dense program."""
    model, params = f32
    tokens = prompt(23, 64)
    served, logits = through_the_cache(
        params, decode_fns(model), tokens, 24)
    whole = np.concatenate([tokens, served])
    sparse = reference_logits(params, whole)
    dense = reference_logits(params, whole, select=False)
    behind = slice(2 * TOPK, None)
    assert rms(sparse[behind] - dense[behind]) \
        > FAULT * sparse[behind].std()
    assert np.abs(logits - sparse[63:-1]).max() < F32_TOL
    assert rms(logits - dense[63:-1]) > FAULT * dense[63:-1].std()


def test_the_selection_differs_from_query_to_query_and_layer_to_layer(f32):
    """What the weights' spreads are for: the 12 chosen are no fixed
    stretch (the newest 12, the first 12) and not the same in two layers."""
    from tpu_pipelines.models import keye

    model, params = f32
    attn = keye.SparseAttention(model.cfg)
    rng = np.random.default_rng(6)
    x = rng.normal(size=(1, 60, 64)).astype(np.float32)
    pos = keye.three(np.arange(60)[None])
    masks = []
    for layer in ("layer_0", "layer_2"):
        p = {"params": params[layer]["attn"]}
        qi, ki, w = attn.apply(p, x, pos, method="index")
        index = attn.apply(p, qi[0], ki[0], w[0], method="scores")
        masks.append(np.asarray(keye.selected(
            index, np.arange(60, dtype=np.int32), TOPK)))
        spread = np.asarray(index)[40:].std(-1).mean()
        assert spread > 0.3          # far over bfloat16's step
    last = masks[0][59]
    assert last.sum() == TOPK
    assert not last[48:].all() and not last[:12].all()
    assert (masks[0][59] != masks[0][50]).sum() >= 4
    assert (masks[0][59] != masks[1][59]).sum() >= 4


# ------------------------------------------------------------- the engine


LENGTHS = [64, 9, 37, 1, 61, 12, 48, 5, 33, 17]
BUDGETS = [12, 40, 30, 25, 8, 14, 20, 9, 31, 6]


@pytest.fixture(scope="module")
def engine_run(f32):
    """A real engine, 4 slots, chunked prefill on: ten requests, short and
    long prompts (under the selection's size to 5 times it) in one queue,
    offered in two bursts, so that short rows take the slots and the
    prefill row that long ones left."""
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
    or within the tolerance of it, and the stream is the one the same row
    gives alone from a blank cache (a short row in a slot, and behind a
    prefill row, that a long one used: stale keys and index keys stay
    unselected and unseen)."""
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
    # A window's kernel is handed key blocks up to the one that holds the
    # window's last position: at this size one block of 128 holds the
    # whole row of 104, so every window of the 3 layers visits the one
    # block its row holds.
    blocks = lambda state: get(
        "serving_decode_window_key_blocks_total", state)
    assert blocks("visited") == blocks("held") == 3 * windows
    # a step at position t has t + 1 valid entries of either kind in each
    # of the 3 layers
    fed = [
        t for p, m in zip(prompts, BUDGETS)
        for t in range(len(p), len(p) + m - 1)]
    read = lambda kind: get("serving_decode_cache_read_bytes_total", kind)
    assert read("kv") == sum(t + 1 for t in fed) * 3 * KV_ENTRY * 4
    assert read("index") == sum(t + 1 for t in fed) * 3 * INDEX_ENTRY * 4
    # what a step FETCHES: of kv the 12 gathered entries a row and layer
    # (one key block of the kernel holds them: min(t + 1, 12) entries
    # round up to the 12 there are), of index every position of the bucket
    span = lambda kind: get("serving_decode_cache_span_bytes_total", kind)
    assert span("kv") == len(fed) * 3 * TOPK * KV_ENTRY * 4
    assert span("index") == len(fed) * 3 * POSITIONS * INDEX_ENTRY * 4
    # the mechanism: what is fetched of the keys and values is a fraction
    # of those that are valid for the same rows, and with every index key
    # of the bucket (at this size 104 positions of 8 numbers beside 12
    # entries of 64) still less than they
    assert span("kv") < 0.35 * read("kv")
    assert span("kv") + span("index") < 0.7 * read("kv")
    # all 8 experts held, 2 choices a token, 3 layers: 6 a token
    assert get("serving_decode_expert_assignments_total") == 6 * len(fed)
    steps = get("serving_decode_steps_total")
    touched = get("serving_decode_experts_touched_total")
    assert 0 < touched <= min(6 * len(fed), steps * 3 * EXPERTS)
    count = get("serving_decode_expert_load_ratio_count")
    assert 0 < count <= steps
    assert 1.0 <= get(
        "serving_decode_expert_load_ratio_sum") / count <= EXPERTS


def test_a_window_is_booked_the_key_blocks_up_to_its_last_position():
    """``window_account`` at the cell's shapes (windows of 512 over 22,528
    positions, blocks of 512 x 512): window ``m`` of a prompt visits ``m +
    1`` of the row's 44 key blocks in each layer."""
    from tpu_pipelines.models import keye

    model = keye.build_keye_model(dict(n_layers=6))
    fns = keye.make_continuous_decode_fns(
        model, max_decode_len=2048, max_input_len=20480,
        prefill_window_len=512)
    for m in (0, 1, 12, 39, 43):
        assert fns.window_account(m) == {
            "key_blocks": {"visited": 6 * (m + 1), "held": 6 * 44}}
    # a prompt of 12,288 tokens: 24 windows, 300 of 1,056 blocks a layer
    visited = sum(
        fns.window_account(m)["key_blocks"]["visited"] for m in range(24))
    assert visited == 6 * 300


def test_the_contract_states_what_the_engine_may_not_guess(f32):
    import jax

    from tpu_pipelines.models import keye
    from tpu_pipelines.serving.generative import GenerativeEngine

    model, params = f32
    fns = decode_fns(model)
    cache = fns.blank_cache(3)
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    kinds = {fns.cache_kind_of(p) for p, _ in flat}
    assert kinds == set(fns.cache_kinds) == {"kv", "index"}
    assert all(k.by_position and k.written and k.in_place
               for k in fns.cache_kinds.values())
    shapes = {fns.cache_kind_of(p): x.shape for p, x in flat}
    assert shapes == {"kv": (3, POSITIONS, 2 * 16),
                      "index": (3, POSITIONS, 8)}
    by_kind = [fns.cache_kind_of(p) for p, _ in flat]
    assert by_kind.count("kv") == 6 and by_kind.count("index") == 3
    assert fns.cache_positions == POSITIONS
    assert fns.step_tally_len == 3 * EXPERTS
    assert int(fns.first_decode_pos(np.array([[1, 1, 1, 0, 0]]))) == 3
    assert fns.prefill is None
    account = fns.step_account([3, 40], [0] * 23 + [2], (2, POSITIONS))
    assert account["cache_entries"] == {
        "kv": 3 * (4 + 41), "index": 3 * (4 + 41)}
    assert account["cache_bytes"] == {
        "kv": 3 * 45 * KV_ENTRY * 4, "index": 3 * 45 * INDEX_ENTRY * 4}
    assert account["selected_entries"] == 3 * (4 + 12)
    assert account["cache_span_bytes"] == {
        "kv": 3 * 2 * 12 * KV_ENTRY * 4,
        "index": 3 * 2 * POSITIONS * INDEX_ENTRY * 4}
    assert account["experts_touched"] == 1
    # the whole cache where no bucket is given; a cache that is no whole
    # number of windows is rounded up to one
    assert fns.step_account([3], [0] * 24)["cache_span_bytes"]["index"] \
        == 3 * POSITIONS * INDEX_ENTRY * 4
    assert decode_fns(model, max_decode_len=43).cache_positions == 112
    with pytest.raises(ValueError, match="prefilled by window"):
        GenerativeEngine(fns, params, prefix_cache_entries=2)
    with pytest.raises(ValueError, match="inside the router"):
        build(expert_offset=1)
    with pytest.raises(ValueError, match="mrope_section"):
        build(mrope_section=[2, 3, 4])
    with pytest.raises(ValueError, match="multiple of n_kv_heads"):
        build(n_kv_heads=3)
