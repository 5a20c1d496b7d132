"""``ops/flash_attention.py selected_attention`` (the interpreted kernel,
small shapes, on the CPU) against a float32 softmax over exactly the keys
that ``models/keye.py selected`` marks, and its rule ``selected_block``
applied block by block against the same rule over the whole row and
against a stable sort, bit for bit.

The blocks are cut to 16 queries x 128 keys for most cases, so that a row
of a few hundred positions spans several key blocks: the carried count,
the blocks past the window's last position and the arrays' ragged ends are
all exercised at sizes the interpreter runs in a second.

Tolerance.  Inputs are float32 and the kernel and the reference sum in
another order: observed 4e-7 at values of spread 1; ``TOL`` 2e-5.  One key
more or less in a set of 24 moves a result by 1e-2 and more, so ``TOL``
also holds the two sets equal.
"""

import importlib

import numpy as np
import pytest

TOL = 2e-5


@pytest.fixture
def fa(monkeypatch):
    fa = importlib.import_module("tpu_pipelines.ops.flash_attention")
    monkeypatch.setattr(fa, "SELECTED_BLOCK_Q", 16)
    monkeypatch.setattr(fa, "GROUPED_BLOCK_K", 128)
    return fa


def stable_topk(scores, t, topk):
    """numpy: per row the ``min(topk, t + 1)`` keys ``s <= t`` with the
    largest scores, equal scores to the lower index."""
    out = np.zeros(scores.shape, bool)
    for i, row in enumerate(scores):
        seen = np.where(row[:t[i] + 1] == 0, 0.0, row[:t[i] + 1])
        out[i, np.argsort(-seen, kind="stable")[:topk]] = True
    return out


def few_values(rng, shape):
    """Scores of five values, both zeros among them: many keys equal to
    any threshold."""
    x = rng.integers(-2, 3, size=shape).astype(np.float32)
    zero = x == 0
    x[zero] = np.where(rng.random(zero.sum()) < 0.5, 0.0, -0.0)
    return x


def exactly_fitting(rng, shape, topk):
    """Every row: 5 keys of score 9 among the first 20 positions, then
    ``topk - 5`` keys of score 1 (equal to the threshold, and as many as
    there is room for), the rest distinct and lower."""
    x = -1 - rng.random(shape).astype(np.float32)
    x[:, :20:4] = 9.0
    x[:, 1:2 * (topk - 5):2] = 1.0
    return x


# id: (kv, g, lq, n, d, start, topk, scores, unwritten tail)
CASES = {
    "start_0": (2, 2, 40, 300, 16, 0, 24, "random", False),
    "start_past_the_selection": (2, 2, 40, 300, 16, 200, 24, "random", False),
    "last_window_of_the_row": (2, 2, 40, 300, 16, 260, 24, "random", False),
    "everything_seen": (2, 2, 40, 60, 16, 0, 500, "random", False),
    "row_passes_the_selection": (2, 2, 40, 300, 16, 10, 24, "random", False),
    "ragged_7_of_131": (1, 2, 7, 131, 16, 124, 5, "random", False),
    "unwritten_tail_of_junk": (2, 2, 40, 300, 16, 100, 24, "random", True),
    "ties_across_blocks": (2, 2, 40, 300, 16, 230, 24, "few", True),
    "ties_all_equal": (1, 2, 40, 300, 16, 130, 24, "equal", False),
    "ties_fit_exactly": (2, 2, 40, 300, 16, 230, 24, "fitting", False),
    "zeros_of_both_signs_and_infinities": (
        2, 2, 40, 300, 16, 150, 24, "infinite", False),
    "huge_and_tiny": (1, 2, 40, 300, 16, 150, 24, "huge", False),
    "g_1": (2, 1, 40, 300, 16, 200, 24, "random", False),
    "g_8": (1, 8, 24, 300, 16, 140, 24, "few", False),
    "head_dim_128": (2, 2, 24, 200, 128, 100, 24, "random", True),
    "one_block_of_512": (1, 2, 40, 300, 16, 200, 24, "few", False),
}


def draw(case):
    kv, g, lq, n, d, start, topk, kind, junk = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    f32 = lambda *shape: rng.normal(size=shape).astype(np.float32)
    q = f32(kv, g, lq, d) * d ** -0.5
    k, v = f32(n, kv * d), f32(n, kv * d)
    if junk:
        # what a former occupant left behind the window: large, finite
        k[start + lq:] = 3e4 * rng.choice([-1, 1], size=k[start + lq:].shape)
        v[start + lq:] = 3e4
    few = few_values(rng, (lq, n))
    scores = {
        "random": lambda: f32(lq, n),
        "few": lambda: few,
        "equal": lambda: np.zeros((lq, n), np.float32),
        "fitting": lambda: exactly_fitting(rng, (lq, n), topk),
        "infinite": lambda: np.where(
            rng.random((lq, n)) < 0.3,
            rng.choice([np.inf, -np.inf], size=(lq, n)), few,
        ).astype(np.float32),
        "huge": lambda: (f32(lq, n) * np.where(
            rng.random((lq, n)) < 0.5, 1e30, 1e-30)).astype(np.float32),
    }[kind]()
    return q, k, v, scores, start, topk


def softmax_over(q, k, v, sees):
    """float64 numpy: each query over exactly the keys ``sees`` marks.
    q [kv, g, lq, d]; k, v [n, kv * d]; sees [lq, n] -> [lq, kv * g * d]."""
    kv, g, lq, d = q.shape
    heads = lambda a: a.reshape(len(a), kv, d).astype(np.float64)
    s = np.einsum("hgqd,khd->hgqk", q.astype(np.float64), heads(k))
    s = np.where(sees, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = np.where(sees, p, 0.0) / p.sum(-1, keepdims=True)
    # a key that is not seen adds nothing, whatever its value holds
    values = np.where(sees.any(0)[:, None, None], heads(v), 0.0)
    return np.einsum("hgqk,khd->qhgd", p, values).reshape(lq, -1)


def served(fa, q, k, v, scores, start, topk):
    import jax
    import jax.numpy as jnp

    from tpu_pipelines.models import keye

    def run(q, k, v, scores, start):
        t = start + jnp.arange(q.shape[2], dtype=jnp.int32)
        return fa.selected_attention(
            q, k, v, *keye.threshold(scores, t, topk), start)

    return np.asarray(jax.jit(run)(q, k, v, scores, np.int32(start)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_attends_over_exactly_the_selected_keys(
        case, fa, monkeypatch):
    """The kernel's result is the float32 softmax over the keys that
    ``selected`` marks, which are the stable sort's."""
    import jax.numpy as jnp

    from tpu_pipelines.models import keye

    if case == "one_block_of_512":
        monkeypatch.undo()       # the served blocks: one of each here
    q, k, v, scores, start, topk = draw(case)
    t = start + np.arange(q.shape[2], dtype=np.int32)
    sees = np.asarray(keye.selected(jnp.asarray(scores), t, topk))
    assert np.array_equal(sees, stable_topk(scores, t, topk))
    want = softmax_over(q, k, v, sees)
    got = served(fa, q, k, v, scores, start, topk)
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_rule_block_by_block_is_the_rule_over_the_row(case, fa):
    """``selected_block`` over blocks of keys with the count of equal keys
    carried from block to block, as the kernel applies it (and without a
    count where no query has more equal keys than room), marks the same
    keys as over the whole row as one block (``selected``) and as a stable
    sort: bit for bit, ties, zeros and infinities included."""
    import jax.numpy as jnp

    from tpu_pipelines.models import keye

    _, _, _, scores, start, topk = draw(case)
    lq, n = scores.shape
    t = start + np.arange(lq, dtype=np.int32)
    keys, kth, room = keye.threshold(jnp.asarray(scores), t, topk)
    equal = np.asarray((keys == kth[:, None]) & (
        np.arange(n)[None, :] <= t[:, None])).sum(1)
    counted = bool((equal > np.asarray(room)).any())
    block = fa.selected_blocks(lq, n)[1]
    seen = jnp.zeros((lq, 1), jnp.float32)
    parts = []
    for at in range(0, n, block):
        sees, seen = fa.selected_block(
            keys[:, at:at + block],
            jnp.arange(at, min(at + block, n), dtype=jnp.int32)[None, :],
            t[:, None], kth[:, None],
            room[:, None] if counted else None, seen)
        parts.append(np.asarray(sees))
    got = np.concatenate(parts, 1)
    assert np.array_equal(got, np.asarray(keye.selected(
        jnp.asarray(scores), t, topk)))
    assert np.array_equal(got, stable_topk(scores, t, topk))
    assert got.sum(1).tolist() == np.minimum(topk, t + 1).tolist()
    assert counted == (case.startswith("ties") and case != "ties_fit_exactly"
                       or CASES[case][7] in ("few", "infinite"))


@pytest.mark.parametrize("case,counts", [
    ("ties_across_blocks", True), ("ties_all_equal", True),
    ("ties_fit_exactly", False), ("start_past_the_selection", False)])
def test_the_count_runs_only_where_equal_keys_outnumber_their_room(
        case, counts, fa, monkeypatch):
    """With the counting branch of the rule spoiled (it lets every equal
    key in), the kernel's result is wrong exactly in the cases in which
    some query has more keys equal to its threshold than room: elsewhere
    the kernel never ran that branch."""
    import jax.numpy as jnp

    from tpu_pipelines.models import keye

    q, k, v, scores, start, topk = draw(case)
    t = start + np.arange(q.shape[2], dtype=np.int32)
    want = softmax_over(q, k, v, np.asarray(
        keye.selected(jnp.asarray(scores), t, topk)))
    rule = fa.selected_block

    def spoiled(keys, at, t, kth, room=None, seen=0.0):
        return rule(keys, at, t, kth, None, seen)

    monkeypatch.setattr(fa, "selected_block", spoiled)
    got = served(fa, q, k, v, scores, start, topk)
    assert (np.abs(got - want).max() > 100 * TOL) == counts


def test_served_precision_weights_enter_the_second_product_in_bfloat16(fa):
    """bfloat16 queries, keys and values (the served precision): float32
    scores and statistics, the weights rounded once; within bfloat16's
    step of the float32 softmax over the same sets."""
    import jax.numpy as jnp

    from tpu_pipelines.models import keye

    q, k, v, scores, start, topk = draw("ties_across_blocks")
    bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16))
    q, k, v = bf(q), bf(k), bf(v)
    t = start + np.arange(q.shape[2], dtype=np.int32)
    sees = np.asarray(keye.selected(jnp.asarray(scores), t, topk))
    f32 = lambda a: a.astype(np.float32)
    want = softmax_over(f32(q), f32(k), f32(v), sees)
    got = served(fa, q, k, v, scores, start, topk)
    assert got.dtype == jnp.bfloat16
    assert np.abs(f32(got) - want).max() < 2e-2


def test_blocks_past_the_windows_last_position_are_not_visited(
        fa, monkeypatch):
    """The fetch table: a window is handed the key blocks up to the one
    that holds its last query's own position, and no other."""
    last = lambda start, l: int(fa.selected_last_block(start, l, 128))
    assert [last(0, 40), last(88, 40), last(89, 40)] == [0, 0, 1]
    assert last(260, 40) == 2               # 299, not the padded 307
    assert int(fa.selected_last_block(np.int32(19968), 512, 512)) == 39
    assert fa.selected_blocks(40, 300) == (16, 128)
    monkeypatch.undo()                      # the served blocks
    assert fa.selected_blocks(512, 22528) == (512, 512)
    assert fa.selected_blocks(8, 104) == (16, 128)
