"""The decode kernel over a ring and a table of ops/flash_attention.py
(``ring_table_decode_attention``), interpreted on the CPU, against
``EvaAttention.attend`` on the same arrays under the step's masks (a ring
valid to ``pos % w``, a table valid to the last completed window) and
against a plain float32 softmax: at the fixture's widths (4 heads of 16
numbers, a ring of 32 entries, a table of 40), with blocks of 8 entries
so that a ring is four blocks and a table five.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the package exports a function under the module's name
fa = importlib.import_module("tpu_pipelines.ops.flash_attention")

H, D = 4, 16
W, C, T = 32, 4, 40            # a ring, a chunk, a table of five windows
PER = W // C                   # a window's entries in the table
BLOCK = 8
SCALE = D ** -0.5


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(fa, "RING_TABLE_BLOCK_K", BLOCK)


def depths(pos, ring=W, per=PER, table=T):
    """As ``EvaAttention.step`` hands them over: the ring's entries ``[0,
    pos % w]`` and the table's of every completed window."""
    pos = np.asarray(pos)
    return pos % ring + 1, np.minimum((pos // ring) * per, table)


def plain(q, rk, rv, tk, tv, pos):
    """Row by row, head by head: ONE softmax over the row's own valid
    entries of both sets and nothing else, float32 throughout."""
    q, rk, rv, tk, tv = (
        np.asarray(a, np.float32) for a in (q, rk, rv, tk, tv))
    out = np.zeros(q.shape, np.float32)
    for i, (n, m) in enumerate(zip(*depths(pos))):
        for h in range(q.shape[1]):
            keys = np.concatenate([rk[i, :n, h], tk[i, :m, h]])
            values = np.concatenate([rv[i, :n, h], tv[i, :m, h]])
            score = keys @ q[i, h] * SCALE
            p = np.exp(score - score.max())
            out[i, h] = (p / p.sum()) @ values
    return out


def inputs(seed, rows, slots, dtype):
    rng = np.random.default_rng(seed)
    # queries wide enough that a deep row still prefers some entries
    q = jnp.asarray(2.5 * rng.normal(size=(rows, H, D)), dtype)
    arrays = [rng.normal(size=(slots, n, H, D)).astype(np.float32)
              for n in (W, W, T, T)]
    return q, arrays


def attend(q, arrays, pos):
    n, m = depths(pos)
    return jax.jit(lambda *a: fa.ring_table_decode_attention(
        *a, scale=SCALE))(
            q, *arrays, jnp.asarray(n, jnp.int32), jnp.asarray(m, jnp.int32))


def the_models_own(q, arrays, pos, dtype):
    """``EvaAttention.attend`` over the whole arrays under the step's two
    masks: what the step computed before the kernel."""
    from tpu_pipelines.models.evabyte import EvaAttention

    layer = EvaAttention(
        d_model=H * D, n_heads=H, head_dim=D, window_size=W, chunk_size=C,
        rope_theta=1e4, init_std=0.02, dtype=dtype, param_dtype=dtype)
    n, m = depths(pos)
    b = q.shape[0]
    k_ok = (np.arange(W)[None, :] < n[:, None])[:, None]
    c_ok = (np.arange(T)[None, :] < m[:, None])[:, None]
    rk, rv, tk, tv = (jnp.asarray(a[:b], dtype) for a in arrays)
    args = (q[:, None], rk, rv, jnp.asarray(k_ok), tk, tv, jnp.asarray(c_ok))
    # ``phi`` and ``mu`` are made with the layer and not read by ``attend``
    out = layer.apply(
        layer.init(jax.random.key(0), *args, method=EvaAttention.attend),
        *args, method=EvaAttention.attend)
    return np.asarray(out[:, 0].astype(jnp.float32)).reshape(b, H, D)


# name -> the rows' positions: a row alone at each edge of a ring, of a
# block and of the table, and rows of every kind in one batch
POSITIONS = {
    "first_position": [0],
    "empty_table_ring_full": [W - 1],
    "just_rolled_over": [W],
    "rolled_over_twice": [2 * W],
    "a_ring_block_less_one": [W + BLOCK - 2],
    "a_ring_block": [W + BLOCK - 1],
    "a_ring_block_and_one": [W + BLOCK],
    "ring_full_table_deep": [4 * W + W - 1],
    "full_table": [5 * W + 3],
    "full_table_ring_full": [6 * W - 1],
    "mixed": [3 * W + 5, 0, W - 1, W, 5 * W + 17, 2 * W + 8, 9],
    "empty_tables_between": [7, 2 * W + 1, 20, 3, 4 * W, 30],
    "every_table_empty": [5, W - 1, 0],
}
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("poison", [np.nan, 1e30], ids=["nan", "huge"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", POSITIONS)
def test_decode_attention_is_attend_over_what_is_valid(name, dtype, poison):
    pos = POSITIONS[name]
    dtype = jnp.dtype(dtype)
    rows = len(pos)
    # two slots more than rows: the kernel is handed every slot's arrays
    q, arrays = inputs(len(name), rows, rows + 2, dtype)
    clean = [jnp.asarray(a, dtype) for a in arrays]
    want = plain(q, *clean, pos)
    own = the_models_own(q, arrays, pos, dtype)
    # whatever lies past a row's depth, or in another slot, is no number,
    # or one that would take the whole softmax
    n, m = depths(pos)
    for a, depth in zip(arrays, (n, n, m, m)):
        for i in range(rows):
            a[i, depth[i]:] = poison
        a[rows:] = poison
    got = attend(q, [jnp.asarray(a, dtype) for a in arrays], pos)
    assert got.shape == (rows, H, D) and got.dtype == dtype
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < TOLERANCE[dtype.name]
    assert np.abs(got - own).max() < TOLERANCE[dtype.name]
    assert want.std() > 0.2
    # and the same without the poison, to the last bit
    again = np.asarray(attend(q, clean, pos).astype(jnp.float32))
    assert np.array_equal(got, again)


def test_one_softmax_over_both_sets():
    """Neither set alone gives the result: raising the table's keys moves
    weight off the ring's entries and the other way round, and the two
    sets' weights sum to one."""
    q, arrays = inputs(2, 2, 2, jnp.float32)
    pos = [2 * W + 9, 4 * W + 30]
    base = np.asarray(attend(q, arrays, pos))
    ones = [arrays[0], np.ones_like(arrays[1]), arrays[2],
            np.ones_like(arrays[3])]
    assert np.abs(np.asarray(attend(q, ones, pos)) - 1).max() < 1e-5
    ring_only = [arrays[0], arrays[1], arrays[2], 0 * arrays[3]]
    table_only = [arrays[0], 0 * arrays[1], arrays[2], arrays[3]]
    parts = np.asarray(attend(q, ring_only, pos)) + np.asarray(
        attend(q, table_only, pos))
    assert np.abs(parts - base).max() < 1e-5
    assert np.abs(np.asarray(attend(q, ring_only, pos)) - base).max() > 1e-2
    assert np.abs(np.asarray(attend(q, table_only, pos)) - base).max() > 1e-2


def test_a_deeper_row_reads_more_and_a_row_reads_only_its_own_slot():
    """Row ``i`` attends over slot ``i``: swapping two slots' arrays swaps
    what two rows give, and an entry more (of the ring, then a window's
    more of the table) changes the row that gained it alone."""
    q, arrays = inputs(5, 3, 4, jnp.float32)
    q = q.at[1].set(q[0])
    pos = [2 * W + 9] * 3
    base = np.asarray(attend(q, arrays, pos))
    assert np.abs(base[0] - base[1]).max() > 1e-2
    swap = [1, 0, 2, 3]
    swapped = np.asarray(attend(q, [a[swap] for a in arrays], pos))
    assert np.array_equal(swapped[0], base[1])
    assert np.array_equal(swapped[1], base[0])
    for more in (2 * W + 10, 3 * W + 9):
        deeper = np.asarray(attend(q, arrays, [pos[0], more, pos[2]]))
        assert np.array_equal(deeper[[0, 2]], base[[0, 2]])
        assert np.abs(deeper[1] - base[1]).max() > 1e-4


def test_a_head_reads_its_own_keys_and_values_and_no_other_heads():
    """An entry's heads lie side by side and the kernel's product is over
    all of them: changing head 1's keys and values, in both sets, moves
    head 1 and leaves the others as they were, to the last bit."""
    q, arrays = inputs(11, 2, 2, jnp.float32)
    pos = [3 * W + 20, W + 2]
    base = np.asarray(attend(q, arrays, pos))
    for a, shift in zip(arrays, (1.5, 1.0, 1.5, 1.0)):
        a[:, :, 1] = a[:, :, 1] * shift + (shift == 1.0)
    moved = np.asarray(attend(q, arrays, pos))
    assert np.array_equal(moved[:, [0, 2, 3]], base[:, [0, 2, 3]])
    assert np.abs(moved[:, 1] - base[:, 1]).min() > 1e-3


def test_peaked_scores_keep_their_sums():
    """Scores hundreds apart from block to block and from the ring to the
    table (the running maximum moves at every block): the sum carried
    over is rescaled, not lost."""
    q, arrays = inputs(9, 2, 2, jnp.float32)
    arrays[0] *= np.linspace(0.1, 6.0, W)[None, :, None, None]
    arrays[2] *= np.linspace(8.0, 0.1, T)[None, :, None, None]
    pos = [5 * W + W - 1, 2 * W + 13]
    got = np.asarray(attend(q * 6, arrays, pos))
    assert np.abs(got - plain(q * 6, *arrays, pos)).max() < 2e-4


def handed(n, m, blocks, ring, table):
    """The (slot, block) pairs of the ring and of the table that the
    kernel's index maps name over the grid's steps, in the order the
    pipeline asks for them, a pair listed when it differs from the step
    before's: the fetches."""
    n, m = jnp.asarray(n, jnp.int32), jnp.asarray(m, jnp.int32)
    rows = len(n)
    came_from = jax.lax.cummax(
        jnp.where(m > 0, jnp.arange(rows, dtype=jnp.int32), 0))
    steps = [-(-ring // blocks[0]), -(-table // blocks[1])]
    fetched, last = ([], []), [None, None]
    for i in range(rows):
        for j in range(sum(steps)):
            named = fa._ring_table_fetch(
                i, j, n, m, came_from, blocks, steps[0])
            for which in (0, 1):
                pair = tuple(int(x) for x in named[which])
                if pair != last[which]:
                    fetched[which].append(pair)
                    last[which] = pair
    return fetched


@pytest.mark.parametrize("ring,chunk,context,block", [
    (32, 4, 160, 8), (32, 4, 160, 128), (64, 4, 300, 16),
    (2048, 16, 13312, 128), (2048, 16, 13312, 256),
    (2048, 16, 13312, 512)])
def test_the_blocks_a_row_is_handed_are_what_the_account_books(
        monkeypatch, ring, chunk, context, block):
    """``step_account``'s span is the kernel's own fetches: per row, the
    ring's blocks up to the one that holds ``pos % w`` and the table's up
    to the last completed window's entries, each a block of every layer's
    two arrays, cut at the array's end; of an empty table nothing, but
    for the block that a call's first step is handed whatever it names."""
    from tpu_pipelines.models import evabyte

    tiny = importlib.import_module("test_evabyte")
    monkeypatch.setattr(fa, "RING_TABLE_BLOCK_K", block)
    model = evabyte.build_evabyte_model({
        **tiny.HP, "window_size": ring, "chunk_size": chunk,
        "compute_dtype": "bfloat16", "param_dtype": "bfloat16"})
    fns = evabyte.make_continuous_decode_fns(
        model, max_decode_len=context // 4, eos_id=tiny.VOCAB,
        max_input_len=context - context // 4)
    table = -(-context // ring) * ring // chunk
    cache = jax.eval_shape(lambda: fns.blank_cache(2))["layer_0"]
    assert cache["window_k"].shape[1] == ring
    assert cache["chunk_k"].shape[1] == table
    held = fa.ring_table_blocks(ring, table)
    assert all(b % 8 == 0 for b in held)
    per = ring // chunk
    windows = table // per
    rows_of = [
        [0], [ring - 1], [ring], [3, ring + held[0] - 1, ring + held[0]],
        [ring * (windows - 1) + 5, 2, ring * 2 - 1, ring + 1],
        [1, 2, ring * 2 + held[0] * 2, 7, ring * windows - 1],
    ]
    entry_bytes = 2 * 2 * 4 * 16 * 2   # k and v x layers x heads x 16 x bf16
    for pos in rows_of:
        n, m = depths(pos, ring, per, table)
        ring_fetches, table_fetches = handed(n, m, held, ring, table)
        # every row fetches its own ring blocks [0, last], once each
        assert ring_fetches == [
            (i, j) for i in range(len(pos))
            for j in range((n[i] - 1) // held[0] + 1)]
        # and its own table blocks where it has a table
        want = [(i, j) for i in range(len(pos)) if m[i]
                for j in range((m[i] - 1) // held[1] + 1)]
        if not m[0]:
            want = [(0, 0)] + want      # the call's first step
        assert table_fetches == want
        booked = {
            "window": entry_bytes * sum(
                min(((x - 1) // held[0] + 1) * held[0], ring) for x in n),
            "chunk": entry_bytes * sum(
                min(-(-x // held[1]) * held[1], table) for x in m)}
        account = fns.step_account(list(pos))
        assert account["cache_span_bytes"] == booked
        assert account["cache_bytes"] == {
            "window": entry_bytes * int(n.sum()),
            "chunk": entry_bytes * int(m.sum())}
        blocks_bytes = lambda fetches, block, size: entry_bytes * sum(
            min(block, size - j * block) for _, j in fetches)
        assert booked["window"] == blocks_bytes(ring_fetches, held[0], ring)
        assert booked["chunk"] == blocks_bytes(
            table_fetches[0 if m[0] else 1:], held[1], table)
        for kind in booked:
            assert booked[kind] >= account["cache_bytes"][kind]
