"""The decode kernel over grouped heads of ops/flash_attention.py
(``grouped_decode_attention``), interpreted on the CPU, against a plain
float32 softmax over each row's own entries ``[0, depth]``: at the
fixtures' widths (2 key/value heads of 4 query heads, 16 numbers a head),
with a key block of 128 so that an array of 320 entries is three blocks,
the last of them ragged, and a ring of 16 a small part of one.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the package exports a function under the module's name
fa = importlib.import_module("tpu_pipelines.ops.flash_attention")

KV, G, D = 2, 4, 16
BLOCK = 128
RING = 16


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(fa, "GROUPED_DECODE_BLOCK_K", BLOCK)


def plain(q, k, v, depth):
    """Row by row, head by head: scores, softmax and weights x values over
    the row's own ``depth + 1`` entries and nothing else, float32
    throughout."""
    q, k, v = (np.asarray(a, np.float32) for a in (q, k, v))
    out = np.zeros(q.shape, np.float32)
    for i, t in enumerate(np.asarray(depth)):
        for h in range(q.shape[1]):
            score = q[i, h] @ k[i, h, :t + 1].T
            p = np.exp(score - score.max(-1, keepdims=True))
            out[i, h] = (p / p.sum(-1, keepdims=True)) @ v[i, h, :t + 1]
    return out


def inputs(seed, rows, slots, entries, dtype):
    rng = np.random.default_rng(seed)
    # queries wide enough that a deep row still prefers some entries
    q = jnp.asarray(0.7 * rng.normal(size=(rows, KV, G, D)), dtype)
    k, v = (rng.normal(size=(slots, KV, entries, D)).astype(np.float32)
            for _ in range(2))
    return q, k, v


def attend(q, k, v, depth, klen):
    return jax.jit(
        lambda q, k, v, depth: fa.grouped_decode_attention(
            q, k, v, depth, klen))(q, k, v, jnp.asarray(depth, jnp.int32))


def ring_depth(pos):
    """As ``GroupedAttention.step`` says it for a window layer: a ring of
    ``RING`` entries holds ``[0, pos]`` until it has wrapped, and every
    entry after."""
    return [min(t, RING - 1) for t in pos]


# name -> (entries, klen, the rows' depths): a row alone at each edge of a
# block, rows of every kind in one batch, and a ring before and after it
# wraps
DEPTHS = {
    "first_entry": (320, 320, [0]),
    "a_block_less_one": (320, 320, [BLOCK - 1]),
    "a_block": (320, 320, [BLOCK]),
    "a_block_and_one": (320, 320, [BLOCK + 1]),
    "last_entry": (320, 320, [319]),
    "mixed": (320, 320, [319, 0, BLOCK, 5, 2 * BLOCK - 1, 2 * BLOCK, 200]),
    # the step's bucket ends before the array does
    "klen_inside_the_array": (320, 256, [255, 0, BLOCK - 1, BLOCK, 77]),
    # the one block reaches past the array's end
    "one_block_and_a_quarter": (160, 160, [159, 0, BLOCK - 1, BLOCK, 130]),
    "less_than_a_block": (104, 64, [63, 0, 40]),
    "ring_not_yet_wrapped": (RING, RING, ring_depth([0, 3, RING - 2])),
    "ring_wrapped": (
        RING, RING, ring_depth([RING - 1, RING, 3 * RING + 5, 2])),
}
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", DEPTHS)
def test_decode_attention_is_a_softmax_over_each_rows_own_depth(name, dtype):
    entries, klen, depths = DEPTHS[name]
    dtype = jnp.dtype(dtype)
    rows = len(depths)
    # two slots more than rows: the kernel is handed every slot's array
    q, k, v = inputs(len(name), rows, rows + 2, entries, dtype)
    clean = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    want = plain(q, *clean, depths)
    # whatever lies past a row's depth, or in another slot, is no number
    for a in (k, v):
        for i, t in enumerate(depths):
            a[i, :, t + 1:] = np.nan
        a[rows:] = np.nan
    got = attend(q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), depths,
                 klen)
    assert got.shape == (rows, KV, G, D) and got.dtype == dtype
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < TOLERANCE[dtype.name]
    assert want.std() > 0.2
    # and the same without the poison
    again = np.asarray(attend(q, *clean, depths, klen).astype(jnp.float32))
    assert np.array_equal(got, again)


def test_a_wrapped_ring_is_read_as_it_lies():
    """A ring's entries lie at ``position % RING``: once it has wrapped
    the kernel reads all of them whatever the order they were written in,
    and the result is the softmax over the last ``RING`` positions."""
    rng = np.random.default_rng(3)
    q, _, _ = inputs(3, 2, 2, RING, jnp.float32)
    pos = [RING + 5, 9]
    keys, values = (rng.normal(size=(2, KV, 2 * RING, D)).astype(np.float32)
                    for _ in range(2))            # by position
    ring_k, ring_v = (np.full((2, KV, RING, D), np.nan, np.float32)
                      for _ in range(2))
    want = np.zeros(q.shape, np.float32)
    for i, t in enumerate(pos):
        seen = range(max(0, t - RING + 1), t + 1)
        for u in seen:
            ring_k[i, :, u % RING] = keys[i, :, u]
            ring_v[i, :, u % RING] = values[i, :, u]
        by_position = lambda a: a[i:i + 1, :, list(seen)]
        want[i] = plain(q[i:i + 1], by_position(keys), by_position(values),
                        [len(seen) - 1])[0]
    got = np.asarray(attend(q, ring_k, ring_v, ring_depth(pos), RING))
    assert np.abs(got - want).max() < 2e-5


def test_a_deeper_row_reads_more_and_a_row_reads_only_its_own_slot():
    """Row ``i`` attends over slot ``i``: swapping two slots' arrays swaps
    what two rows give, and an entry more changes the row that gained it
    alone."""
    q, k, v = inputs(5, 3, 3, 320, jnp.float32)
    q = q.at[1].set(q[0])
    k, v = jnp.asarray(k), jnp.asarray(v)
    base = np.asarray(attend(q, k, v, [200, 200, 200], 320))
    assert np.abs(base[0] - base[1]).max() > 1e-2
    swap = jnp.asarray([1, 0, 2])
    swapped = np.asarray(attend(q, k[swap], v[swap], [200, 200, 200], 320))
    assert np.array_equal(swapped[0], base[1])
    assert np.array_equal(swapped[1], base[0])
    deeper = np.asarray(attend(q, k, v, [200, 201, 200], 320))
    assert np.array_equal(deeper[[0, 2]], base[[0, 2]])
    assert np.abs(deeper[1] - base[1]).max() > 1e-4


def test_query_heads_share_their_key_value_heads_entries_and_no_others():
    """Query head ``(h, i)`` reads key/value head ``h``: changing head 1's
    keys and values moves head 1's four query heads and leaves head 0's
    as they were, to the last bit."""
    q, k, v = inputs(11, 2, 2, 320, jnp.float32)
    base = np.asarray(attend(q, k, v, [300, 150], 320))
    k[:, 1] *= 1.5
    v[:, 1] += 1.0
    moved = np.asarray(attend(q, k, v, [300, 150], 320))
    assert np.array_equal(moved[:, 0], base[:, 0])
    assert np.abs(moved[:, 1] - base[:, 1]).min() > 1e-3


def test_peaked_scores_keep_their_sums():
    """Scores hundreds apart from block to block (the running maximum
    moves at every block): the sum carried over is rescaled, not lost."""
    q, k, v = inputs(9, 2, 2, 320, jnp.float32)
    k *= np.linspace(0.1, 6.0, 320)[None, None, :, None]
    got = np.asarray(attend(q * 6, k, v, [319, 130], 320))
    want = plain(q * 6, k, v, [319, 130])
    assert np.abs(got - want).max() < 2e-4


@pytest.mark.parametrize("positions,window,block", [
    (104, 16, 128), (320, 16, 128), (160, 256, 128), (640, 256, 512),
    (18432, 4096, 512), (18432, 4096, 1024)])
def test_the_blocks_a_row_is_handed_are_what_the_account_books(
        monkeypatch, positions, window, block):
    """``step_account``'s span is the kernel's own fetches: per row and
    kind, the distinct key blocks that the index map names over the grid's
    steps, each ``block`` entries of every layer's two arrays, cut at the
    array's end; a ring's depth is the whole ring once it has wrapped."""
    from tpu_pipelines.models import command_a

    tiny = importlib.import_module("test_command_a")
    monkeypatch.setattr(fa, "GROUPED_DECODE_BLOCK_K", block)
    model = command_a.build_command_a_model({
        **tiny.HP, "window_size": window, "compute_dtype": "bfloat16",
        "param_dtype": "bfloat16"})
    fns = command_a.make_continuous_decode_fns(
        model, max_decode_len=positions // 2, max_input_len=positions // 2,
        eos_id=tiny.VOCAB, prefill_window_len=tiny.PREFILL)
    assert fns.cache_positions == positions
    at = sorted({t for t in (
        0, 1, window - 2, window - 1, window, block - 1, block,
        positions // 2, positions - 1) if 0 <= t < positions})
    entry_bytes = 2 * 2 * 16 * 2          # k and v x heads x numbers x bf16
    booked = {"window": 0, "full": 0}
    for kind, entries, layers, depth_of in (
            ("window", window, 6, lambda t: min(t, window - 1)),
            ("full", positions, 2, lambda t: t)):
        held_block = fa.grouped_decode_block(entries)
        assert held_block % 128 == 0 and held_block <= max(block, 128)
        steps = -(-entries // held_block)
        for t in at:
            handed = {
                int(fa._latent_fetch(j, depth_of(t), held_block))
                for j in range(steps)}
            assert handed == set(range(depth_of(t) // held_block + 1))
            booked[kind] += layers * entry_bytes * min(
                (max(handed) + 1) * held_block, entries)
    account = fns.step_account(at, [0] * fns.step_tally_len, (len(at), 0))
    assert account["cache_span_bytes"] == booked
    assert account["cache_bytes"] == {
        "window": 6 * entry_bytes * sum(min(t + 1, window) for t in at),
        "full": 2 * entry_bytes * sum(t + 1 for t in at)}
    for kind in booked:
        assert booked[kind] >= account["cache_bytes"][kind]
