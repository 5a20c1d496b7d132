"""The latent decode kernel of ops/flash_attention.py
(``latent_decode_attention``), interpreted on the CPU, against a plain
float32 softmax over each row's first ``pos + 1`` cached rows: at the
fixtures' widths (4 heads, latents of 16 + 8: no multiple of 128), with a
key block of 128 so that a cache of 320 positions is three blocks, the
last of them ragged, and one of 160 a block and a quarter.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the package exports a function under the module's name
fa = importlib.import_module("tpu_pipelines.ops.flash_attention")

HEADS, R, ROPE = 4, 16, 8
WIDTH = R + ROPE
BLOCK = 128
SCALE = WIDTH ** -0.5


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(fa, "LATENT_BLOCK_K", BLOCK)


def plain(q, cache, pos):
    """Row by row: scores, softmax and weights x latents over the row's
    own ``pos + 1`` rows and nothing else, float32 throughout."""
    q, cache = np.asarray(q, np.float32), np.asarray(cache, np.float32)
    out = np.zeros(q.shape[:2] + (R,), np.float32)
    for i, t in enumerate(np.asarray(pos)):
        rows = cache[i, :t + 1]
        score = q[i] @ rows.T * SCALE
        p = np.exp(score - score.max(-1, keepdims=True))
        out[i] = (p / p.sum(-1, keepdims=True)) @ rows[:, :R]
    return out


def inputs(seed, rows, slots, positions, dtype):
    rng = np.random.default_rng(seed)
    # queries wide enough that a deep row still prefers some positions
    q = jnp.asarray(2 * rng.normal(size=(rows, HEADS, WIDTH)), dtype)
    cache = rng.normal(size=(slots, positions, WIDTH)).astype(np.float32)
    return q, cache


def attend(q, cache, pos, klen):
    return jax.jit(
        lambda q, cache, pos: fa.latent_decode_attention(
            q, cache, pos, klen, scale=SCALE, r=R))(
                q, cache, jnp.asarray(pos, jnp.int32))


# name -> (positions, klen, the rows' depths): a row alone at each edge of
# a block, and rows of every kind in one batch
DEPTHS = {
    "first_position": (320, 320, [0]),
    "a_block_less_one": (320, 320, [BLOCK - 1]),
    "a_block": (320, 320, [BLOCK]),
    "a_block_and_one": (320, 320, [BLOCK + 1]),
    "last_position": (320, 320, [319]),
    "mixed": (320, 320, [319, 0, BLOCK, 5, 2 * BLOCK - 1, 2 * BLOCK, 200]),
    # the step's bucket ends before the array does
    "klen_inside_the_array": (320, 256, [255, 0, BLOCK - 1, BLOCK, 77]),
    # the one block reaches past the array's end
    "one_block_and_a_quarter": (160, 160, [159, 0, BLOCK - 1, BLOCK, 130]),
    "less_than_a_block": (96, 96, [95, 0, 40]),
}
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", DEPTHS)
def test_latent_attention_is_a_softmax_over_each_rows_own_depth(name, dtype):
    positions, klen, depths = DEPTHS[name]
    dtype = jnp.dtype(dtype)
    rows = len(depths)
    # two slots more than rows: the kernel is handed every slot's array
    q, cache = inputs(len(name), rows, rows + 2, positions, dtype)
    clean = jnp.asarray(cache, dtype)
    want = plain(q, clean, depths)
    # whatever lies past a row's depth is no number
    for i, t in enumerate(depths):
        cache[i, t + 1:] = np.nan
    cache[rows:] = np.nan
    got = attend(q, jnp.asarray(cache, dtype), depths, klen)
    assert got.shape == (rows, HEADS, R) and got.dtype == dtype
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < TOLERANCE[dtype.name]
    assert want.std() > 0.2
    # and the same without the poison
    again = np.asarray(attend(q, clean, depths, klen).astype(jnp.float32))
    assert np.array_equal(got, again)


def test_a_deeper_row_reads_more_and_a_row_reads_only_its_own_slot():
    """Row ``i`` attends over slot ``i``: swapping two slots' arrays swaps
    what two rows give, and a position more changes the row that gained
    it alone."""
    q, cache = inputs(5, 3, 3, 320, jnp.float32)
    q = q.at[1].set(q[0])
    cache = jnp.asarray(cache)
    base = np.asarray(attend(q, cache, [200, 200, 200], 320))
    assert np.abs(base[0] - base[1]).max() > 1e-2
    swapped = np.asarray(attend(
        q, cache[jnp.asarray([1, 0, 2])], [200, 200, 200], 320))
    assert np.array_equal(swapped[0], base[1])
    assert np.array_equal(swapped[1], base[0])
    deeper = np.asarray(attend(q, cache, [200, 201, 200], 320))
    assert np.array_equal(deeper[[0, 2]], base[[0, 2]])
    assert np.abs(deeper[1] - base[1]).max() > 1e-4


def test_peaked_scores_keep_their_sums():
    """Scores hundreds apart from block to block (the running maximum
    moves at every block): the sum carried over is rescaled, not lost."""
    q, cache = inputs(9, 2, 2, 320, jnp.float32)
    cache[:, :, :] *= np.linspace(0.1, 6.0, 320)[None, :, None]
    got = np.asarray(attend(q * 4, jnp.asarray(cache), [319, 130], 320))
    want = plain(q * 4, cache, [319, 130])
    assert np.abs(got - want).max() < 2e-4


@pytest.mark.parametrize("positions,block", [
    (320, 128), (160, 128), (160, 512), (96, 512), (2560, 512), (2560, 640)])
def test_the_blocks_a_row_is_handed_are_what_the_account_books(
        monkeypatch, positions, block):
    """``step_account``'s span is the kernel's own fetches: per row, the
    distinct key blocks that the index map names over the grid's steps,
    each ``block`` positions of every layer's array, cut at its end."""
    from tpu_pipelines.models import pangu_moe

    tiny = importlib.import_module("test_pangu_moe")
    monkeypatch.setattr(fa, "LATENT_BLOCK_K", block)
    model = pangu_moe.build_pangu_moe_model(
        {**tiny.HP, "compute_dtype": "bfloat16", "param_dtype": "bfloat16"})
    fns = pangu_moe.make_continuous_decode_fns(
        model, max_decode_len=positions // 2, max_input_len=positions // 2,
        eos_id=tiny.VOCAB, prefill_window_len=tiny.WINDOW)
    assert fns.cache_positions == positions
    held_block = fa.latent_block(positions)
    assert held_block % 128 == 0 and held_block <= max(block, 128)
    steps = -(-positions // held_block)
    at = [t for t in (0, 1, held_block - 1, held_block, positions // 2,
                      positions - 1) if t < positions]
    handed = [
        {int(fa._latent_fetch(j, t, held_block)) for j in range(steps)}
        for t in at]
    for t, blocks in zip(at, handed):
        assert blocks == set(range(t // held_block + 1))
    row_bytes = 3 * tiny.ROW * 2              # layers x numbers x bfloat16
    account = fns.step_account(at, [0] * fns.step_tally_len)
    assert account["cache_span_bytes"] == {"latent": row_bytes * sum(
        min((max(blocks) + 1) * held_block, positions) for blocks in handed)}
    assert account["cache_bytes"] == {
        "latent": row_bytes * sum(t + 1 for t in at)}
    assert account["cache_span_bytes"]["latent"] \
        >= account["cache_bytes"]["latent"]
