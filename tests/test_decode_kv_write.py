"""The decode step's write of new K/V rows into the static cache at
per-row positions (models/transformer.py ``_write_rows_at``, the
continuous-batching branch of ``MultiHeadAttention``).

The write keeps the cache's layout on the chip (a one-hot select, not a
scatter; tests/test_tpu_compile.py holds the compiled programs to that).
Here, on the CPU, it is held to what it writes: the same values at the
same places as a plain numpy reference, an out-of-range position dropped
as ``.at[].set`` dropped it, and a row never writing outside itself.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_pipelines.models.transformer import MultiHeadAttention

pytestmark = pytest.mark.generative

B, KV, HEADS, HEAD_DIM, D_MODEL = 5, 16, 2, 8, 16


@pytest.fixture(scope="module")
def attn():
    """A bf16 self-attention layer, its parameters, and a filled cache."""
    layer = MultiHeadAttention(
        n_heads=HEADS, head_dim=HEAD_DIM, dtype=jnp.bfloat16
    )
    x = jax.random.normal(jax.random.key(1), (B, 1, D_MODEL), jnp.float32)
    variables = layer.init(
        jax.random.key(0), x, decode_pos=jnp.zeros((B,), jnp.int32),
        max_decode_len=KV,
    )
    shape = (B, KV, HEADS, HEAD_DIM)
    cache = {
        "cached_key": jax.random.normal(
            jax.random.key(2), shape).astype(jnp.bfloat16),
        "cached_value": jax.random.normal(
            jax.random.key(3), shape).astype(jnp.bfloat16),
    }
    return layer, variables["params"], cache


def _apply(layer, params, cache, x, pos):
    out, mut = layer.apply(
        {"params": params, "cache": cache}, x, decode_pos=pos,
        max_decode_len=cache["cached_key"].shape[1], mutable=["cache"],
    )
    return np.asarray(out), jax.tree.map(np.asarray, mut["cache"])


def _assert_same_bits(got, want, err_msg=""):
    np.testing.assert_array_equal(
        got.view(np.uint16), want.view(np.uint16), err_msg=err_msg)


def _new_rows(layer, params, x):
    """This step's K and V, ``[b, 1, heads, head_dim]`` each: what the
    scalar-position path stores at position 0 of a blank cache."""
    blank = {
        name: jnp.zeros((x.shape[0], 1, HEADS, HEAD_DIM), jnp.bfloat16)
        for name in ("cached_key", "cached_value")
    }
    return _apply(layer, params, blank, x, 0)[1]


def _reference(cache, new, pos):
    """Plain numpy: row ``i``'s entry at ``pos[i]``, a position outside
    the cache dropped."""
    want = {name: np.array(leaf) for name, leaf in cache.items()}
    for name, leaf in want.items():
        for i, p in enumerate(pos):
            if 0 <= p < leaf.shape[1]:
                leaf[i, p] = new[name][i, 0]
    return want


CASES = {
    # every row at one position: also the scalar-position path, bit for bit
    "one_position": [7] * B,
    "mixed_with_both_ends": [0, KV - 1, 3, KV - 1, 9],
    # a dead slot's stale position beside live rows: dropped
    "stale_position_past_kv": [4, KV + 9, 11, 0, KV],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_are_written_where_numpy_writes_them(attn, case):
    layer, params, cache = attn
    pos = CASES[case]
    x = jax.random.normal(jax.random.key(4), (B, 1, D_MODEL), jnp.float32)
    out, got = _apply(layer, params, cache, x, jnp.asarray(pos, jnp.int32))
    want = _reference(cache, _new_rows(layer, params, x), pos)
    for name in want:
        _assert_same_bits(got[name], want[name], f"{case}: {name}")
    if case == "one_position":
        s_out, s_got = _apply(layer, params, cache, x, pos[0])
        _assert_same_bits(out, s_out)
        for name in want:
            _assert_same_bits(got[name], s_got[name])


def test_a_dead_row_with_a_stale_position_harms_no_live_row():
    """Through the decode contract of a real T5: a slot whose sequence has
    gone keeps its old position, which may lie past the bucket the live
    rows fit.  The live rows' cache and logits are those of a step without
    it, bit for bit, and its own cache is left as it was."""
    from tpu_pipelines.models.t5 import T5, make_continuous_decode_fns

    model = T5(
        vocab_size=48, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, dropout_rate=0.0, dtype=jnp.float32,
    )
    inputs = np.asarray(
        [[5, 9, 12, 3, 0, 0], [7, 7, 2, 0, 0, 0], [4, 30, 21, 8, 6, 2]],
        np.int32,
    )
    mask = (inputs > 0).astype(np.int32)
    params = model.init(
        jax.random.key(0),
        {"inputs": inputs, "targets": np.ones((3, 5), np.int32)},
    )["params"]
    L = 8
    fns = make_continuous_decode_fns(
        model, max_decode_len=L, eos_id=1, max_input_len=6
    )
    cache, encoded, _ = fns.prefill(params, inputs, mask)
    tok = jnp.asarray([11, 13, 17], jnp.int32)
    live = np.asarray([0, 2])

    def step(rows, pos):
        sub = jax.tree.map(lambda x: x[rows], cache)
        new, logits = jax.jit(fns.step, static_argnames="klen")(
            params, sub, tok[rows], jnp.asarray(pos, jnp.int32),
            encoded[rows], mask[rows], klen=L,
        )
        return jax.tree.map(np.asarray, new), np.asarray(logits)

    with_dead, logits_with = step(np.arange(3), [1, L + 5, L - 1])
    without, logits_without = step(live, [1, L - 1])
    np.testing.assert_array_equal(logits_with[live], logits_without)
    before = jax.tree.map(np.asarray, cache)
    for got, want, was in zip(
        jax.tree.leaves(with_dead), jax.tree.leaves(without),
        jax.tree.leaves(before),
    ):
        np.testing.assert_array_equal(got[live], want)
        np.testing.assert_array_equal(got[1], was[1])
