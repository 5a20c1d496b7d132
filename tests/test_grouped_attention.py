"""The grouped attention kernel of ops/flash_attention.py, interpreted on
the CPU, against a plain masked softmax and against the blocked online
softmax in ``jnp`` that it took the place of (models/command_a.py until
PR 37): the entries as a prefill window of Command A+ hands them over, at
sizes small enough for the interpreter and with tiles small enough that
every case spans several blocks of queries and of keys.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the package exports a function under the module's name
fa = importlib.import_module("tpu_pipelines.ops.flash_attention")

NEG_INF = -1e30
KV, D, P, W, POSITIONS = 2, 16, 32, 256, 512


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """16 queries x 128 keys a step: a window of 32 queries is two query
    blocks, a ring of 256 behind it three key blocks, the by-position
    array four."""
    monkeypatch.setattr(fa, "GROUPED_BLOCK_Q", 16)
    monkeypatch.setattr(fa, "GROUPED_BLOCK_K", 128)


def sees_of(window):
    """The rule of ``GroupedAttention.sees``."""
    def sees(t, u):
        ok = (u <= t) & (u >= 0)
        return ok if window is None else ok & (u > t - window)
    return sees


def plain(q, k, v, held, start, sees):
    """Scores, mask, softmax, values: nothing blocked, float32 throughout
    but for the products' operands."""
    t = start + jnp.arange(q.shape[2])
    score = jnp.einsum(
        "hgld,hsd->hgls", q, k, preferred_element_type=jnp.float32)
    score = jnp.where(sees(t[:, None], held[None, :]), score, NEG_INF)
    return jnp.einsum(
        "hgls,hsd->hgld", jax.nn.softmax(score, -1).astype(v.dtype), v,
        preferred_element_type=jnp.float32)


def blocked(q, k, v, held, start, sees, kb):
    """The ``jnp`` oracle: an online softmax over blocks of ``kb`` keys,
    every block computed, the heads of a group stacked as rows."""
    kv, g, l, d = q.shape
    f32 = dict(preferred_element_type=jnp.float32)
    rows = q.reshape(kv, g * l, d)
    t = jnp.tile(start + jnp.arange(l), g)
    top = jnp.full((kv, g * l), NEG_INF, jnp.float32)
    total = jnp.zeros((kv, g * l), jnp.float32)
    acc = jnp.zeros((kv, g * l, d), jnp.float32)
    for j in range(0, k.shape[1], kb):
        ok = sees(t[:, None], held[None, j:j + kb])[None]
        score = jnp.where(ok, jnp.einsum(
            "hrd,hsd->hrs", rows, k[:, j:j + kb], **f32), NEG_INF)
        new_top = jnp.maximum(top, score.max(-1))
        p = jnp.exp(score - new_top[..., None])
        keep = jnp.exp(top - new_top)
        total = total * keep + p.sum(-1)
        acc = acc * keep[..., None] + jnp.einsum(
            "hrs,hsd->hrd", p.astype(v.dtype), v[:, j:j + kb], **f32)
        top = new_top
    return (acc / total[..., None]).reshape(kv, g, l, d)


def ring_held(start):
    """What ``GroupedAttention.window`` hands over for a window layer:
    the window's own positions, then the ring as it was."""
    at = np.arange(W)
    return np.concatenate(
        [start + np.arange(P), start - 1 - (start - 1 - at) % W])


# name -> (window or None, start, held, entries whose keys and values are
# poisoned with NaN: whole key blocks of 128 that no query sees, which the
# kernel must not compute)
CASES = {
    # the by-position array of a full layer: what lies past the window's
    # last position is not yet written
    "full_at_0": (None, 0, np.arange(POSITIONS), slice(128, None)),
    "full_mid_array": (None, 224, np.arange(POSITIONS), slice(256, None)),
    "full_last_window": (None, POSITIONS - P, np.arange(POSITIONS), None),
    # a ring behind the window's own keys
    "ring_blank": (W, 0, ring_held(0), slice(128, None)),
    "ring_part_filled": (W, 96, ring_held(96), slice(P + 96, None)),
    "ring_wrapped_once": (W, W + 64, ring_held(W + 64), None),
    "ring_wrapped_off_its_start": (W, 2 * W + 96, ring_held(2 * W + 96), None),
    # a window whose last positions are past the prompt's end: the ring
    # behind the NEXT one would hold only the valid ones; here the entries
    # 20.. of the 32 written last hold nothing
    "ring_after_a_short_window": (
        W, 64, np.where(
            (np.arange(P + W) >= P + 52) & (np.arange(P + W) < P + 64),
            -1, ring_held(64)), slice(128, None)),
    # entries that hold nothing, scattered among those that do
    "held_negative_scattered": (
        None, 300, np.where(np.arange(POSITIONS) % 3 == 1, -1,
                            np.arange(POSITIONS)), None),
    # a key block that is wholly dead between two live ones (entries
    # 128..255 hold nothing; 0..127 and 256.. do)
    "dead_block_between_live_ones": (
        None, 400, np.where((np.arange(POSITIONS) >= 128)
                            & (np.arange(POSITIONS) < 256), -1,
                            np.arange(POSITIONS)), slice(128, 256)),
    "dead_block_between_live_ones_ring": (
        W, 3 * W, np.where((np.arange(P + W) >= 128)
                           & (np.arange(P + W) < 256), -1,
                           ring_held(3 * W)), slice(128, 256)),
}
GROUPS = {"full_at_0": (1, 4, 16), "ring_wrapped_once": (1, 4, 16)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name,g", [
    (name, g) for name in CASES for g in GROUPS.get(name, (4,))])
def test_grouped_attention_is_a_masked_softmax_over_the_seen_keys(
        name, g, dtype):
    window, start, held, poisoned = CASES[name]
    dtype = jnp.dtype(dtype)
    sees = sees_of(window)
    rng = np.random.default_rng(len(name) + g)
    entries = len(held)
    q = jnp.asarray(rng.normal(size=(KV, g, P, D)) * D ** -0.5, dtype)
    k = rng.normal(size=(KV, entries, D)).astype(np.float32)
    v = rng.normal(size=(KV, entries, D)).astype(np.float32)
    held = jnp.asarray(held, jnp.int32)
    # every query sees a key, and none sees a poisoned one
    seen = np.asarray(sees((start + jnp.arange(P))[:, None], held[None, :]))
    assert seen.any(1).all()
    clean = (jnp.array(k, dtype), jnp.array(v, dtype))    # copies
    if poisoned is not None:
        assert not seen[:, poisoned].any()
        k[:, poisoned] = np.nan
        v[:, poisoned] = np.nan
    got = jax.jit(lambda q, k, v, held, start: fa.grouped_attention(
        q, k, v, held, start, sees))(
            q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), held,
            jnp.int32(start))
    assert got.shape == q.shape and got.dtype == dtype
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    # the same arithmetic in jnp, every block computed: equal to rounding
    # of the result's own format (the order of a row's sum differs)
    same = np.asarray(blocked(q, *clean, held, start, sees, 128))
    exact = np.asarray(plain(
        q.astype(jnp.float32), *(a.astype(jnp.float32) for a in clean),
        held, start, sees))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, same, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got, exact, rtol=2e-5, atol=2e-5)
    else:
        np.testing.assert_allclose(got, same, rtol=1e-2, atol=1e-2)
        np.testing.assert_allclose(got, exact, rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("l,entries", [(1, 1), (7, 7), (50, 50), (40, 300)])
def test_lengths_that_no_tile_divides_are_padded_inside(l, entries):
    """A whole sequence of any length (``CommandA.__call__``): the kernel
    pads its own blocks with entries that hold nothing."""
    sees = sees_of(None)
    rng = np.random.default_rng(l)
    start = entries - l
    q = jnp.asarray(rng.normal(size=(KV, 4, l, D)) * D ** -0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(KV, entries, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(KV, entries, D)), jnp.float32)
    held = jnp.arange(entries)
    got = fa.grouped_attention(q, k, v, held, start, sees)
    assert got.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(plain(q, k, v, held, start, sees)),
        rtol=2e-5, atol=2e-5)
