"""The generators are functions of the seed alone, and every seed offers
the same sizes and arrivals in another order."""

import itertools

import numpy as np
import pytest

from benchmark import manifest, traffic

CLOSED = traffic.load(manifest.traffic_path("decode-heavy-closed"))
OPEN = traffic.load(manifest.traffic_path("decode-heavy-stratified"))
TRAIN = traffic.load(manifest.traffic_path("finetune-b256-s128"))
BIG_SEED = 2 ** 31 + 12345


def _take(spec, seed, n):
    return list(itertools.islice(traffic.requests(spec, seed, 32128), n))


@pytest.mark.parametrize("spec", [CLOSED, OPEN], ids=["closed", "paced"])
def test_requests_are_a_function_of_the_seed(spec):
    a, b = _take(spec, BIG_SEED, 300), _take(spec, BIG_SEED, 300)
    c = _take(spec, BIG_SEED + 1, 300)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.gap_s) == (y.max_new_tokens, y.gap_s)
    assert any(
        not np.array_equal(x.prompt, z.prompt) for x, z in zip(a, c))


@pytest.mark.parametrize("spec", [CLOSED, OPEN], ids=["closed", "paced"])
def test_every_seed_offers_the_same_work_in_the_same_order(spec):
    block = spec["block"]
    a, b = _take(spec, 1, 2 * block), _take(spec, BIG_SEED, 2 * block)
    sizes = lambda reqs: [
        (len(r.prompt), r.max_new_tokens, r.gap_s) for r in reqs]
    assert sizes(a) == sizes(b)
    # every block holds the same sizes, shuffled anew
    for field in range(3):
        first = [x[field] for x in sizes(a[:block])]
        second = [x[field] for x in sizes(a[block:])]
        assert sorted(first) == sorted(second)
    assert sizes(a[:block]) != sizes(a[block:])
    assert [r.index for r in a] == list(range(2 * block))


def test_lengths_stay_inside_the_mix():
    reqs = _take(CLOSED, 3, CLOSED["block"])
    lens = [len(r.prompt) for r in reqs]
    outs = [r.max_new_tokens for r in reqs]
    assert min(lens) >= 32 and max(lens) <= 128
    assert min(outs) >= 32 and max(outs) <= 224
    assert 90 <= sorted(outs)[len(outs) // 2] <= 102
    assert all(r.prompt.min() >= traffic.FIRST_TOKEN_ID for r in reqs)
    assert all(r.gap_s == 0.0 for r in reqs)


def test_paced_gaps_average_the_rate():
    reqs = _take(OPEN, 9, OPEN["block"])
    mean_gap = sum(r.gap_s for r in reqs) / len(reqs)
    assert mean_gap == pytest.approx(1.0 / OPEN["rate_rps"], rel=0.03)
    assert min(r.gap_s for r in reqs) > 0


def test_quantiles_of_each_distribution():
    assert traffic.quantiles({"dist": "constant", "value": 3}, 2) == [3, 3]
    uni = traffic.quantiles(
        {"dist": "uniform_int", "low": 1, "high": 4}, 4)
    assert uni == [1.0, 2.0, 3.0, 4.0]
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "zipf"}, 4)


def test_train_pool_is_a_function_of_the_seed():
    spec = dict(TRAIN, batch_size=64, pool=3)
    a = traffic.train_pool(spec, BIG_SEED, 30528)
    b = traffic.train_pool(spec, BIG_SEED, 30528)
    c = traffic.train_pool(spec, 4, 30528)
    assert len(a) == 3
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a[0]["input_ids"], c[0]["input_ids"])
    assert not np.array_equal(a[0]["input_ids"], a[1]["input_ids"])
    batch = a[0]
    assert batch["input_ids"].shape == (64, 128)
    assert 0.6 < batch["label"].mean() < 0.9
    lens = batch["attention_mask"].sum(axis=1)
    assert lens.min() >= 32 and lens.max() <= 128
    assert (batch["input_ids"] * (1 - batch["attention_mask"])).sum() == 0
    assert set(np.unique(batch["label"])) <= {0, 1}
