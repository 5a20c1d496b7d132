"""What decides ``correct`` in the engine cells (ISSUE 39): the statistics
of the served tokens' gaps that a configuration's ``check.limits`` may name,
held by one rule, and the sample of requests drawn by request index.  Made-up
gaps and made-up requests: nothing here runs a model."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import engine

# 1,000 gaps: 985 at nought, 14 at 0.5, one at 2.0
GAPS = np.asarray([0.0] * 985 + [0.5] * 14 + [2.0])
WIDEST, MEAN, P99 = 2.0, 0.009, 0.5


def compared(limits, prefix=""):
    checks = harness.Checks()
    engine.compare_gaps(checks, prefix, GAPS, limits)
    return {r["check"]: (r["value"], r["limit"], r["ok"])
            for r in checks.rows}, checks.ok


@pytest.mark.parametrize("name,value", [
    ("served_token_gap.widest", WIDEST),
    ("served_token_gap.mean", MEAN),
    ("served_token_gap.p99", P99),
])
def test_a_statistic_is_compared_when_the_limits_name_it(
        capsys, name, value):
    rows, ok = compared({name: value * 1.01})
    assert set(rows) == {name} and ok
    assert rows[name][0] == pytest.approx(value)
    rows, ok = compared({name: value * 0.99})
    assert rows[name][2] is False and not ok
    # the other two are printed all the same, and say so
    out = capsys.readouterr().out
    others = set(engine.GAP_STATISTICS) - {name}
    for other in others:
        assert f"  {other}: " in out and "(not compared)" in out


def test_every_named_statistic_has_to_hold():
    limits = {"served_token_gap.widest": 2.5, "served_token_gap.mean": 0.01,
              "served_token_gap.p99": 0.6}
    rows, ok = compared(limits)
    assert set(rows) == set(limits) and ok
    rows, ok = compared({**limits, "served_token_gap.mean": 0.005})
    assert not ok
    assert [name for name, row in rows.items() if not row[2]] == [
        "served_token_gap.mean"]


def test_widest_alone_reads_as_before(capsys):
    """The four configurations that name only the widest gap: one row, by
    its old name, the largest gap against the limit."""
    rows, ok = compared({"served_token_gap.widest": 2.0})
    assert rows == {"served_token_gap.widest": (2.0, 2.0, True)} and ok
    assert "check served_token_gap.widest: 2.0 (limit 2.0) ok" in \
        capsys.readouterr().out
    rows, ok = compared({"served_token_gap.widest": 1.05}, "control[int8].")
    assert rows == {
        "control[int8].served_token_gap.widest": (2.0, 1.05, False)}
    assert not ok


@pytest.mark.parametrize("limits", [
    {"served_token_gap.widest": 1.0, "served_token_gap.median": 0.1},
    {"served_token_gap.wides": 1.0},
    {},
])
def test_a_key_that_is_no_statistic_is_an_error(limits):
    with pytest.raises(KeyError, match="check.limits"):
        compared(limits)


def test_p99_is_the_nearest_rank():
    checks = harness.Checks()
    engine.compare_gaps(
        checks, "", np.arange(1, 201) / 100.0,
        {"served_token_gap.p99": 1.98})
    assert checks.rows[0]["value"] == 1.98 and checks.ok


# ------------------------------------------------------------- the sample


def offered(n, lengths=None):
    """``n`` requests as the generator offers them: index ``i`` asks for
    ``lengths[i % len(lengths)]`` tokens."""
    lengths = lengths or [32, 224, 96, 224, 64, 160, 48, 128]
    return [
        SimpleNamespace(request=SimpleNamespace(
            index=i, max_new_tokens=lengths[i % len(lengths)]))
        for i in range(n)]


def indices(sample):
    return [o.request.index for o in sample]


def test_the_sample_is_a_function_of_the_seed_and_the_indices():
    """The same requests whatever the order they finished in, whatever was
    offered behind them and whatever tail of the finished list is cut:
    only a run that did not finish a sampled request samples another."""
    everyone = offered(400)
    finished = everyone[:300]
    want = indices(engine.draw_sample(everyone, finished, 2 ** 31 + 7, 6))
    assert len(set(want)) == 6
    rng = np.random.default_rng(0)
    for _ in range(20):
        shuffled = [finished[i] for i in rng.permutation(len(finished))]
        assert indices(engine.draw_sample(
            everyone, shuffled, 2 ** 31 + 7, 6)) == want
    # another run offered fewer or more requests, and finished fewer: every
    # cut that keeps the sampled six samples the same six
    assert max(want) < 299
    for n_offered, n_finished in ((400, 299), (391, max(want) + 1),
                                  (431, 300)):
        assert indices(engine.draw_sample(
            offered(n_offered), everyone[:n_finished], 2 ** 31 + 7, 6
        )) == want
    # a cut that loses one of them replaces that one alone
    lost = max(want[1:])
    cut = [o for o in finished if o.request.index != lost]
    again = indices(engine.draw_sample(everyone, cut, 2 ** 31 + 7, 6))
    assert [i for i in want if i != lost] == again[:5]
    assert again[5] not in want


def test_another_seed_draws_another_sample():
    everyone = offered(400)
    first = indices(engine.draw_sample(everyone, everyone[:300], 11, 6))
    second = indices(engine.draw_sample(everyone, everyone[:300], 12, 6))
    assert first[0] == second[0]            # the longest is the traffic's
    assert set(first[1:]) != set(second[1:])


def test_the_longest_is_chosen_by_what_was_asked_well_inside_the_window():
    """The lowest index among those that asked for the most tokens, of the
    first three quarters of the indices offered: not whichever long one
    the window's edge let through."""
    everyone = offered(100, lengths=[10, 50, 20, 50])
    sample = engine.draw_sample(everyone, everyone[:90], 5, 3)
    assert indices(sample)[0] == 1
    # index 1 did not finish: the next that asked for as much
    sample = engine.draw_sample(
        everyone, [o for o in everyone[:90] if o.request.index != 1], 5, 3)
    assert indices(sample)[0] == 3
    # a longer one at the edge (index 80 of 100 offered) is left alone
    everyone[80].request.max_new_tokens = 500
    assert indices(engine.draw_sample(everyone, everyone[:90], 5, 3))[0] == 1
    # but where nothing finished well inside, the longest that did
    late = engine.draw_sample(everyone, everyone[78:90], 5, 3)
    assert indices(late)[0] == 80


def test_a_sample_never_holds_more_than_finished():
    everyone = offered(10)
    assert sorted(indices(engine.draw_sample(
        everyone, everyone[:2], 3, 6))) == [0, 1]
