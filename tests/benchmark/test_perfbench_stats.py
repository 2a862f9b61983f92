"""Percentile, spread and the seeded schedules."""

import random
import statistics

import numpy as np
import pytest

from perfbench import stats
from perfbench.kinds import serve_closed, serve_common, serve_open
from perfbench.tools import rehearse


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_is_numpys(q):
    rng = random.Random(q)
    xs = [rng.random() for _ in range(137)]
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_by_hand():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([10], 95) == 10
    assert stats.percentile([0, 10], 95) == 9.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_the_quartile_distance_over_the_median():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 12.5)


@pytest.mark.parametrize("rate,seconds", [(4.0, 45.0), (0.5, 10.0),
                                          (7.3, 51.0)])
def test_open_loop_schedule_same_gaps_for_every_seed(rate, seconds):
    a = stats.poisson_arrivals(rate, seconds, random.Random(1))
    b = stats.poisson_arrivals(rate, seconds, random.Random(2**31 + 7))
    assert len(a) == len(b) == round(rate * seconds)
    assert a != b
    gaps = lambda xs: sorted(y - x for x, y in zip([0.0] + xs, xs))  # noqa
    assert gaps(a) == pytest.approx(gaps(b))
    assert all(0 < t < seconds for t in a) and a == sorted(a)
    # the gaps are an exponential's, scaled so that the last arrival is
    # half a mean gap inside the window: mean seconds / (n + 0.5), long tail
    g = gaps(a)
    assert statistics.mean(g) == pytest.approx(seconds / (len(a) + 0.5))
    if len(g) > 100:
        assert g[-1] > 3 * statistics.median(g)


def test_sizes_are_one_set_in_another_order():
    dist = {"dist": "loguniform", "low": 16, "high": 256}
    a = stats.sizes(dist, 40, random.Random(1))
    b = stats.sizes(dist, 40, random.Random(2))
    assert sorted(a) == sorted(b) and a != b
    assert min(a) >= 16 and max(a) <= 256
    assert statistics.median(a) < (16 + 256) / 2          # log-uniform
    assert stats.sizes({"dist": "fixed", "value": 128}, 3,
                       random.Random(0)) == [128] * 3
    u = stats.sizes({"dist": "uniform", "low": 64, "high": 256}, 24,
                    random.Random(0))
    assert statistics.mean(u) == pytest.approx(160, abs=1)


def test_requests_of_a_mix_same_work_for_every_seed():
    traffic = {"prompt_tokens": {"dist": "uniform", "low": 8, "high": 40},
               "output_tokens": {"dist": "uniform", "low": 4, "high": 12},
               "distinct_prompt_lengths": 5}
    config = rehearse.manifest().config("tiny")
    a = serve_open.schedule(traffic, config, 3, 10.0, 4.0)
    b = serve_open.schedule(traffic, config, 2**31 + 3, 10.0, 4.0)
    assert len(a) == len(b) == 40
    assert sorted(len(r.prompt) for r in a) == sorted(
        len(r.prompt) for r in b)
    assert set(len(r.prompt) for r in a) == set(
        serve_common.prompt_lengths(traffic))
    assert sorted(r.n_out for r in a) == sorted(r.n_out for r in b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(0 <= t < 250 for r in a for t in r.prompt)
    # the same seed gives the same inputs
    again = serve_open.schedule(traffic, config, 3, 10.0, 4.0)
    assert [(r.due, r.prompt, r.n_out) for r in a] == [
        (r.due, r.prompt, r.n_out) for r in again]


def test_a_mix_with_a_schedule_seed_times_one_sequence_for_every_seed():
    traffic = {"prompt_tokens": {"dist": "uniform", "low": 8, "high": 40},
               "output_tokens": {"dist": "uniform", "low": 4, "high": 12},
               "distinct_prompt_lengths": 5, "schedule_seed": 24}
    config = rehearse.manifest().config("tiny")
    a = serve_open.schedule(traffic, config, 3, 10.0, 4.0)
    b = serve_open.schedule(traffic, config, 2**31 + 3, 10.0, 4.0)
    assert [(r.due, len(r.prompt), r.n_out) for r in a] == [
        (r.due, len(r.prompt), r.n_out) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    other = serve_open.schedule(dict(traffic, schedule_seed=25), config, 3,
                                10.0, 4.0)
    assert [r.due for r in other] != [r.due for r in a]


def test_closed_loop_every_caller_cycles_its_own_lengths():
    traffic = {"clients": 4, "requests_per_client": 9,
               "prompt_tokens": {"dist": "uniform", "low": 8, "high": 40},
               "output_tokens": {"dist": "fixed", "value": 8},
               "distinct_prompt_lengths": 12}
    config = rehearse.manifest().config("tiny")
    a = serve_closed.plan_for(traffic, config, 5)
    b = serve_closed.plan_for(traffic, config, 2**31 + 5)
    lengths = serve_common.prompt_lengths(traffic)
    assert len(a) == 4 and all(len(mine) == 9 for mine in a)
    for i, (mine, theirs) in enumerate(zip(a, b)):
        own = sorted(lengths[i::4])
        # one pass over a caller's lengths is the same work for every seed
        assert sorted(len(r.prompt) for r in mine[:3]) == own
        assert sorted(len(r.prompt) for r in theirs[:3]) == own
        assert [len(r.prompt) for r in mine[3:6]] == [
            len(r.prompt) for r in mine[:3]]
        assert all(r.n_out == 8 for r in mine)
    assert [r.prompt for mine in a for r in mine] != [
        r.prompt for mine in b for r in mine]


def test_token_waits_and_first_token_waits():
    r = serve_common.Request(100.0, [1, 2], 5)
    r.arrivals = [(100.5, 1), (100.6, 1), (100.9, 3)]
    late = serve_common.Request(101.0, [1], 5)          # got nothing
    assert serve_common.token_waits([r, late]) == pytest.approx(
        [0.1, 0.1, 0.1, 0.1])
    assert serve_common.first_token_waits([r, late], 45.0) == pytest.approx(
        [0.5, 45.0])
    assert serve_common.tokens_in([r], 100.55, 101.0) == 4
    assert serve_common.failed([r, late]) == 1
