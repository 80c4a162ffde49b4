"""Order statistics over every sample, and the traffic generator's
promise: every seed asks for the same work in another order."""

import math
import statistics

import numpy as np
import pytest

from bench import stats, traffic
from bench.sweep import knee_of
from tinycells import decode_mix, score_mix

BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("q", [50, 95, 99])
def test_percentile_is_over_all_samples(q):
    rng = np.random.default_rng(0)
    xs = rng.lognormal(size=10_001).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_counts_missing_as_infinite():
    xs = [1.0] * 90 + [math.inf] * 10
    assert math.isinf(stats.percentile(xs, 95))
    assert stats.percentile(xs, 50) == 1.0


def test_spread_is_statistics_quartiles_over_median():
    xs = [10.0, 11.0, 12.0, 13.0, 100.0, 9.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / med)


def test_union_length_counts_overlaps_once():
    assert stats.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4.0


def test_sessions_same_sizes_for_every_seed():
    mix = decode_mix("lss")
    a = traffic.decode_sessions(mix, 1024, 7, 128)
    b = traffic.decode_sessions(mix, 1024, BIG_SEED, 128)
    assert sorted(len(s.prompt) for s in a) == sorted(len(s.prompt) for s in b)
    assert sorted(s.max_new_tokens for s in a) == \
        sorted(s.max_new_tokens for s in b)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    lo, hi = traffic.length_range(mix["prompt_len"])
    assert all(lo <= len(s.prompt) <= hi for s in a)


def test_arrivals_same_gaps_for_every_seed():
    mix = score_mix("lss")
    a = traffic.arrival_offsets(mix, 3.0, 1)
    b = traffic.arrival_offsets(mix, 3.0, BIG_SEED)
    assert len(a) == len(b) == 600
    gaps = [np.sort(np.diff(np.append(t, 3.0))) for t in (a, b)]
    assert np.allclose(gaps[0], gaps[1])
    assert a[0] == 0.0 and a[-1] < 3.0 and np.all(np.diff(a) > 0)


def test_zipf_ids_follow_rank():
    rng = np.random.default_rng(1)
    ids = traffic.zipf_ids(1000, 1.0, 200_000, rng)
    counts = np.bincount(ids, minlength=1000)
    assert counts[0] > 1.8 * counts[1] > 0
    assert ids.min() >= 0 and ids.max() < 1000


def test_score_ids_same_for_a_seed_and_zipf_over_the_width():
    mix = score_mix("lss")
    a = traffic.score_ids(mix, 4096, 5000, BIG_SEED)
    assert np.array_equal(a, traffic.score_ids(mix, 4096, 5000, BIG_SEED))
    assert not np.array_equal(a, traffic.score_ids(mix, 4096, 5000, 7))
    assert a.min() >= 0 and a.max() < 4096
    assert np.mean(a == 0) > 5 * np.mean(a == 9)


def test_lengths_are_clipped_stratified_lognormal_quantiles():
    spec = decode_mix("lss")["prompt_len"]
    block = traffic.length_block(spec, 64)
    assert np.all(np.diff(block) >= 0)
    assert block.min() == spec["min"] and block.max() == spec["max"]
    assert np.median(block) == pytest.approx(spec["median"], abs=1)


def test_knee_is_the_highest_rate_most_seeds_sustain():
    # one stall on one seed neither sets nor lifts the knee
    held = {3000: [True, True, True], 3500: [False, True, True],
            4000: [True, False, False], 4500: [True, True, False],
            5000: [False, False, True]}
    assert knee_of(held) == 4500
    assert knee_of({1000: [False, False, True]}) is None
