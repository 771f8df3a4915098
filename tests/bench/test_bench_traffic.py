"""The benchmark's traffic generator and order statistics."""
import math
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import stats, traffic  # noqa: E402

MIX = {"arrival": {"process": "poisson", "rate_per_s": 4.0},
       "prompt_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                         "min": 32, "max": 512},
       "output_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.6,
                         "min": 64, "max": 1024},
       "lead_in_s": 5}


def _key(reqs):
    return [(r.due_s, r.max_new_tokens, r.prompt.tobytes()) for r in reqs]


def test_same_seed_same_traffic_and_large_seeds():
    big = 2**31 + 12345
    a = traffic.generate(MIX, big, [60.0], 151936)
    b = traffic.generate(MIX, big, [60.0], 151936)
    c = traffic.generate(MIX, big + 1, [60.0], 151936)
    assert a and _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_lengths_within_clip_ranges_and_ids_in_vocab():
    reqs = traffic.generate(MIX, 7, [200.0], 1000)
    for r in reqs:
        assert 32 <= len(r.prompt) <= 512
        assert 64 <= r.max_new_tokens <= 1024
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 1000
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues) and dues[-1] < 200.0
    # a stretch of some tens of requests reaches the tails and the clip
    win = traffic.generate(MIX, 7, [15.0], 1000)
    assert max(len(r.prompt) for r in win) == 512
    assert max(r.max_new_tokens for r in win) == 1024
    assert len({len(r.prompt) for r in win}) > len(win) // 2


def test_every_stretch_holds_the_same_work_in_another_order():
    spans = traffic.stretches(MIX, 30.0, 20.0)
    assert spans == [5.0, 30.0, 20.0]
    a = traffic.generate(MIX, 1, spans, 100)
    b = traffic.generate(MIX, 2, spans, 100)
    edges = np.cumsum([0.0] + spans)
    for lo, hi in zip(edges, edges[1:]):
        sa = [r for r in a if lo <= r.due_s < hi]
        sb = [r for r in b if lo <= r.due_s < hi]
        assert len(sa) == len(sb) == round(4.0 * (hi - lo))
        assert sorted(len(r.prompt) for r in sa) == sorted(
            len(r.prompt) for r in sb)
        assert sorted(r.max_new_tokens for r in sa) == sorted(
            r.max_new_tokens for r in sb)
        assert [r.max_new_tokens for r in sa] != [
            r.max_new_tokens for r in sb]
        assert [r.due_s for r in sa] != [r.due_s for r in sb]


def test_poisson_arrivals_given_their_count_are_uniform():
    reqs = traffic.generate(MIX, 9, [2500.0], 100)
    gaps = np.diff([r.due_s for r in reqs])
    assert gaps.var() / gaps.mean() ** 2 == pytest.approx(1.0, abs=0.1)
    # uniform arrival times: a quarter of them in each quarter
    counts = np.histogram([r.due_s for r in reqs], bins=4,
                          range=(0, 2500.0))[0]
    assert np.all(np.abs(counts - len(reqs) / 4) < 0.05 * len(reqs))


def test_rate_and_median_follow_the_mix():
    reqs = traffic.generate(MIX, 3, [1000.0], 100)
    assert len(reqs) / 1000.0 == pytest.approx(4.0, rel=0.05)
    med = statistics.median(len(r.prompt) for r in reqs)
    assert med == pytest.approx(128, rel=0.1)


def test_gamma_arrivals_are_burstier_than_poisson():
    gam = dict(MIX, arrival={"process": "gamma", "rate_per_s": 4.0,
                             "cv2": 4.0})
    reqs = traffic.generate(gam, 5, [1000.0], 100)
    gaps = np.diff([r.due_s for r in reqs])
    cv2 = gaps.var() / gaps.mean() ** 2
    assert len(reqs) / 1000.0 == pytest.approx(4.0, rel=0.1)
    assert cv2 > 2.0


def test_unknown_distribution_is_refused():
    bad = dict(MIX, prompt_tokens={"dist": "zipf", "min": 1, "max": 2})
    with pytest.raises(ValueError):
        traffic.generate(bad, 1, [10.0], 100)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None
    assert stats.percentile([5, 1, 4, 2, 3], 40) == 2


def test_spread_uses_python_quartiles():
    v = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q2, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / q2)
    assert math.isclose(stats.spread([2.0] * 6), 0.0)
