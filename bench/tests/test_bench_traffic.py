"""The open-loop generator and the percentile arithmetic of the benchmark."""
import math
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from sasabench import stats, traffic  # noqa: E402

SHARES = {"720p": 0.25, "1080p": 0.60, "2160p": 0.15}
BIG_SEED = 2**33 + 12345          # seeds are wider than 32 bits


def test_schedule_is_reproducible_from_the_seed():
    a = traffic.open_loop(3.0, 40.0, SHARES, BIG_SEED)
    b = traffic.open_loop(3.0, 40.0, SHARES, BIG_SEED)
    assert a == b
    assert a != traffic.open_loop(3.0, 40.0, SHARES, BIG_SEED + 1)


def test_every_seed_gets_the_same_work_in_another_order():
    runs = [traffic.open_loop(3.0, 40.0, SHARES, s) for s in (1, 7, BIG_SEED)]
    for r in runs:
        assert len(r) == 120
        assert Counter(x.label for x in r) == {"1080p": 72, "720p": 30,
                                               "2160p": 18}
        assert r[0].at_s == 0.0 and r[-1].at_s < 40.0
        assert all(x.at_s < y.at_s for x, y in zip(r, r[1:]))
    gaps = [sorted(np.diff([x.at_s for x in r] + [40.0])) for r in runs]
    for g in gaps[1:]:
        np.testing.assert_allclose(g, gaps[0], rtol=1e-9)
    assert np.mean(gaps[0]) == pytest.approx(1 / 3.0)


def test_split_counts_largest_remainder():
    assert traffic.split_counts(SHARES, 10) == {"720p": 3, "1080p": 6,
                                                "2160p": 1}
    assert sum(traffic.split_counts(SHARES, 7).values()) == 7


def test_checked_sample_holds_every_shape():
    arr = traffic.open_loop(3.0, 40.0, SHARES, BIG_SEED)
    idx = traffic.checked_sample(arr, 16, BIG_SEED, largest="2160p")
    assert len(idx) == 16 == len(set(idx))
    assert {arr[i].label for i in idx} == set(SHARES)
    assert idx == traffic.checked_sample(arr, 16, BIG_SEED, largest="2160p")


def test_request_pool_is_reproducible():
    shapes = {"a": (5, 7), "b": (3, 4)}
    p = traffic.request_pool(shapes, 2, BIG_SEED)
    q = traffic.request_pool(shapes, 2, BIG_SEED)
    for k in shapes:
        assert [g.shape for g in p[k]] == [shapes[k]] * 2
        assert all(g.dtype == np.float32 for g in p[k])
        for g, h in zip(p[k], q[k]):
            np.testing.assert_array_equal(g, h)
    assert not np.array_equal(p["a"][0], p["a"][1])


@pytest.mark.parametrize("q", [0, 5, 50, 90, 95, 99, 100])
def test_percentile_matches_linear_interpolation(q):
    rng = np.random.default_rng(3)
    xs = list(rng.exponential(size=101))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_counts_failures_beyond_any_limit():
    xs = [1.0] * 95 + [math.inf] * 5
    assert stats.percentile(xs, 50) == 1.0
    assert stats.percentile(xs, 99) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 50)
