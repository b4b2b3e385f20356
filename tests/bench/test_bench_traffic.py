"""The arrival generator: seeded, deterministic, and the same work for
every seed."""
import numpy as np
import pytest

from benchlib import traffic
from benchlib.pool import fingerprints, make_pool


def test_gaps_fill_the_window_at_the_rate():
    gaps = traffic.gaps(120.0, 10.0)
    assert len(gaps) == 1200
    assert gaps.sum() == pytest.approx(10.0)
    assert (gaps > 0).all()
    # exponential quantiles: the median gap is ln 2 of the mean
    assert np.median(gaps) / gaps.mean() == pytest.approx(np.log(2), 1e-2)


def test_same_seed_same_plan_and_another_seed_another_order():
    a = traffic.arrival_times(120.0, 10.0, 2 ** 33 + 7, 0.25)
    b = traffic.arrival_times(120.0, 10.0, 2 ** 33 + 7, 0.25)
    c = traffic.arrival_times(120.0, 10.0, 2 ** 33 + 8, 0.25)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


@pytest.mark.parametrize("rate_hz", [120.0, 3.5])
def test_every_seed_gets_the_same_gaps_in_another_order(rate_hz):
    lead = 0.25
    runs = [traffic.arrival_times(rate_hz, 10.0, s, lead) for s in (1, 2, 3)]
    assert len({len(t) for t in runs}) == 1
    for t in runs:
        assert (np.diff(t) > 0).all()
        # the window opens at the lead and its last arrival closes it
        assert t[0] > lead and t[-1] == pytest.approx(lead + 10.0)
    gap_sets = [np.sort(np.diff(np.concatenate([[lead], t]))) for t in runs]
    for g in gap_sets[1:]:
        np.testing.assert_allclose(g, gap_sets[0], rtol=1e-9, atol=1e-12)
    assert not np.array_equal(runs[0], runs[1])


def test_plan_deals_round_robin_and_keeps_each_device_sorted():
    plan = traffic.arrival_plan(120.0, 5.0, 9, 4)
    assert [len(p) for p in plan] == [150] * 4
    merged = np.sort(np.concatenate(plan))
    np.testing.assert_array_equal(
        merged, traffic.arrival_times(120.0, 5.0, 9))
    for p in plan:
        assert (np.diff(p) > 0).all()


@pytest.mark.parametrize("rate_hz, seconds", [(0.0, 1.0), (1.0, 0.0)])
def test_a_window_needs_a_rate_and_a_length(rate_hz, seconds):
    with pytest.raises(ValueError):
        traffic.arrival_times(rate_hz, seconds, 0)


def test_pool_is_seeded_and_shaped_like_the_miniapp_mixture():
    kw = dict(n_messages=3, n_points=500, n_features=32, n_clusters=25,
              outlier_frac=0.02, cluster_std=1.0, spread=10.0)
    a, b = make_pool(2 ** 40, **kw), make_pool(2 ** 40, **kw)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (3, 500, 32) and a.dtype == np.float64
    assert a.flags.c_contiguous
    # 2% of the points are outliers from the 4x box; inliers stay near
    # the centres' box
    far = (np.abs(a) > 10.0 + 5.0).any(axis=2)
    assert far.sum(axis=1).max() <= 10
    assert len(fingerprints(a)) == 3
    assert not np.array_equal(a, make_pool(2 ** 40 + 1, **kw))
