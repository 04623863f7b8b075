"""The benchmark's generators: one seed gives the same arrays twice,
different seeds differ, and every seed gives the same shapes."""
import numpy as np

from bench.gen import data as gen


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


def test_federation_repeats_per_seed_and_differs_across_seeds():
    kw = dict(dataset="unsw", n_samples=3000, n_clients=8, size_seed=3)
    a, b = gen.federation(7, **kw), gen.federation(7, **kw)
    c = gen.federation(2**32 + 9, **kw)
    assert _same(a.x, b.x) and _same(a.y, b.y)
    assert np.array_equal(a.test_x, b.test_x)
    assert not np.array_equal(a.test_x, c.test_x)
    # the shard sizes are one multiset for every seed: one padded shape
    assert sorted(map(len, a.x)) == sorted(map(len, c.x))
    assert sum(map(len, a.x)) + len(a.test_x) == 3000


def test_road_windows_and_population_repeat():
    x1, y1, shape = gen.samples(np.random.default_rng(5), "road_raw", 64)
    x2, y2, _ = gen.samples(np.random.default_rng(5), "road_raw", 64)
    x3, _, _ = gen.samples(np.random.default_rng(6), "road_raw", 64)
    assert shape == (64, 6) and x1.shape == (64, 384)
    assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    assert not np.array_equal(x1, x3)
    kw = dict(dataset="unsw", n_clients=500, pool_samples=2000,
              members_per_client=8)
    p, q, r = gen.population(4, **kw), gen.population(4, **kw), gen.population(5, **kw)
    assert np.array_equal(p.member_idx, q.member_idx)
    assert np.array_equal(p.pool_x, q.pool_x)
    assert not np.array_equal(p.member_idx, r.member_idx)
    assert p.member_idx.shape == r.member_idx.shape == (500, 8)


def test_open_loop_arrivals():
    d1, i1 = gen.open_loop_arrivals(11, 1000.0, 2.0, 50)
    d2, i2 = gen.open_loop_arrivals(11, 1000.0, 2.0, 50)
    d3, i3 = gen.open_loop_arrivals(12, 1000.0, 2.0, 50)
    assert np.array_equal(d1, d2) and np.array_equal(i1, i2)
    assert not np.array_equal(d1, d3)
    # every seed offers the same number of windows, in time order
    assert len(d1) == len(d3) == 2000
    assert np.all(np.diff(d1) >= 0) and 0 <= d1[0] and d1[-1] <= 2.0
    assert i1.min() >= 0 and i1.max() < 50
