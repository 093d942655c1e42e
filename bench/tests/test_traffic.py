"""The generators: the same seed gives the same traffic, and the Graph500
shape holds."""

import numpy as np
import pytest

from bench import traffic
from bench.graphs import kronecker

KRON = {"kind": "kronecker", "scale": 10, "edge_factor": 16, "A": 0.57,
        "B": 0.19, "C": 0.19, "pool": 2}


@pytest.mark.parametrize("graphs", [KRON, dict(KRON, scale=6, pool=5)])
def test_pool_is_deterministic_per_seed(graphs):
    a = traffic.make_pool({"graphs": graphs}, 2**31 + 7)
    b = traffic.make_pool({"graphs": graphs}, 2**31 + 7)
    c = traffic.make_pool({"graphs": graphs}, 5)
    assert [n for n, _ in a] == [n for n, _ in b]
    assert all(np.array_equal(x, y) for (_, x), (_, y) in zip(a, b))
    assert any(not np.array_equal(x, y) for (_, x), (_, y) in zip(a, c))


def test_kronecker_pool_has_the_same_sizes_for_every_seed():
    for seed in (1, 2, 2**31 + 11):
        pool = traffic.make_pool({"graphs": KRON}, seed)
        assert [(n, e.shape) for n, e in pool] == [(1024, (16 << 10, 2))] * 2
        assert all(e.min() >= 0 and e.max() < n for n, e in pool)


def test_kronecker_follows_graph500():
    rng = np.random.default_rng(0)
    e = kronecker.kronecker(12, 16, 0.57, 0.19, 0.19, rng)
    assert e.shape == (16 << 12, 2) and e.max() < 4096
    deg = np.bincount(e.ravel(), minlength=4096)
    assert deg.max() > 20 * deg.mean()        # a power-law tail


def test_open_loop_offers_the_same_gaps_in_another_order():
    a = traffic.poisson_offsets(24.0, 40.0, 1)
    b = traffic.poisson_offsets(24.0, 40.0, 2**31 + 3)
    assert len(a) == len(b) == 960
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    assert abs(a[-1] - 40.0) < 1.0
    assert not np.allclose(a, b)


def test_graph_order_cycles_through_the_pool():
    order = traffic.graph_order(8, 20, 4)
    assert sorted(order[:8]) == list(range(8))
    assert sorted(order[8:16]) == list(range(8))


def test_request_keys_are_distinct_and_deterministic():
    k1 = traffic.request_keys(1000, 9)
    assert np.array_equal(k1, traffic.request_keys(1000, 9))
    assert len({tuple(k) for k in k1}) == 1000
