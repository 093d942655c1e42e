"""The LFR family: the same seed gives the same pool, and every graph of the
grid has the degrees, community sizes and mixing the configuration asks
for."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic
from bench.graphs import lfr

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "lfr_lf09.json").read_text())
GRID = CONFIG["graphs"]
SEED = 2**31 + 13


@pytest.fixture(scope="module")
def realized():
    """Each grid point's graph and communities, drawn in pool order."""
    rng = traffic.seeded(SEED, 0)
    out = []
    for n, name, mu in lfr.grid(GRID):
        s_min, s_max = GRID["communities"][name]
        edges, comm = lfr.lfr(n, mu, GRID["k_avg"], GRID["k_max"],
                              GRID["tau1"], GRID["tau2"], s_min, s_max, rng)
        out.append(((n, name, mu), edges, comm))
    return out


def test_pool_is_deterministic_per_seed():
    small = dict(GRID, sizes=[300], mu=[0.3])
    a = traffic.make_pool({"graphs": dict(small, kind="lfr")}, SEED)
    b = traffic.make_pool({"graphs": dict(small, kind="lfr")}, SEED)
    c = traffic.make_pool({"graphs": dict(small, kind="lfr")}, 5)
    assert [n for n, _ in a] == [n for n, _ in b] == [300, 300]
    assert all(np.array_equal(x, y) for (_, x), (_, y) in zip(a, b))
    assert any(not np.array_equal(x, y) for (_, x), (_, y) in zip(a, c))


def test_the_pool_is_the_grid_in_order():
    points = lfr.grid(GRID)
    assert len(points) == 32 and len(set(points)) == 32
    assert [n for n, _, _ in points] == [1000] * 16 + [5000] * 16


def test_graphs_are_simple(realized):
    for (n, _, _), edges, _ in realized:
        assert edges.dtype == np.int64 and edges.shape[1] == 2
        assert (edges[:, 0] < edges[:, 1]).all()
        assert edges.min() >= 0 and edges.max() < n
        assert len(np.unique(edges[:, 0] * n + edges[:, 1])) == len(edges)


def test_degrees_follow_the_grid(realized):
    for point, edges, _ in realized:
        deg = np.bincount(edges.ravel(), minlength=point[0])
        assert abs(deg.mean() - GRID["k_avg"]) <= 0.1 * GRID["k_avg"], point
        assert deg.max() <= GRID["k_max"], point


def test_community_sizes_lie_in_their_range(realized):
    for (n, name, mu), _, comm in realized:
        s_min, s_max = GRID["communities"][name]
        sizes = np.bincount(comm)
        assert sizes.sum() == n
        assert sizes.min() >= s_min and sizes.max() <= s_max, (n, name, mu)


def test_mixing_is_the_target(realized):
    for (n, name, mu), edges, comm in realized:
        outside = comm[edges[:, 0]] != comm[edges[:, 1]]
        assert abs(outside.mean() - mu) <= 0.05, (n, name, mu)


def test_k_min_gives_the_mean():
    k_min = lfr.solve_k_min(20, 50, 2.0)
    assert 9.0 < k_min < 11.0
    draws = lfr.power_law(k_min, 50, 2.0, 200_000, np.random.default_rng(0))
    assert draws.mean() == pytest.approx(20, rel=0.01)


def test_config_lists_the_generator_conventions():
    assert CONFIG["assumed"] == lfr.ASSUMED
    assert CONFIG["reduced"] == ["realizations"]
    assert GRID["realizations"] == 1
