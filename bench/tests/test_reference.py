"""The plain reference agrees with itself where two definitions meet."""

import heapq

import jax
import numpy as np
import pytest

from bench import reference
from bench.graphs import kronecker


def heap_degeneracy(n, und):
    adj = [[] for _ in range(n)]
    for u, v in und:
        adj[u].append(v)
        adj[v].append(u)
    deg = [len(a) for a in adj]
    heap = [(d, v) for v, d in enumerate(deg)]
    heapq.heapify(heap)
    gone, best = [False] * n, 0
    while heap:
        d, v = heapq.heappop(heap)
        if gone[v] or d != deg[v]:
            continue
        gone[v], best = True, max(best, d)
        for u in adj[v]:
            if not gone[u]:
                deg[u] -= 1
                heapq.heappush(heap, (deg[u], u))
    return best


def graphs():
    rng = np.random.default_rng(1)
    out = [(1 << s, kronecker.kronecker(s, f, 0.57, 0.19, 0.19, rng))
           for s, f in ((4, 2), (7, 4), (8, 16))]
    out.append((512, kronecker.kronecker(9, 16, 0.57, 0.19, 0.19, rng)))
    star = np.stack([np.zeros(40, int), np.arange(1, 41)], axis=1)
    out.append((41, star))
    return out


@pytest.mark.parametrize("n,edges", graphs())
def test_degeneracy_matches_heap_peeling(n, edges):
    und = reference.canonical_edges(n, edges)
    assert reference.degeneracy(n, und) == heap_degeneracy(n, und)


@pytest.mark.parametrize("n,edges", graphs())
def test_rounds_pivot_is_sequential_pivot(n, edges):
    plan = reference.plan(n, edges, 2.0)
    keys = [jax.random.PRNGKey(s) for s in range(3)]
    ranks = reference.sample_ranks(jax, {n: keys}, 4)[n]
    for ranks_k in ranks:
        for r in ranks_k:
            labels, rounds = reference.pivot(plan, r)
            assert np.array_equal(labels,
                                  reference.sequential_pivot(plan, r))
            assert rounds >= 1


def test_ranks_are_jax_permutations():
    key = jax.random.PRNGKey(3)
    ranks = reference.sample_ranks(jax, {50: [key]}, 2)[50][0]
    perm = np.asarray(jax.random.permutation(jax.random.fold_in(key, 1), 50))
    assert np.array_equal(ranks[1][perm], np.arange(50))


def test_degree_cap_makes_the_star_centre_a_singleton():
    star = np.stack([np.zeros(40, int), np.arange(1, 41)], axis=1)
    plan = reference.plan(41, star, 2.0)
    assert plan.lam == 1 and plan.threshold == 12.0
    assert plan.high_degree == 1 and not plan.eligible[0]
    assert plan.kept == 0 and (plan.R, plan.W) == (64, 4)


def test_cost_counts_both_kinds_of_disagreement():
    plan = reference.plan(4, np.array([[0, 1], [1, 2]]), 2.0)
    # {0,1,2} together: edge (0,2) missing -> 1; vertex 3 alone: 0.
    assert reference.cost(plan, np.array([0, 0, 0, 3])) == 1
    # all apart: two positive edges cut.
    assert reference.cost(plan, np.arange(4)) == 2


def test_control_bound_is_not_the_exact_degeneracy():
    n, edges = graphs()[3]
    und = reference.canonical_edges(n, edges)
    d = reference.degeneracy(n, und)
    bound = reference.doubling_degeneracy_bound(n, und)
    assert d <= bound < 2 * d and bound != d
