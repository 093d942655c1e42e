"""Lemma 25 structure, Corollary 32 clique algorithm, arboricity bounds."""

import heapq

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    arboricity_bounds,
    build_graph,
    clique_clustering,
    clustering_cost,
    connected_components,
    degeneracy_parallel,
    degeneracy_peel,
    graph_fingerprint,
    lemma25_transform,
    plan_graph,
)
from repro.core.arboricity import SMALL_FRONTIER
from repro.core.graph import (
    barbell,
    clique,
    disjoint_cliques,
    gnp,
    path,
    random_arboric,
    random_forest,
    scale_free,
    star,
)
from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest


def heap_degeneracy(g):
    """Exact degeneracy via a min-degree peeling with a heap: the oracle."""
    n = g.n
    if n == 0:
        return 0
    deg = np.asarray(g.deg).copy()
    dst = np.asarray(g.dst)
    row = np.asarray(g.row_offsets)
    removed = np.zeros(n, dtype=bool)
    heap = [(int(deg[v]), v) for v in range(n)]
    heapq.heapify(heap)
    degeneracy = 0
    seen = 0
    while heap and seen < n:
        d, v = heapq.heappop(heap)
        if removed[v] or d != deg[v]:
            continue
        removed[v] = True
        seen += 1
        degeneracy = max(degeneracy, d)
        for e in range(row[v], row[v + 1]):
            u = int(dst[e])
            if u < n and not removed[u]:
                deg[u] -= 1
                heapq.heappush(heap, (int(deg[u]), u))
    return int(degeneracy)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(6, 40), lam=st.integers(1, 4), seed=st.integers(0, 99))
def test_lemma25_transform_property(n, lam, seed):
    """From ANY clustering, the local updates reach ≤4λ−2 clusters without
    cost increase — the constructive content of Lemma 25."""
    rng = np.random.default_rng(seed)
    edges, _ = random_arboric(n, lam, rng)
    g = build_graph(n, edges)
    labels = rng.integers(0, max(1, n // 3), n).astype(np.int32)
    before = clustering_cost(g, labels)
    after_labels = lemma25_transform(g, labels, lam)
    after = clustering_cost(g, after_labels)
    assert after <= before
    assert np.bincount(after_labels).max() <= 4 * lam - 2


def test_lemma25_on_optimal_grows_nothing(rng):
    """Cor 27 special case: on forests the transform of the all-singleton
    clustering is free (already ≤ 2 = 4·1−2)."""
    e = random_forest(50, rng)
    g = build_graph(50, e)
    labels = np.arange(50, dtype=np.int32)
    out = lemma25_transform(g, labels, 1)
    assert clustering_cost(g, out) == clustering_cost(g, labels)


def test_clique_clustering_exact_on_cliques():
    n, e = disjoint_cliques([5, 3, 7, 2])
    g = build_graph(n, e)
    labels = np.asarray(clique_clustering(g))
    assert clustering_cost(g, labels) == 0


def test_clique_clustering_barbell_ratio():
    """Remark 33: barbell is the λ² tight case; algorithm must stay within
    O(λ²)·OPT (OPT = 1 disagreement)."""
    for lam in (3, 5, 8):
        n, e = barbell(lam)
        g = build_graph(n, e)
        labels = np.asarray(clique_clustering(g))
        cost = clustering_cost(g, labels)
        opt = 1
        assert cost <= 4 * lam * lam * opt  # O(λ²) with explicit constant
        # and it must not merge across the bridge
        assert labels[0] != labels[-1]


def test_clique_clustering_never_false_merges(rng):
    """Property: accepted groups are exactly clique components — on a path
    (no nontrivial cliques) everything is singleton."""
    g = build_graph(30, path(30))
    labels = np.asarray(clique_clustering(g))
    # path has K2 components only if isolated edges; a path of 30 has none
    # except... every adjacent pair has extra neighbours, so all singleton:
    assert (labels == np.arange(30)).all()
    # single edge → one 2-clique
    g2 = build_graph(2, np.array([[0, 1]]))
    l2 = np.asarray(clique_clustering(g2))
    assert l2[0] == l2[1]


def test_connected_components(rng):
    n, e = disjoint_cliques([4, 6, 3])
    g = build_graph(n, e)
    labels, iters = connected_components(
        g, np.ones(n, dtype=bool))
    labels = np.asarray(labels)
    assert len(np.unique(labels)) == 3
    assert int(iters) <= 8


@pytest.mark.parametrize("lam", [1, 2, 4])
def test_arboricity_bounds(lam, rng):
    edges, _ = random_arboric(100, lam, rng)
    g = build_graph(100, edges)
    lo, hi = arboricity_bounds(g)
    assert lo <= lam <= hi + 1  # degeneracy ≤ 2λ−1 ⇒ hi ≥ λ… allow slack
    assert hi <= 2 * lam  # union of λ forests has degeneracy ≤ 2λ−1


def test_degeneracy_parallel_upper_bounds_sequential(rng):
    edges, _ = random_arboric(150, 3, rng)
    g = build_graph(150, edges)
    d = degeneracy_peel(g).d
    k, rounds = degeneracy_parallel(g)
    assert k >= d
    assert k <= 4 * max(1, d)  # doubling peel ≤ 2× optimal, slack 4×
    assert rounds > 0


def test_clique_arboricity():
    g = build_graph(8, clique(8))
    d = degeneracy_peel(g).d
    assert d == 7  # K8 degeneracy


def _square_path_broom(n=200, leaves=80):
    """The square of a path (2-degenerate, peeled from its ends one vertex
    at a time) whose middle vertex also holds ``leaves`` pendant leaves:
    the cascade along the chain reaches a vertex whose CSR slice is too
    large for it and hands the frontier back to a strip round."""
    i = np.arange(n)
    edges = [np.stack([i[:-1], i[1:]], 1), np.stack([i[:-2], i[2:]], 1),
             np.stack([np.full(leaves, n // 2), n + np.arange(leaves)], 1)]
    return n + leaves, np.concatenate(edges)


def _peel_graphs():
    rng = np.random.default_rng(16)
    out = {
        "path2": (2, path(2)),
        "path300": (300, path(300)),
        "star": (500, star(500)),
        "clique8": (8, clique(8)),
        "clique40": (40, clique(40)),
        "disjoint_cliques": disjoint_cliques([5, 3, 7, 2, 1, 12]),
        "barbell": barbell(6),
        "random_forest": (600, random_forest(600, rng)),
        "scale_free": (800, scale_free(800, 4, rng)[0]),
        "gnp": (150, gnp(150, 0.12, rng)),
        "isolated": (60, np.array([[0, 1], [1, 2], [2, 0], [5, 9]])),
        "no_edges": (7, np.zeros((0, 2), np.int64)),
        "empty": (0, np.zeros((0, 2), np.int64)),
        "parallel_chains": (40 * 50, np.concatenate(
            [path(50) + 50 * c for c in range(40)])),
        "square_path_broom": _square_path_broom(),
    }
    for lam in (1, 2, 4, 8):
        out[f"random_arboric{lam}"] = (500, random_arboric(500, lam, rng)[0])
    return out


PEEL_GRAPHS = _peel_graphs()


@pytest.mark.parametrize("name", sorted(PEEL_GRAPHS) + ["padded"])
def test_degeneracy_peel_equals_heap(name):
    if name == "padded":
        n, edges = PEEL_GRAPHS["random_arboric4"]
        m = build_graph(n, edges).m
        g = build_graph(n, edges, pad_to=2 * m + 96)
    else:
        g = build_graph(*PEEL_GRAPHS[name])
    peel = degeneracy_peel(g)
    assert peel.d == heap_degeneracy(g)
    assert peel.rounds >= 0 and 0 <= peel.single <= g.n


@settings(max_examples=40, deadline=None)
@given(n=st.integers(0, 90), density=st.floats(0.0, 0.3),
       seed=st.integers(0, 10_000), pad=st.integers(0, 20))
def test_degeneracy_peel_equals_heap_on_random_graphs(n, density, seed, pad):
    rng = np.random.default_rng(seed)
    edges = rng.integers(0, max(n, 1), size=(int(density * n * n), 2))
    m = build_graph(n, edges).m
    g = build_graph(n, edges, pad_to=2 * m + 2 * pad)
    assert degeneracy_peel(g).d == heap_degeneracy(g)


def test_peel_counts_show_which_step_engaged():
    """A long path peels from its two ends, a cascade of a couple of
    vertices a step, so the per-vertex step takes every vertex; a random
    union of forests strips in wide rounds."""
    chain = degeneracy_peel(build_graph(4096, path(4096)))
    assert chain.d == 1
    assert chain.single >= 4096 - SMALL_FRONTIER and chain.rounds <= 2
    edges, _ = random_arboric(4096, 4, np.random.default_rng(3))
    wide = degeneracy_peel(build_graph(4096, edges))
    assert wide.rounds >= 1 and wide.single < 4096 // 4
    broom = degeneracy_peel(build_graph(*_square_path_broom()))
    assert broom.rounds >= 1 and broom.single >= 100


def test_cluster_stats_accumulate_the_peel_counts():
    graphs = [build_graph(4096, path(4096)),
              build_graph(4096, random_arboric(
                  4096, 4, np.random.default_rng(3))[0])]
    eng = ClusterBatcher(max_batch=64, prebuild_rows=False,
                         result_cache=False)
    for uid, g in enumerate(graphs):
        eng.admit(ClusterRequest(uid=uid, graph=g,
                                 key=jax.random.PRNGKey(uid)))
    peels = [degeneracy_peel(g) for g in graphs]
    assert eng.stats.peel_rounds == sum(p.rounds for p in peels)
    assert eng.stats.peel_single == sum(p.single for p in peels)
    assert eng.stats.peel_rounds >= 1 and eng.stats.peel_single >= 4000


@pytest.mark.parametrize("seed", range(4))
def test_plan_graph_matches_the_heap_degeneracy(seed):
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(200, 2000))
    edges = np.concatenate([random_arboric(n, 1 + seed, rng)[0],
                            scale_free(n, 2 + seed, rng)[0]])
    g = build_graph(n, edges)
    key = jax.random.PRNGKey(seed)
    plan = plan_graph(g)
    oracle = plan_graph(g, lam=max(1, heap_degeneracy(g)))
    assert plan.lam == oracle.lam
    assert plan.threshold == oracle.threshold
    assert (plan.eligible == oracle.eligible).all()
    assert plan.bucket == oracle.bucket
    fp = dict(method="pivot", num_samples=4, eps=2.0)
    assert graph_fingerprint(plan, key, **fp).digest \
        == graph_fingerprint(oracle, key, **fp).digest
