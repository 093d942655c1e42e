"""Batched multi-graph engine ≡ per-graph engine, bit-exactly.

The contract under test (core/batch.py): for matching per-graph PRNG keys,
``correlation_cluster_batch`` returns labels and costs identical to looping
``correlation_cluster`` — across shape-bucket boundaries (n = R−1, R, R+1),
degree-capped and raw methods, best-of-k sampling, and both neighbour-min
paths (pure-jnp gather and the batched Pallas kernel)."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    build_graph,
    correlation_cluster,
    correlation_cluster_batch,
    plan_graph,
)
from repro.core import batch as batch_mod
from repro.core.graph import gnp, path, random_arboric, star
from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest


def _rand_graph(n, lam, seed):
    edges, _ = random_arboric(n, lam, np.random.default_rng(seed))
    return build_graph(n, edges)


def _assert_matches(g, key, res_batch, **kwargs):
    res_single = correlation_cluster(g, key=key, **kwargs)
    assert (res_batch.labels == res_single.labels).all(), (
        g.n, np.flatnonzero(res_batch.labels != res_single.labels))
    assert res_batch.cost == res_single.cost


# n values straddling the R buckets (8, 16, 32): R−1, R, R+1.
BOUNDARY_NS = [7, 8, 9, 15, 16, 17, 31, 32, 33]


@pytest.mark.parametrize("n", BOUNDARY_NS)
def test_batch_bucket_boundaries_bit_exact(n):
    g = _rand_graph(n, 2, seed=n)
    key = jax.random.PRNGKey(n)
    (res,) = correlation_cluster_batch([g], keys=[key])
    _assert_matches(g, key, res)


@pytest.mark.parametrize("method",
                         ["pivot", "pivot_raw", "precluster"])
def test_batch_64_graphs_bit_exact(method):
    """Acceptance: ≥64 mixed-shape graphs, labels/costs ≡ per-graph engine."""
    rng = np.random.default_rng(0)
    graphs, keys = [], []
    for i in range(64):
        n = int(rng.integers(4, 70))
        lam = int(rng.integers(1, 4))
        edges, _ = random_arboric(n, lam, rng)
        graphs.append(build_graph(n, edges))
        keys.append(jax.random.PRNGKey(1000 + i))
    results = correlation_cluster_batch(graphs, keys=keys, method=method)
    assert len(results) == 64
    for g, key, res in zip(graphs, keys, results):
        _assert_matches(g, key, res, method=method)


@pytest.mark.parametrize("method", ["pivot", "pivot_raw", "precluster"])
def test_minmax_objective_matches_host_oracle(method):
    """objective='minmax' scores the same labels with the worst-vertex
    disagreement: the rounds body is untouched (labels identical to the
    'disagree' run at num_samples=1) and every returned cost equals the
    numpy host oracle. λ=1 inputs keep every vertex under the Theorem 26
    cap, where the device pass and the full-graph oracle agree exactly."""
    graphs = [_rand_graph(n, 1, seed=n) for n in (6, 9, 14, 20, 33)]
    keys = [jax.random.PRNGKey(i) for i in range(len(graphs))]
    res_d = correlation_cluster_batch(graphs, keys=keys, method=method)
    res_m = correlation_cluster_batch(graphs, keys=keys, method=method,
                                      objective="minmax")
    for g, rd, rm in zip(graphs, res_d, res_m):
        assert (rd.labels == rm.labels).all()
        assert rm.cost == batch_mod._minmax_cost_host(g, rm.labels)
        # Min-max is a per-vertex maximum: never above the total.
        assert rm.cost <= rd.cost


def test_batch_degree_cap_active_bit_exact():
    """Star hub exceeds 12λ: the cap must singleton it in the batch too."""
    g = build_graph(40, star(40))
    key = jax.random.PRNGKey(3)
    (res,) = correlation_cluster_batch([g], keys=[key])
    _assert_matches(g, key, res)
    assert res.info["high_degree"] == 1


def test_batch_edgeless_graph():
    g = build_graph(5, np.zeros((0, 2), dtype=np.int64))
    (res,) = correlation_cluster_batch([g])
    assert (res.labels == np.arange(5)).all()
    assert res.cost == 0


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("num_samples", [2, 3, 4])
def test_batch_num_samples_matches_single(num_samples, use_kernel):
    graphs = [_rand_graph(n, 2, seed=n) for n in (10, 20, 30)]
    keys = [jax.random.PRNGKey(i) for i in range(3)]
    results = correlation_cluster_batch(graphs, keys=keys,
                                        num_samples=num_samples,
                                        use_kernel=use_kernel)
    for g, key, res in zip(graphs, keys, results):
        _assert_matches(g, key, res, num_samples=num_samples,
                        use_kernel=use_kernel)
        assert res.info["num_samples"] == num_samples


@pytest.mark.parametrize("objective", ["disagree", "minmax"])
@pytest.mark.parametrize("method", ["pivot", "pivot_raw", "precluster"])
def test_batch_kernel_path_bit_exact(method, objective):
    graphs = [_rand_graph(n, 2, seed=n) for n in (9, 16, 33)]
    keys = [jax.random.PRNGKey(i) for i in range(3)]
    opts = dict(keys=keys, method=method, objective=objective,
                num_samples=2)
    jnp_res = correlation_cluster_batch(graphs, use_kernel=False, **opts)
    ker_res = correlation_cluster_batch(graphs, use_kernel=True, **opts)
    for a, b in zip(jnp_res, ker_res):
        assert (a.labels == b.labels).all()
        assert a.cost == b.cost
        assert a.info["picked_sample"] == b.info["picked_sample"]


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 40), p=st.floats(0.05, 0.5), seed=st.integers(0, 99))
def test_batch_property_bit_exact(n, p, seed):
    rng = np.random.default_rng(seed)
    g = build_graph(n, gnp(n, p, rng))
    key = jax.random.PRNGKey(seed)
    # Batch alongside a second graph so the bucket is genuinely multi-graph.
    g2 = _rand_graph(max(4, n // 2), 1, seed + 1)
    res = correlation_cluster_batch([g, g2],
                                    keys=[key, jax.random.PRNGKey(seed + 1)])
    _assert_matches(g, key, res[0])
    _assert_matches(g2, jax.random.PRNGKey(seed + 1), res[1])


def test_batch_compile_count_tracks_buckets_not_graphs():
    """Bucketing contract: compiles grow with #buckets, not #graphs."""
    before = batch_mod.program_cache_size()
    # 24 path graphs in exactly two (R, W) buckets (max degree 2 ⇒ W = 4).
    graphs = [build_graph(10, path(10)) for _ in range(12)]
    graphs += [build_graph(20, path(20)) for _ in range(12)]
    keys = [jax.random.PRNGKey(i) for i in range(24)]
    results = correlation_cluster_batch(graphs, keys=keys)
    buckets = {r.info["bucket"] for r in results}
    assert len(buckets) == 2
    added = batch_mod.program_cache_size() - before
    assert added <= len(buckets), (
        f"{added} compiles for {len(buckets)} buckets / {len(graphs)} graphs")


def test_plan_graph_width_bounded_by_degree_cap():
    """The eligible-induced width is capped at 12λ (ε=2) — the ELL padding
    bound that makes shape bucketing viable (paper Theorem 26)."""
    g = build_graph(60, star(60))
    plan = plan_graph(g, method="pivot", eps=2.0, lam=1)
    assert plan.wreq == 0           # hub singled out, leaves isolated
    assert plan.W == batch_mod.MIN_WIDTH
    raw = plan_graph(g, method="pivot_raw")
    assert raw.wreq == 59           # no cap: hub row is full width


def test_cluster_batcher_bit_exact_and_flushes():
    rng = np.random.default_rng(5)
    batcher = ClusterBatcher(max_batch=4)
    reqs = []
    for i in range(11):
        n = int(rng.integers(5, 40))
        edges, _ = random_arboric(n, 2, rng)
        req = ClusterRequest(uid=i, graph=build_graph(n, edges),
                             key=jax.random.PRNGKey(i))
        reqs.append(req)
        batcher.submit(req)
    batcher.flush_all()
    assert batcher.pending() == 0
    assert all(r.done for r in reqs)
    for r in reqs:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result)
    assert batcher.stats.clustered == 11
    assert batcher.stats.flushes >= 1


def test_dedup_batched_matches_sharded_single():
    """Component-sharded batch dedup ≡ clustering each shard individually."""
    from repro.data.dedup import (dedup_corpus_batched, minhash_signatures,
                                  shard_similarity_graph, similarity_edges)
    from repro.data.synthetic import synthetic_corpus

    corpus = synthetic_corpus(n_docs=60, dup_fraction=0.5, mutate_p=0.05,
                              seed=7)
    res = dedup_corpus_batched(corpus, threshold=0.45, seed=7)
    sigs = minhash_signatures(corpus.docs, num_hashes=64, seed=7)
    edges = similarity_edges(sigs, threshold=0.45)
    shards = shard_similarity_graph(len(corpus.docs), edges)
    expect = np.arange(len(corpus.docs), dtype=np.int32)
    total = 0
    for i, (ids, local) in enumerate(shards):
        g = build_graph(len(ids), local)
        single = correlation_cluster(
            g, key=jax.random.fold_in(jax.random.PRNGKey(7), i),
            num_samples=4)
        expect[ids] = ids[single.labels]
        total += single.cost
    assert (res.labels == expect).all()
    assert res.clustering.cost == total
