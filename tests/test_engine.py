"""Unified serving engine: deadline flushes, device cost, donated buffers.

The contracts under test (serve/engine.py, serve/cluster_batcher.py,
core/batch.py):

* partial-bucket (deadline) flushes are bit-exact vs per-graph
  ``correlation_cluster`` — flush grouping can never change a result;
* the device-side cost pass equals the ``_cost_host`` numpy oracle across
  methods and kernel paths, and the device best-of-k argmin picks the same
  sample index as the host loop;
* flushes through a :class:`BucketBufferPool` (staging reuse + donated
  device inputs) return identical results on reuse;
* the packer's ``PackStats`` is the single source of pad accounting;
* ``ClusterBatcher`` satisfies the :class:`ClusterEngine` protocol.
"""

import jax
import numpy as np
import pytest

from repro.core import (
    BucketBufferPool,
    build_graph,
    correlation_cluster,
    correlation_cluster_batch,
    plan_graph,
)
from repro.core import batch as batch_mod
from repro.core.batch import _cost_host
from repro.core.graph import gnp, random_arboric, star
from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest
from repro.serve.engine import ClusterEngine, EngineStats, serve_all
from repro.util import VirtualClock, next_pow2


def _rand_graph(n, lam, seed):
    edges, _ = random_arboric(n, lam, np.random.default_rng(seed))
    return build_graph(n, edges)


def _assert_matches(g, key, res_batch, **kwargs):
    res_single = correlation_cluster(g, key=key, **kwargs)
    assert (res_batch.labels == res_single.labels).all()
    assert res_batch.cost == res_single.cost


# ---------------------------------------------------------------------------
# pow2 helper + packer-stat single-sourcing (satellite: no drift).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("x,want", [(0, 1), (1, 1), (2, 2), (3, 4), (4, 4),
                                    (5, 8), (63, 64), (64, 64), (65, 128)])
def test_next_pow2(x, want):
    assert next_pow2(x) == want


def test_pack_stats_match_batcher_stats():
    """ClusterStats.padded_slots comes straight from the packer: a full
    flush of 4 path graphs pads nothing, the deadline flush of the 3
    stragglers pads one group = k entries."""
    from repro.core.graph import path

    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=4, num_samples=3, max_wait=1.0,
                             clock=clock)
    for i in range(7):      # path(6): one (8, 4) bucket for all requests
        batcher.admit(ClusterRequest(uid=i, graph=build_graph(6, path(6)),
                                     key=jax.random.PRNGKey(i)))
    clock.advance(2.0)
    batcher.poll()
    assert batcher.pending() == 0
    assert batcher.stats.clustered == 7
    assert batcher.stats.flushes == 2
    # full flush: G=4 → pad 0; deadline flush: G=3 → pad (4−3)·k = 3.
    assert batcher.stats.padded_slots == 3
    assert batcher.stats.pad_vertex_waste == 7 * (8 - 6)
    # Cross-check the packer directly under the same grouping.
    _, pack = correlation_cluster_batch(
        [build_graph(6, path(6))] * 3,
        keys=[jax.random.PRNGKey(i) for i in (4, 5, 6)],
        num_samples=3, with_stats=True)
    assert pack.padded_entries == 3


def test_engine_returns_pack_stats():
    graphs = [_rand_graph(n, 2, seed=n) for n in (9, 10, 20)]
    results, stats = correlation_cluster_batch(
        graphs, keys=[jax.random.PRNGKey(i) for i in range(3)],
        num_samples=2, with_stats=True)
    assert len(results) == 3
    assert stats.n_graphs == 3
    assert stats.n_entries == 6
    # groups pad to pow2 per bucket; entries pad by the same factor k
    assert stats.padded_entries % 2 == 0
    assert stats.pad_vertex_waste == sum(
        plan_graph(g).R - g.n for g in graphs)
    for (R, W, B) in stats.bucket_shapes:
        assert B % 2 == 0 and next_pow2(B // 2) == B // 2


# ---------------------------------------------------------------------------
# Deadline (partial-bucket) flush bit-exactness.
# ---------------------------------------------------------------------------


def test_deadline_partial_flush_bit_exact():
    """A max_wait flush runs a partial bucket — results must still be
    bit-identical to the per-graph engine."""
    rng = np.random.default_rng(3)
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=64, max_wait=0.5, clock=clock)
    reqs = []
    for i in range(5):
        n = int(rng.integers(5, 40))
        g = _rand_graph(n, 2, seed=100 + i)
        req = ClusterRequest(uid=i, graph=g, key=jax.random.PRNGKey(i))
        reqs.append(req)
        assert batcher.admit(req) == []     # nothing fills a 64-bucket
    assert batcher.poll() == []             # not overdue yet
    clock.advance(1.0)
    retired = batcher.poll()
    assert sorted(r.uid for r in retired) == list(range(5))
    assert batcher.pending() == 0
    assert batcher.stats.deadline_flushes >= 1
    for r in reqs:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result)


def test_deadline_flush_only_overdue_buckets():
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=64, max_wait=1.0, clock=clock)
    g_small = _rand_graph(6, 1, seed=1)     # R=8 bucket
    g_big = _rand_graph(30, 1, seed=2)      # R=32 bucket
    batcher.admit(ClusterRequest(uid=0, graph=g_small,
                                 key=jax.random.PRNGKey(0)))
    clock.advance(0.8)
    batcher.admit(ClusterRequest(uid=1, graph=g_big,
                                 key=jax.random.PRNGKey(1)))
    clock.advance(0.4)                      # uid0 is 1.2s old, uid1 0.4s
    retired = batcher.poll()
    assert [r.uid for r in retired] == [0]
    assert batcher.pending() == 1
    clock.advance(1.0)
    assert [r.uid for r in batcher.poll()] == [1]


def test_serve_all_driver_retires_everything_once():
    rng = np.random.default_rng(9)
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=4, max_wait=10.0, clock=clock)
    stream = []
    for i in range(9):
        n = int(rng.integers(5, 30))
        stream.append(ClusterRequest(uid=i, graph=_rand_graph(n, 2, seed=i),
                                     key=jax.random.PRNGKey(i)))
    retired = serve_all(batcher, stream)
    assert sorted(r.uid for r in retired) == list(range(9))
    assert all(r.done for r in retired)
    assert batcher.stats.retired == 9
    for r in retired:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result)


# ---------------------------------------------------------------------------
# Device-side cost == host oracle; device argmin == host argmin.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["pivot", "pivot_raw"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_device_cost_matches_host_oracle(method, use_kernel):
    rng = np.random.default_rng(11)
    graphs, keys = [], []
    for i in range(10):
        n = int(rng.integers(4, 50))
        graphs.append(build_graph(n, gnp(n, 0.15, rng)))
        keys.append(jax.random.PRNGKey(500 + i))
    # star: exercises cap-dropped edges (always cut) in the cost identity
    graphs.append(build_graph(40, star(40)))
    keys.append(jax.random.PRNGKey(999))
    results = correlation_cluster_batch(graphs, keys=keys, method=method,
                                        use_kernel=use_kernel)
    for g, res in zip(graphs, results):
        assert res.cost == _cost_host(g, res.labels), (g.n, method)


def test_device_argmin_matches_host_pick():
    """Best-of-k selection on device picks the identical sample index."""
    for seed in range(6):
        g = _rand_graph(12 + seed, 2, seed=seed)
        key = jax.random.PRNGKey(seed)
        (res,) = correlation_cluster_batch([g], keys=[key], num_samples=5)
        single = correlation_cluster(g, key=key, num_samples=5)
        assert res.info["picked_sample"] == single.info["picked_sample"]
        assert (res.labels == single.labels).all()
        assert res.cost == single.cost


# ---------------------------------------------------------------------------
# Donated buffer pool: identical results on reuse, O(#buckets) staging.
# ---------------------------------------------------------------------------


def test_pool_reuse_bit_identical():
    graphs = [_rand_graph(n, 2, seed=n) for n in (7, 9, 16, 33)]
    keys = [jax.random.PRNGKey(i) for i in range(4)]
    pool = BucketBufferPool()
    ref = correlation_cluster_batch(graphs, keys=keys, num_samples=2)
    for _ in range(3):          # repeated flushes reuse staging + donation
        got = correlation_cluster_batch(graphs, keys=keys, num_samples=2,
                                        pool=pool)
        for a, b in zip(got, ref):
            assert (a.labels == b.labels).all()
            assert a.cost == b.cost
    buckets = {plan_graph(g).bucket for g in graphs}
    assert pool.n_buffers == len(buckets)   # staging is O(#buckets)


def test_pool_reuse_with_different_graphs_no_stale_state():
    """Staging arrays are refilled in place — a smaller second flush must
    not see leftovers from a larger first flush in the same bucket."""
    pool = BucketBufferPool()
    dense = [build_graph(10, gnp(10, 0.5, np.random.default_rng(i)))
             for i in range(4)]
    keys4 = [jax.random.PRNGKey(i) for i in range(4)]
    correlation_cluster_batch(dense, keys=keys4, pool=pool)
    sparse = [_rand_graph(9, 1, seed=7)]
    (res,) = correlation_cluster_batch(sparse, keys=[jax.random.PRNGKey(7)],
                                       pool=pool)
    _assert_matches(sparse[0], jax.random.PRNGKey(7), res)


def test_batcher_warmup_precompiles_subbatch_programs():
    rng = np.random.default_rng(21)
    graphs = [_rand_graph(int(rng.integers(5, 12)), 1, seed=i)
              for i in range(4)]
    # num_samples=7 keys program-cache entries no other test compiles, so
    # the cold-warmup count below is robust to suite ordering (the LRU is
    # process-global).
    batcher = ClusterBatcher(max_batch=4, num_samples=7)
    compiled = batcher.warmup(graphs)
    assert compiled >= 1
    before = batch_mod.program_cache_size()
    for i, g in enumerate(graphs):
        batcher.admit(ClusterRequest(uid=i, graph=g,
                                     key=jax.random.PRNGKey(i)))
    batcher.flush()
    assert batch_mod.program_cache_size() == before, \
        "warmed engine must not compile during serving"


# ---------------------------------------------------------------------------
# Validation / edge cases (satellite).
# ---------------------------------------------------------------------------


def test_engine_rejects_bad_num_samples():
    g = _rand_graph(10, 1, seed=0)
    with pytest.raises(ValueError, match="num_samples"):
        correlation_cluster_batch([g], num_samples=0)
    with pytest.raises(ValueError, match="num_samples"):
        correlation_cluster_batch([g], num_samples=-3)


def test_batcher_clamps_num_samples_and_validates_args():
    assert ClusterBatcher(num_samples=0).num_samples == 1
    with pytest.raises(ValueError, match="max_batch"):
        ClusterBatcher(max_batch=0)
    with pytest.raises(ValueError, match="max_wait"):
        ClusterBatcher(max_wait=-1.0)


def test_width_exceeding_largest_bucket_raises():
    n = batch_mod.MAX_WIDTH + 2
    g = build_graph(n, star(n))     # hub degree n-1 > MAX_WIDTH
    with pytest.raises(ValueError, match="MAX_WIDTH"):
        plan_graph(g, method="pivot_raw")
    # ... and the batcher surfaces it at admission, not inside a flush.
    batcher = ClusterBatcher(method="pivot_raw")
    with pytest.raises(ValueError, match="MAX_WIDTH"):
        batcher.admit(ClusterRequest(uid=0, graph=g,
                                     key=jax.random.PRNGKey(0)))
    assert batcher.pending() == 0


def test_rows_exceeding_largest_bucket_raises():
    n = batch_mod.MAX_ROWS + 1
    g = build_graph(n, np.zeros((0, 2), dtype=np.int64))
    with pytest.raises(ValueError, match="MAX_ROWS"):
        plan_graph(g, method="pivot_raw")


def test_empty_graph_request_is_graceful():
    g0 = build_graph(0, np.zeros((0, 2), dtype=np.int64))
    (res,) = correlation_cluster_batch([g0])
    assert res.cost == 0 and res.labels.shape == (0,)
    batcher = ClusterBatcher(max_batch=1)
    retired = batcher.admit(ClusterRequest(uid=0, graph=g0,
                                           key=jax.random.PRNGKey(0)))
    assert len(retired) == 1 and retired[0].result.cost == 0


# ---------------------------------------------------------------------------
# Protocol conformance.
# ---------------------------------------------------------------------------


def test_cluster_batcher_satisfies_engine_protocol():
    cluster = ClusterBatcher(max_batch=2)
    assert isinstance(cluster, ClusterEngine)
    # An idle engine: flush/retire are safe no-ops returning [].
    assert cluster.flush() == [] and cluster.retire() == []
    assert cluster.pending() == 0
    assert isinstance(cluster.stats, EngineStats)


def test_streaming_dedup_rejects_mismatched_reused_batcher():
    from repro.data.dedup import dedup_corpus_streaming
    from repro.data.synthetic import synthetic_corpus

    corpus = synthetic_corpus(n_docs=10, dup_fraction=0.5, mutate_p=0.05,
                              seed=1)
    reused = ClusterBatcher(num_samples=1)
    with pytest.raises(ValueError, match="reused batcher"):
        dedup_corpus_streaming(corpus, seed=1, num_samples=4, batcher=reused)


def test_streaming_dedup_matches_batched():
    from repro.data.dedup import dedup_corpus_batched, dedup_corpus_streaming
    from repro.data.synthetic import synthetic_corpus

    corpus = synthetic_corpus(n_docs=50, dup_fraction=0.5, mutate_p=0.05,
                              seed=3)
    rb = dedup_corpus_batched(corpus, threshold=0.45, seed=3)
    # Tiny buckets + aggressive deadline: many partial flushes, same answer.
    rs = dedup_corpus_streaming(corpus, threshold=0.45, seed=3,
                                max_batch=4, max_wait=0.0)
    assert (rs.labels == rb.labels).all()
    assert rs.clustering.cost == rb.clustering.cost
    assert (rs.keep == rb.keep).all()
    assert rs.clustering.info["flushes"] >= 1


def test_streaming_dedup_reused_batcher_deltas_are_per_call():
    """A reused engine carries lifetime stats; each streaming call's
    ``info`` must report only *its own* flush/sample activity. The
    shallow ``dataclasses.replace`` snapshot this guards against aliased
    the nested telemetry, so ``flush_samples`` read 0 for every call."""
    from repro.data.dedup import dedup_corpus_batched, dedup_corpus_streaming
    from repro.data.synthetic import synthetic_corpus

    batcher = ClusterBatcher(max_batch=4, max_wait=0.0, num_samples=4)
    c1 = synthetic_corpus(n_docs=30, dup_fraction=0.5, mutate_p=0.05, seed=3)
    c2 = synthetic_corpus(n_docs=40, dup_fraction=0.4, mutate_p=0.05, seed=4)
    r1 = dedup_corpus_streaming(c1, threshold=0.45, seed=3, max_batch=4,
                                max_wait=0.0, batcher=batcher)
    r2 = dedup_corpus_streaming(c2, threshold=0.45, seed=4, max_batch=4,
                                max_wait=0.0, batcher=batcher)
    for res in (r1, r2):
        info = res.clustering.info
        # Per-call deltas, not engine-lifetime totals: the nested-telemetry
        # sample count must agree with the top-level flush delta, and both
        # must be this call's own (>= 1, not the running sum).
        assert info["flushes"] >= 1
        assert info["flush_samples"] == info["flushes"]
    total = batcher.stats.latency.total_flushes
    assert (r1.clustering.info["flush_samples"]
            + r2.clustering.info["flush_samples"]) == total
    # And reuse did not bend the bit-exactness contract.
    rb2 = dedup_corpus_batched(c2, threshold=0.45, seed=4, num_samples=4)
    assert (r2.labels == rb2.labels).all()
    assert r2.clustering.cost == rb2.clustering.cost


class _GatedDeadlinePolicy:
    """One request in the system at a time: refuse admission while any
    queue is non-empty, flush only once the oldest request is ``max_wait``
    old. Progress therefore *requires* engine-clock time to advance while
    serve_all retries a rejected admission."""

    name = "gated-deadline"

    def __init__(self, max_wait: float):
        self.max_wait = max_wait

    def on_admit(self, queues, now, telemetry) -> bool:
        return not any(queues.values())

    def select_flushes(self, queues, now, telemetry):
        from repro.serve.scheduler import FlushDecision

        return [FlushDecision(bucket=b, count=len(q))
                for b, q in queues.items()
                if q and now - q[0].admitted_at >= self.max_wait]

    def on_retire(self, bucket, telemetry) -> None:
        pass


def test_serve_all_advances_virtual_clock_on_rejection():
    """Regression: serve_all backed off with a wall-clock ``time.sleep``
    even when the engine ran on a virtual clock, so a rejection loop spun
    with the deadline frozen — virtual time never moved, the gated bucket
    never flushed, and the loop never terminated. The backoff must advance
    the *engine's* clock when it is injectable."""
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=8, clock=clock,
                             policy=_GatedDeadlinePolicy(max_wait=0.01))
    graphs = [_rand_graph(10, 2, seed=s) for s in range(3)]
    reqs = [ClusterRequest(uid=i, graph=g, key=jax.random.PRNGKey(i))
            for i, g in enumerate(graphs)]
    retired = serve_all(batcher, reqs, reject_backoff=0.005)
    assert sorted(r.uid for r in retired) == [0, 1, 2]
    for g, r in zip(graphs, sorted(retired, key=lambda r: r.uid)):
        _assert_matches(g, jax.random.PRNGKey(r.uid), r.result)
    # Each gated admission needed >= max_wait of engine time to open.
    assert clock.t >= 0.02
    assert batcher.stats.rejected >= 2
    assert batcher.pending() == 0


def test_serve_all_fails_loudly_when_stalled():
    """An admission gate that can never open must surface as a loud
    RuntimeError after ``max_stalled_rounds`` no-progress retries, not an
    unbounded spin."""

    class _NeverAdmitPolicy(_GatedDeadlinePolicy):
        name = "never"

        def on_admit(self, queues, now, telemetry) -> bool:
            return False

    batcher = ClusterBatcher(max_batch=8, clock=VirtualClock(),
                             policy=_NeverAdmitPolicy(max_wait=1.0))
    req = ClusterRequest(uid=0, graph=_rand_graph(8, 2, seed=0),
                         key=jax.random.PRNGKey(0))
    with pytest.raises(RuntimeError, match="no progress"):
        serve_all(batcher, [req], reject_backoff=0.001,
                  max_stalled_rounds=25)
