"""Admission-time packing: PackedRows build, assemble, promotion.

The contracts under test (core/plan.py build_packed_rows / pack_bucket /
PackedRows.promote, serve/cluster_batcher.py admission, core/batch.py):

* one canonical edge list per plan — ``plan_graph`` lexsorts once and
  both ``graph_fingerprint`` and the row builder consume it;
* a bucket assembled from :func:`build_packed_rows` rows is
  **byte-identical** to the derive-at-flush pack the engine once kept
  beside it (:func:`_reference_staging`, this file's own numpy reference)
  — same ELL/rank/eligibility/m_edges staging tensors, not merely the
  same clustering (device reductions are order-invariant, but we hold the
  stronger property so the bit-exactness contract can never hinge on it);
* ``PackedRows.promote`` relayouts into any larger ``(R, W)`` and the
  promoted rows assemble byte-identically to the reference pack of the
  promoted plans (the coalesced-flush path);
* ``pack_bucket`` only copies rows: a plan without them is refused;
* the batch API and a serving flush stage the same bytes for the same
  graphs and keys, since both build rows with ``build_packed_rows``;
* the serving engine retires bit-identical results across executors,
  kernel paths, partial deadline sub-batches and coalesced (stolen)
  flushes;
* ``warmup(autotune=True)`` stages its sweep tensors through pool leases
  (and releases them).
"""

import jax
import numpy as np
import pytest

from repro.core import (
    BucketBufferPool,
    PackedRows,
    SyncExecutor,
    build_graph,
    build_packed_rows,
    correlation_cluster,
    correlation_cluster_batch,
    pack_bucket,
    plan_graph,
    promote_plan,
)
from repro.core.api import sample_keys
from repro.core.graph import path, random_arboric
from repro.core.mis import (random_permutation_ranks,
                             random_permutation_ranks_batch)
from repro.core.plan import graph_fingerprint, plan_canonical_edges
from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest
from repro.serve.engine import serve_all
from repro.serve.scheduler import CoalescingPolicy
from repro.util import VirtualClock, next_pow2

_INT32_MAX = np.iinfo(np.int32).max


def _graphs(num, lo, hi, seed, lam_hi=2):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(num):
        n = int(rng.integers(lo, hi))
        edges, lam = random_arboric(n, int(rng.integers(1, lam_hi + 1)), rng)
        out.append(build_graph(n, edges))
    return out


def _path_graphs(ns):
    """Path graphs whose n all land in one (R, W) shape bucket."""
    return [build_graph(n, path(n)) for n in ns]


def _with_rows(plans, keys):
    """Attach each plan's rows, built as admission builds them."""
    for p, ks in zip(plans, keys):
        p.rows = build_packed_rows(p, ks)
    return plans


def _reference_staging(plans, keys, k, g_pad=None):
    """The derive-at-flush pack, kept as this file's numpy reference.

    Vertex ``x``'s ELL row lists its neighbours where it is the first
    endpoint of a canonical edge, then where it is the second, each in
    canonical order; pad id ``R``. Ranks are the per-graph engine's
    ``random_permutation_ranks(n, key)`` per sample key (``INT32_MAX``
    beyond ``n``), eligibility the plan's mask (slot ``R`` False), and
    ``m_edges`` the full edge count. Group padding entries are all pad.
    """
    R, W = plans[0].bucket
    g_pad = next_pow2(len(plans)) if g_pad is None else g_pad
    b_pad = g_pad * k
    ell = np.full((b_pad, R, W), R, dtype=np.int32)
    ranks = np.full((b_pad, R + 1), _INT32_MAX, dtype=np.int32)
    elig = np.zeros((b_pad, R + 1), dtype=bool)
    m_edges = np.zeros((b_pad,), dtype=np.int32)
    for gi, (plan, ks) in enumerate(zip(plans, keys)):
        n = plan.n
        adj = [[] for _ in range(n)]
        edges = plan_canonical_edges(plan).tolist()
        for u, v in edges:
            adj[u].append(v)
        for u, v in edges:
            adj[v].append(u)
        for si in range(k):
            b = gi * k + si
            for x, nbrs in enumerate(adj):
                ell[b, x, :len(nbrs)] = nbrs
            if n:
                ranks[b, :n] = np.asarray(random_permutation_ranks(n, ks[si]))
                elig[b, :n] = plan.eligible
            m_edges[b] = plan.g.m
    return ell, ranks, elig, m_edges, g_pad - len(plans)


def _pack_pair(graphs, k=1, promote=False, seed=0):
    """(assembled staging, reference staging) for the same graphs and keys.

    With ``promote`` the plans are relayed into a bucket one pow2 step
    above the component-wise max of the group — the coalesced-flush path.
    Without it the graphs must already share one shape bucket.
    """
    keys = [sample_keys(jax.random.PRNGKey(seed + i), k)
            for i in range(len(graphs))]
    built = _with_rows([plan_graph(g) for g in graphs], keys)
    ref = [plan_graph(g) for g in graphs]
    if promote:
        R = 2 * max(p.R for p in built)
        W = 2 * max(p.W for p in built)
        built = [promote_plan(p, R, W) for p in built]
        ref = [promote_plan(p, R, W) for p in ref]
    return pack_bucket(built, k=k), _reference_staging(ref, keys, k)


def _assert_staging_equal(a, b):
    ell_a, ranks_a, elig_a, m_a, pad_a = a
    ell_b, ranks_b, elig_b, m_b, pad_b = b
    assert (ell_a == ell_b).all()
    assert (ranks_a == ranks_b).all()
    assert (elig_a == elig_b).all()
    assert (m_a == m_b).all()
    assert pad_a == pad_b


# ---------------------------------------------------------------------------
# Staging byte-equality: assembled rows == the reference pack.
# ---------------------------------------------------------------------------


def test_prebuilt_assembly_matches_legacy_pack_bytes():
    _assert_staging_equal(*_pack_pair(_path_graphs([9, 12, 14, 16, 10])))


def test_prebuilt_assembly_matches_legacy_best_of_k():
    _assert_staging_equal(*_pack_pair(_path_graphs([11, 13, 16, 9]), k=3))


def test_promoted_rows_match_legacy_pack_at_promoted_shape():
    # The coalesced-flush relayout: mixed native buckets promoted into one
    # shape a pow2 step above the largest of them.
    graphs = _graphs(4, 5, 14, seed=3)
    _assert_staging_equal(*_pack_pair(graphs, k=2, promote=True))


def test_mixed_prebuilt_and_legacy_bucket():
    # Rows from two engines' admissions, interleaved into one bucket,
    # assemble to the reference pack of the same graphs and keys.
    graphs = _path_graphs([10, 16, 9, 13, 15, 12])
    engines = [ClusterBatcher(max_batch=64, result_cache=False)
               for _ in range(2)]
    reqs = [ClusterRequest(uid=i, graph=g, key=jax.random.PRNGKey(i))
            for i, g in enumerate(graphs)]
    for i, req in enumerate(reqs):
        assert engines[i % 2].admit(req) == []
    plans = [r.plan for r in reqs]
    assert all(isinstance(p.rows, PackedRows) for p in plans)
    keys = [sample_keys(r.key, 1) for r in reqs]
    _assert_staging_equal(pack_bucket(plans, k=1),
                          _reference_staging(plans, keys, k=1))


def test_staging_reuse_resets_stale_tail():
    # A lease previously filled by a larger group must not leak rows into
    # a smaller pack (only the tail is re-stamped).
    pool = BucketBufferPool()
    keys = [sample_keys(jax.random.PRNGKey(i), 1) for i in range(5)]
    big = _with_rows([plan_graph(g)
                      for g in _path_graphs([9, 11, 13, 15, 16])], keys)
    R, W = big[0].bucket
    small = big[:2]
    lease = pool.acquire(8, R, W)
    pack_bucket(big, k=1, staging=lease.arrays, g_pad=8)
    lease.release()
    lease = pool.acquire(8, R, W)      # same pooled (now dirty) buffers
    reused = pack_bucket(small, k=1, staging=lease.arrays, g_pad=8)
    lease.release()
    fresh = pack_bucket(small, k=1, g_pad=8)
    _assert_staging_equal(reused, fresh)
    _assert_staging_equal(reused,
                          _reference_staging(small, keys[:2], k=1, g_pad=8))


def test_pack_bucket_rejects_mismatched_prebuilt_shape():
    plan = plan_graph(build_graph(6, path(6)))
    plan.rows = build_packed_rows(plan, sample_keys(jax.random.PRNGKey(0), 1))
    bigger = promote_plan(plan, plan.R * 2, plan.W)
    bigger.rows = plan.rows            # stale rows at the old bucket
    with pytest.raises(ValueError, match="prebuilt rows"):
        pack_bucket([bigger], k=1)
    with pytest.raises(ValueError, match="prebuilt rows"):
        pack_bucket([plan], k=2)   # k mismatch


def test_pack_bucket_refuses_plan_without_rows():
    # pack_bucket only copies rows; a plan without them names the builder,
    # whether it packs alone or beside plans that have rows.
    plans = [plan_graph(g) for g in _path_graphs([9, 12])]
    with pytest.raises(ValueError, match="build_packed_rows"):
        pack_bucket(plans[:1], k=1)
    plans[0].rows = build_packed_rows(
        plans[0], sample_keys(jax.random.PRNGKey(0), 1))
    with pytest.raises(ValueError, match="build_packed_rows"):
        pack_bucket(plans, k=1)


def test_promote_rejects_shrinking():
    plan = plan_graph(build_graph(10, path(10)))
    rows = build_packed_rows(plan, sample_keys(jax.random.PRNGKey(0), 1))
    with pytest.raises(ValueError):
        rows.promote(plan.R // 2, plan.W)


def test_packed_rows_lazy_ranks_match_direct_dispatch():
    plan = plan_graph(build_graph(9, path(9)))
    keys = sample_keys(jax.random.PRNGKey(7), 2)
    rows = build_packed_rows(plan, keys)
    direct = np.asarray(random_permutation_ranks_batch(plan.n, keys))
    assert rows.ranks.shape == (2, plan.R + 1)
    assert (rows.ranks[:, :plan.n] == direct).all()
    assert (rows.ranks[:, plan.n:] == np.iinfo(np.int32).max).all()


# ---------------------------------------------------------------------------
# One row builder: the batch API and a serving flush stage the same bytes.
# ---------------------------------------------------------------------------


class _RecordingExecutor(SyncExecutor):
    """A sync executor that keeps a copy of every staged flush."""

    def __init__(self):
        super().__init__()
        self.staged = []

    def submit(self, ell, ranks_p, elig_p, m_edges, k, **kw):
        self.staged.append(tuple(np.array(a, copy=True)
                                 for a in (ell, ranks_p, elig_p, m_edges)))
        return super().submit(ell, ranks_p, elig_p, m_edges, k, **kw)


@pytest.mark.parametrize("method", ["pivot", "precluster"])
def test_batch_api_and_batcher_flush_stage_identical_bytes(method):
    # Two shape buckets, best of 2: the batch API flushes its buckets in
    # order of first appearance, as the batcher's end-of-stream drain does.
    graphs = _graphs(6, 5, 40, seed=21, lam_hi=3)
    keys = [jax.random.PRNGKey(50 + i) for i in range(len(graphs))]
    assert len({plan_graph(g, method=method).bucket for g in graphs}) > 1
    via_batch = _RecordingExecutor()
    correlation_cluster_batch(graphs, keys=keys, method=method,
                              num_samples=2, executor=via_batch)
    via_engine = _RecordingExecutor()
    engine = ClusterBatcher(max_batch=64, method=method, num_samples=2,
                            executor=via_engine, result_cache=False)
    for i, (g, key) in enumerate(zip(graphs, keys)):
        engine.admit(ClusterRequest(uid=i, graph=g, key=key))
    assert len(engine.flush()) == len(graphs)
    assert len(via_batch.staged) == len(via_engine.staged) > 1
    for a, b in zip(via_batch.staged, via_engine.staged):
        for x, y in zip(a, b):
            assert x.shape == y.shape and (x == y).all()


# ---------------------------------------------------------------------------
# Canonical edge list shared with the fingerprint (PR 6 contract).
# ---------------------------------------------------------------------------


def test_fingerprint_payload_survives_canonical_sharing():
    for g in _graphs(4, 6, 30, seed=6, lam_hi=3):
        with_cache = plan_graph(g)
        assert with_cache.canonical_edges is not None
        stripped = plan_graph(g)
        stripped.canonical_edges = None      # hand-built-plan fallback
        fp_a = graph_fingerprint(with_cache, jax.random.PRNGKey(1))
        fp_b = graph_fingerprint(stripped, jax.random.PRNGKey(1))
        assert fp_a.digest == fp_b.digest
        # The lazy fallback memoizes the same canonical order.
        assert (plan_canonical_edges(stripped)
                == plan_canonical_edges(with_cache)).all()


# ---------------------------------------------------------------------------
# Serving engine: bit-exactness of admission-built rows.
# ---------------------------------------------------------------------------


def _serve(graphs, executor="sync", use_kernel=False, policy=None,
           max_batch=4, num_samples=1, max_wait=None):
    batcher = ClusterBatcher(max_batch=max_batch, max_wait=max_wait,
                             num_samples=num_samples, executor=executor,
                             use_kernel=use_kernel, policy=policy,
                             result_cache=False)
    reqs = [ClusterRequest(uid=i, graph=g, key=jax.random.PRNGKey(i))
            for i, g in enumerate(graphs)]
    done = {r.uid: r.result for r in serve_all(batcher, reqs)}
    assert len(done) == len(graphs)
    return done, batcher.stats


@pytest.mark.parametrize("executor", ["sync", "async", "sharded"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_serving_bitexact_across_executors_and_kernels(executor, use_kernel):
    graphs = _graphs(6, 6, 24, seed=8)
    done, stats = _serve(graphs, executor=executor, use_kernel=use_kernel,
                         num_samples=2)
    assert stats.latency.total_builds == len(graphs)
    for i, g in enumerate(graphs):
        ref = correlation_cluster(g, key=jax.random.PRNGKey(i),
                                  num_samples=2, use_kernel=use_kernel)
        assert (done[i].labels == ref.labels).all()
        assert done[i].cost == ref.cost
        assert done[i].info["picked_sample"] == ref.info["picked_sample"]


def test_deadline_partial_subbatch_prebuilt_bitexact():
    # max_batch never fills: every flush is a partial deadline sub-batch.
    graphs = _graphs(5, 6, 20, seed=9)
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=64, max_wait=0.01, clock=clock,
                             result_cache=False)
    done = {}
    for i, g in enumerate(graphs):
        clock.advance(0.004)
        for r in batcher.admit(ClusterRequest(
                uid=i, graph=g, key=jax.random.PRNGKey(i))):
            done[r.uid] = r.result
        for r in batcher.poll():
            done[r.uid] = r.result
    for r in batcher.flush():
        done[r.uid] = r.result
    assert batcher.stats.deadline_flushes > 0
    for i, g in enumerate(graphs):
        ref = correlation_cluster(g, key=jax.random.PRNGKey(i))
        assert (done[i].labels == ref.labels).all()
        assert done[i].cost == ref.cost


def test_coalesced_stolen_flush_prebuilt_bitexact():
    # Hot (32, 4) bucket + starved small bucket, aggressive stealing: the
    # stolen requests run at a promoted shape assembled from promoted
    # PackedRows (virtual clock: a deterministic steal schedule).
    clock = VirtualClock()
    batcher = ClusterBatcher(
        max_batch=8, clock=clock, result_cache=False,
        policy=CoalescingPolicy(8, max_wait=0.01, steal_wait=0.001))
    done = {}
    graphs = {}
    rng = np.random.default_rng(11)
    for i in range(24):
        n = 6 if i % 8 == 0 else int(rng.integers(17, 30))
        graphs[i] = build_graph(n, path(n))
        clock.advance(0.002)
        for r in batcher.admit(ClusterRequest(
                uid=i, graph=graphs[i], key=jax.random.PRNGKey(i))):
            done[r.uid] = r.result
        for r in batcher.poll():
            done[r.uid] = r.result
    for r in batcher.flush():
        done[r.uid] = r.result
    assert batcher.stats.stolen_requests > 0
    for i, g in graphs.items():
        ref = correlation_cluster(g, key=jax.random.PRNGKey(i))
        assert (done[i].labels == ref.labels).all()
        assert done[i].cost == ref.cost


# ---------------------------------------------------------------------------
# Warmup autotune sweep: staged through pool leases.
# ---------------------------------------------------------------------------


def test_warmup_autotune_sweeps_through_pool_lease(tmp_path):
    from repro.kernels import autotune as at

    prev = at.set_tuning_cache(
        at.TuningCache(path=str(tmp_path / "tuning.json")))
    try:
        batcher = ClusterBatcher(max_batch=4)
        graphs = _graphs(3, 20, 24, seed=12)
        compiled = batcher.warmup(graphs, autotune=True,
                                  candidates=(16, 32), repeats=1)
        assert compiled > 0
        # The sweep leased (and released) pool staging instead of packing
        # into ad-hoc buffers: buffers exist, none outstanding.
        assert batcher.pool.leased == 0
        assert batcher.pool.n_buffers > 0
        assert batcher.stats.tuning
    finally:
        at.set_tuning_cache(prev)
