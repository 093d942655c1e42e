"""Pluggable bucket-executor layer: sync ≡ async ≡ sharded, bit-exactly.

The contracts under test (core/plan.py, core/executor.py,
serve/cluster_batcher.py):

* all three executors return labels/costs/picked sample indices
  bit-identical to per-graph ``correlation_cluster`` — for full flushes,
  partial deadline flushes, and both kernel paths;
* ``BucketBufferPool`` leases: a staging buffer feeding an in-flight
  program is never handed out again until that flush's outputs are
  fetched (the async-overlap regression);
* ``max_in_flight`` admission backpressure rejects at admit time and
  counts the rejection;
* the compiled-program cache is a bounded LRU with eviction/compile
  counters, and eviction only costs a recompile, never correctness; its
  hint surface (``contains`` probe, ``touch`` recency refresh,
  ``pin``/``unpin`` protection) never mutates order on probes and never
  lets pins defeat the hard capacity bound;
* the sharded executor raises group padding to its device count (8-device
  proof runs in a subprocess, mirroring tests/test_dist.py).
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import (
    AsyncExecutor,
    BucketBufferPool,
    BucketExecutor,
    ShardedExecutor,
    SyncExecutor,
    build_graph,
    correlation_cluster,
    correlation_cluster_batch,
    make_executor,
    plan_graph,
    pow2_device_mesh,
)
from repro.core import executor as exec_mod
from repro.core.api import sample_keys
from repro.core.executor import run_bucket_program
from repro.core.graph import path, random_arboric
from repro.core.plan import build_packed_rows, pack_bucket
from repro.serve.cluster_batcher import (
    AdmissionRejected,
    ClusterBatcher,
    ClusterRequest,
)
from repro.util import VirtualClock


def _rand_graph(n, lam, seed):
    edges, _ = random_arboric(n, lam, np.random.default_rng(seed))
    return build_graph(n, edges)


def _assert_matches(g, key, res_batch, **kwargs):
    res_single = correlation_cluster(g, key=key, **kwargs)
    assert (res_batch.labels == res_single.labels).all()
    assert res_batch.cost == res_single.cost


class _StallingExecutor(AsyncExecutor):
    """Async executor whose harvests are deferred until released — makes
    in-flight overlap deterministic for backpressure/lease tests."""

    def __init__(self):
        super().__init__()
        self.stalled = True

    def retire(self):
        return [] if self.stalled else super().retire()


# ---------------------------------------------------------------------------
# Bit-exactness: every executor, full + partial deadline flushes, both
# kernel paths (the tentpole contract).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("executor", ["sync", "async", "sharded"])
def test_executor_full_and_deadline_flushes_bit_exact(executor, use_kernel):
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=4, max_wait=1.0, clock=clock,
                             executor=executor, use_kernel=use_kernel,
                             num_samples=2)
    rng = np.random.default_rng(17)
    reqs = []
    for i in range(6):          # 4 fill one bucket; 2 become stragglers
        n = int(rng.integers(5, 13))
        req = ClusterRequest(uid=i, graph=_rand_graph(n, 2, seed=200 + i),
                             key=jax.random.PRNGKey(i))
        reqs.append(req)
        batcher.admit(req)
    clock.advance(2.0)
    batcher.poll()              # deadline partial flush for the stragglers
    batcher.flush()             # drains in-flight work too
    assert batcher.pending() == 0
    assert all(r.done for r in reqs)
    for r in reqs:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result,
                        num_samples=2)
    assert batcher.stats.clustered == 6
    assert batcher.stats.deadline_flushes >= 1


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("executor", ["sync", "async", "sharded"])
def test_precluster_executors_bit_exact(executor, use_kernel):
    """Satellite 3 of PR 10: the 'precluster' bucket program — full and
    deadline-partial flushes alike — is bit-identical to the per-graph
    'precluster' engine under every executor × kernel path, exactly like
    the pivot contract above."""
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=4, max_wait=1.0, clock=clock,
                             executor=executor, use_kernel=use_kernel,
                             method="precluster", num_samples=2)
    reqs = []
    for i in range(6):
        n = int(np.random.default_rng(40 + i).integers(5, 13))
        req = ClusterRequest(uid=i, graph=_rand_graph(n, 2, seed=300 + i),
                             key=jax.random.PRNGKey(i))
        reqs.append(req)
        batcher.admit(req)
    clock.advance(2.0)
    batcher.poll()
    batcher.flush()
    assert all(r.done for r in reqs)
    for r in reqs:
        assert r.result.method == "precluster"
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result,
                        method="precluster", num_samples=2)


@pytest.mark.parametrize("executor", ["sync", "async", "sharded"])
def test_batch_api_executor_param_bit_exact(executor):
    graphs = [_rand_graph(n, 2, seed=n) for n in (7, 9, 16, 33)]
    keys = [jax.random.PRNGKey(i) for i in range(4)]
    pool = BucketBufferPool()
    results = correlation_cluster_batch(graphs, keys=keys, num_samples=2,
                                        executor=executor, pool=pool)
    for g, key, res in zip(graphs, keys, results):
        _assert_matches(g, key, res, num_samples=2)
    # One-shot calls harvest everything before returning: no leaked leases.
    assert pool.leased == 0


def test_async_executor_overlaps_then_drains():
    """Handles stay in flight across admits; flush() collects everything."""
    ex = AsyncExecutor()
    batcher = ClusterBatcher(max_batch=2, executor=ex)
    reqs = [ClusterRequest(uid=i, graph=build_graph(6, path(6)),
                           key=jax.random.PRNGKey(i)) for i in range(6)]
    retired = []
    for r in reqs:
        retired += batcher.admit(r)     # 3 full-bucket flushes dispatched
    retired += batcher.flush()
    assert sorted(r.uid for r in retired) == list(range(6))
    assert ex.in_flight == 0 and batcher.pending() == 0
    for r in reqs:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result)


# ---------------------------------------------------------------------------
# Admission backpressure (max_in_flight).
# ---------------------------------------------------------------------------


def test_backpressure_rejects_at_admit_and_recovers():
    ex = _StallingExecutor()
    batcher = ClusterBatcher(max_batch=2, executor=ex, max_in_flight=1)
    g = build_graph(6, path(6))
    for i in range(2):          # fills the bucket → one in-flight flush
        batcher.admit(ClusterRequest(uid=i, graph=g,
                                     key=jax.random.PRNGKey(i)))
    assert ex.in_flight == 1
    with pytest.raises(AdmissionRejected):
        batcher.admit(ClusterRequest(uid=2, graph=g,
                                     key=jax.random.PRNGKey(2)))
    assert batcher.stats.rejected == 1
    assert batcher.stats.submitted == 2     # the rejected one never entered
    ex.stalled = False
    done = batcher.flush()      # blocking harvest clears the backpressure
    assert sorted(r.uid for r in done) == [0, 1]
    out = batcher.admit(ClusterRequest(uid=2, graph=g,
                                       key=jax.random.PRNGKey(2)))
    assert out == []            # admitted fine once capacity freed
    assert batcher.stats.in_flight_peak == 1
    batcher.flush()


def test_batcher_validates_max_in_flight():
    with pytest.raises(ValueError, match="max_in_flight"):
        ClusterBatcher(max_in_flight=0)


# ---------------------------------------------------------------------------
# BucketBufferPool leases: the async-overlap regression (satellite).
# ---------------------------------------------------------------------------


def test_pool_lease_not_reused_while_outstanding():
    pool = BucketBufferPool()
    lease1 = pool.acquire(4, 8, 4)
    lease2 = pool.acquire(4, 8, 4)      # same shape, first still leased
    assert lease1.arrays["ell"] is not lease2.arrays["ell"]
    assert pool.n_buffers == 2 and pool.leased == 2
    lease1.release()
    lease1.release()                    # idempotent
    assert pool.leased == 1
    lease3 = pool.acquire(4, 8, 4)      # reuses the freed generation
    assert lease3.arrays["ell"] is lease1.arrays["ell"]
    assert pool.n_buffers == 2
    lease2.release()
    lease3.release()
    assert pool.leased == 0


def test_interleaved_async_flushes_never_refill_in_flight_staging():
    """Two same-shape flushes in flight at once must pack into *distinct*
    staging generations, and both must stay bit-exact — the regression
    guard for the async host↔device overlap path."""
    ex = _StallingExecutor()
    pool = BucketBufferPool()
    batcher = ClusterBatcher(max_batch=2, executor=ex, pool=pool)
    graphs = [_rand_graph(6, 1, seed=s) for s in range(4)]
    for i, g in enumerate(graphs):      # two flushes of the same (8,4) bucket
        batcher.admit(ClusterRequest(uid=i, graph=g,
                                     key=jax.random.PRNGKey(i)))
    assert ex.in_flight == 2
    # Both flushes hold their own staging lease — nothing was refilled.
    assert pool.leased == 2 and pool.n_buffers == 2
    ex.stalled = False
    done = {r.uid: r for r in batcher.flush()}
    assert sorted(done) == [0, 1, 2, 3]
    assert pool.leased == 0             # harvest released both leases
    for i, g in enumerate(graphs):
        _assert_matches(g, jax.random.PRNGKey(i), done[i].result)
    # Steady state: the freed generations are reused, the pool stops growing.
    for i, g in enumerate(graphs):
        batcher.admit(ClusterRequest(uid=10 + i, graph=g,
                                     key=jax.random.PRNGKey(i)))
    batcher.flush()
    assert pool.n_buffers == 2


def test_flush_failure_releases_lease_and_requeues_requests():
    """A failed dispatch must release the staging lease (no pool growth)
    and put the popped requests back so none are silently lost."""
    class _FailingExecutor(SyncExecutor):
        def __init__(self):
            super().__init__()
            self.fail = True

        def submit(self, *args, **kwargs):
            if self.fail:
                raise RuntimeError("injected submit failure")
            return super().submit(*args, **kwargs)

    ex = _FailingExecutor()
    pool = BucketBufferPool()
    batcher = ClusterBatcher(max_batch=2, executor=ex, pool=pool)
    g = build_graph(6, path(6))
    batcher.admit(ClusterRequest(uid=0, graph=g, key=jax.random.PRNGKey(0)))
    with pytest.raises(RuntimeError, match="injected"):
        batcher.admit(ClusterRequest(uid=1, graph=g,
                                     key=jax.random.PRNGKey(1)))
    assert pool.leased == 0             # lease released on the failure path
    assert batcher.pending() == 2       # both requests requeued
    ex.fail = False
    done = batcher.flush()              # retry succeeds with the same state
    assert sorted(r.uid for r in done) == [0, 1]
    for r in done:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result)
    assert pool.leased == 0


def test_harvest_failure_requeues_requests_and_releases_lease():
    """A device-side error surfacing at fetch time must requeue the
    flush's requests, release its staging lease, and keep pending()
    accounting sound — then a retry must succeed."""
    class _Boom:
        def __array__(self, *args, **kwargs):
            raise RuntimeError("injected fetch failure")

    ex = _StallingExecutor()
    pool = BucketBufferPool()
    batcher = ClusterBatcher(max_batch=2, executor=ex, pool=pool)
    g = build_graph(6, path(6))
    for i in range(2):
        batcher.admit(ClusterRequest(uid=i, graph=g,
                                     key=jax.random.PRNGKey(i)))
    assert ex.in_flight == 1
    ex._pending[0]._outputs = (_Boom(),) * 4    # poison the fetch
    ex.stalled = False
    with pytest.raises(RuntimeError, match="injected fetch"):
        batcher.flush()
    assert pool.leased == 0                     # lease released on failure
    assert batcher.pending() == 2               # requests requeued, not lost
    done = batcher.flush()                      # retry re-packs and succeeds
    assert sorted(r.uid for r in done) == [0, 1]
    for r in done:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result)


def test_handle_result_releases_lease_exactly_once():
    pool = BucketBufferPool()
    g = build_graph(6, path(6))
    plan = plan_graph(g)
    plan.rows = build_packed_rows(plan, sample_keys(jax.random.PRNGKey(0), 1))
    lease = pool.acquire(1, plan.R, plan.W)
    ell, ranks, elig, m, _ = pack_bucket(
        [plan], k=1, staging=lease.arrays, g_pad=1)
    ex = AsyncExecutor()
    h = ex.submit(ell, ranks, elig, m, k=1, donate=pool.donate, lease=lease)
    assert pool.leased == 1
    h.result()
    h.result()      # second fetch is a no-op
    assert pool.leased == 0
    (res,) = correlation_cluster_batch([g], keys=[jax.random.PRNGKey(0)])
    assert (h.result()[0][0, :6].astype(np.int32) == res.labels).all()


# ---------------------------------------------------------------------------
# Bounded LRU program cache (satellite).
# ---------------------------------------------------------------------------


def test_program_cache_lru_evicts_and_recompiles_correctly():
    prev = exec_mod.set_program_cache_capacity(2)
    try:
        evict0 = exec_mod.program_cache_info()["evictions"]
        # Three distinct bucket shapes through a capacity-2 cache.
        graphs = [build_graph(6, path(6)), build_graph(12, path(12)),
                  build_graph(24, path(24))]
        keys = [jax.random.PRNGKey(i) for i in range(3)]
        for g, key in zip(graphs, keys):
            (res,) = correlation_cluster_batch([g], keys=[key])
            _assert_matches(g, key, res)
        info = exec_mod.program_cache_info()
        assert info["size"] <= 2 and info["capacity"] == 2
        assert info["evictions"] > evict0
        # The evicted shape recompiles and still answers bit-exactly.
        (res,) = correlation_cluster_batch([graphs[0]], keys=[keys[0]])
        _assert_matches(graphs[0], keys[0], res)
    finally:
        exec_mod.set_program_cache_capacity(prev)


def test_program_cache_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        exec_mod.set_program_cache_capacity(0)
    info = exec_mod.program_cache_info()
    assert info["size"] <= info["capacity"]


def _run_dummy(R, W, B=1, k=1, donate=False):
    """Compile/run one tiny bucket program of shape (B, R, W)."""
    ell = np.full((B, R, W), R, dtype=np.int32)
    ranks = np.full((B, R + 1), np.iinfo(np.int32).max, dtype=np.int32)
    elig = np.zeros((B, R + 1), dtype=bool)
    m = np.zeros((B,), dtype=np.int32)
    jax.block_until_ready(run_bucket_program(ell, ranks, elig, m, k=k,
                                             donate=donate))


def test_program_cache_contains_probe_is_non_mutating():
    prev = exec_mod.set_program_cache_capacity(2)
    try:
        _run_dummy(8, 4)        # key A (the LRU after B runs)
        _run_dummy(16, 4)       # key B
        assert exec_mod.program_cache_contains((1, 8, 4), 1)
        assert exec_mod.program_cache_contains((1, 16, 4), 1)
        # Different signature, same shape: not resident.
        assert not exec_mod.program_cache_contains((1, 8, 4), 2)
        assert not exec_mod.program_cache_contains((2, 8, 4), 1)
        # Probing A must NOT refresh it: a third shape evicts A (the true
        # LRU), which a mutating probe would have protected.
        assert exec_mod.program_cache_contains((1, 8, 4), 1)
        _run_dummy(32, 4)       # key C → evicts A
        assert not exec_mod.program_cache_contains((1, 8, 4), 1)
        assert exec_mod.program_cache_contains((1, 16, 4), 1)
    finally:
        exec_mod.set_program_cache_capacity(prev)


def test_program_cache_touch_refreshes_recency():
    prev = exec_mod.set_program_cache_capacity(2)
    try:
        _run_dummy(8, 4)
        _run_dummy(16, 4)
        # Touch the LRU shape: the next insert must evict the other one.
        assert exec_mod.program_cache_touch((8, 4)) >= 1
        assert exec_mod.program_cache_touch((64, 64)) == 0   # no-op miss
        _run_dummy(32, 4)
        assert exec_mod.program_cache_contains((1, 8, 4), 1)
        assert not exec_mod.program_cache_contains((1, 16, 4), 1)
    finally:
        exec_mod.set_program_cache_capacity(prev)


def test_program_cache_pin_protects_until_unpin_with_hard_capacity():
    prev = exec_mod.set_program_cache_capacity(2)
    try:
        _run_dummy(8, 4)
        assert exec_mod.program_cache_pin((8, 4)) >= 1
        assert (8, 4) in exec_mod.program_cache_info()["pinned"]
        # Churn: two fresh shapes; the pinned LRU survives both inserts.
        _run_dummy(16, 4)
        _run_dummy(32, 4)
        assert exec_mod.program_cache_contains((1, 8, 4), 1)
        assert exec_mod.program_cache_info()["size"] <= 2
        # Unpinned, the same churn evicts it.
        assert exec_mod.program_cache_unpin((8, 4))
        assert not exec_mod.program_cache_unpin((8, 4))      # idempotent
        _run_dummy(16, 4)
        _run_dummy(32, 4)
        assert not exec_mod.program_cache_contains((1, 8, 4), 1)
        # Pins are preferences, capacity is the law: with every resident
        # shape pinned, inserts still evict (hard bound, no growth).
        for bucket in [(16, 4), (32, 4), (64, 4)]:
            exec_mod.program_cache_pin(bucket)
        _run_dummy(64, 4)
        assert exec_mod.program_cache_info()["size"] <= 2
    finally:
        for bucket in list(exec_mod.program_cache_info()["pinned"]):
            exec_mod.program_cache_unpin(tuple(bucket))
        exec_mod.set_program_cache_capacity(prev)


def test_program_cache_pin_is_refcounted():
    """Pins are process-global while pinners are per-engine: each pin
    needs a matching unpin, and a shape stays protected while any pinner
    remains."""
    try:
        exec_mod.program_cache_pin((8, 4))
        exec_mod.program_cache_pin((8, 4))      # second pinner
        assert exec_mod.program_cache_unpin((8, 4))
        assert (8, 4) in exec_mod.program_cache_info()["pinned"]
        assert exec_mod.program_cache_unpin((8, 4))
        assert (8, 4) not in exec_mod.program_cache_info()["pinned"]
        assert not exec_mod.program_cache_unpin((8, 4))
    finally:
        while exec_mod.program_cache_unpin((8, 4)):
            pass


def test_program_cache_counts_compiles():
    info0 = exec_mod.program_cache_info()
    _run_dummy(8, 8)            # width-8 shape: unused elsewhere
    _run_dummy(8, 8)            # cache hit — no second compile
    info1 = exec_mod.program_cache_info()
    assert info1["compiles"] == info0["compiles"] + 1


@pytest.mark.parametrize("use_kernel", [False, True])
def test_warmup_builds_each_program_once(use_kernel):
    """Warmup compiles its programs ahead of their first run, side by
    side; jit reuses those executables, so XLA builds each program at
    most once (fewer when a persistent cache holds it), a second warmup
    builds nothing, and neither does serving the warmed graph — its
    rank-permutation program included."""
    from jax._src import monitoring

    from repro.core.mis import random_permutation_ranks_batch

    builds = []

    def on_duration(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            builds.append(kwargs.get("fun_name"))

    # num_samples=3 and a width-8 bucket: programs no other test compiles.
    eng = ClusterBatcher(max_batch=4, num_samples=3, use_kernel=use_kernel)
    g = _rand_graph(40, 2, 0)
    # The rank program first, so the builds counted below are the bucket
    # programs alone.
    random_permutation_ranks_batch(g.n, sample_keys(jax.random.PRNGKey(0),
                                                    3))
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        compiled = eng.warmup([g])
        first = len(builds)
        assert eng.warmup([g]) == 0
        served = eng.admit(ClusterRequest(uid=0, graph=g,
                                          key=jax.random.PRNGKey(9)))
        served += eng.flush()
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    assert compiled == 3                   # pow2 sub-batches 1, 2, 4
    assert first <= compiled
    assert len(served) == 1
    assert len(builds) == first


# ---------------------------------------------------------------------------
# Factory / protocol / sharded group padding.
# ---------------------------------------------------------------------------


def test_make_executor_resolves_names_and_instances():
    assert isinstance(make_executor(None), SyncExecutor)
    assert isinstance(make_executor("async"), AsyncExecutor)
    assert isinstance(make_executor("sharded"), ShardedExecutor)
    ex = AsyncExecutor()
    assert make_executor(ex) is ex
    for impl in (SyncExecutor(), AsyncExecutor(), make_executor("sharded")):
        assert isinstance(impl, BucketExecutor)
    with pytest.raises(ValueError, match="unknown executor"):
        make_executor("turbo")
    with pytest.raises(TypeError, match="executor"):
        make_executor(42)


def test_sharded_group_pad_floors_at_device_count():
    ex = ShardedExecutor(mesh=pow2_device_mesh(1))
    assert ex.num_devices == 1
    assert ex.group_pad(3) == 4         # plain pow2 on a 1-device mesh
    assert ex.group_pad(0) == 1


def test_sync_executor_completes_at_submit():
    ex = SyncExecutor()
    g = build_graph(6, path(6))
    plan = plan_graph(g)
    plan.rows = build_packed_rows(plan, sample_keys(jax.random.PRNGKey(0), 1))
    ell, ranks, elig, m, _ = pack_bucket([plan], k=1)
    h = ex.submit(ell, ranks, elig, m, k=1)
    assert h.ready() and h.harvested
    assert ex.retire() == [h]           # delivered exactly once
    assert ex.retire() == []


# ---------------------------------------------------------------------------
# 8-virtual-device sharded execution (slow, subprocess — mirrors test_dist).
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_executor_eight_devices_subprocess():
    """One flush spans all 8 host devices and stays bit-exact vs the
    per-graph engine, with group padding raised to the device count."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.core import (build_graph, correlation_cluster,
                                correlation_cluster_batch)
        from repro.core.executor import ShardedExecutor
        from repro.core.graph import random_arboric
        from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest
        ex = ShardedExecutor()
        assert ex.num_devices == 8, ex.num_devices
        assert ex.group_pad(3) == 8     # floored at the device count
        rng = np.random.default_rng(4)
        graphs = [build_graph(n, random_arboric(n, 2, rng)[0])
                  for n in rng.integers(5, 30, size=12)]
        keys = [jax.random.PRNGKey(i) for i in range(12)]
        res, stats = correlation_cluster_batch(
            graphs, keys=keys, num_samples=2, executor=ex, with_stats=True)
        assert all(B % 8 == 0 for _, _, B in stats.bucket_shapes)
        for g, key, r in zip(graphs, keys, res):
            ref = correlation_cluster(g, key=key, num_samples=2)
            assert (r.labels == ref.labels).all(), "8-shard label mismatch"
            assert r.cost == ref.cost
        b = ClusterBatcher(max_batch=4, executor="sharded", num_samples=2)
        done = []
        for i, g in enumerate(graphs):
            done += b.admit(ClusterRequest(uid=i, graph=g,
                                           key=jax.random.PRNGKey(i)))
        done += b.flush()
        assert len(done) == 12
        for r in done:
            ref = correlation_cluster(r.graph,
                                      key=jax.random.PRNGKey(r.uid),
                                      num_samples=2)
            assert (r.result.labels == ref.labels).all()
            assert r.result.cost == ref.cost
        print("OK devices=", ex.num_devices)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_engine_close_is_pin_refcount_idempotent():
    """Double-close / close-then-__del__ must release an engine's pin
    refs exactly once: with two live engines pinning the same shape, one
    engine's sloppy teardown can never strip the other's pin."""
    from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest
    from repro.serve.costmodel import ShapeHeat
    from repro.serve.scheduler import CostAwareCoalescingPolicy

    def make_engine():
        policy = CostAwareCoalescingPolicy(
            2, max_wait=10.0,
            heat=ShapeHeat(window=8, max_pinned=1, min_heat=1))
        return ClusterBatcher(policy=policy)

    engines = [make_engine(), make_engine()]
    for i, eng in enumerate(engines):
        for j in range(2):       # fill the (8, 4) bucket → flush → retire
            eng.admit(ClusterRequest(uid=j, graph=build_graph(6, path(6)),
                                     key=jax.random.PRNGKey(10 * i + j)))
        eng.flush()
    assert (8, 4) in exec_mod.program_cache_info()["pinned"]   # refcount 2

    a, b = engines
    a.close()
    a.close()                    # double close: second must be a no-op
    del a                        # __del__ after close: also a no-op
    assert (8, 4) in exec_mod.program_cache_info()["pinned"], \
        "engine A's teardown stole engine B's pin ref"
    b.close()
    assert (8, 4) not in exec_mod.program_cache_info()["pinned"]
    b.close()                    # close after the pin is gone: still safe


# ---------------------------------------------------------------------------
# Ragged kernels inside the bucket programs, on a power-law graph.
# ---------------------------------------------------------------------------


def _kronecker(scale, edge_factor, rng, a=0.57, b=0.19, c=0.19):
    """Graph500 Kronecker edges (specification section 3): ``scale``
    quadrant draws per edge, then vertex labels permuted."""
    m = edge_factor << scale
    c_norm, a_norm = c / (1.0 - a - b), a / (a + b)
    ij = np.zeros((m, 2), dtype=np.int64)
    for bit in range(scale):
        ii = rng.random(m) > a + b
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[:, 0] += ii.astype(np.int64) << bit
        ij[:, 1] += jj.astype(np.int64) << bit
    return rng.permutation(1 << scale)[ij]


@pytest.fixture(scope="module")
def kron_graph():
    return build_graph(1 << 9, _kronecker(9, 16, np.random.default_rng(9)))


def _kron_pack(graph, method, k=2):
    plan = plan_graph(graph, method=method)
    plan.rows = build_packed_rows(plan, sample_keys(jax.random.PRNGKey(3), k))
    return pack_bucket([plan], k=k)[:4]


@pytest.mark.parametrize("objective", ["disagree", "minmax"])
@pytest.mark.parametrize("method", ["pivot", "pivot_raw", "precluster"])
def test_ragged_kernel_program_bit_equal_to_jnp(kron_graph, method,
                                                objective):
    """``bucket_impl`` on the kernel path (degree-ordered, ragged sweep)
    equals the jnp path bit for bit: labels, costs, picks and rounds."""
    import functools

    from repro.core.programs import bucket_impl, method_spec

    args = _kron_pack(kron_graph, method)
    outs = [jax.jit(functools.partial(
        bucket_impl, k=2, use_kernel=use_kernel, block_rows=None,
        program=method_spec(method).program, objective=objective))(*args)
        for use_kernel in (False, True)]
    for jnp_out, kern_out in zip(*outs):
        assert (np.asarray(jnp_out) == np.asarray(kern_out)).all()


def test_swept_tiles_reach_stats(kron_graph):
    """The program counts the ELL tiles its kernels sweep — per entry,
    Σ over 128-lane groups of ceil(widest row / 8) once the rows are
    ordered by width — and all of them, R_lanes/128 · W/8; the harvest
    adds both to ``ClusterBatcher.stats``."""
    k = 2
    ell = np.asarray(_kron_pack(kron_graph, "pivot", k)[0])
    b, r, w = ell.shape
    width = np.where(ell < r, np.arange(1, w + 1), 0).max(axis=2)
    swept = sum(-(-int(np.sort(row)[::-1][g]) // 8)
                for row in width for g in range(0, r, 128))
    full = b * (-(-r // 128)) * (w // 8)
    assert swept < full

    eng = ClusterBatcher(max_batch=1, num_samples=k, use_kernel=True)
    done = eng.admit(ClusterRequest(uid=0, graph=kron_graph,
                                    key=jax.random.PRNGKey(3)))
    done += eng.flush()
    assert len(done) == 1
    assert (eng.stats.ell_tiles_swept, eng.stats.ell_tiles_full) \
        == (swept, full)
