"""Scheduling-policy layer: decisions, promotion, bit-exactness, leases.

The contracts under test (serve/scheduler.py, serve/cluster_batcher.py,
core/plan.py promote_plan, core/executor.py telemetry):

* policy unit behaviour — full-bucket/deadline/adaptive/coalescing
  ``select_flushes``/``on_admit`` decisions are pure functions of the
  queues, the injected engine clock and the telemetry (no wall-clock);
* shape promotion (``promote_plan``) validates its target, and coalesced
  flushes — requests running at a *promoted* ``(R, W)`` — stay
  bit-identical to per-graph ``correlation_cluster``;
* all four policies satisfy the bit-exactness contract under randomized
  arrival traces (hypothesis-style), while ``BucketBufferPool`` never
  hands out a staging buffer whose lease is outstanding;
* executor telemetry (wall/pack per flush) reaches ``ClusterStats`` and
  drives the adaptive admission window;
* ``serve_all`` retries rejected admissions, so backpressure policies can
  be driven by the reference outer loop.
"""

import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    BucketBufferPool,
    build_graph,
    correlation_cluster,
    estimate_pack_stats,
    plan_graph,
    promote_plan,
)
from repro.core.executor import AsyncExecutor
from repro.core.graph import path, random_arboric
from repro.serve.cluster_batcher import (
    AdmissionRejected,
    ClusterBatcher,
    ClusterRequest,
)
from repro.serve.engine import serve_all
from repro.serve.costmodel import FlushCostModel, ShapeHeat
from repro.serve.scheduler import (
    AdaptivePolicy,
    CoalescingPolicy,
    CostAwareCoalescingPolicy,
    DeadlinePolicy,
    FlushDecision,
    FlushTelemetry,
    FullBucketPolicy,
    SchedulerPolicy,
    make_policy,
)
from repro.util import VirtualClock


def _rand_graph(n, lam, seed):
    edges, _ = random_arboric(n, lam, np.random.default_rng(seed))
    return build_graph(n, edges)


@pytest.fixture(autouse=True)
def _unpin_program_cache():
    """Cost-policy heat tracking pins bucket shapes in the *global*
    program cache; never let pins leak between tests."""
    yield
    from repro.core.executor import program_cache_info, program_cache_unpin

    for bucket in program_cache_info()["pinned"]:
        while program_cache_unpin(tuple(bucket)):   # drain all refs
            pass


def _assert_matches(g, key, res_batch, **kwargs):
    res_single = correlation_cluster(g, key=key, **kwargs)
    assert (res_batch.labels == res_single.labels).all()
    assert res_batch.cost == res_single.cost


@dataclasses.dataclass
class _Req:
    """Queue stand-in: policies only read ``admitted_at``."""

    admitted_at: float


def _queues(spec):
    """{bucket: [ages...]} → {bucket: [requests admitted at those times]}."""
    return {b: [_Req(admitted_at=t) for t in ts] for b, ts in spec.items()}


# ---------------------------------------------------------------------------
# Policy unit behaviour (pure decisions over queues + clock + telemetry).
# ---------------------------------------------------------------------------


def test_full_bucket_policy_flushes_only_full_queues():
    pol = FullBucketPolicy(max_batch=4)
    tele = FlushTelemetry()
    qs = _queues({(8, 4): [0.0, 0.1, 0.2], (16, 4): [0.0] * 4})
    out = pol.select_flushes(qs, now=10.0, telemetry=tele)
    assert out == [FlushDecision(bucket=(16, 4), count=4)]
    # Oversized queue drains in max_batch chunks within one call.
    qs = _queues({(8, 4): [0.0] * 9})
    out = pol.select_flushes(qs, now=0.0, telemetry=tele)
    assert [d.count for d in out] == [4, 4]


def test_deadline_policy_flags_overdue_partial_flushes():
    pol = DeadlinePolicy(max_batch=4, max_wait=1.0)
    tele = FlushTelemetry()
    qs = _queues({(8, 4): [0.0, 0.5], (16, 4): [4.5]})
    out = pol.select_flushes(qs, now=5.0, telemetry=tele)
    # (8, 4) is overdue and flushes its whole queue; (16, 4) aged only 0.5s.
    assert out == [FlushDecision(bucket=(8, 4), count=2, deadline=True)]
    assert pol.select_flushes(qs, now=0.9, telemetry=tele) == []


def test_adaptive_policy_window_tracks_latency_ratio():
    pol = AdaptivePolicy(max_batch=4, min_window=1, max_window=8)
    tele = FlushTelemetry(alpha=1.0)    # alpha=1: window = last sample
    assert pol.admission_window(tele) == 8      # cold: never throttle
    tele.record((8, 4), wall_s=0.100, assemble_s=0.010)
    assert pol.admission_window(tele) == 8      # ceil(10) clamped to max
    tele.record((8, 4), wall_s=0.030, assemble_s=0.010)
    assert pol.admission_window(tele) == 3      # device 3x the host
    tele.record((8, 4), wall_s=0.001, assemble_s=0.010)
    assert pol.admission_window(tele) == 1      # host-bound: no pipelining
    # Queue-inclusive wall is normalized by the in-flight depth at submit:
    # 80ms of wall behind 7 other flushes is 10ms of service, not a signal
    # to deepen the window (the feedback loop the normalization breaks).
    tele.record((8, 4), wall_s=0.080, assemble_s=0.010, depth=8)
    assert pol.admission_window(tele) == 1
    tele.in_flight = 1
    assert not pol.on_admit({}, now=0.0, telemetry=tele)
    tele.in_flight = 0
    assert pol.on_admit({}, now=0.0, telemetry=tele)


def test_static_backpressure_window_is_policy_driven():
    pol = FullBucketPolicy(max_batch=2, max_in_flight=2)
    tele = FlushTelemetry()
    tele.in_flight = 1
    assert pol.on_admit({}, now=0.0, telemetry=tele)
    tele.in_flight = 2
    assert not pol.on_admit({}, now=0.0, telemetry=tele)


def test_coalescing_policy_steals_compatible_starving_buckets():
    pol = CoalescingPolicy(max_batch=6, max_wait=2.0, steal_wait=1.0)
    tele = FlushTelemetry()
    qs = _queues({
        (16, 8): [0.0, 0.1],    # overdue at now=3 → deadline flush, room 4
        (8, 4): [1.5, 1.6],     # age ≥ steal_wait, < max_wait → stolen
        (8, 16): [1.5],         # W too large to fit (16, 8) → never stolen
        (32, 8): [1.5],         # R too large to fit (16, 8) → never stolen
    })
    (d,) = pol.select_flushes(qs, now=3.0, telemetry=tele)
    assert d.bucket == (16, 8) and d.count == 2 and d.deadline
    assert d.steal == (((8, 4), 2),)
    # Below the steal threshold nothing is stolen.
    (d,) = pol.select_flushes(qs, now=2.3, telemetry=tele)
    assert d.steal == ()


def test_full_flush_at_capacity_has_no_steal_room():
    pol = CoalescingPolicy(max_batch=4, steal_wait=0.0)
    qs = _queues({(16, 8): [0.0] * 4, (8, 4): [0.0]})
    (d,) = pol.select_flushes(qs, now=5.0, telemetry=FlushTelemetry())
    assert d.bucket == (16, 8) and d.count == 4 and d.steal == ()


def test_coalescing_steal_capacity_and_starvation_order():
    pol = CoalescingPolicy(max_batch=4, max_wait=10.0, steal_wait=0.0)
    qs = _queues({
        (32, 8): [0.0],             # overdue at now=11 → room for 3
        (8, 4): [9.0, 9.1],         # older queue → stolen first
        (16, 8): [9.5, 9.6],        # younger → only 1 of 2 fits
    })
    (d,) = pol.select_flushes(qs, now=11.0, telemetry=FlushTelemetry())
    assert d.bucket == (32, 8) and d.count == 1 and d.deadline
    assert d.steal == (((8, 4), 2), ((16, 8), 1))


@pytest.mark.parametrize("policy_cls", [CoalescingPolicy,
                                        CostAwareCoalescingPolicy])
def test_coalescing_policies_never_steal_cross_method(policy_cls):
    """Two methods sharing one ``(R, W)`` shape: an overdue ``'pivot'``
    flush may steal only from ``'pivot'`` queues. The ``'precluster'``
    queue is *older* and its shape fits, so a method-blind starvation
    order would promote it first — both built-in coalescing policies must
    skip it (its own deadline still bounds its wait)."""
    pol = policy_cls(max_batch=6, max_wait=2.0, steal_wait=1.0)
    qs = _queues({
        ("pivot", 16, 8): [0.0, 0.1],     # overdue at now=3 → room for 4
        ("pivot", 8, 4): [1.5, 1.6],      # same method → stealable
        ("precluster", 8, 4): [1.3],      # oldest, shape fits: wrong method
        ("precluster", 16, 8): [1.5],     # the flush's own shape, too
    })
    decisions = pol.select_flushes(qs, now=3.0, telemetry=FlushTelemetry())
    (d,) = [d for d in decisions if d.bucket == ("pivot", 16, 8)]
    assert d.deadline and d.count == 2
    assert d.steal == ((("pivot", 8, 4), 2),)
    for other in decisions:
        for src, _ in other.steal:
            assert src[:-2] == other.bucket[:-2], (
                f"{pol.name} proposed a cross-method steal {src} -> "
                f"{other.bucket}")


def test_batcher_refuses_hand_built_cross_method_decision():
    """A custom policy that does propose a cross-method steal is refused
    by ``_execute`` with a clear ValueError, and the popped requests are
    requeued — nothing is lost, and a subsequent clean flush still serves
    both requests bit-exactly under their own methods."""
    g = _rand_graph(12, 1, seed=7)
    eng = ClusterBatcher(max_batch=4)          # full-bucket: never auto-flush
    eng.admit(ClusterRequest(uid=0, graph=g, key=jax.random.PRNGKey(0)))
    eng.admit(ClusterRequest(uid=1, graph=g, key=jax.random.PRNGKey(1),
                             method="precluster"))
    pivot_key = next(b for b in eng.buckets if b[0] == "pivot")
    pre_key = next(b for b in eng.buckets if b[0] == "precluster")
    assert pivot_key[1:] == pre_key[1:]        # same (R, W), distinct queues
    bad = FlushDecision(bucket=pivot_key, count=1,
                        steal=((pre_key, 1),))
    with pytest.raises(ValueError, match="cross-method"):
        eng._execute(bad)
    # Both requests were requeued into their own queues...
    assert len(eng.buckets[pivot_key]) == 1
    assert len(eng.buckets[pre_key]) == 1
    # ...and a clean drain serves each under its own method, bit-exactly.
    done = {r.uid: r for r in eng.flush_all()}
    assert done[0].result.method == "pivot"
    assert done[1].result.method == "precluster"
    _assert_matches(g, jax.random.PRNGKey(0), done[0].result)
    _assert_matches(g, jax.random.PRNGKey(1), done[1].result,
                    method="precluster")
    eng.close()


@pytest.mark.parametrize("executor", ["sync", "async", "sharded"])
def test_mixed_method_trace_cost_policy_bit_exact(executor):
    """The PR 10 acceptance smoke: one engine, both registered methods in
    one trace, cost policy active. Every result must be bit-identical to
    the per-graph engine of its own method, and the flush telemetry must
    show both methods flushing through their own queues."""
    methods = ("pivot", "precluster")
    reqs = [(uid, _rand_graph(6 + 3 * (uid % 5), 1 + uid % 2, seed=uid))
            for uid in range(12)]
    eng = ClusterBatcher(max_batch=4, max_wait=0.005, policy="cost",
                         executor=executor)
    done = {}
    for uid, g in reqs:
        for r in eng.admit(ClusterRequest(uid=uid, graph=g,
                                          key=jax.random.PRNGKey(uid),
                                          method=methods[uid % 2])):
            done[r.uid] = r
    for r in eng.flush_all():
        done[r.uid] = r
    assert len(done) == len(reqs)
    for uid, g in reqs:
        m = methods[uid % 2]
        assert done[uid].result.method == m
        _assert_matches(g, jax.random.PRNGKey(uid), done[uid].result,
                        method=m)
    flushed_methods = {key.split(":", 1)[0]
                       for key in eng.stats.latency.summary()}
    assert set(methods) <= flushed_methods
    eng.close()


def test_make_policy_resolution_and_validation():
    assert make_policy(None, max_batch=4).name == "full"
    assert make_policy(None, max_batch=4, max_wait=0.1).name == "deadline"
    assert make_policy("adaptive", max_batch=4,
                       max_in_flight=3).max_window == 3
    assert make_policy("coalesce", max_batch=4,
                       max_wait=1.0).steal_wait == 0.5
    assert make_policy("cost", max_batch=4, max_wait=1.0).name == "cost"
    pol = CoalescingPolicy(max_batch=2)
    assert pol.steal_wait == 0.0    # direct construction: steal when room
    assert make_policy(pol, max_batch=99) is pol
    # ... but the name route requires a deadline, or the policy would
    # silently degenerate to full-bucket (full flushes have no steal room).
    with pytest.raises(ValueError, match="coalesce.*max_wait|max_wait"):
        make_policy("coalesce", max_batch=4)
    with pytest.raises(ValueError, match="max_wait"):
        make_policy("cost", max_batch=4)
    for impl in (FullBucketPolicy(2), DeadlinePolicy(2, 0.1),
                 AdaptivePolicy(2), CoalescingPolicy(2),
                 CostAwareCoalescingPolicy(2)):
        assert isinstance(impl, SchedulerPolicy)
    with pytest.raises(ValueError, match="max_wait"):
        make_policy("deadline", max_batch=4)
    with pytest.raises(ValueError, match="unknown scheduling policy"):
        make_policy("turbo", max_batch=4)
    with pytest.raises(TypeError, match="policy"):
        make_policy(42, max_batch=4)
    with pytest.raises(ValueError, match="min_window"):
        AdaptivePolicy(4, min_window=0)
    with pytest.raises(ValueError, match="steal_wait"):
        CoalescingPolicy(4, steal_wait=-1.0)


def test_make_policy_rejects_knobs_conflicting_with_instance():
    """A policy instance carries its own max_wait/max_in_flight; silently
    ignoring the engine-level knobs (the old behaviour) hid real
    misconfigurations — ClusterBatcher(policy=AdaptivePolicy(...),
    max_wait=0.05) got no deadline and no error."""
    pol = AdaptivePolicy(4, max_wait=0.2)
    with pytest.raises(ValueError, match="max_wait"):
        make_policy(pol, max_batch=4, max_wait=0.05)
    with pytest.raises(ValueError, match="max_in_flight"):
        make_policy(DeadlinePolicy(4, 0.1), max_batch=4, max_in_flight=2)
    with pytest.raises(ValueError, match="max_wait and max_in_flight"):
        make_policy(pol, max_batch=4, max_wait=0.05, max_in_flight=2)
    # Clean pass-through: knobs on the instance itself are fine.
    assert make_policy(pol, max_batch=4) is pol
    # The batcher-level surface: conflict raises, instance-only works and
    # the instance's own deadline actually drives the engine.
    with pytest.raises(ValueError, match="max_wait"):
        ClusterBatcher(max_batch=4, policy=AdaptivePolicy(4, max_wait=0.2),
                       max_wait=0.05)
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=4, clock=clock,
                             policy=DeadlinePolicy(4, max_wait=0.1))
    batcher.admit(ClusterRequest(uid=0, graph=build_graph(6, path(6)),
                                 key=jax.random.PRNGKey(0)))
    clock.advance(0.2)
    assert {r.uid for r in batcher.poll()} == {0}   # the deadline fired


# ---------------------------------------------------------------------------
# promote_plan: validation + bit-exact coalesced flushes (tentpole contract).
# ---------------------------------------------------------------------------


def test_promote_plan_validates_and_is_identity_at_native_shape():
    plan = plan_graph(build_graph(6, path(6)))          # (8, 4)
    assert promote_plan(plan, 8, 4) is plan
    bigger = promote_plan(plan, 32, 8)
    assert bigger.bucket == (32, 8)
    assert bigger.n == plan.n and bigger.wreq == plan.wreq
    assert plan.bucket == (8, 4)                        # original untouched
    with pytest.raises(ValueError, match="promote"):
        promote_plan(plan, 4, 4)
    with pytest.raises(ValueError, match="promote"):
        promote_plan(bigger, 32, 4)
    with pytest.raises(ValueError, match="largest supported"):
        promote_plan(plan, 1 << 20, 4)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("policy", ["coalesce", "cost"])
@pytest.mark.parametrize("executor", ["sync", "async", "sharded"])
def test_coalesced_flush_promotes_and_stays_bit_exact(executor, policy,
                                                      use_kernel):
    """Hot bucket goes overdue below capacity; the younger starving cold
    request is stolen into its deadline flush at a promoted (R, W) shape,
    and every result matches the per-graph engine bit-exactly. The cost
    policy takes the same steal here (cold telemetry → it degrades to
    age-only coalescing), so both stealing policies run the promoted
    path under every executor and kernel."""
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=8, policy=policy, max_wait=0.1,
                             clock=clock, executor=executor,
                             use_kernel=use_kernel, num_samples=2)
    hot = [build_graph(n, path(n)) for n in (17, 20, 24)]   # bucket (32, 4)
    for i, g in enumerate(hot):
        batcher.admit(ClusterRequest(uid=i, graph=g,
                                     key=jax.random.PRNGKey(i)))
        clock.advance(0.01)
    cold = build_graph(6, path(6))                          # bucket (8, 4)
    batcher.admit(ClusterRequest(uid=9, graph=cold,
                                 key=jax.random.PRNGKey(9)))
    # Hot oldest is now 0.03s old, cold 0.0s. Advance so the hot bucket is
    # overdue (0.11 ≥ max_wait) while cold (0.08) is past steal_wait (0.05)
    # but under its own deadline — the exact starvation-steal window.
    clock.advance(0.08)
    retired = batcher.poll()
    retired += batcher.flush()
    done = {r.uid: r for r in retired}
    assert sorted(done) == [0, 1, 2, 9]
    assert batcher.stats.flushes == 1       # one coalesced flush served all
    assert batcher.stats.coalesced_flushes == 1
    assert batcher.stats.stolen_requests == 1
    for uid, g in [(0, hot[0]), (1, hot[1]), (2, hot[2]), (9, cold)]:
        _assert_matches(g, jax.random.PRNGKey(uid), done[uid].result,
                        num_samples=2)
    # Promotion is transparent to the caller: the result still reports the
    # request's native bucket.
    assert done[9].result.info["bucket"] == (8, 4)


def test_coalescing_full_flush_steals_when_room_remains():
    """A full-bucket flush below max_batch capacity... cannot exist — but a
    repeating hot stream with spare room shows steady-state stealing: the
    cold request rides the first hot deadline flush, never the drain."""
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=4, policy="coalesce", max_wait=0.05,
                             clock=clock)
    cold = build_graph(5, path(5))
    hot = [build_graph(n, path(n)) for n in (17, 18, 19)]
    # Cold arrives first and would starve behind the hot stream under the
    # full-bucket policy (its bucket never fills).
    batcher.admit(ClusterRequest(uid=100, graph=cold,
                                 key=jax.random.PRNGKey(100)))
    clock.advance(0.04)     # cold nearly overdue
    for i, g in enumerate(hot):
        batcher.admit(ClusterRequest(uid=i, graph=g,
                                     key=jax.random.PRNGKey(i)))
    clock.advance(0.06)     # everyone overdue → cold's own deadline fires
    retired = batcher.poll()
    done = {r.uid: r for r in retired}
    # Cold is overdue itself, so it flushes regardless of stealing — the
    # guarantee that coalescing never *worsens* the deadline contract.
    assert 100 in done
    assert batcher.pending() == 0
    _assert_matches(cold, jax.random.PRNGKey(100), done[100].result)
    for i, g in enumerate(hot):
        _assert_matches(g, jax.random.PRNGKey(i), done[i].result)


# ---------------------------------------------------------------------------
# Cost model: pricing arithmetic, abstention, cost-aware steal decisions.
# ---------------------------------------------------------------------------


def _warm_telemetry(bucket=(32, 4), wall_s=0.08, assemble_s=0.001):
    tele = FlushTelemetry(alpha=1.0)    # alpha=1: EWMA = last sample
    tele.record(bucket, wall_s=wall_s, assemble_s=assemble_s)
    return tele


def test_cost_model_abstains_cold_and_prices_warm():
    model = FlushCostModel()
    cold = FlushTelemetry()
    # Cold telemetry, no floor: the model abstains — callers degrade to
    # plain age-only coalescing.
    cost = model.price_steal((32, 4), 8, [((8, 4), 0.01)], 0.1, cold)
    assert not cost.priced and cost.accepts()
    # With a floor the same cold engine *can* price (a pessimistic prior).
    floored = FlushCostModel(service_floor_s=0.05)
    cost = floored.price_steal((32, 4), 8, [((8, 4), 0.01)], 0.1, cold)
    assert cost.priced
    # Warm pricing at a pow2 boundary: count 8 + 1 steal doubles g_pad, so
    # the marginal pad entries are (16 − 8) − 1 = 7, priced at the per-entry
    # service time 80ms/8 — far above the 10ms of slack the steal saves.
    tele = _warm_telemetry(wall_s=0.08)
    cost = model.price_steal((32, 4), 8, [((8, 4), 0.09)], 0.1, tele)
    assert cost.pad_entries_added == 7
    assert cost.vertex_waste_added == 32 - 8
    assert cost.benefit_s == pytest.approx(0.1 - 0.09)
    assert cost.pad_cost_s > 0.06       # ≥ 7 · 10ms of pad alone
    assert not cost.accepts()
    # Riding existing padding is (nearly) free: count 5 + 3 steals stays at
    # g_pad 8 — no pad entries added, only the promoted-row fraction.
    cost = model.price_steal((32, 4), 5, [((8, 4), 0.02)] * 3, 0.1, tele)
    assert cost.pad_entries_added == -3
    assert cost.pad_cost_s == pytest.approx(
        3 * (32 - 8) / 32 * 0.08 / 8)
    assert cost.accepts()               # 3 × 80ms slack ≫ 22.5ms


def test_cost_model_hurdle_and_validation():
    tele = _warm_telemetry(wall_s=0.08)
    # benefit 60ms vs cost ≈ 22.5ms: accepted at hurdle 1, rejected at 10.
    free = [((8, 4), 0.04)] * 3
    assert FlushCostModel().price_steal((32, 4), 5, free, 0.1,
                                        tele).accepts(1.0)
    assert not FlushCostModel().price_steal((32, 4), 5, free, 0.1,
                                            tele).accepts(10.0)
    with pytest.raises(ValueError, match="hurdle"):
        FlushCostModel(hurdle=0.0)
    with pytest.raises(ValueError, match=">= 0"):
        FlushCostModel(compile_cost_s=-1.0)
    with pytest.raises(ValueError):
        ShapeHeat(window=0)
    with pytest.raises(ValueError):
        ShapeHeat(min_heat=0)


def test_cost_model_compile_charge_uses_cache_probe():
    from repro.core.executor import run_bucket_program

    import numpy as _np

    model = FlushCostModel(compile_cost_s=0.5, service_floor_s=0.01)
    model.bind_engine(num_samples=1, use_kernel=False, donate=False)
    tele = _warm_telemetry(bucket=(8, 4), wall_s=0.001)
    # Shape (2, 8, 4) not compiled with this exact signature → charged.
    probe = model.price_steal((8, 4), 1, [((8, 4), 0.05)], 0.1, tele)
    if probe.compile_cost_s == 0.0:
        # Another test may have compiled it; force a fresh shape instead.
        pytest.skip("shape already resident — probe covered elsewhere")
    assert probe.compile_cost_s == 0.5
    # Compile it for real; the charge disappears.
    ell = _np.full((2, 8, 4), 8, dtype=_np.int32)
    ranks = _np.full((2, 9), _np.iinfo(_np.int32).max, dtype=_np.int32)
    elig = _np.zeros((2, 9), dtype=bool)
    m = _np.zeros((2,), dtype=_np.int32)
    run_bucket_program(ell, ranks, elig, m, k=1)
    after = model.price_steal((8, 4), 1, [((8, 4), 0.05)], 0.1, tele)
    assert after.compile_cost_s == 0.0


def test_cost_aware_policy_rejects_boundary_steal_and_trims_to_free_room():
    """Unit decisions: at a pow2 boundary the steal is dropped entirely;
    below it the free prefix is kept and the inflating tail rejected."""
    tele = _warm_telemetry(wall_s=0.08)
    # Boundary: 8 native hot requests overdue, one starving cold — the
    # age-only parent steals it, the cost policy refuses (7 pad entries
    # at ~10ms each vs 10ms slack).
    pol = CostAwareCoalescingPolicy(16, max_wait=0.1, steal_wait=0.01)
    qs = _queues({(32, 4): [0.0] * 8, (8, 4): [0.02]})
    (d,) = pol.select_flushes(qs, now=0.11, telemetry=tele)
    assert d.bucket == (32, 4) and d.count == 8 and d.steal == ()
    assert pol.steals_rejected == 1 and pol.steals_accepted == 0
    assert pol.pad_entries_avoided == 7
    # Same queues, cold telemetry: degrades to the parent's age-only steal.
    pol2 = CostAwareCoalescingPolicy(16, max_wait=0.1, steal_wait=0.01)
    (d2,) = pol2.select_flushes(qs, now=0.11, telemetry=FlushTelemetry())
    assert d2.steal == (((8, 4), 1),)
    assert pol2.steals_accepted == 1 and pol2.steals_rejected == 0
    # Trim: 6 native (g_pad 8 → 2 free slots) + 4 starving cold. Taking
    # all 4 inflates to g_pad 16; the free 2 ride existing padding.
    pol3 = CostAwareCoalescingPolicy(16, max_wait=0.1, steal_wait=0.01)
    qs3 = _queues({(32, 4): [0.0] * 6, (8, 4): [0.02, 0.02, 0.03, 0.03]})
    (d3,) = pol3.select_flushes(qs3, now=0.11, telemetry=tele)
    assert d3.count == 6 and d3.steal == (((8, 4), 2),)
    assert pol3.steals_accepted == 2 and pol3.steals_rejected == 2


def test_trimmed_steal_reanchors_later_decisions_at_queue_front():
    """Cross-decision pricing: when an earlier decision's steal is
    rejected, a later decision stealing from the same queue must be
    priced against the queue *front* entries execution will actually pop
    (the oldest, with the least deadline slack) — not the younger offsets
    the parent planned assuming the first steal happened. Here the
    re-anchored benefit (0.05s of slack) falls below the promoted-row
    cost (~0.066s at the 0.3s service floor) while the stale offsets'
    benefit (0.09s) would have cleared it — so the steal must be
    rejected."""
    pol = CostAwareCoalescingPolicy(
        10, max_wait=0.1, steal_wait=0.01,
        cost_model=FlushCostModel(service_floor_s=0.3))
    qs = _queues({
        (32, 4): [0.0] * 8,             # boundary: stealing into it inflates
        (64, 4): [0.005] * 6,           # g_pad 8: two free steal slots
        (8, 4): [0.03, 0.04, 0.05, 0.06],
    })
    d_a, d_b = pol.select_flushes(qs, now=0.11, telemetry=FlushTelemetry())
    # First decision's steal rejected on the pow2 inflation...
    assert d_a.bucket == (32, 4) and d_a.steal == ()
    # ...and the second decision's steal — re-anchored at the queue front
    # — is priced too expensive as well (stale offsets would accept it).
    assert d_b.bucket == (64, 4)
    assert d_b.steal == ()
    assert pol.steals_rejected == 4 and pol.steals_accepted == 0


def test_shape_heat_release_does_not_strip_other_trackers():
    """Pins are refcounted process-globally: one engine's teardown must
    not strip a shape another live engine still pins."""
    from repro.core.executor import program_cache_info

    heat_a = ShapeHeat(window=8, max_pinned=1, min_heat=1)
    heat_b = ShapeHeat(window=8, max_pinned=1, min_heat=1)
    heat_a.on_retire((8, 4))
    heat_b.on_retire((8, 4))
    try:
        assert (8, 4) in program_cache_info()["pinned"]
        heat_a.release()
        # B's pin survives A's teardown.
        assert (8, 4) in program_cache_info()["pinned"]
    finally:
        heat_b.release()
        heat_a.release()
    assert (8, 4) not in program_cache_info()["pinned"]


def test_shape_heat_pins_hot_bucket_and_releases_cold():
    pins, unpins, touches = [], [], []
    heat = ShapeHeat(window=8, max_pinned=1, min_heat=3,
                     pin=pins.append, unpin=unpins.append,
                     touch=touches.append)
    hot, cold = (8, 4), (32, 4)
    for _ in range(3):
        heat.on_retire(hot)
    assert pins == [hot] and heat.pinned == {hot}
    assert touches == [hot] * 3
    # A different shape taking over the window displaces the pin.
    for _ in range(8):
        heat.on_retire(cold)
    assert hot in unpins and heat.pinned == {cold}
    heat.release()
    assert heat.pinned == set() and cold in unpins


def test_cost_policy_pins_hot_shape_through_batcher_retires():
    """End-to-end heat: serving a hot shape through the cost policy pins
    it in the real program cache; teardown unpins."""
    from repro.core.executor import program_cache_info, program_cache_unpin

    batcher = ClusterBatcher(max_batch=1, policy="cost", max_wait=0.05)
    g = build_graph(6, path(6))
    try:
        for i in range(4):
            batcher.admit(ClusterRequest(uid=i, graph=g,
                                         key=jax.random.PRNGKey(i)))
            batcher.flush()
        assert (8, 4) in batcher.policy.heat.pinned
        assert (8, 4) in program_cache_info()["pinned"]
    finally:
        batcher.close()         # engine teardown releases the global pins
    assert (8, 4) not in program_cache_info()["pinned"]
    batcher.close()             # idempotent


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("executor", ["sync", "async", "sharded"])
def test_cost_rejected_steal_stays_bit_exact(executor, use_kernel):
    """The acceptance-criteria path: a steal *rejected* on cost. The cold
    request must still retire (its own deadline) and every result must
    match the per-graph engine bit-exactly — pricing can only ever decide
    whether a steal happens, never what a flush computes."""
    clock = VirtualClock()
    model = FlushCostModel(service_floor_s=10.0)    # poison: reject all
    pol = CostAwareCoalescingPolicy(8, max_wait=0.1, steal_wait=0.05,
                                    cost_model=model)
    batcher = ClusterBatcher(max_batch=8, policy=pol, clock=clock,
                             executor=executor, use_kernel=use_kernel,
                             num_samples=2)
    hot = [build_graph(n, path(n)) for n in (17, 20, 24)]   # bucket (32, 4)
    for i, g in enumerate(hot):
        batcher.admit(ClusterRequest(uid=i, graph=g,
                                     key=jax.random.PRNGKey(i)))
        clock.advance(0.01)
    cold = build_graph(6, path(6))                          # bucket (8, 4)
    batcher.admit(ClusterRequest(uid=9, graph=cold,
                                 key=jax.random.PRNGKey(9)))
    clock.advance(0.08)
    retired = batcher.poll()        # hot deadline flush; steal refused
    assert pol.steals_rejected >= 1
    assert batcher.stats.stolen_requests == 0
    assert 9 not in {r.uid for r in retired}
    clock.advance(0.05)             # cold crosses its own deadline
    retired += batcher.poll()
    retired += batcher.flush()
    done = {r.uid: r for r in retired}
    assert sorted(done) == [0, 1, 2, 9]
    assert batcher.stats.coalesced_flushes == 0
    for uid, g in [(0, hot[0]), (1, hot[1]), (2, hot[2]), (9, cold)]:
        _assert_matches(g, jax.random.PRNGKey(uid), done[uid].result,
                        num_samples=2)


# ---------------------------------------------------------------------------
# Steal-induced pad accounting (satellite): serving stats must equal the
# promoted pack's own numbers — the quantity the cost model prices.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("executor", ["sync", "async"])
def test_steal_pad_accounting_matches_promoted_pack(executor):
    clock = VirtualClock()
    k = 2
    batcher = ClusterBatcher(max_batch=8, policy="coalesce", max_wait=0.1,
                             clock=clock, executor=executor, num_samples=k)
    hot = [build_graph(n, path(n)) for n in (17, 20, 24)]   # bucket (32, 4)
    cold = [build_graph(5, path(5)), build_graph(6, path(6))]  # (8, 4)
    for i, g in enumerate(hot):
        batcher.admit(ClusterRequest(uid=i, graph=g,
                                     key=jax.random.PRNGKey(i)))
        clock.advance(0.01)
    for j, g in enumerate(cold):
        batcher.admit(ClusterRequest(uid=10 + j, graph=g,
                                     key=jax.random.PRNGKey(10 + j)))
    clock.advance(0.08)
    batcher.poll()                  # one coalesced flush: 3 hot + 2 stolen
    assert batcher.stats.flushes == 1
    assert batcher.stats.stolen_requests == 2
    # Independent ground truth: the promoted pack priced by the pure
    # PackStats formula — 5 graphs at (32, 4), g_pad = 8.
    expected = estimate_pack_stats(
        [promote_plan(plan_graph(g), 32, 4) for g in hot + cold], k=k)
    assert expected.padded_entries == (8 - 5) * k
    assert expected.pad_vertex_waste == sum(
        32 - g.n for g in hot + cold)
    assert batcher.stats.padded_slots == expected.padded_entries
    assert batcher.stats.pad_vertex_waste == expected.pad_vertex_waste
    retired = batcher.flush()
    for r in retired:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result,
                        num_samples=k)


# ---------------------------------------------------------------------------
# Harvest-error deferral (satellite): one failed earlier flush must not
# drop the rest of a tick's decisions.
# ---------------------------------------------------------------------------


class _ExplodingOutput:
    """Device-output stand-in: reports ready, then fails the fetch."""

    def is_ready(self):
        return True

    def __array__(self, *args, **kwargs):
        raise RuntimeError("device fetch exploded")


class _MidTickFailureExecutor(AsyncExecutor):
    """Poisons one flush's outputs so its fetch fails, and withholds the
    handle from ``retire()`` until armed + one extra call — landing the
    failure exactly in ``_execute``'s trailing harvest, mid-tick, between
    two policy decisions."""

    def __init__(self):
        super().__init__()
        self.poison_next = False
        self.released = False
        self._skip = 0
        self._held = None

    def _post_submit(self, handle):
        if self.poison_next:
            handle._outputs = (_ExplodingOutput(),) * 4
            self._held = handle
            self.poison_next = False

    def arm(self):
        """Deliver the poisoned handle on the *second* retire() from now
        (skipping a tick's initial harvest)."""
        self.released = True
        self._skip = 1

    def retire(self):
        out = super().retire()
        if self._held is not None and self._held in out:
            if not self.released or self._skip > 0:
                if self.released:
                    self._skip -= 1
                out.remove(self._held)
                self._pending.append(self._held)
        return out


def test_harvest_error_does_not_drop_remaining_decisions():
    """Regression: a harvest error from a previous flush surfaced between
    two FlushDecisions used to abort the tick — the second (due!) deadline
    flush was silently skipped past its budget. Now every decision
    executes, the error is re-raised afterwards, and the failed flush's
    requests are requeued and succeed on retry."""
    ex = _MidTickFailureExecutor()
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=2, max_wait=0.05, clock=clock,
                             executor=ex)
    g_a = build_graph(6, path(6))           # bucket (8, 4)
    g_b = build_graph(20, path(20))         # bucket (32, 4)
    ex.poison_next = True                   # the first flush will fail
    batcher.admit(ClusterRequest(uid=0, graph=g_a,
                                 key=jax.random.PRNGKey(0)))
    batcher.admit(ClusterRequest(uid=1, graph=g_a,
                                 key=jax.random.PRNGKey(1)))   # full → flush
    assert batcher.stats.flushes == 1
    # Two more buckets go due together.
    batcher.admit(ClusterRequest(uid=2, graph=g_a,
                                 key=jax.random.PRNGKey(2)))
    batcher.admit(ClusterRequest(uid=3, graph=g_b,
                                 key=jax.random.PRNGKey(3)))
    clock.advance(0.1)
    ex.arm()
    with pytest.raises(RuntimeError, match="exploded"):
        batcher.poll()
    # BOTH due deadline flushes were dispatched before the error surfaced
    # (the old behaviour stopped at 2: the first deadline flush's trailing
    # harvest raised and dropped the second decision).
    assert batcher.stats.flushes == 3
    # The failed flush's requests are back in their native bucket, oldest
    # first; nothing was lost.
    assert [r.uid for r in batcher.buckets.get(("pivot", 8, 4), [])] == [0, 1]
    retired = batcher.flush()               # failing-then-succeeding retry
    done = {r.uid: r for r in retired}
    assert sorted(done) == [0, 1, 2, 3]
    for uid, g in [(0, g_a), (1, g_a), (2, g_a), (3, g_b)]:
        _assert_matches(g, jax.random.PRNGKey(uid), done[uid].result)


class _FailOnceSubmitExecutor(AsyncExecutor):
    """Raises on the first submit of one bucket shape (a dispatch-time
    failure, e.g. device OOM), then behaves normally."""

    def __init__(self, fail_bucket):
        super().__init__()
        self.fail_bucket = fail_bucket
        self.failed = False

    def submit(self, ell, *args, **kwargs):
        shape = np.shape(ell)
        if (shape[1], shape[2]) == self.fail_bucket and not self.failed:
            self.failed = True
            raise RuntimeError("submit boom")
        return super().submit(ell, *args, **kwargs)


def test_flush_drains_remaining_buckets_past_dispatch_error():
    """flush()'s deferral covers dispatch failures too: one bucket's
    pack/submit blowing up must not strand the other queued buckets
    undispatched or skip the blocking harvest."""
    ex = _FailOnceSubmitExecutor(fail_bucket=(8, 4))
    batcher = ClusterBatcher(max_batch=4, executor=ex)
    g_a, g_b = build_graph(6, path(6)), build_graph(20, path(20))
    batcher.admit(ClusterRequest(uid=0, graph=g_a,
                                 key=jax.random.PRNGKey(0)))
    batcher.admit(ClusterRequest(uid=1, graph=g_b,
                                 key=jax.random.PRNGKey(1)))
    with pytest.raises(RuntimeError, match="submit boom"):
        batcher.flush()
    assert batcher.stats.flushes == 1               # (32,4) still drained
    assert [r.uid for r in batcher.buckets.get(("pivot", 8, 4), [])] == [0]
    done = {r.uid: r for r in batcher.flush()}      # retry succeeds
    assert sorted(done) == [0, 1]
    for uid, g in [(0, g_a), (1, g_b)]:
        _assert_matches(g, jax.random.PRNGKey(uid), done[uid].result)


def test_poll_dispatch_error_does_not_drop_remaining_decisions():
    """The policy tick contains dispatch failures like flush() does: one
    decision's pack/submit blowing up must not skip the tick's other due
    deadline flushes past their budget."""
    ex = _FailOnceSubmitExecutor(fail_bucket=(8, 4))
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=4, max_wait=0.05, clock=clock,
                             executor=ex)
    g_a, g_b = build_graph(6, path(6)), build_graph(20, path(20))
    batcher.admit(ClusterRequest(uid=0, graph=g_a,
                                 key=jax.random.PRNGKey(0)))
    batcher.admit(ClusterRequest(uid=1, graph=g_b,
                                 key=jax.random.PRNGKey(1)))
    clock.advance(0.1)                      # both buckets due
    with pytest.raises(RuntimeError, match="submit boom"):
        batcher.poll()
    assert batcher.stats.flushes == 1       # the second decision ran
    assert [r.uid for r in batcher.buckets.get(("pivot", 8, 4), [])] == [0]
    done = {r.uid: r for r in batcher.flush()}
    assert sorted(done) == [0, 1]
    for uid, g in [(0, g_a), (1, g_b)]:
        _assert_matches(g, jax.random.PRNGKey(uid), done[uid].result)


def test_poll_leading_harvest_error_does_not_drop_decisions():
    """The tick's *leading* harvest joins the deferral discipline too: an
    error surfacing there (failed flush already ready when poll starts)
    must not stop the due deadline flushes from dispatching."""
    ex = _MidTickFailureExecutor()
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=2, max_wait=0.05, clock=clock,
                             executor=ex)
    g_a, g_b = build_graph(6, path(6)), build_graph(20, path(20))
    ex.poison_next = True
    batcher.admit(ClusterRequest(uid=0, graph=g_a,
                                 key=jax.random.PRNGKey(0)))
    batcher.admit(ClusterRequest(uid=1, graph=g_a,
                                 key=jax.random.PRNGKey(1)))   # poisoned
    batcher.admit(ClusterRequest(uid=2, graph=g_b,
                                 key=jax.random.PRNGKey(2)))
    clock.advance(0.1)                      # uid2 due
    ex.released = True                      # poison lands at poll's start
    with pytest.raises(RuntimeError, match="exploded"):
        batcher.poll()
    # The tick still dispatched everything due: uid2's deadline flush AND
    # the requeued uid0/uid1 (their bucket refilled by the requeue, so it
    # re-flushed in the same tick) — 1 poisoned + 2 live flushes.
    assert batcher.stats.flushes == 3
    done = {r.uid: r for r in batcher.flush()}
    assert sorted(done) == [0, 1, 2]


def test_flush_drains_remaining_buckets_past_harvest_error():
    """Same deferral discipline at end-of-stream: flush() must dispatch
    every queued bucket even when an earlier flush's harvest fails
    mid-drain (the old behaviour stranded the later buckets undispatched)."""
    ex = _MidTickFailureExecutor()
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=2, clock=clock, executor=ex)
    g_a = build_graph(6, path(6))           # bucket (8, 4)
    g_b = build_graph(20, path(20))         # bucket (32, 4)
    g_c = build_graph(40, path(40))         # bucket (64, 4)
    ex.poison_next = True
    batcher.admit(ClusterRequest(uid=0, graph=g_a,
                                 key=jax.random.PRNGKey(0)))
    batcher.admit(ClusterRequest(uid=1, graph=g_a,
                                 key=jax.random.PRNGKey(1)))   # poisoned
    batcher.admit(ClusterRequest(uid=2, graph=g_b,
                                 key=jax.random.PRNGKey(2)))
    batcher.admit(ClusterRequest(uid=3, graph=g_c,
                                 key=jax.random.PRNGKey(3)))
    ex.released = True                      # deliver on the next retire
    with pytest.raises(RuntimeError, match="exploded"):
        batcher.flush()
    # The poison surfaced inside the first bucket's trailing harvest, yet
    # the second queued bucket was still dispatched: 1 poisoned + 2 drains.
    assert batcher.stats.flushes == 3
    done = {r.uid: r for r in batcher.flush()}
    assert sorted(done) == [0, 1, 2, 3]
    for uid, g in [(0, g_a), (1, g_a), (2, g_b), (3, g_c)]:
        _assert_matches(g, jax.random.PRNGKey(uid), done[uid].result)


# ---------------------------------------------------------------------------
# Telemetry plumbing: executor → ClusterStats → adaptive window.
# ---------------------------------------------------------------------------


def test_flush_latency_telemetry_reaches_stats():
    batcher = ClusterBatcher(max_batch=2)
    g = build_graph(6, path(6))
    for i in range(4):
        batcher.admit(ClusterRequest(uid=i, graph=g,
                                     key=jax.random.PRNGKey(i)))
    batcher.flush()
    tele = batcher.stats.latency
    assert tele.total_flushes == batcher.stats.flushes == 2
    assert tele.ewma_wall is not None and tele.ewma_wall >= 0.0
    assert tele.ewma_assemble is not None and tele.ewma_assemble >= 0.0
    # Default engine prebuilds rows at admission: one build per request,
    # in its own telemetry stream, off every flush's wall.
    assert tele.total_builds == 4
    assert tele.ewma_build is not None and tele.ewma_build >= 0.0
    summary = tele.summary()
    assert list(summary) == ["pivot:8x4"]     # keys are method-qualified
    rec = summary["pivot:8x4"]
    assert rec["flushes_total"] == 2
    assert rec["window_samples"] == 2
    for field in ("wall_p50_ms", "wall_p99_ms", "assemble_p50_ms",
                  "assemble_p99_ms", "wall_ewma_ms", "build_p50_ms",
                  "build_p99_ms"):
        assert rec[field] >= 0.0
    assert rec["builds_total"] == 4
    assert batcher.stats.policy == "full"


def test_telemetry_summary_separates_lifetime_from_window_counts():
    """Past the retention window, lifetime flush counts and the sample
    count percentiles are computed over must diverge — and the summary
    must say so explicitly (the old single 'flushes' field silently mixed
    a lifetime count with windowed percentiles)."""
    tele = FlushTelemetry(window=4)
    for i in range(10):
        tele.record((8, 4), wall_s=0.001 * (i + 1), assemble_s=0.0005)
    rec = tele.summary()["8x4"]
    assert rec["flushes_total"] == 10
    assert rec["window_samples"] == 4
    # Percentiles really are windowed: all retained walls are the last 4.
    assert rec["wall_p50_ms"] >= 0.001 * 7 * 1e3 - 1e-9


def test_adaptive_policy_serves_and_windows_from_real_telemetry():
    batcher = ClusterBatcher(max_batch=2, policy="adaptive",
                             executor="async")
    assert batcher.stats.policy == "adaptive"
    reqs = [ClusterRequest(uid=i, graph=_rand_graph(6 + (i % 3), 1, seed=i),
                           key=jax.random.PRNGKey(i)) for i in range(8)]
    retired = serve_all(batcher, reqs)
    assert sorted(r.uid for r in retired) == list(range(8))
    for r in retired:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result)
    # Telemetry accumulated, and the window is now latency-derived.
    assert batcher.stats.latency.total_flushes >= 1
    window = batcher.policy.admission_window(batcher.stats.latency)
    assert 1 <= window <= batcher.policy.max_window


class _ReleasingExecutor(AsyncExecutor):
    """Stalls harvests for a fixed number of retire() calls, then releases
    — deterministic backpressure that eventually clears."""

    def __init__(self, stall_retires=2):
        super().__init__()
        self.stall_retires = stall_retires

    def retire(self):
        if self.stall_retires > 0:
            self.stall_retires -= 1
            return []
        return super().retire()


def test_serve_all_retries_rejected_admissions():
    """The reference driver must survive AdmissionRejected (harvest +
    retry) so backpressure/adaptive policies can be driven by it."""
    ex = _ReleasingExecutor(stall_retires=8)
    batcher = ClusterBatcher(max_batch=1, executor=ex, max_in_flight=1)
    g = build_graph(6, path(6))
    reqs = [ClusterRequest(uid=i, graph=g, key=jax.random.PRNGKey(i))
            for i in range(4)]
    retired = serve_all(batcher, reqs)
    assert sorted(r.uid for r in retired) == list(range(4))
    assert batcher.stats.rejected >= 1      # backpressure actually fired
    for r in retired:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result)


# ---------------------------------------------------------------------------
# Determinism: scheduling decisions only ever see the injected clock.
# ---------------------------------------------------------------------------


def test_no_wall_clock_on_any_scheduling_path(monkeypatch):
    """With a virtual clock injected, admit/poll/oldest_wait/flush must
    never fall back to time.monotonic — freeze it to a poisoned callable
    and drive a full deadline + coalescing cycle."""
    import sys
    import time as _time

    real_monotonic = _time.monotonic

    def _guarded():
        # JAX internals legitimately use time.monotonic; only calls from
        # this repo's serving layer are a clock-injection violation.
        caller = sys._getframe(1).f_globals.get("__name__", "")
        if caller.startswith("repro.serve"):
            raise AssertionError(
                "bare time.monotonic() on a scheduling path")
        return real_monotonic()

    monkeypatch.setattr(_time, "monotonic", _guarded)
    clock = VirtualClock()
    batcher = ClusterBatcher(max_batch=4, max_wait=0.5, policy="coalesce",
                             clock=clock)
    g_small, g_big = build_graph(6, path(6)), build_graph(20, path(20))
    batcher.admit(ClusterRequest(uid=0, graph=g_small,
                                 key=jax.random.PRNGKey(0)))
    clock.advance(0.3)
    batcher.admit(ClusterRequest(uid=1, graph=g_big,
                                 key=jax.random.PRNGKey(1)))
    assert batcher.oldest_wait() == pytest.approx(0.3)
    clock.advance(0.3)
    retired = batcher.poll()        # uid0 overdue → deadline flush
    assert 0 in {r.uid for r in retired}
    retired += batcher.flush()
    assert sorted(r.uid for r in retired) == [0, 1]
    # Default clock resolves to the real monotonic clock when not injected.
    monkeypatch.undo()
    assert ClusterBatcher(max_batch=2).clock is _time.monotonic


# ---------------------------------------------------------------------------
# Randomized arrival traces: lease invariant + bit-exactness per policy
# (hypothesis-style satellite; runs under the conftest stub too).
# ---------------------------------------------------------------------------


class _LeaseAuditPool(BucketBufferPool):
    """Asserts the lease invariant: acquire never hands out staging arrays
    whose lease is still outstanding."""

    def __init__(self):
        super().__init__()
        self.outstanding = set()

    def acquire(self, b, r, w):
        lease = super().acquire(b, r, w)
        ident = id(lease.arrays["ell"])
        assert ident not in self.outstanding, \
            "BucketBufferPool refilled a staging buffer still in flight"
        self.outstanding.add(ident)
        return lease

    def _release(self, lease):
        self.outstanding.discard(id(lease.arrays["ell"]))
        super()._release(lease)


@settings(max_examples=12, deadline=None)
@given(policy=st.sampled_from(["full", "deadline", "adaptive", "coalesce",
                               "cost"]),
       seed=st.integers(min_value=0, max_value=10_000),
       gap_ms=st.floats(min_value=0.0, max_value=30.0),
       wait_ms=st.floats(min_value=1.0, max_value=60.0))
def test_random_traces_bit_exact_and_lease_safe(policy, seed, gap_ms,
                                                wait_ms):
    """Drive each policy over a random (n, arrival-gap, deadline) stream on
    a virtual clock: every result must match the per-graph engine and the
    pool must never refill an in-flight lease."""
    rng = np.random.default_rng(seed)
    clock = VirtualClock()
    pool = _LeaseAuditPool()
    max_wait = wait_ms / 1e3 if policy != "full" else None
    batcher = ClusterBatcher(max_batch=4, policy=policy, max_wait=max_wait,
                             clock=clock, pool=pool, executor="async")
    n_reqs = int(rng.integers(6, 12))
    reqs = []
    retired = []
    for uid in range(n_reqs):
        clock.advance(gap_ms / 1e3 * float(rng.random()))
        n = int(rng.integers(5, 15))
        req = ClusterRequest(uid=uid,
                             graph=_rand_graph(n, 1, seed * 31 + uid),
                             key=jax.random.PRNGKey(uid))
        reqs.append(req)
        while True:
            try:
                retired += batcher.admit(req)
                break
            except AdmissionRejected:       # adaptive window can reject
                retired += batcher.retire()
        retired += batcher.poll()
    retired += batcher.flush()
    assert sorted(r.uid for r in retired) == list(range(n_reqs))
    assert pool.leased == 0 and not pool.outstanding
    for r in reqs:
        _assert_matches(r.graph, jax.random.PRNGKey(r.uid), r.result)
