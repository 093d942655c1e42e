"""Serving latency/throughput: scheduling policies × bucket executors.

Three questions answered, machine-readably (``BENCH_serve.json``):

* **Policy** — what does each scheduling policy cost in throughput and buy
  in tail latency? A stream of small clustering queries is driven through
  :class:`ClusterBatcher` under the full-bucket policy (buckets flush only
  when they fill ``max_batch``), the deadline policy (``poll()`` flushes
  any bucket whose oldest request waited past ``max_wait``), and — when
  ``--policy`` selects them — the adaptive and coalescing policies from
  ``repro.serve.scheduler``. Every pass emits its per-bucket flush-latency
  telemetry (p50/p99 wall + assemble, plus the per-request row-build
  stats of admission) so scheduling quality is tracked across PRs.
* **Starvation** (the coalescing acceptance scenario) — a skewed
  two-bucket arrival stream on a *virtual* clock: a hot bucket fills
  constantly while a cold bucket trickles. Under the full-bucket policy
  the cold requests wait for the end-of-stream drain; the coalescing
  policy promotes them into hot flushes and bounds their p99 wait; the
  cost-aware policy may reject individual steals but must stay inside the
  deadline bound. The comparison is deterministic (virtual time) and
  asserted.
* **Pad-hostile stream** (the cost-model acceptance scenario; runs on
  ``--policy cost`` passes) — hot deadline flushes land exactly on a pow2
  boundary, so every age-only steal doubles the sub-batch; the cost-aware
  policy prices the inflation and rejects, producing strictly fewer
  ``padded_slots`` at the same latency bound (virtual clock, asserted).
* **Shape-churn eviction** (``--policy cost`` passes) — a parade of fresh
  bucket shapes churns a deliberately small compiled-program cache while
  one hot shape keeps flushing: the cost policy's ``on_retire`` shape
  heat pins the hot shape, so hint-driven eviction recompiles no more
  than blind LRU (asserted; compile/eviction counts emitted).
* **Repeat traffic** (the result-cache acceptance scenario) — a
  zipf-skewed stream over a small unique pool, same engine with the
  content-addressed result cache on vs off. Every repeat of an already
  clustered (graph, key) retires at admission (or rides an identical
  in-flight request as a single-flight subscriber); hit rate and
  graphs/s speedup are asserted, and every served result — hit,
  subscriber, or cold — is checked bit-identical to the per-graph
  engine.
* **Mixed-method trace** (the PR 10 method-registry acceptance scenario;
  always runs) — requests alternating ``method='pivot'`` /
  ``method='precluster'`` through one engine under the cost policy. Each
  method flushes through its own ``(method, R, W)`` queue (telemetry keys
  asserted for both), cross-method steals are refused by construction,
  and every result is asserted bit-identical to the per-graph engine of
  its own method. Emitted as ``mixed_method``. The headline policy
  passes take a ``--method`` axis so CI can smoke each registered bucket
  program end to end.
* **Executor / adaptive window** — what does pipelined execution buy, and
  does the adaptive in-flight window match a hand-tuned static
  ``max_in_flight``? Closed-loop steady-state comparisons, interleaved so
  background-load drift hits every engine equally; best-of-N reported.
  These engines run with the result cache *off*: the closed loop replays
  one request set, which a content-addressed cache would short-circuit,
  measuring the cache instead of the executor.

Per-request latency = admit → retire on the engine clock. Policy passes run
twice: the first warms the jit caches (the serving steady state), the
second measures.

Run:  PYTHONPATH=src python benchmarks/serve_bench.py \
          [--graphs 200] [--max-batch 16] [--max-wait 0.05] \
          [--policy deadline] [--executor sync] [--method pivot] \
          [--smoke] [--json BENCH_serve.json]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core import build_graph, correlation_cluster, program_cache_info
from repro.core.graph import path, random_arboric
from repro.core.programs import registered_methods
from repro.serve.cluster_batcher import (
    AdmissionRejected,
    ClusterBatcher,
    ClusterRequest,
)
from repro.serve.engine import serve_all
from repro.serve.scheduler import POLICY_NAMES
from repro.util import VirtualClock
from repro.util import enable_compile_cache


def make_requests(num_graphs: int, seed: int = 0, n_lo: int = 8,
                  n_hi: int = 96, lam_lo: int = 1, lam_hi: int = 3):
    """(uid, graph, λ) stream. λ rides along like batch_bench's ``lams``:
    real clients (dedup bands, LSH shards) know their arboricity bound, and
    passing it keeps admission off the degeneracy-peeling slow path."""
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(num_graphs):
        n = int(rng.integers(n_lo, n_hi))
        edges, lam = random_arboric(n, int(rng.integers(lam_lo, lam_hi + 1)),
                                    rng)
        reqs.append((uid, build_graph(n, edges), lam))
    return reqs


def drive(reqs, max_batch: int, max_wait, num_samples: int,
          executor: str = "sync", arrival_gap: float = 0.0, batcher=None,
          policy=None, method: str = "pivot"):
    """One serving pass; returns (wall_seconds, per-request waits, stats).

    ``arrival_gap`` spaces admissions in time (a Poisson-ish open-loop
    stream approximated by a fixed gap): with it, a bucket that fills
    slowly *ages*, which is exactly the situation the deadline policy
    exists for — the full-bucket policy makes those requests wait for the
    end-of-stream drain. Pass a long-lived ``batcher`` to measure the
    steady state (warm pools and caches) instead of a cold engine.
    Admissions refused by a backpressure window are retried after a
    harvest, like the ``serve_all`` reference loop.
    """
    if batcher is None:
        batcher = ClusterBatcher(max_batch=max_batch, max_wait=max_wait,
                                 num_samples=num_samples, executor=executor,
                                 policy=policy, method=method)
    waits = {}

    def account(done):
        now = batcher.clock()
        for r in done:
            waits[r.uid] = now - r.admitted_at

    t0 = time.perf_counter()
    for uid, g, lam in reqs:
        if arrival_gap:
            time.sleep(arrival_gap)
        req = ClusterRequest(uid=uid, graph=g, key=jax.random.PRNGKey(uid),
                             lam=lam)
        while True:
            try:
                account(batcher.admit(req))
                break
            except AdmissionRejected:
                done = batcher.retire()
                account(done)
                if not done:
                    # No progress: sleep like serve_all's reject_backoff —
                    # a zero-backoff spin would burn the very host cores
                    # the steady-state comparison measures.
                    time.sleep(0.0005)
        account(batcher.poll())
    account(batcher.flush())
    dt = time.perf_counter() - t0
    assert len(waits) == len(reqs), "requests lost in the engine"
    return dt, np.array([waits[uid] for uid, *_ in reqs]), batcher.stats


def steady_throughput(reqs, engines, repeat: int = 5):
    """Steady-state closed-loop graphs/s per named engine, interleaved.

    Long-lived engines (so pools, jit caches and — for the pipelined path
    — the extra in-flight staging generations are all warm, as in real
    serving). Passes alternate between engines (a, b, a, ...) so
    background-load drift on a shared host degrades every engine's sample
    set equally; best-of-N per engine is reported.
    """
    best = {name: None for name in engines}
    for name, engine in engines.items():        # warm pass per engine
        drive(reqs, engine.max_batch, None, engine.num_samples,
              batcher=engine)
    for _ in range(repeat):
        for name, engine in engines.items():
            dt, _, _ = drive(reqs, engine.max_batch, None,
                             engine.num_samples, batcher=engine)
            best[name] = dt if best[name] is None else min(best[name], dt)
    return {name: len(reqs) / t for name, t in best.items()}


def starvation_comparison(smoke: bool, max_batch: int = 16,
                          gap: float = 0.002):
    """Skewed two-bucket stream on a virtual clock: full vs coalesce vs
    cost-aware coalesce.

    A hot ``(32, 4)`` bucket receives almost every arrival; a cold
    ``(8, 4)`` bucket gets one request every ``cold_every`` arrivals and
    never fills ``max_batch``. Waits are measured in *virtual* seconds, so
    the comparison is deterministic: under the full-bucket policy cold
    requests survive to the end-of-stream drain (p99 wait grows with the
    stream), under the coalescing policy (deadline ``10·gap``, aggressive
    ``steal_wait``) the hot bucket's partial deadline flushes have spare
    room and the cold requests are promoted into them — their p99 wait is
    bounded by the hot flush cadence, not the stream length. The
    cost-aware policy may *reject* individual steals (priced against real
    flush telemetry), but a rejected request still flushes on its own
    ``max_wait`` deadline, so its p99 must stay within the coalesce-style
    bound — asserted against ``max_wait`` plus one poll tick.
    """
    n_hot = 64 if smoke else 240
    cold_every = 16
    max_wait = 10 * gap

    def build_stream():
        # Fresh rng per pass: all policies must see the *identical* stream
        # or the asserted A/B would compare two different workloads.
        rng = np.random.default_rng(7)
        stream = []
        uid = 0
        for i in range(n_hot):
            if i % cold_every == 0:
                stream.append((uid, build_graph(6, path(6)), True))
                uid += 1
            n = int(rng.integers(17, 30))
            stream.append((uid, build_graph(n, path(n)), False))
            uid += 1
        return stream

    from repro.serve.scheduler import (CoalescingPolicy,
                                       CostAwareCoalescingPolicy)

    results = {}
    for policy in ("full", "coalesce", "cost"):
        clock = VirtualClock()
        if policy == "coalesce":
            pol = CoalescingPolicy(max_batch, max_wait=max_wait,
                                   steal_wait=gap / 2)
        elif policy == "cost":
            pol = CostAwareCoalescingPolicy(max_batch, max_wait=max_wait,
                                            steal_wait=gap / 2)
        else:
            pol = policy
        batcher = ClusterBatcher(max_batch=max_batch, policy=pol,
                                 clock=clock)
        waits, is_cold = {}, {}
        stream = build_stream()

        def account(done, now):
            for r in done:
                waits[r.uid] = now - r.admitted_at

        for uid, g, cold in stream:
            is_cold[uid] = cold
            clock.advance(gap)
            account(batcher.admit(
                ClusterRequest(uid=uid, graph=g,
                               key=jax.random.PRNGKey(uid))), clock.t)
            account(batcher.poll(), clock.t)
        account(batcher.flush(), clock.t)
        cold_waits = np.array([w for uid, w in waits.items() if is_cold[uid]])
        hot_waits = np.array([w for uid, w in waits.items()
                              if not is_cold[uid]])
        results[policy] = {
            "cold_p99_ms": pct(cold_waits, 99) * 1e3,
            "cold_max_ms": float(cold_waits.max()) * 1e3,
            "hot_p99_ms": pct(hot_waits, 99) * 1e3,
            "coalesced_flushes": batcher.stats.coalesced_flushes,
            "stolen_requests": batcher.stats.stolen_requests,
        }
        if policy == "cost":
            results[policy].update(batcher.policy.cost_stats())
        print(f"[starve:{policy:8s}] cold p99={results[policy]['cold_p99_ms']:8.1f}ms "
              f"max={results[policy]['cold_max_ms']:8.1f}ms   "
              f"hot p99={results[policy]['hot_p99_ms']:6.1f}ms   "
              f"stolen={batcher.stats.stolen_requests}")
    assert results["coalesce"]["stolen_requests"] > 0, \
        "coalescing policy never stole — the scenario is broken"
    assert results["coalesce"]["cold_p99_ms"] < results["full"]["cold_p99_ms"], (
        "coalescing must bound the starved bucket's p99 wait below the "
        "full-bucket policy's end-of-stream drain")
    # The cost-aware policy's rejections must never void the latency
    # contract: every cold request is bounded by its own deadline (plus
    # one poll tick, since polls ride the gap-spaced admit loop), while
    # the end-of-stream drain under full-bucket grows with the stream.
    cost_bound_ms = (max_wait + 2 * gap) * 1e3
    assert results["cost"]["cold_max_ms"] <= cost_bound_ms + 1e-6, (
        f"cost-aware coalescing exceeded the deadline bound: "
        f"{results['cost']['cold_max_ms']:.1f}ms > {cost_bound_ms:.1f}ms")
    assert results["cost"]["cold_p99_ms"] < results["full"]["cold_p99_ms"]
    return results


def pad_hostile_comparison(smoke: bool, max_batch: int = 16,
                           gap: float = 0.002):
    """Pow2-boundary mixed stream on a virtual clock: age-only coalescing
    vs the cost-aware policy (the tentpole acceptance scenario).

    Each window admits exactly 8 hot ``(32, 4)`` requests (a deadline
    flush of 8 packs into ``g_pad = 8`` with zero empty group slots) plus
    one starving cold ``(8, 4)`` request. Age-only coalescing promotes the
    cold request into every hot deadline flush — inflating the sub-batch
    to ``g_pad = 16`` and paying 7 empty entries per flush. The cost-aware
    policy prices that inflation (a pessimistic ``service_floor_s`` makes
    the pricing independent of host timing noise: floor cost ≥ 50 ms of
    device time vs ≤ ``max_wait`` = 20 ms of slack saved) and rejects the
    steal; the cold request rides its *own* deadline at ``g_pad = 1`` with
    zero padding. Asserted: strictly fewer ``padded_slots`` under the cost
    policy, with the cold p99 still inside the deadline bound.
    """
    from repro.serve.costmodel import FlushCostModel
    from repro.serve.scheduler import (CoalescingPolicy,
                                       CostAwareCoalescingPolicy)

    n_windows = 6 if smoke else 14
    max_wait = 10 * gap
    hot_per_window = 8

    def build_window(rng, uid):
        window = []
        for j in range(hot_per_window):
            n = int(rng.integers(17, 30))
            window.append((uid, build_graph(n, path(n)), False))
            uid += 1
            if j == 3:          # cold trickles in mid-window
                window.append((uid, build_graph(6, path(6)), True))
                uid += 1
        return window, uid

    results = {}
    for policy in ("coalesce", "cost"):
        clock = VirtualClock()
        if policy == "coalesce":
            pol = CoalescingPolicy(max_batch, max_wait=max_wait,
                                   steal_wait=gap / 2)
        else:
            pol = CostAwareCoalescingPolicy(
                max_batch, max_wait=max_wait, steal_wait=gap / 2,
                cost_model=FlushCostModel(service_floor_s=0.05))
        batcher = ClusterBatcher(max_batch=max_batch, policy=pol,
                                 clock=clock)
        waits, is_cold = {}, {}
        rng = np.random.default_rng(11)     # identical stream per arm
        uid = 0

        def account(done, now):
            for r in done:
                waits[r.uid] = now - r.admitted_at

        for _ in range(n_windows):
            window, uid = build_window(rng, uid)
            for w_uid, g, cold in window:
                is_cold[w_uid] = cold
                clock.advance(gap)
                account(batcher.admit(
                    ClusterRequest(uid=w_uid, graph=g,
                                   key=jax.random.PRNGKey(w_uid))), clock.t)
                account(batcher.poll(), clock.t)
            # Idle tail of the window: the oldest hot request crosses
            # max_wait here, so the deadline flush carries exactly the 8
            # hot requests — a pow2 boundary every steal would double.
            clock.advance(3 * gap)
            account(batcher.poll(), clock.t)
        account(batcher.flush(), clock.t)
        cold_waits = np.array([w for uid, w in waits.items() if is_cold[uid]])
        results[policy] = {
            "padded_slots": batcher.stats.padded_slots,
            "stolen_requests": batcher.stats.stolen_requests,
            "cold_p99_ms": pct(cold_waits, 99) * 1e3,
            "cold_max_ms": float(cold_waits.max()) * 1e3,
        }
        if policy == "cost":
            results[policy].update(batcher.policy.cost_stats())
        print(f"[pad-hostile:{policy:8s}] padded_slots="
              f"{results[policy]['padded_slots']:4d}  "
              f"stolen={results[policy]['stolen_requests']:3d}  "
              f"cold p99={results[policy]['cold_p99_ms']:6.1f}ms")
    assert results["coalesce"]["stolen_requests"] > 0, \
        "age-only coalescing never stole — the pad-hostile stream is broken"
    assert results["cost"]["steals_rejected"] > 0, \
        "cost model never rejected a steal on the pad-hostile stream"
    assert results["cost"]["padded_slots"] < results["coalesce"]["padded_slots"], (
        "cost-aware coalescing must produce strictly fewer padded slots "
        f"than age-only on the pad-hostile stream "
        f"({results['cost']['padded_slots']} vs "
        f"{results['coalesce']['padded_slots']})")
    cost_bound_ms = (max_wait + 2 * gap) * 1e3
    assert results["cost"]["cold_max_ms"] <= cost_bound_ms + 1e-6, (
        "rejected steals must still retire on their own deadline")
    return results


def eviction_churn_comparison(smoke: bool):
    """Shape churn through a small program cache: blind LRU vs the
    scheduler's heat-driven ``touch``/``pin`` eviction hints.

    One hot bucket shape flushes three times per sweep while a parade of
    *fresh* cold shapes (distinct ``(B, R, W)`` programs, never repeated)
    churns through a deliberately small compiled-program cache. Under
    blind LRU the cold parade evicts the hot shape's program between
    visits, so the hot shape recompiles every sweep; the cost policy's
    ``on_retire`` heat tracking pins the hot shape, which survives the
    churn. First-time compiles are identical in both arms (same
    workload), so the compile-count difference is exactly the recompiles
    — asserted: hinted ≤ blind. The hinted arm runs *first* so any cache
    residue between arms favours the blind baseline.
    """
    from repro.core.executor import (program_cache_info, program_cache_unpin,
                                     set_program_cache_capacity)
    from repro.serve.costmodel import ShapeHeat
    from repro.serve.scheduler import (CostAwareCoalescingPolicy,
                                       DeadlinePolicy)

    capacity = 4
    sweeps = 3 if smoke else 4
    cold_ns = (9, 17, 33, 65)           # R = 16 / 32 / 64 / 128
    max_wait = 0.01
    prev = set_program_cache_capacity(capacity)

    def reset_cache():
        # Bounce the capacity to evict (almost) everything, so each arm
        # starts from the same near-empty cache; drop any leftover pins.
        for bucket in program_cache_info()["pinned"]:
            program_cache_unpin(tuple(bucket))
        set_program_cache_capacity(1)
        set_program_cache_capacity(capacity)

    def drive(policy) -> dict:
        reset_cache()
        clock = VirtualClock()
        batcher = ClusterBatcher(max_batch=8, policy=policy, clock=clock)
        hot = build_graph(6, path(6))                    # bucket (8, 4)
        uid = 0
        info0 = program_cache_info()
        for sweep in range(sweeps):
            for _ in range(3):                           # hot keeps coming
                batcher.admit(ClusterRequest(uid=uid, graph=hot,
                                             key=jax.random.PRNGKey(uid)))
                uid += 1
                clock.advance(2 * max_wait)
                batcher.poll()
            for n in cold_ns:                            # fresh cold shapes:
                count = 1 << sweep                       # new pow2 B per sweep
                for _ in range(count):
                    batcher.admit(ClusterRequest(
                        uid=uid, graph=build_graph(n, path(n)),
                        key=jax.random.PRNGKey(uid)))
                    uid += 1
                clock.advance(2 * max_wait)
                batcher.poll()
        batcher.flush()
        info1 = program_cache_info()
        return {
            "compiles": info1["compiles"] - info0["compiles"],
            "evictions": info1["evictions"] - info0["evictions"],
            "pinned": [list(b) for b in info1["pinned"]],
        }

    try:
        hinted = drive(CostAwareCoalescingPolicy(
            8, max_wait=max_wait, steal_wait=max_wait,
            heat=ShapeHeat(window=32, max_pinned=1, min_heat=3)))
        for bucket in program_cache_info()["pinned"]:
            program_cache_unpin(tuple(bucket))
        blind = drive(DeadlinePolicy(8, max_wait=max_wait))
    finally:
        for bucket in program_cache_info()["pinned"]:
            program_cache_unpin(tuple(bucket))
        set_program_cache_capacity(prev)
    print(f"[churn:hinted ] compiles={hinted['compiles']:3d} "
          f"evictions={hinted['evictions']:3d} pinned={hinted['pinned']}")
    print(f"[churn:blind  ] compiles={blind['compiles']:3d} "
          f"evictions={blind['evictions']:3d}")
    assert blind["evictions"] > 0, \
        "churn never evicted — the cache is not under pressure"
    assert hinted["compiles"] <= blind["compiles"], (
        "hint-driven eviction must not recompile more than blind LRU "
        f"({hinted['compiles']} vs {blind['compiles']})")
    return {"hinted": hinted, "blind": blind, "capacity": capacity}


def repeat_traffic_comparison(smoke: bool, max_batch: int = 16,
                              executor: str = "sync"):
    """Zipf repeat traffic: content-addressed result cache + single-flight
    coalescing vs the identical engine with the cache off.

    A stream of ``n_stream`` requests drawn zipf-skewed (``p ∝ 1/rank^s``,
    explicit bounded pmf — ``rng.zipf`` has an unbounded tail) from
    ``n_unique`` (graph, key) pairs. Deduplicated serving traffic looks
    exactly like this: a few hot similarity shards dominate the stream.
    With the cache on, the first occurrence of each pair flushes cold and
    every later one either retires at admission (cache hit) or subscribes
    to the in-flight primary; with it off, every request packs and
    flushes. Both arms run the deadline policy on the real clock — full
    buckets never fill under duplicate-heavy traffic (the duplicates
    subscribe instead of queueing), so primaries must flush on a deadline
    for repeats to find a *completed* winner.

    The cache-off arm runs first, so any residual warmth (jit programs,
    allocator state) favours the baseline. Asserted: zero hits with the
    cache off, hit rate > 0.5 and ≥ 1.5× graphs/s with it on, and every
    retired result — hit, subscriber, or cold — bit-identical to the
    per-graph engine.
    """
    n_unique = 24 if smoke else 48
    n_stream = 192 if smoke else 768
    zipf_s = 1.2
    max_wait = 0.002

    pool = make_requests(n_unique, seed=17, n_lo=24, n_hi=64)
    ranks = np.arange(1, n_unique + 1, dtype=np.float64)
    pmf = ranks ** -zipf_s
    pmf /= pmf.sum()
    stream = np.random.default_rng(23).choice(n_unique, size=n_stream,
                                              p=pmf)
    refs = {int(idx): correlation_cluster(
                pool[idx][1], key=jax.random.PRNGKey(1000 + int(idx)),
                lam=pool[idx][2])
            for idx in set(stream.tolist())}

    # Shared jit warmup: bucket programs live in the process-global cache,
    # so one warm engine covers both arms identically.
    ClusterBatcher(max_batch=max_batch,
                   executor=executor).warmup(g for _, g, _ in pool)

    results = {}
    for arm, cache_on in (("no_cache", False), ("cache", True)):
        batcher = ClusterBatcher(max_batch=max_batch, max_wait=max_wait,
                                 executor=executor, result_cache=cache_on)
        reqs = [ClusterRequest(uid=pos, graph=pool[idx][1],
                               key=jax.random.PRNGKey(1000 + int(idx)),
                               lam=pool[idx][2])
                for pos, idx in enumerate(stream)]
        t0 = time.perf_counter()
        done = {r.uid: r for r in serve_all(batcher, reqs)}
        dt = time.perf_counter() - t0
        assert len(done) == n_stream, "requests lost in the engine"
        for pos, idx in enumerate(stream):
            ref = refs[int(idx)]
            assert (done[pos].result.labels == ref.labels).all(), \
                "cached/subscribed result diverged from the cold engine"
            assert done[pos].result.cost == ref.cost
        stats = batcher.stats
        results[arm] = {
            "gps": n_stream / dt,
            "wall_s": dt,
            "flushes": stats.flushes,
            "cache_hits": stats.cache_hits,
            "subscribed": stats.subscribed,
            "hit_rate": stats.cache_hits / n_stream,
        }
        if stats.result_cache is not None:
            rc = stats.result_cache
            results[arm]["result_cache"] = {
                "hits": rc.hits, "misses": rc.misses,
                "evictions": rc.evictions, "collisions": rc.collisions,
                "entries": rc.entries, "bytes": rc.bytes,
            }
        print(f"[repeat:{arm:9s}] {results[arm]['gps']:8.1f} graphs/s   "
              f"flushes={stats.flushes:4d}  hits={stats.cache_hits:4d}  "
              f"subscribed={stats.subscribed:3d}")
    hit_rate = results["cache"]["hit_rate"]
    speedup = results["cache"]["gps"] / results["no_cache"]["gps"]
    results.update(speedup=speedup, zipf_s=zipf_s,
                   n_unique=n_unique, n_stream=n_stream)
    assert results["no_cache"]["cache_hits"] == 0, \
        "cache-off arm recorded hits — the baseline is not cache-free"
    assert hit_rate > 0.5, (
        f"repeat-traffic hit rate {hit_rate:.2f} <= 0.5 — primaries are "
        "not completing before their repeats arrive (deadline too long?)")
    assert speedup >= 1.5, (
        f"result cache bought only {speedup:.2f}x over the cache-off arm "
        "on zipf repeat traffic (expected >= 1.5x)")
    print(f"[repeat] hit rate={hit_rate:.2f}  "
          f"cache speedup={speedup:.2f}x over cache-off")
    return results


def mixed_method_comparison(smoke: bool, max_batch: int = 16,
                            executor: str = "sync"):
    """One engine serving both registered bucket programs in one trace,
    cost policy active (the PR 10 acceptance scenario).

    Requests alternate ``method='pivot'`` / ``method='precluster'`` over
    assorted shapes through a single :class:`ClusterBatcher` under the
    cost-aware coalescing policy, so the per-``(method, R, W)`` queues,
    the cross-method steal refusal, and the method-tagged program-cache
    probes are all exercised together. Asserted: every retired result is
    bit-identical to the per-graph engine *of its own method* — a
    coalesced flush that mixed programs would break this immediately —
    and the flush-latency telemetry carries method-prefixed bucket keys
    for both methods (proving the queues never merged).
    """
    n = 48 if smoke else 128
    methods = ("pivot", "precluster")
    reqs = make_requests(n, seed=31, n_lo=8, n_hi=64)
    engine = ClusterBatcher(max_batch=max_batch, max_wait=0.005,
                            policy="cost", executor=executor)
    creqs = [ClusterRequest(uid=uid, graph=g, lam=lam,
                            key=jax.random.PRNGKey(uid),
                            method=methods[uid % 2])
             for uid, g, lam in reqs]
    t0 = time.perf_counter()
    done = {r.uid: r for r in serve_all(engine, creqs)}
    dt = time.perf_counter() - t0
    assert len(done) == n, "requests lost in the mixed-method engine"
    for uid, g, lam in reqs:
        m = methods[uid % 2]
        ref = correlation_cluster(g, key=jax.random.PRNGKey(uid), lam=lam,
                                  method=m)
        assert done[uid].result.method == m
        assert (done[uid].result.labels == ref.labels).all() \
            and done[uid].result.cost == ref.cost, (
            f"mixed-method engine diverged from the per-graph {m!r} "
            f"engine on request {uid}")
    stats = engine.stats
    tele_methods = {key.split(":", 1)[0]
                    for key in stats.latency.summary()}
    assert set(methods) <= tele_methods, (
        f"telemetry saw methods {sorted(tele_methods)}; both methods must "
        "flush through their own queues")
    engine.close()
    block = {
        "n_requests": n,
        "gps": n / dt,
        "flushes": stats.flushes,
        "coalesced_flushes": stats.coalesced_flushes,
        "stolen_requests": stats.stolen_requests,
        "buckets_seen": stats.buckets_seen,
        "methods": sorted(tele_methods),
    }
    block.update(engine.policy.cost_stats())
    print(f"[mixed-method] {block['gps']:8.1f} graphs/s   "
          f"flushes={block['flushes']}  stolen={block['stolen_requests']}  "
          f"queues={block['buckets_seen']}  "
          f"bit-exact per method: {n} requests")
    return block


def pct(x, q):
    return float(np.percentile(x, q))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", type=int, default=200)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait", type=float, default=0.05,
                    help="deadline budget in seconds")
    ap.add_argument("--num-samples", type=int, default=1)
    ap.add_argument("--arrival-ms", type=float, default=2.0,
                    help="inter-arrival gap of the simulated request stream")
    ap.add_argument("--policy", choices=list(POLICY_NAMES),
                    default="deadline",
                    help="scheduling policy for the headline policy pass")
    ap.add_argument("--executor", choices=["sync", "async", "sharded"],
                    default="sync",
                    help="bucket executor for the policy passes")
    ap.add_argument("--method", choices=list(registered_methods()),
                    default="pivot",
                    help="bucket program for the headline policy passes "
                         "(the mixed-method scenario always runs both)")
    ap.add_argument("--json", default="BENCH_serve.json",
                    help="machine-readable results path ('' to skip)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: fewer graphs, correctness focus")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep kernel block shapes per bucket tier during "
                         "warmup and emit the tuning block")
    args = ap.parse_args()
    n_graphs = 32 if args.smoke else args.graphs
    # Keep the arrival gap in smoke mode: without it the stream outruns
    # max_wait, no deadline flush ever fires, and the CI step would not
    # exercise the partial-flush machinery at all.
    arrival_gap = args.arrival_ms / 1e3

    reqs = make_requests(n_graphs)
    print(f"workload: {n_graphs} graphs, max_batch={args.max_batch}, "
          f"max_wait={args.max_wait * 1e3:.0f}ms, "
          f"arrival gap={arrival_gap * 1e3:.1f}ms, "
          f"policy={args.policy}, executor={args.executor}, "
          f"method={args.method}")

    # Warm every pow2 sub-batch program the workload can hit (deadline
    # flushes run partial buckets, and flush grouping is timing-dependent,
    # so per-policy warm passes alone leave compile spikes in the tail).
    warmer = ClusterBatcher(max_batch=args.max_batch,
                            num_samples=args.num_samples,
                            executor=args.executor, method=args.method)
    t0 = time.perf_counter()
    compiled = warmer.warmup((g for _, g, _ in reqs),
                             autotune=args.autotune,
                             repeats=2 if args.smoke else 3)
    print(f"warmup: {compiled} bucket programs compiled in "
          f"{time.perf_counter() - t0:.1f}s")
    tuning_block = {"enabled": bool(args.autotune)}
    if args.autotune:
        tuning_block.update(warmer.stats.tuning or {})
        cache_info = tuning_block.get("sweeps"), tuning_block.get("hits")
        print(f"autotune: sweeps={cache_info[0]} cache hits={cache_info[1]} "
              f"({len(tuning_block.get('sweep_log', []))} sweep records)")

    # Policy comparison: full-bucket and deadline always (the cross-PR
    # baseline pair), plus the selected --policy when it is neither.
    policy_runs = ["full", "deadline"]
    if args.policy not in policy_runs:
        policy_runs.append(args.policy)
    results = {}
    for policy in policy_runs:
        max_wait = None if policy == "full" else args.max_wait
        drive(reqs, args.max_batch, max_wait, args.num_samples,
              executor=args.executor, policy=policy,
              method=args.method)                             # warm pass
        dt, waits, stats = drive(reqs, args.max_batch, max_wait,
                                 args.num_samples, executor=args.executor,
                                 policy=policy, arrival_gap=arrival_gap,
                                 method=args.method)
        results[policy] = (dt, waits, stats)
        extra = ""
        if stats.stolen_requests:
            extra = f" stolen={stats.stolen_requests}"
        if stats.rejected:
            extra += f" rejected={stats.rejected}"
        print(f"[{policy:9s}] {n_graphs / dt:8.1f} graphs/s   "
              f"wait p50={pct(waits, 50) * 1e3:7.1f}ms  "
              f"p99={pct(waits, 99) * 1e3:7.1f}ms  "
              f"max={waits.max() * 1e3:7.1f}ms   "
              f"flushes={stats.flushes} (deadline={stats.deadline_flushes})"
              f"{extra}")
        if policy == "deadline":
            assert stats.deadline_flushes > 0, (
                "deadline policy never fired — the comparison below would "
                "be two full-bucket runs; raise --arrival-ms or lower "
                "--max-wait")

    # Starvation: the coalescing acceptance scenario (virtual clock,
    # deterministic, asserted) — now three-armed with the cost policy.
    starvation = starvation_comparison(args.smoke)

    # Pad-hostile stream: the cost-model acceptance scenario — strictly
    # fewer padded slots than age-only coalescing, deadline bound intact.
    # Both cost-model scenarios are policy-independent A/Bs that build
    # their own engines, so run them only on the --policy cost passes
    # instead of repeating them across the whole CI smoke matrix.
    pad_hostile = pad_hostile_comparison(args.smoke) \
        if args.policy == "cost" else None

    # Executor comparison: closed-loop steady state, sync vs pipelined
    # (vs the selected executor when it is neither). The async win is the
    # host packing bucket i+1 while bucket i computes and transfers, so it
    # runs on the compute-heavy tier (n∈[100,250], λ≤4) where a flush's
    # device program is comparable to its host-side packing — on the small
    # tier the device is <15% of a flush cycle and there is nothing to
    # pipeline into. The warm drive pass inside steady_throughput compiles
    # exactly the shapes the closed loop hits.
    comp_reqs = make_requests(64 if args.smoke else 160, seed=1,
                              n_lo=100, n_hi=250, lam_lo=2, lam_hi=4)
    exec_names = ["sync", "async"]
    if args.executor not in exec_names:
        exec_names.append(args.executor)
    # Cache off: the closed loop replays the same request set, which the
    # content-addressed cache would short-circuit after the first pass —
    # the comparison would measure the cache, not the executor.
    engines = {name: ClusterBatcher(max_batch=args.max_batch,
                                    num_samples=args.num_samples,
                                    executor=name, result_cache=False)
               for name in exec_names}
    comparison = steady_throughput(comp_reqs, engines,
                                   repeat=3 if args.smoke else 6)
    for name in exec_names:
        print(f"[executor:{name:8s}] {comparison[name]:8.1f} graphs/s "
              "steady-state (closed loop, full buckets, heavy tier)")
    async_speedup = comparison["async"] / comparison["sync"]
    print(f"[executor] async pipelining: {async_speedup:.2f}x over sync")

    # Adaptive in-flight window vs a hand-tuned static max_in_flight: same
    # closed loop, pipelined executor, interleaved best-of-N. The adaptive
    # window replaces the static knob, so steady-state throughput should
    # match or beat it.
    window_engines = {
        "static": ClusterBatcher(max_batch=args.max_batch,
                                 num_samples=args.num_samples,
                                 executor="async", max_in_flight=4,
                                 result_cache=False),
        "adaptive": ClusterBatcher(max_batch=args.max_batch,
                                   num_samples=args.num_samples,
                                   executor="async", policy="adaptive",
                                   result_cache=False),
    }
    window_cmp = steady_throughput(comp_reqs, window_engines,
                                   repeat=3 if args.smoke else 6)
    adaptive_ratio = window_cmp["adaptive"] / window_cmp["static"]
    print(f"[in-flight] static(4)={window_cmp['static']:8.1f} g/s   "
          f"adaptive={window_cmp['adaptive']:8.1f} g/s   "
          f"ratio={adaptive_ratio:.2f}x")

    # Repeat traffic: the result-cache acceptance scenario (real clock,
    # asserted hit rate + speedup + bit-exactness).
    repeat_traffic = repeat_traffic_comparison(args.smoke,
                                               max_batch=args.max_batch,
                                               executor=args.executor)

    # Bit-exactness spot check against the per-graph engine, under the
    # selected policy.
    sample = reqs[:: max(1, len(reqs) // 8)]
    batcher = ClusterBatcher(max_batch=args.max_batch,
                             max_wait=args.max_wait,
                             num_samples=args.num_samples,
                             executor=args.executor, policy=args.policy,
                             method=args.method)
    sample_reqs = [ClusterRequest(uid=uid, graph=g,
                                  key=jax.random.PRNGKey(uid), lam=lam)
                   for uid, g, lam in sample]
    done = {r.uid: r for r in serve_all(batcher, sample_reqs)}
    for uid, g, lam in sample:
        ref = correlation_cluster(g, key=jax.random.PRNGKey(uid), lam=lam,
                                  num_samples=args.num_samples,
                                  method=args.method)
        assert (done[uid].result.labels == ref.labels).all()
        assert done[uid].result.cost == ref.cost
    print(f"bit-exactness: {len(sample)} sampled requests match the "
          f"per-graph engine under the {args.policy!r} policy "
          f"({args.executor} executor, {args.method!r} method)")

    # Mixed-method trace: both registered bucket programs through one
    # engine under the cost policy, asserted bit-exact per method.
    mixed_method = mixed_method_comparison(args.smoke,
                                           max_batch=args.max_batch,
                                           executor=args.executor)

    # Shape-churn eviction: scheduler heat hints vs blind LRU (runs last —
    # it squeezes the global program cache, which would otherwise force
    # recompiles into the timed passes above; cost passes only, like the
    # pad-hostile scenario).
    eviction_churn = eviction_churn_comparison(args.smoke) \
        if args.policy == "cost" else None

    dt_full, w_full, s_full = results["full"]
    dt_dead, w_dead, s_dead = results["deadline"]
    print(f"\nsummary: deadline policy holds p99 wait at "
          f"{pct(w_dead, 99) * 1e3:.1f}ms vs {pct(w_full, 99) * 1e3:.1f}ms "
          f"full-bucket, at {dt_full / dt_dead * 100:.0f}% of full-bucket "
          "throughput")

    if args.json:
        def policy_payload(dt, waits, stats):
            return {
                "gps": n_graphs / dt,
                "wait_p50_ms": pct(waits, 50) * 1e3,
                "wait_p99_ms": pct(waits, 99) * 1e3,
                "wait_max_ms": float(waits.max()) * 1e3,
                "flushes": stats.flushes,
                "deadline_flushes": stats.deadline_flushes,
                "coalesced_flushes": stats.coalesced_flushes,
                "stolen_requests": stats.stolen_requests,
                "padded_slots": stats.padded_slots,
                "rejected": stats.rejected,
                "in_flight_peak": stats.in_flight_peak,
                "flush_latency": stats.latency.summary(),
            }
        policies_payload = {
            "full_bucket": policy_payload(*results["full"]),
            "deadline": policy_payload(*results["deadline"]),
        }
        for policy in policy_runs:
            if policy not in ("full", "deadline"):
                policies_payload[policy] = policy_payload(*results[policy])
        payload = {
            "bench": "serve",
            "policy": args.policy,
            "executor": args.executor,
            "method": args.method,
            "smoke": bool(args.smoke),
            "n_graphs": n_graphs,
            "max_batch": args.max_batch,
            "max_wait_ms": args.max_wait * 1e3,
            "arrival_gap_ms": arrival_gap * 1e3,
            "warmup_programs": compiled,
            "policies": policies_payload,
            "starvation": starvation,
            "executor_steady_gps": comparison,
            "async_speedup_vs_sync": async_speedup,
            "inflight_window_gps": window_cmp,
            "adaptive_vs_static_ratio": adaptive_ratio,
            "repeat_traffic": repeat_traffic,
            "mixed_method": mixed_method,
            "tuning": tuning_block,
            "program_cache": program_cache_info(),
        }
        # Host metadata + tuning-cache state: makes the perf trajectory
        # comparable across machines.
        from repro.kernels.autotune import host_provenance
        payload["provenance"] = host_provenance()
        if pad_hostile is not None:
            payload["pad_hostile"] = pad_hostile
        if eviction_churn is not None:
            payload["eviction_churn"] = eviction_churn
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
