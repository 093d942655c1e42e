"""Batch engine throughput: graphs/sec and compile counts vs a per-graph loop.

The serving regime this measures: a stream of many *small* clustering
queries of assorted shapes (near-dup buckets, LSH bands, per-shard
similarity graphs). The per-graph engine retraces/recompiles its while-loop
for every new ``(n, m)`` shape; the batch engine compiles one program per
``(B, R, W)`` shape bucket and amortizes it over every graph that ever
lands in the bucket. ``--executor`` picks how buckets reach the device:
``sync`` (block per bucket), ``async`` (all buckets dispatched before any
harvest — packing overlaps device execution), ``sharded`` (each bucket
data-parallel across all local devices). ``--policy`` picks the scheduling
policy for the serving-style pass (the same workload streamed through
``ClusterBatcher`` + ``serve_all``), whose per-bucket flush-latency
telemetry is emitted alongside the one-shot numbers.

``--method`` picks the registered bucket program the loop/batch/serve
passes run (``pivot`` default, ``precluster`` for the constant-round
agreement program); independent of that axis, a ``method_quality`` pass
always compares the two programs' disagreement costs at matched
wall-clock (the faster method earns a best-of-k budget) plus device round
counts, emitted as the ``method_quality`` block of the JSON.

Run:  PYTHONPATH=src python benchmarks/batch_bench.py \
          [--graphs 96] [--repeat 3] [--executor sync] [--policy full] \
          [--method pivot] [--json BENCH_batch.json]

Reported (and written machine-readably to ``--json`` for cross-PR perf
tracking):
  * graphs/sec of the per-graph ``correlation_cluster`` loop
  * graphs/sec of ``correlation_cluster_batch`` (same graphs, same keys —
    output is bit-identical, which is also asserted)
  * p50/p99 over the steady-state repeats
  * graphs/sec of the serving pass under ``--policy`` + its flush-latency
    telemetry (p50/p99 wall + assemble per bucket shape; since the PR 8
    admission-time packing split the pre-split ``pack_*`` fields are
    renamed ``assemble_*`` and per-request ``build_*`` stats ride along,
    plus ``host_pack`` wall fractions of both streams over the serve wall)
  * compile counts: per-graph MIS programs vs batch bucket programs, plus
    the bounded program-cache state (size/capacity/evictions)
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core import build_graph, correlation_cluster, correlation_cluster_batch
from repro.core import batch as batch_mod
from repro.core import make_executor, program_cache_info
from repro.core.graph import random_arboric
from repro.core.mis import _greedy_mis_parallel_impl
from repro.core.programs import registered_methods
from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest
from repro.serve.engine import serve_all
from repro.serve.scheduler import POLICY_NAMES
from repro.util import enable_compile_cache


def make_workload(num_graphs: int, seed: int = 0):
    """Assorted small graphs: sizes 8..96, arboricity 1..3, distinct keys."""
    rng = np.random.default_rng(seed)
    graphs, keys, lams = [], [], []
    for i in range(num_graphs):
        n = int(rng.integers(8, 96))
        lam = int(rng.integers(1, 4))
        edges, _ = random_arboric(n, lam, rng)
        graphs.append(build_graph(n, edges))
        keys.append(jax.random.PRNGKey(i))
        lams.append(lam)
    return graphs, keys, lams


def bench_loop(graphs, keys, lams, method: str = "pivot"):
    t0 = time.perf_counter()
    results = [correlation_cluster(g, key=k, lam=lam, method=method)
               for g, k, lam in zip(graphs, keys, lams)]
    return time.perf_counter() - t0, results


def bench_batch(graphs, keys, lams, executor, method: str = "pivot",
                num_samples: int = 1):
    t0 = time.perf_counter()
    results = correlation_cluster_batch(graphs, keys=keys, lams=lams,
                                        executor=executor, method=method,
                                        num_samples=num_samples)
    return time.perf_counter() - t0, results


def bench_method_quality(graphs, keys, lams, executor,
                         max_matched_k: int = 16) -> dict:
    """Clustering quality per registered method at matched wall-clock.

    PIVOT is a 3-approx in expectation; the constant-round precluster
    program trades quality for O(1) rounds-loop trips. A raw cost
    comparison at one sample each would hide that trade, so the faster
    method is granted a best-of-k budget: ``k_matched = floor(pivot_wall /
    precluster_wall)`` (clamped to [1, max_matched_k]) extra samples, the
    budget equalizing the two methods' steady-state walls. Emits total
    disagreement costs, the cost ratio vs PIVOT at 1 sample and at the
    matched budget, and mean device round counts per method — the
    ``method_quality`` block of ``BENCH_batch.json``.
    """
    walls, runs = {}, {}
    for method in ("pivot", "precluster"):
        bench_batch(graphs, keys, lams, executor, method=method)   # warm
        walls[method], runs[method] = bench_batch(graphs, keys, lams,
                                                  executor, method=method)
    k_matched = max(1, min(max_matched_k,
                           int(walls["pivot"] // max(walls["precluster"],
                                                     1e-9))))
    if k_matched > 1:
        bench_batch(graphs, keys, lams, executor, method="precluster",
                    num_samples=k_matched)                          # warm
        wall_m, res_m = bench_batch(graphs, keys, lams, executor,
                                    method="precluster",
                                    num_samples=k_matched)
    else:
        wall_m, res_m = walls["precluster"], runs["precluster"]
    cost_pivot = sum(r.cost for r in runs["pivot"])
    cost_pre = sum(r.cost for r in runs["precluster"])
    cost_pre_m = sum(r.cost for r in res_m)
    block = {
        "n_graphs": len(graphs),
        "matched_samples": k_matched,
        "per_method": {
            "pivot": {
                "wall_s": walls["pivot"],
                "total_cost": cost_pivot,
                "mean_rounds": float(np.mean(
                    [r.info["depth"] for r in runs["pivot"]])),
            },
            "precluster": {
                "wall_s": walls["precluster"],
                "total_cost": cost_pre,
                "mean_rounds": float(np.mean(
                    [r.info["depth"] for r in runs["precluster"]])),
                "matched_wall_s": wall_m,
                "matched_total_cost": cost_pre_m,
            },
        },
        # >1 means precluster leaves more disagreements than PIVOT.
        "cost_ratio_vs_pivot": cost_pre / max(1, cost_pivot),
        "cost_ratio_vs_pivot_matched": cost_pre_m / max(1, cost_pivot),
    }
    return block


def bench_serve_policy(graphs, lams, policy: str, executor: str,
                       method: str = "pivot"):
    """Stream the workload through the serving engine under a policy.

    Same graphs/keys as the one-shot passes (so results are asserted
    bit-identical to the per-graph loop), driven by ``serve_all``. Returns
    ``(wall_seconds, {uid: request}, batcher)`` — the batcher, not just
    its stats, so the JSON can also emit the cost policy's steal-pricing
    counters alongside the flush-latency telemetry.
    """
    max_wait = None if policy == "full" else 0.05
    batcher = ClusterBatcher(max_batch=32, policy=policy, max_wait=max_wait,
                             executor=executor, method=method)
    reqs = [ClusterRequest(uid=i, graph=g, key=jax.random.PRNGKey(i),
                           lam=lam)
            for i, (g, lam) in enumerate(zip(graphs, lams))]
    t0 = time.perf_counter()
    retired = serve_all(batcher, reqs)
    dt = time.perf_counter() - t0
    return dt, {r.uid: r for r in retired}, batcher


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", type=int, default=96)
    ap.add_argument("--repeat", type=int, default=3,
                    help="steady-state repeats after the cold pass")
    ap.add_argument("--executor", choices=["sync", "async", "sharded"],
                    default="sync")
    ap.add_argument("--policy", choices=list(POLICY_NAMES), default="full",
                    help="scheduling policy for the serving-style pass")
    ap.add_argument("--method", choices=list(registered_methods()),
                    default="pivot",
                    help="registered bucket program for the loop/batch/"
                         "serve passes (the method_quality block always "
                         "compares pivot vs precluster)")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep kernel block shapes per bucket tier "
                         "(after the cold/steady passes, so those stay "
                         "cold) and emit the tuning block")
    ap.add_argument("--json", default="BENCH_batch.json",
                    help="machine-readable results path ('' to skip)")
    args = ap.parse_args()

    graphs, keys, lams = make_workload(args.graphs)
    n_graphs = len(graphs)
    # One executor instance across passes — what a serving process would do.
    executor = make_executor(args.executor)

    # --- cold pass: fresh shapes, compiles included (the serving scenario) --
    mis_cache0 = int(_greedy_mis_parallel_impl._cache_size())
    t_loop, loop_res = bench_loop(graphs, keys, lams, method=args.method)
    mis_compiles = int(_greedy_mis_parallel_impl._cache_size()) - mis_cache0

    batch_cache0 = batch_mod.program_cache_size()
    t_batch, batch_res = bench_batch(graphs, keys, lams, executor,
                                     method=args.method)
    batch_compiles = batch_mod.program_cache_size() - batch_cache0
    buckets = sorted({r.info["bucket"] for r in batch_res})

    for a, b in zip(loop_res, batch_res):
        assert (a.labels == b.labels).all() and a.cost == b.cost, \
            "batch output diverged from the per-graph engine"

    print(f"workload: {n_graphs} graphs, {len(buckets)} buckets {buckets}, "
          f"executor={args.executor} method={args.method}")
    print(f"[cold]   per-graph loop: {t_loop:8.2f}s  "
          f"{n_graphs / t_loop:8.1f} graphs/s  "
          f"({mis_compiles} MIS compiles)")
    print(f"[cold]   batch engine:   {t_batch:8.2f}s  "
          f"{n_graphs / t_batch:8.1f} graphs/s  "
          f"({batch_compiles} bucket compiles)")
    print(f"[cold]   speedup: {t_loop / t_batch:.1f}x   "
          f"compile ratio: {mis_compiles}/{batch_compiles} "
          "(graphs-shapes vs buckets)")

    # --- steady state: every shape already compiled --------------------------
    loop_times = [bench_loop(graphs, keys, lams, method=args.method)[0]
                  for _ in range(args.repeat)]
    batch_times = [bench_batch(graphs, keys, lams, executor,
                               method=args.method)[0]
                   for _ in range(args.repeat)]
    t_loop_w, t_batch_w = min(loop_times), min(batch_times)
    print(f"[steady] per-graph loop: {t_loop_w:8.2f}s  "
          f"{n_graphs / t_loop_w:8.1f} graphs/s")
    print(f"[steady] batch engine:   {t_batch_w:8.2f}s  "
          f"{n_graphs / t_batch_w:8.1f} graphs/s")
    print(f"[steady] speedup: {t_loop_w / t_batch_w:.1f}x")

    assert batch_compiles <= len(buckets) + 1, (
        "bucket contract violated: compiles must track buckets, not graphs")

    # --- autotune pass: sweep kernel block shapes over the real buckets ----
    # Runs after the cold/steady passes so those numbers stay untuned and
    # comparable across PRs; the tuning block reports the per-tier winners
    # and the measured default-vs-tuned kernel speedup.
    tuning_block = {"enabled": bool(args.autotune)}
    if args.autotune:
        t0 = time.perf_counter()
        warmer = ClusterBatcher(max_batch=32, executor=args.executor,
                                method=args.method)
        warmer.warmup(graphs, autotune=True)
        tuning_block.update(warmer.stats.tuning or {})
        tuning_block["sweep_wall_s"] = time.perf_counter() - t0
        for rec in tuning_block.get("sweep_log", []):
            print(f"[tuning] {rec['kernel']:12s} "
                  f"{rec['R']}x{rec['W']} B={rec['batch']:4d} "
                  f"winner={rec['winner']:4d} "
                  f"default={rec['default_ms']:7.2f}ms "
                  f"tuned={rec['winner_ms']:7.2f}ms "
                  f"speedup={rec['speedup_vs_default']:.2f}x")

    # --- method quality: disagreement cost per method at matched wall ------
    method_quality = bench_method_quality(graphs, keys, lams, executor)
    mq_pre = method_quality["per_method"]["precluster"]
    print(f"[quality] precluster/pivot cost ratio: "
          f"{method_quality['cost_ratio_vs_pivot']:.3f} (1 sample), "
          f"{method_quality['cost_ratio_vs_pivot_matched']:.3f} "
          f"(best-of-{method_quality['matched_samples']} matched wall); "
          f"rounds pivot="
          f"{method_quality['per_method']['pivot']['mean_rounds']:.1f} "
          f"precluster={mq_pre['mean_rounds']:.1f}")

    # --- serving pass: same workload through the scheduler-driven engine ----
    bench_serve_policy(graphs, lams, args.policy, args.executor,
                       method=args.method)  # warm
    t_serve, served, serve_batcher = bench_serve_policy(
        graphs, lams, args.policy, args.executor, method=args.method)
    serve_stats = serve_batcher.stats
    for uid, a in enumerate(loop_res):
        b = served[uid].result
        assert (a.labels == b.labels).all() and a.cost == b.cost, \
            "serving-policy output diverged from the per-graph engine"
    print(f"[serve]  policy={args.policy:9s} {n_graphs / t_serve:8.1f} "
          f"graphs/s  flushes={serve_stats.flushes} "
          f"(deadline={serve_stats.deadline_flushes}, "
          f"stolen={serve_stats.stolen_requests})")
    print(f"[serve]  host packing: build "
          f"{serve_stats.latency.total_build_s / t_serve * 100:5.1f}% of "
          f"wall (admission)  assemble "
          f"{serve_stats.latency.total_assemble_s / t_serve * 100:5.1f}% "
          "(flush path)")

    if args.json:
        payload = {
            "bench": "batch",
            "executor": args.executor,
            "policy": args.policy,
            "method": args.method,
            "n_graphs": n_graphs,
            "n_buckets": len(buckets),
            "cold": {
                "loop_s": t_loop,
                "batch_s": t_batch,
                "loop_gps": n_graphs / t_loop,
                "batch_gps": n_graphs / t_batch,
                "speedup": t_loop / t_batch,
                "mis_compiles": mis_compiles,
                "batch_compiles": batch_compiles,
            },
            "steady": {
                "loop_gps": n_graphs / t_loop_w,
                "batch_gps": n_graphs / t_batch_w,
                "speedup": t_loop_w / t_batch_w,
                "batch_s_p50": float(np.percentile(batch_times, 50)),
                "batch_s_p99": float(np.percentile(batch_times, 99)),
            },
        }
        serve_payload = {
            "policy": args.policy,
            "gps": n_graphs / t_serve,
            "flushes": serve_stats.flushes,
            "deadline_flushes": serve_stats.deadline_flushes,
            "coalesced_flushes": serve_stats.coalesced_flushes,
            "stolen_requests": serve_stats.stolen_requests,
            "padded_slots": serve_stats.padded_slots,
            "flush_latency": serve_stats.latency.summary(),
            # The two host packing streams of the admission-time split as
            # fractions of the serve wall: build = per-request row builds
            # at admission, assemble = per-bucket staging assembly on the
            # flush path (the only packing cost left there).
            "host_pack": {
                "build_wall_s": serve_stats.latency.total_build_s,
                "assemble_wall_s": serve_stats.latency.total_assemble_s,
                "build_frac": serve_stats.latency.total_build_s / t_serve,
                "assemble_frac":
                    serve_stats.latency.total_assemble_s / t_serve,
            },
            # Result-cache counters ride along for cross-PR tracking even
            # though this workload is all-unique (hits stay 0 here; the
            # repeat-traffic scenario in serve_bench exercises them).
            "cache_hits": serve_stats.cache_hits,
            "subscribed": serve_stats.subscribed,
        }
        if serve_stats.result_cache is not None:
            rc = serve_stats.result_cache
            serve_payload["result_cache"] = {
                "hits": rc.hits, "misses": rc.misses,
                "evictions": rc.evictions, "collisions": rc.collisions,
                "insertions": rc.insertions, "entries": rc.entries,
                "bytes": rc.bytes,
            }
        cost_stats = getattr(serve_batcher.policy, "cost_stats", None)
        if cost_stats is not None:      # cost policy: steal pricing counters
            serve_payload["cost"] = cost_stats()
        payload["serve"] = serve_payload
        payload["method_quality"] = method_quality
        payload["tuning"] = tuning_block
        # Host metadata + tuning-cache state: makes the perf trajectory
        # comparable across machines.
        from repro.kernels.autotune import host_provenance
        payload["provenance"] = host_provenance()
        # program_cache now also reports lifetime compiles and the pinned
        # bucket shapes (the scheduler's eviction hints).
        payload["program_cache"] = program_cache_info()
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
