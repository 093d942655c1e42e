"""Clustering-as-a-service demo: streaming graphs through the engine API.

Simulates the north-star serving workload — a stream of small similarity
graphs (per-band near-dup buckets) arriving one at a time — under the
scheduling policies of the pluggable scheduler layer
(``repro.serve.scheduler``):

* **Full-bucket** (throughput mode): a bucket flushes only when it fills
  ``max_batch`` slots; stragglers wait for the end-of-stream drain.
* **Deadline** (latency mode): ``max_wait`` bounds how long any request
  can sit in a partial bucket; ``poll()`` flushes overdue buckets padded
  to the next power-of-two sub-batch.
* **Adaptive** (self-tuning pipelining): the deadline policy plus a
  dynamic in-flight admission window derived from observed flush latency
  — it replaces the hand-tuned ``max_in_flight`` knob. At the window,
  ``admit`` raises ``AdmissionRejected`` (here the demo just drains and
  retries — a real front-end would shed load).
* **Coalescing** (work-stealing): requests starving in a small shape
  bucket are promoted into a compatible larger bucket's flush, so no
  queue waits unboundedly behind a hot one.
* **Cost-aware coalescing** (priced work-stealing): every steal is priced
  by ``repro.serve.costmodel.FlushCostModel`` — pow2 pad inflation and
  promoted-row waste at the bucket's observed service time, plus any
  compile the inflated sub-batch would pay — and taken only when the wait
  it saves covers the bill. Its ``on_retire`` also feeds bucket-shape
  heat to the compiled-program LRU (touch/pin eviction hints).

The full-bucket/deadline drives also contrast the **async executor**
(pipelined mode): flushes are dispatched without blocking, so the engine
packs the next bucket while the previous one computes on device —
completed flushes are harvested on later ``admit``/``poll``/``flush``
calls.

Every result is bit-identical to running ``correlation_cluster`` on that
graph alone, under every policy and executor.

Run:  PYTHONPATH=src python examples/batch_serving.py
"""

import time

import jax
import numpy as np

from repro.core import build_graph
from repro.core.graph import random_arboric
from repro.serve.cluster_batcher import (
    AdmissionRejected,
    ClusterBatcher,
    ClusterRequest,
)
from repro.util import enable_compile_cache


def make_stream(n_requests: int, seed: int = 42):
    rng = np.random.default_rng(seed)
    for uid in range(n_requests):
        n = int(rng.integers(8, 64))
        edges, _ = random_arboric(n, int(rng.integers(1, 4)), rng)
        yield ClusterRequest(uid=uid, graph=build_graph(n, edges),
                             key=jax.random.PRNGKey(uid))


def drive(batcher: ClusterBatcher, n_requests: int, label: str):
    print(f"\n--- {label} ---")
    t0 = time.perf_counter()
    waits, retired = [], 0

    def account(done):
        nonlocal retired
        now = batcher.clock()   # same clock base as req.admitted_at
        for r in done:
            retired += 1
            waits.append(now - r.admitted_at)
            if retired % 25 == 0:
                print(f"  uid={r.uid:3d} n={r.graph.n:3d} "
                      f"clusters={len(np.unique(r.result.labels)):3d} "
                      f"cost={r.result.cost:4d} "
                      f"bucket={r.result.info['bucket']}")

    for req in make_stream(n_requests):
        while True:
            try:
                account(batcher.admit(req))
                break
            except AdmissionRejected:
                # Backpressure: the executor is at max_in_flight. Harvest
                # whatever finished and retry (a front-end would 429 here).
                done = batcher.retire()
                account(done)
                if not done:
                    time.sleep(0.001)   # let the device catch up
        account(batcher.poll())
    account(batcher.flush())
    dt = time.perf_counter() - t0

    s = batcher.stats
    print(f"served {retired} queries in {dt:.2f}s "
          f"({retired / dt:.1f} graphs/s)  [policy={s.policy}]")
    print(f"flushes={s.flushes} (deadline={s.deadline_flushes}, "
          f"coalesced={s.coalesced_flushes})  "
          f"buckets_seen={s.buckets_seen}  padded_slots={s.padded_slots}  "
          f"pad_vertex_waste={s.pad_vertex_waste}")
    if s.stolen_requests:
        print(f"work-stealing: {s.stolen_requests} requests promoted into "
              "larger-bucket flushes")
    if s.rejected or s.in_flight_peak:
        print(f"backpressure: rejected={s.rejected}  "
              f"in_flight_peak={s.in_flight_peak}")
    if s.latency.total_flushes:
        print(f"flush latency: wall EWMA={s.latency.ewma_wall * 1e3:.1f}ms  "
              f"assemble EWMA={s.latency.ewma_assemble * 1e3:.1f}ms"
              + (f"  build EWMA={s.latency.ewma_build * 1e3:.2f}ms"
                 if s.latency.total_builds else ""))
    print(f"max in-engine wait: {max(waits):.3f}s")


def main():
    enable_compile_cache()
    n_requests = 100
    print(f"streaming {n_requests} clustering queries (max_batch=16)...")
    drive(ClusterBatcher(max_batch=16, num_samples=2),
          n_requests, "full-bucket policy (throughput mode)")
    drive(ClusterBatcher(max_batch=16, num_samples=2, max_wait=0.05),
          n_requests, "deadline policy (max_wait=50ms, bounded tail)")
    # Pipelined serving: non-blocking flush dispatch + bounded in-flight
    # work. The same stream, same answers — packing just overlaps compute.
    drive(ClusterBatcher(max_batch=16, num_samples=2, max_wait=0.05,
                         executor="async", max_in_flight=4),
          n_requests, "async executor (pipelined flushes, max_in_flight=4)")
    # Self-tuning pipelining: the adaptive policy derives the in-flight
    # window from the flush-latency telemetry instead of the knob above.
    drive(ClusterBatcher(max_batch=16, num_samples=2, max_wait=0.05,
                         executor="async", policy="adaptive"),
          n_requests, "adaptive policy (latency-derived in-flight window)")
    # Work-stealing: requests stuck in a rare shape bucket ride a hot
    # bucket's flush at a promoted (R, W) shape — same answers, bounded
    # wait for the starved bucket.
    drive(ClusterBatcher(max_batch=16, num_samples=2, max_wait=0.05,
                         policy="coalesce"),
          n_requests, "coalescing policy (cross-bucket work-stealing)")
    # Priced work-stealing: same steals, but only when the wait saved
    # covers the pad/compile cost added; plus shape-heat eviction hints
    # to the compiled-program cache.
    cost_batcher = ClusterBatcher(max_batch=16, num_samples=2,
                                  max_wait=0.05, policy="cost")
    drive(cost_batcher, n_requests,
          "cost-aware coalescing (priced steals + eviction hints)")
    print(f"steal pricing: {cost_batcher.policy.cost_stats()}")


if __name__ == "__main__":
    main()
