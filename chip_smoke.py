"""Serve a seeded graph stream through the clustering engine on a TPU.

Usage, from the repository root:

    python chip_smoke.py [--seed 0]             # one chip
    python chip_smoke.py [--seed 0] --chips 4   # sharded executor, four chips

One chip drives the served path through its public entry points —
``ClusterBatcher.admit/poll/flush`` with the async executor, a deadline
``max_wait``, best-of-4 samples and ``max_batch=64`` — over about 2,000
seeded graphs from ``core.graph.random_arboric``, first with
``method='pivot'`` and then with ``method='precluster'``. Every answer is
checked exactly against the numpy host oracles. A subset is then served
again with ``use_kernel=True`` (the Mosaic kernels, never the interpreter)
and must match the jnp path bit for bit. ``--chips 4`` runs only the
sharded phase: ``ShardedExecutor`` over four chips against
``SyncExecutor`` on one, on the largest buckets of the same pivot stream.

Earlier lines report what ran; the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure, or a host without a TPU, exits nonzero without that line.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

REQUIRED_PLATFORM = "tpu"
NUM_SAMPLES = 4
MAX_BATCH = 64
MAX_WAIT_S = 0.25
STREAM_GRAPHS = 2000
# Vertex counts are log-uniform over 64 sizes from 32 to 4,096, not over
# every integer: the engine compiles one rank-permutation program per
# distinct vertex count (about half a second each on the chip's host, in
# the warm-up), and a cold run must fit its time limit.
SIZES = np.unique(np.rint(32 * 128 ** (np.arange(64) / 63)).astype(int))
MAX_LAMBDA = 8
BIG_GRAPHS = 3            # graphs at MAX_ROWS in the pivot stream
BIG_LAMBDA = 2
# The jnp pivot program at (B, R, W) = (256, 2^15, 32) needs 15.2 GB of
# temporaries on a v5e (compile rehearsal), so the MAX_ROWS bucket is
# served by its own engine whose max_batch fits the chip.
BIG_MAX_BATCH = 2
# precluster's common-neighbour pass holds O(B·R·W²) temporaries: at
# (32, 1024, 64) the rehearsal reports 6.5 GB, at (4, 2^15, 16) 5.9 GB —
# both under half of the v5e's HBM. Its stream stays inside those shapes.
PRECLUSTER_GRAPHS = 500
PRECLUSTER_MAX_BATCH = 8
PRECLUSTER_MAX_R = 1024
PRECLUSTER_MAX_W = 64
PRECLUSTER_MAX_LAMBDA = 4
PRECLUSTER_BIG_W = 16     # a forest at MAX_ROWS: degree cap 12 ⇒ W ≤ 16
KERNEL_MAX_BATCH = 8
KERNEL_GRAPHS_PER_BUCKET = 24
# The sharded phase serves every request of four buckets of the pivot
# stream — the fullest and the widest at the largest R below MAX_ROWS, the
# fullest with R <= 256, and MAX_ROWS — with no deadline, so the large
# buckets run full flushes (at seed 0: (4096, 32) x 153 and (4096, 64) x 56,
# up to 268 MB of ELL per flush). The MAX_ROWS engine's max_batch is the
# four-chip group pad.
SHARDED_BIG_MAX_BATCH = 4


class CompileWatch:
    """Counts XLA executables built (compiled or loaded from the
    persistent cache) and persistent-cache hits and misses."""

    _BUILD = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.built = 0
        self.hits = 0
        self.misses = 0
        self.names = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        if event == self._BUILD:
            self.built += 1
            self.names[kwargs.get("fun_name", "?")] += 1

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.built, self.hits, self.misses, self.names.copy())

    def since(self, snap) -> dict:
        built, hits, misses, names = snap
        return {"executables": self.built - built,
                "cache_hits": self.hits - hits,
                "cache_misses": self.misses - misses,
                "by_name": dict(self.names - names)}


class Smoke:
    """One process's run: the device, the counters and the report."""

    def __init__(self, seed: int):
        import jax

        from repro.core.executor import program_cache_info

        self.seed = seed
        self.device = jax.devices()[0]
        self.watch = CompileWatch(jax)
        self._programs = lambda: program_cache_info()["compiles"]

    def say(self, phase: str, **fields) -> None:
        body = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"[{phase}] {body}", flush=True)

    def peak_bytes(self, device=None) -> int:
        stats = (device or self.device).memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def timed(self, phase: str, fn, **extra):
        """Run ``fn``; report its wall, bucket programs and executables."""
        snap, programs = self.watch.snapshot(), self._programs()
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        counts = self.watch.since(snap)
        self.say(phase, wall_s=wall,
                 bucket_programs=self._programs() - programs,
                 executables=counts["executables"],
                 cache_hits=counts["cache_hits"],
                 cache_misses=counts["cache_misses"],
                 peak_bytes_in_use=self.peak_bytes(), **extra)
        return out, counts, self._programs() - programs


# -- traffic -----------------------------------------------------------------


def make_graphs(rng, count: int, sizes, max_lambda: int):
    """``count`` seeded (n, λ, edges) draws over the given vertex sizes."""
    from repro.core.graph import random_arboric

    out = []
    for _ in range(count):
        n = int(rng.choice(sizes))
        lam = int(rng.integers(1, max_lambda + 1))
        edges, _ = random_arboric(n, lam, rng)
        out.append((n, edges))
    return out


def make_requests(draws, seed: int, uid0: int = 0):
    import jax

    from repro.core import build_graph
    from repro.serve.cluster_batcher import ClusterRequest

    base = jax.random.PRNGKey(seed)
    return [ClusterRequest(uid=uid0 + i, graph=build_graph(n, edges),
                           key=jax.random.fold_in(base, uid0 + i))
            for i, (n, edges) in enumerate(draws)]


def fresh(reqs):
    """Unserved copies of requests: same graph, key and uid."""
    from repro.serve.cluster_batcher import ClusterRequest

    return [ClusterRequest(uid=r.uid, graph=r.graph, key=r.key) for r in reqs]


# -- serving -----------------------------------------------------------------


def engine(method: str, max_batch: int, use_kernel: bool = False,
           executor="async", max_wait=MAX_WAIT_S):
    from repro.serve.cluster_batcher import ClusterBatcher

    return ClusterBatcher(max_batch=max_batch, method=method,
                          num_samples=NUM_SAMPLES, use_kernel=use_kernel,
                          executor=executor, max_wait=max_wait)


def recording(executor_cls):
    """An ``executor_cls`` that logs the packed shape and the devices
    holding the outputs of every flush the engine submits through it."""

    class Recording(executor_cls):
        def __init__(self):
            super().__init__()
            self.flushes = []

        def _post_submit(self, handle):
            devices = handle._outputs[0].sharding.device_set
            self.flushes.append((handle.shape,
                                 sorted(d.id for d in devices)))
            super()._post_submit(handle)

    return Recording()


def serve(engines, reqs, route) -> None:
    """Admit ``reqs`` in order, each through ``route(req)``, ticking every
    other engine's deadline on the way; then drain every engine."""
    done = 0
    for req in reqs:
        eng = route(req)
        done += len(eng.admit(req))
        for other in engines:
            if other is not eng:
                done += len(other.poll())
    for eng in engines:
        done += len(eng.flush())
    if done != len(reqs) or not all(r.done for r in reqs):
        raise AssertionError(f"served {done} of {len(reqs)} requests")


def check_oracle(reqs, method: str) -> None:
    """Every result equals the host oracle for the sample it picked."""
    import jax

    from repro.core import build_graph
    from repro.core.api import sample_keys
    from repro.core.batch import _cost_host
    from repro.core.mis import pivot_sequential, random_permutation_ranks_batch
    from repro.core.programs import precluster_host

    for req in reqs:
        plan, res = req.plan, req.result
        n = plan.n
        picked = int(res.info.get("picked_sample", 0))
        ranks = np.asarray(random_permutation_ranks_batch(
            n, sample_keys(req.key, NUM_SAMPLES)))[picked]
        if method == "pivot":
            expect = pivot_sequential(build_graph(n, plan.canonical_edges),
                                      ranks)
        else:
            expect, rounds = precluster_host(n, plan.canonical_edges,
                                             plan.eligible, ranks)
            if res.info["depth"] != rounds:
                raise AssertionError(
                    f"{method} request {req.uid}: rounds {res.info['depth']}"
                    f" != host oracle {rounds}")
        if not np.array_equal(res.labels, expect):
            raise AssertionError(
                f"{method} request {req.uid} (n={n}, bucket={plan.bucket}): "
                "labels differ from the host oracle")
        cost = _cost_host(plan.g, np.asarray(res.labels))
        if res.cost != cost:
            raise AssertionError(
                f"{method} request {req.uid}: cost {res.cost} != host "
                f"oracle {cost}")
        jax.block_until_ready(ranks)


def check_same(got, want, what: str) -> None:
    """Two servings of the same requests agree bit for bit."""
    by_uid = {r.uid: r.result for r in want}
    for req in got:
        a, b = req.result, by_uid[req.uid]
        if not (np.array_equal(a.labels, b.labels) and a.cost == b.cost
                and a.info.get("picked_sample") == b.info.get("picked_sample")
                and a.info["depth"] == b.info["depth"]):
            raise AssertionError(f"{what}: request {req.uid} differs")


def is_big(req) -> bool:
    from repro.core.plan import MAX_ROWS

    return req.graph.n > MAX_ROWS // 2


def serve_phase(smoke, label: str, method: str, reqs, max_batch: int,
                big_max_batch: int, use_kernel: bool = False,
                executors=("async", "async"), max_wait=MAX_WAIT_S):
    """Warm up and serve ``reqs``: MAX_ROWS graphs go to an engine of
    their own. Fails if the stream compiled anything. Returns the
    engines."""
    small = [r for r in reqs if not is_big(r)]
    big = [r for r in reqs if is_big(r)]
    engines = [engine(method, max_batch, use_kernel, executors[0], max_wait)]
    if big:
        engines.append(engine(method, big_max_batch, use_kernel,
                              executors[1], max_wait))
    smoke.timed(f"{label} warm-up",
                lambda: [e.warmup([r.graph for r in rs])
                         for e, rs in zip(engines, (small, big)) if rs])
    _, counts, programs = smoke.timed(
        f"{label} stream",
        lambda: serve(engines, reqs,
                      lambda r: engines[-1] if is_big(r) else engines[0]),
        graphs=len(reqs))
    if programs or counts["executables"]:
        raise AssertionError(
            f"{label} stream compiled {programs} bucket programs and built "
            f"{counts['executables']} executables: {counts['by_name']}")
    return engines


def run_method(smoke, method: str, reqs, max_batch: int, big_max_batch: int):
    """Serve one method's stream and check it against the host oracles.
    Returns the served requests."""
    stats = [e.stats for e in serve_phase(smoke, method, method, reqs,
                                          max_batch, big_max_batch)]
    smoke.say(f"{method} served", graphs=sum(s.clustered for s in stats),
              flushes=sum(s.flushes for s in stats),
              deadline_flushes=sum(s.deadline_flushes for s in stats),
              buckets=sum(s.buckets_seen for s in stats),
              largest_bucket=max(r.plan.bucket for r in reqs))
    t0 = time.perf_counter()
    check_oracle(reqs, method)
    smoke.say(f"{method} oracle", matched=len(reqs),
              wall_s=time.perf_counter() - t0)
    return reqs


def bucket_subset(reqs, bucket_of, per_bucket=None, widest=False):
    """Requests of a few buckets: the fullest at the largest R below
    MAX_ROWS (and with ``widest`` the widest there too), the fullest with
    R ≤ 256, and the MAX_ROWS bucket — the first ``per_bucket`` of each
    (all when None)."""
    by_bucket = collections.defaultdict(list)
    for r in reqs:
        by_bucket[bucket_of(r)].append(r)
    normal = [b for b in by_bucket if not is_big(by_bucket[b][0])]
    top_r = max(b[0] for b in normal)
    picks = {max((b for b in normal if b[0] == top_r),
                 key=lambda b: len(by_bucket[b])),
             max((b for b in normal if b[0] <= 256),
                 key=lambda b: len(by_bucket[b]))}
    if widest:
        picks.add(max(b for b in normal if b[0] == top_r))
    picks |= {b for b in by_bucket if is_big(by_bucket[b][0])}
    return {b: by_bucket[b][:per_bucket] for b in sorted(picks)}


def run_kernels(smoke, method: str, served, big_max_batch: int):
    """Serve a subset again through the Mosaic kernels: bit-identical to
    the jnp answers."""
    from repro.kernels import ops

    if ops.interpret_mode():
        raise AssertionError("the kernels would run in the interpreter")
    subset = bucket_subset(served, lambda r: r.plan.bucket,
                           KERNEL_GRAPHS_PER_BUCKET)
    reqs = fresh(sorted((r for rs in subset.values() for r in rs),
                        key=lambda r: r.uid))
    serve_phase(smoke, f"{method} kernel", method, reqs, KERNEL_MAX_BATCH,
                big_max_batch, use_kernel=True)
    check_same(reqs, served, f"{method} kernel vs jnp")
    smoke.say(f"{method} kernel", interpret=ops.interpret_mode(),
              matched_jnp=len(reqs), buckets=list(subset))


def pivot_traffic(rng, seed: int):
    """The pivot stream: ``STREAM_GRAPHS`` graphs plus ``BIG_GRAPHS`` at
    MAX_ROWS, inserted at seeded places."""
    from repro.core.graph import random_arboric
    from repro.core.plan import MAX_ROWS

    draws = make_graphs(rng, STREAM_GRAPHS, SIZES, MAX_LAMBDA)
    for _ in range(BIG_GRAPHS):
        at = int(rng.integers(0, len(draws) + 1))
        draws.insert(at, (MAX_ROWS,
                          random_arboric(MAX_ROWS, BIG_LAMBDA, rng)[0]))
    return make_requests(draws, seed)


def precluster_traffic(rng, seed: int):
    """The precluster stream, cut to the shapes that fit the chip."""
    from repro.core.graph import random_arboric
    from repro.core.plan import MAX_ROWS, plan_graph

    draws = make_graphs(rng, PRECLUSTER_GRAPHS,
                        SIZES[SIZES <= PRECLUSTER_MAX_R],
                        PRECLUSTER_MAX_LAMBDA)
    draws.append((MAX_ROWS, random_arboric(MAX_ROWS, 1, rng)[0]))
    kept = []
    for r in make_requests(draws, seed, uid0=len(draws) * 10):
        R, W = plan_graph(r.graph, method="precluster").bucket
        if W <= (PRECLUSTER_BIG_W if R == MAX_ROWS else PRECLUSTER_MAX_W):
            kept.append(r)
    return kept


def run_one_chip(smoke) -> None:
    rng = np.random.default_rng(smoke.seed)
    (pivot_reqs, pre_reqs), _, _ = smoke.timed(
        "set-up", lambda: (pivot_traffic(rng, smoke.seed),
                           precluster_traffic(rng, smoke.seed)))
    smoke.say("traffic", pivot_graphs=len(pivot_reqs),
              precluster_graphs=len(pre_reqs),
              distinct_n=len({r.graph.n for r in pivot_reqs + pre_reqs}))
    served = run_method(smoke, "pivot", pivot_reqs, MAX_BATCH, BIG_MAX_BATCH)
    run_kernels(smoke, "pivot", served, BIG_MAX_BATCH)
    served = run_method(smoke, "precluster", pre_reqs, PRECLUSTER_MAX_BATCH, 1)
    run_kernels(smoke, "precluster", served, 1)


def run_sharded(smoke) -> None:
    """ShardedExecutor over four chips vs SyncExecutor on chip 0, on the
    largest buckets of the one-chip pivot stream: bit-identical, and every
    sharded flush's outputs on every chip."""
    import jax

    from repro.core.executor import ShardedExecutor, SyncExecutor
    from repro.core.plan import plan_graph

    devices = jax.devices()
    everywhere = sorted(d.id for d in devices)

    def traffic():
        reqs = pivot_traffic(np.random.default_rng(smoke.seed), smoke.seed)
        subset = bucket_subset(
            reqs, lambda r: plan_graph(r.graph, method="pivot").bucket,
            widest=True)
        return sorted((r for rs in subset.values() for r in rs),
                      key=lambda r: r.uid), subset

    (reqs, subset), _, _ = smoke.timed("set-up", traffic)
    smoke.say("traffic", graphs=len(reqs),
              buckets={b: len(rs) for b, rs in subset.items()})
    runs = {}
    for name, cls in (("sharded", ShardedExecutor), ("sync", SyncExecutor)):
        executors = [recording(cls), recording(cls)]
        mine = fresh(reqs)
        serve_phase(smoke, name, "pivot", mine, MAX_BATCH,
                    SHARDED_BIG_MAX_BATCH, executors=executors,
                    max_wait=None)
        flushes = [f for ex in executors for f in ex.flushes]
        placed = sorted({tuple(ids) for _, ids in flushes})
        largest = max((shape for shape, _ in flushes), key=np.prod)
        smoke.say(f"{name} served", flushes=len(flushes),
                  flush_shapes=sorted({shape for shape, _ in flushes}),
                  largest_flush=largest,
                  largest_ell_bytes=int(np.prod(largest)) * 4,
                  output_devices=placed)
        if name == "sharded" and (executors[0].num_devices != len(devices)
                                  or placed != [tuple(everywhere)]):
            raise AssertionError(
                f"sharded flushes placed their outputs on {placed}, not on "
                f"every one of {everywhere}")
        runs[name] = mine
    check_same(runs["sharded"], runs["sync"], "sharded vs sync")
    t0 = time.perf_counter()
    check_oracle(runs["sharded"], "pivot")
    peaks = [smoke.peak_bytes(d) for d in devices]
    smoke.say("sharded checked", matched_sync=len(reqs),
              matched_oracle=len(reqs), oracle_wall_s=time.perf_counter() - t0,
              peak_bytes_in_use=peaks)
    if not all(peaks):
        raise AssertionError(f"a device held no memory: {peaks}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph stream and request keys")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded executor phase")
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.util import enable_compile_cache
    except ImportError as err:
        print(f"chip_smoke: cannot import the clustering package: {err}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != REQUIRED_PLATFORM:
        print(f"chip_smoke: needs a {REQUIRED_PLATFORM} device; JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    smoke = Smoke(args.seed)
    smoke.say("device", platform=devices[0].platform,
              kind=devices[0].device_kind, count=len(devices),
              compile_cache=cache_dir, seed=args.seed)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_sharded(smoke)
        else:
            run_one_chip(smoke)
    except Exception:
        traceback.print_exc()
        return 1
    smoke.say("total", wall_s=time.perf_counter() - t0,
              executables=smoke.watch.built, cache_hits=smoke.watch.hits,
              cache_misses=smoke.watch.misses,
              peak_bytes_in_use=smoke.peak_bytes())
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
