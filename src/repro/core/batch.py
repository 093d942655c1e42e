"""Batched multi-graph PIVOT engine — the public entry point.

The per-graph engine (``correlation_cluster``) retraces and recompiles for
every new ``(n, m)`` shape, which is hopeless for serving millions of small
clustering queries. The batch engine packs many small graphs into **shape
buckets** and runs each bucket through one fused device program, so compile
count is O(#buckets · log B), not O(#graphs).

The engine is layered (this module is the thin composition of the two):

* :mod:`repro.core.plan` — host side: ``plan_graph`` bucketing, the
  ``build_packed_rows`` row builder (the same call serving makes at
  admission), the ``pack_bucket`` row assembler, ``PackStats`` pad
  accounting, and the lease-based ``BucketBufferPool`` staging reuse.
* :mod:`repro.core.executor` — device side: the fused bucket programs
  (rounds body × cost pass × best-of-k, composed from the method/objective
  registries in :mod:`repro.core.programs`), the bounded LRU of compiled
  bucket programs, and the ``BucketExecutor`` implementations (``sync``
  blocking, ``async`` pipelined, ``sharded`` multi-device ``shard_map``).

Bit-exactness contract: for the same per-graph PRNG key,
``correlation_cluster_batch`` returns labels, costs and picked sample
indices **bit-identical** to per-graph ``correlation_cluster`` — under any
executor, any flush grouping (including partial deadline flushes), and
both kernel paths. Enforced in ``tests/test_batch.py``,
``tests/test_engine.py`` and ``tests/test_executor.py``.

Benchmarks: ``PYTHONPATH=src python benchmarks/batch_bench.py`` and
``benchmarks/serve_bench.py`` (both take ``--executor {sync,async,sharded}``
and emit machine-readable ``BENCH_*.json``).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import numpy as np

from .graph import Graph

# Re-exports: the pre-split module exposed all of these.
from .plan import (  # noqa: F401
    MAX_ROWS, MAX_WIDTH, MIN_ROWS, MIN_WIDTH, BucketBufferPool, GraphPlan,
    PackedRows, PackStats, StagingLease, build_packed_rows, pack_bucket,
    plan_graph, promote_plan, result_for_plan,
)
from .executor import (  # noqa: F401
    IN_MIS, REMOVED, UNDECIDED, AsyncExecutor, BucketExecutor, InFlightBucket,
    ShardedExecutor, SyncExecutor, make_executor, pack_and_submit,
    program_cache_capacity, program_cache_info, program_cache_size,
    run_bucket_program, set_program_cache_capacity,
)


def _cost_host(g: Graph, labels: np.ndarray) -> int:
    """Disagreement cost, same convention as ``core.cost.clustering_cost``.

    The serving path computes cost on device (see the fused program); this
    integer-exact numpy version is kept as the oracle the tests compare
    against.
    """
    und = g.undirected_edges()
    intra_pos = int((labels[und[:, 0]] == labels[und[:, 1]]).sum()) \
        if len(und) else 0
    pos_disagree = g.m - intra_pos
    sizes = np.bincount(labels, minlength=g.n)
    intra_pairs = int((sizes.astype(np.int64) * (sizes - 1) // 2).sum())
    return pos_disagree + (intra_pairs - intra_pos)


def _minmax_cost_host(g: Graph, labels: np.ndarray) -> int:
    """Worst-vertex disagreement oracle, alongside :func:`_cost_host`.

    Full-graph semantics (every positive edge attributed to both
    endpoints); the device ``'minmax'`` cost pass scores the
    eligible-induced capped subgraph, so the two agree exactly when the
    degree cap drops nothing (see :mod:`repro.core.programs`).
    """
    from .programs import minmax_cost_host

    return minmax_cost_host(g.n, g.undirected_edges(), labels)


def correlation_cluster_batch(
    graphs: Sequence[Graph],
    keys: Optional[Sequence[jax.Array] | jax.Array] = None,
    method: str = "pivot",
    eps: float = 2.0,
    lams: Optional[Sequence[Optional[int]]] = None,
    num_samples: int = 1,
    use_kernel: bool = False,
    pool: Optional[BucketBufferPool] = None,
    with_stats: bool = False,
    executor=None,
    objective: str = "disagree",
):
    """Cluster many graphs through the shape-bucketed batch engine.

    Args:
      graphs: the positive-edge graphs (``Graph`` instances).
      keys: per-graph PRNG keys (one key broadcast to all if a single key is
        given; defaults to ``PRNGKey(0)`` like the per-graph api).
      method: one of {METHODS} — each a registered
        :class:`~repro.core.programs.BucketProgramSpec`:
{METHOD_LINES}
      objective: one of {OBJECTIVES} — the registered cost pass scoring
        each sample before best-of-k selection:
{OBJECTIVE_LINES}
      lams: optional per-graph arboricity bounds (estimated when omitted).
      num_samples: best-of-k PIVOT — each graph is clustered under ``k``
        folded keys *within the same bucket* and the lowest-cost replica is
        selected by an on-device argmin, matching
        ``correlation_cluster(num_samples=k)`` bit-exactly (including the
        picked sample index). Must be >= 1.
      use_kernel: route neighbour-min and the cost reduction through the
        batched Pallas kernels.
      pool: optional :class:`BucketBufferPool` — reuse host staging buffers
        and run the donated device program (the serving path).
      with_stats: also return the packer's :class:`PackStats` as
        ``(results, stats)`` so callers track padding without re-deriving it.
      executor: a :class:`~repro.core.executor.BucketExecutor`, one of
        ``'sync'``/``'async'``/``'sharded'``, or None (sync). With the
        async/sharded executors all buckets are dispatched before any
        result is harvested, so packing overlaps device execution.

    Returns one :class:`repro.core.api.ClusterResult` per input graph with
    labels/costs bit-identical to per-graph ``correlation_cluster`` calls
    under the same keys (plus ``PackStats`` when ``with_stats``).
    """
    from .api import ClusterResult, sample_keys  # deferred: api imports us
    from .programs import objective_spec

    objective_spec(objective)        # fail fast, listing registered names
    if num_samples < 1:
        raise ValueError(
            f"num_samples must be >= 1, got {num_samples} (use 1 for a "
            "single PIVOT draw)")

    graphs = list(graphs)
    n_graphs = len(graphs)
    stats = PackStats()
    if n_graphs == 0:
        return ([], stats) if with_stats else []
    if keys is None:
        keys = [jax.random.PRNGKey(0)] * n_graphs
    elif isinstance(keys, jax.Array) and keys.ndim <= 1:
        # One key (legacy uint32 (2,) or typed 0-d) broadcast to all graphs.
        keys = [keys] * n_graphs
    else:
        keys = list(keys)
    if len(keys) != n_graphs:
        raise ValueError(f"{len(keys)} keys for {n_graphs} graphs")
    if lams is None:
        lams = [None] * n_graphs

    k = num_samples
    ex = make_executor(executor)
    plans = [plan_graph(g, method=method, eps=eps, lam=lam)
             for g, lam in zip(graphs, lams)]
    # The same row build admission does: ELL scatter plus an async rank
    # dispatch per graph, so the packs below only copy rows.
    for plan, key in zip(plans, keys):
        plan.rows = build_packed_rows(plan, sample_keys(key, k))

    buckets: dict = {}
    for gi, plan in enumerate(plans):
        buckets.setdefault(plan.bucket, []).append(gi)

    # Dispatch every bucket before harvesting any: with an async or sharded
    # executor the host packs bucket i+1 while bucket i computes.
    handles: List[InFlightBucket] = []
    for flush, members in enumerate(buckets.values()):
        bplans = [plans[gi] for gi in members]
        handle, bucket_stats = pack_and_submit(
            bplans, k, ex, pool=pool, use_kernel=use_kernel,
            payload=(members, bplans), track=False, objective=objective,
            flush=flush)
        handles.append(handle)
        stats.merge(bucket_stats)

    results_by_graph: dict = {}
    for handle in handles:       # submission order: block at most once each
        labels, costs, picked, rounds = handle.result()
        members, bplans = handle.payload
        for slot, (gi, plan) in enumerate(zip(members, bplans)):
            results_by_graph[gi] = result_for_plan(
                plan, labels[slot], int(costs[slot]), int(picked[slot]),
                int(rounds[slot]), k, method)

    results: List[ClusterResult] = [results_by_graph[gi]
                                    for gi in range(n_graphs)]
    return (results, stats) if with_stats else results


def _registry_doc() -> None:
    # Fill the method/objective sections of the docstring from the program
    # registry, so adding a method can never leave stale user-facing docs.
    from .programs import method_spec, objective_spec, registered_methods, \
        registered_objectives

    def names(seq):
        return "/".join(f"``'{name}'``" for name in seq)

    def lines(seq, describe):
        return "\n".join(f"        * ``'{name}'`` — {describe(name)}"
                         for name in seq)

    doc = correlation_cluster_batch.__doc__
    if doc is None:              # stripped docstrings (python -OO)
        return
    doc = doc.replace("{METHODS}", names(registered_methods()))
    doc = doc.replace("{METHOD_LINES}", lines(
        registered_methods(), lambda m: method_spec(m).description))
    doc = doc.replace("{OBJECTIVES}", names(registered_objectives()))
    doc = doc.replace("{OBJECTIVE_LINES}", lines(
        registered_objectives(), lambda o: objective_spec(o).description))
    correlation_cluster_batch.__doc__ = doc


_registry_doc()
del _registry_doc


__all__ = [
    "GraphPlan", "PackStats", "BucketBufferPool", "StagingLease",
    "plan_graph", "promote_plan", "result_for_plan",
    "correlation_cluster_batch",
    "BucketExecutor", "SyncExecutor", "AsyncExecutor", "ShardedExecutor",
    "InFlightBucket", "make_executor", "program_cache_size",
    "program_cache_capacity", "set_program_cache_capacity",
    "program_cache_info", "run_bucket_program",
    "MIN_ROWS", "MIN_WIDTH", "MAX_ROWS", "MAX_WIDTH",
]
