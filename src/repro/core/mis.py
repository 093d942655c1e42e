"""Randomized greedy MIS — sequential oracle and round-parallel simulation.

Greedy MIS w.r.t. a permutation π (paper footnote 2): iterate vertices in
π-order; add a vertex iff no earlier neighbour was added. The parallel
simulation repeatedly selects *local minima* of the permutation rank among
undecided vertices — by Fischer–Noever (Theorem 5) the number of parallel
rounds equals the longest dependency path, which is ``O(log n)`` w.h.p., and
the resulting set is **identical** to the sequential greedy MIS for the same
π (tested bit-exactly).

PIVOT's cluster assignment (each removed vertex joins the *first* pivot in
π-order among its neighbours) equals "min-rank MIS neighbour" and is computed
in a single post-pass (:func:`assign_to_min_rank_mis_neighbor`) — assigning
during the rounds would be wrong, since a smaller-rank MIS neighbour of a
vertex can become a winner in a *later* round than a larger-rank one.

The per-round hot loop — every undecided vertex computing the min rank over
its undecided neighbours — is exposed as :func:`neighbor_min_ranks`; the
Pallas TPU kernel ``repro.kernels.neighbor_min`` implements the same contract
with CSR tiles staged through VMEM and can be swapped in via ``use_kernel``.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph

# Vertex status codes.
UNDECIDED = jnp.int32(0)
IN_MIS = jnp.int32(1)
REMOVED = jnp.int32(2)

INF_RANK = jnp.int32(2**31 - 1)


def random_permutation_ranks(n: int, key: jax.Array) -> jnp.ndarray:
    """rank[v] = position of v in a uniform-at-random permutation π."""
    perm = jax.random.permutation(key, n)
    ranks = jnp.zeros((n,), jnp.int32).at[perm].set(jnp.arange(n, dtype=jnp.int32))
    return ranks


@lru_cache(maxsize=1024)
def _perm_ranks_batch_for(n: int):
    # One jitted vmap per vertex count, held in a bounded LRU: a long-lived
    # server seeing arbitrarily many distinct n must not accumulate one
    # resident executable per size forever (evicted sizes just recompile).
    def rank_draw(key):
        return random_permutation_ranks(n, key)

    return jax.jit(jax.vmap(rank_draw))


@lru_cache(maxsize=1024)
def _perm_ranks_single_for(n: int):
    # k=1 fastpath: the broadcast to a (1, n) batch happens inside the
    # trace, so a single-sample caller pays one dispatch instead of a host
    # jnp.stack plus the vmapped call. Bit-identical to the batch of one.
    def rank_draw(key):
        return random_permutation_ranks(n, key)[None]

    return jax.jit(rank_draw)


def random_permutation_ranks_batch(n: int, keys) -> jax.Array:
    """Ranks for several keys of one graph in a single fused dispatch.

    Row ``i`` is bit-identical to ``random_permutation_ranks(n, keys[i])``
    (``jax.random.permutation`` is deterministic per key under ``vmap``;
    asserted in ``tests/test_mis.py``). The batch-engine packer uses this
    for the best-of-k sample keys of each graph: one async dispatch per
    graph instead of ``k`` eager permutation calls, which keeps host-side
    packing off the device's critical path. A single-key list (best-of-1,
    the serving default) skips the host-side key stack entirely — that
    stack is pure dispatch overhead when admission-time row builds issue
    one rank op per request.
    """
    if not isinstance(keys, jax.Array):
        keys = list(keys)
        if len(keys) == 1:
            return _perm_ranks_single_for(n)(keys[0])
        keys = jnp.stack(keys)
    return _perm_ranks_batch_for(n)(keys)


# ---------------------------------------------------------------------------
# Sequential oracle (numpy) — ground truth for tests.
# ---------------------------------------------------------------------------


def greedy_mis_sequential(g: Graph, ranks: np.ndarray) -> np.ndarray:
    """Sequential greedy MIS; returns bool mask of MIS membership."""
    n = g.n
    ranks = np.asarray(ranks)
    order = np.argsort(ranks, kind="stable")
    dst = np.asarray(g.dst)
    row = np.asarray(g.row_offsets)
    in_mis = np.zeros(n, dtype=bool)
    blocked = np.zeros(n, dtype=bool)
    for v in order:
        if blocked[v]:
            continue
        in_mis[v] = True
        for e in range(row[v], row[v + 1]):
            blocked[dst[e]] = True
    return in_mis


def pivot_sequential(g: Graph, ranks: np.ndarray) -> np.ndarray:
    """Sequential PIVOT (Ailon–Charikar–Newman): cluster labels per vertex."""
    n = g.n
    order = np.argsort(np.asarray(ranks), kind="stable")
    dst = np.asarray(g.dst)
    row = np.asarray(g.row_offsets)
    labels = np.full(n, -1, dtype=np.int32)
    for v in order:
        if labels[v] >= 0:
            continue
        labels[v] = v
        for e in range(row[v], row[v + 1]):
            u = dst[e]
            if u < n and labels[u] < 0:
                labels[u] = v
    return labels


# ---------------------------------------------------------------------------
# Round-parallel simulation (JAX).
# ---------------------------------------------------------------------------


def _masked_segment_min(g: Graph, vals_at_dst: jnp.ndarray,
                        mask_at_dst: jnp.ndarray) -> jnp.ndarray:
    """segment-min over COO edges: per src vertex, min of vals[dst] | mask[dst]."""
    n = g.n
    dst_ok = g.dst < n
    dst_idx = jnp.minimum(g.dst, n - 1)
    vals = jnp.where(dst_ok & mask_at_dst[dst_idx], vals_at_dst[dst_idx], INF_RANK)
    seg = jax.ops.segment_min(
        vals, jnp.minimum(g.src, n), num_segments=n + 1, indices_are_sorted=True
    )
    return seg[:n]


def neighbor_min_ranks(g: Graph, ranks: jnp.ndarray, active: jnp.ndarray,
                       use_kernel: bool = False,
                       ell: jnp.ndarray | None = None) -> jnp.ndarray:
    """For every vertex: min rank over *active* neighbours (INF if none).

    ``ell`` is the precomputed ELL adjacency for the Pallas kernel path
    (built once per MIS run, outside the round loop).
    """
    if use_kernel:
        from repro.kernels import ops as _kops  # local import: kernels optional
        from repro.kernels.neighbor_min import ell_from_graph, pad_state

        if ell is None:
            ell = ell_from_graph(g)
        rp, ap = pad_state(jnp.asarray(ranks, jnp.int32), active)
        return _kops.neighbor_min_ell(ell, rp, ap)
    return _masked_segment_min(g, ranks, active)


class MISState(NamedTuple):
    status: jnp.ndarray      # (n,) int32 in {UNDECIDED, IN_MIS, REMOVED}
    rounds: jnp.ndarray      # scalar int32 — parallel rounds executed


def _mis_round(g: Graph, ranks: jnp.ndarray, state: MISState,
               eligible: jnp.ndarray, use_kernel: bool = False,
               ell: jnp.ndarray | None = None) -> MISState:
    """One parallel round restricted to ``eligible`` vertices.

    Local minima among undecided∩eligible join the MIS; their undecided
    neighbours (eligible or not) are removed.
    """
    und = (state.status == UNDECIDED) & eligible
    nmin = neighbor_min_ranks(g, ranks, und, use_kernel=use_kernel, ell=ell)
    winners = und & (ranks < nmin)

    # Any undecided vertex adjacent to a winner is removed.
    wmin = _masked_segment_min(g, ranks, winners)
    hit = (state.status == UNDECIDED) & (~winners) & (wmin < INF_RANK)

    status = jnp.where(winners, IN_MIS, state.status)
    status = jnp.where(hit, REMOVED, status)
    return MISState(status=status, rounds=state.rounds + 1)


@partial(jax.jit, static_argnames=("use_kernel", "ell_width"))
def _greedy_mis_parallel_impl(g: Graph, ranks: jnp.ndarray,
                              eligible: jnp.ndarray | None,
                              use_kernel: bool, ell_width: int) -> MISState:
    n = g.n
    if eligible is None:
        eligible = jnp.ones((n,), bool)
    status0 = jnp.where(eligible, UNDECIDED, REMOVED)
    init = MISState(status=status0, rounds=jnp.int32(0))

    ell = None
    if use_kernel:
        from repro.kernels.neighbor_min import ell_from_graph

        # Built once, loop-invariant (lives outside the while body).
        ell = ell_from_graph(g, width=ell_width)

    def cond(state: MISState):
        return jnp.any(state.status == UNDECIDED)

    def body(state: MISState):
        return _mis_round(g, ranks, state, eligible, use_kernel=use_kernel,
                          ell=ell)

    return jax.lax.while_loop(cond, body, init)


def greedy_mis_parallel(g: Graph, ranks: jnp.ndarray,
                        eligible: jnp.ndarray | None = None,
                        use_kernel: bool = False) -> MISState:
    """Full round-parallel greedy MIS via ``lax.while_loop``.

    ``eligible`` restricts the instance to an induced subgraph (used by the
    Theorem 26 degree cap); ineligible vertices start REMOVED and never
    participate. Returns final state; ``state.rounds`` is the dependency
    depth actually realized (Fischer–Noever: O(log n) w.h.p.).
    """
    ell_width = max(1, g.max_degree()) if use_kernel else 0
    return _greedy_mis_parallel_impl(g, ranks, eligible, use_kernel, ell_width)


def assign_to_min_rank_mis_neighbor(g: Graph, ranks: jnp.ndarray,
                                    in_mis: jnp.ndarray) -> jnp.ndarray:
    """PIVOT post-pass: label every vertex with its min-rank MIS neighbour.

    MIS vertices label themselves. Non-MIS vertices take the MIS neighbour of
    minimum rank (maximality guarantees one exists). One MPC round
    (convergecast) in the cost model.
    """
    n = g.n
    wmin = _masked_segment_min(g, ranks, in_mis)
    rank_to_v = jnp.zeros((n,), jnp.int32).at[ranks].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    pivot = rank_to_v[jnp.minimum(wmin, n - 1)]
    own = jnp.arange(n, dtype=jnp.int32)
    return jnp.where(in_mis, own, jnp.where(wmin < INF_RANK, pivot, own))


def greedy_mis_rounds_trace(g: Graph, ranks: jnp.ndarray,
                            max_rounds: int = 100_000) -> Tuple[MISState, list]:
    """Python-stepped variant that records per-round stats (for benchmarks)."""
    n = g.n
    state = MISState(status=jnp.zeros((n,), jnp.int32), rounds=jnp.int32(0))
    eligible = jnp.ones((n,), bool)
    step = jax.jit(lambda s: _mis_round(g, ranks, s, eligible))
    trace = []
    for _ in range(max_rounds):
        und = int(jnp.sum(state.status == UNDECIDED))
        if und == 0:
            break
        state = step(state)
        trace.append(
            {
                "round": int(state.rounds),
                "undecided_before": und,
                "mis_size": int(jnp.sum(state.status == IN_MIS)),
            }
        )
    return state, trace


def dependency_depth(g: Graph, ranks) -> int:
    """Longest dependency path realized by the parallel simulation (= rounds)."""
    state = greedy_mis_parallel(g, jnp.asarray(ranks, jnp.int32))
    return int(state.rounds)


__all__ = [
    "UNDECIDED",
    "IN_MIS",
    "REMOVED",
    "INF_RANK",
    "MISState",
    "random_permutation_ranks",
    "greedy_mis_sequential",
    "pivot_sequential",
    "greedy_mis_parallel",
    "greedy_mis_rounds_trace",
    "assign_to_min_rank_mis_neighbor",
    "neighbor_min_ranks",
    "dependency_depth",
    "_mis_round",
    "_masked_segment_min",
]
