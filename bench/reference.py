"""Plain reference of the served clustering path, from raw edges and keys.

It imports nothing of the program and reads nothing the program made: it
starts from a request's raw edge list, vertex count and PRNG key, and
follows the configuration's stated semantics step by step.

1. Admission and plan. Self-loops and duplicate edges are dropped. The
   arboricity bound is the exact degeneracy ``d`` (here a batched k-core
   peel; the program peels with a heap), ``λ = max(1, d)``. The degree cap
   (Theorem 26) is ``8(1+ε)/ε · λ``; a vertex of larger degree is a
   singleton and every other vertex is eligible. The bucket is
   ``R = max(8, pow2(n))`` and ``W = max(4, pow2(max eligible-induced
   degree))``.
2. The rank draw. Best-of-``k`` sample ``i`` uses ``fold_in(key, i)`` (the
   key itself when ``k = 1``); its rank of vertex ``v`` is ``v``'s position
   in ``jax.random.permutation(sample_key, n)``. JAX's PRNG defines the
   permutation, so the reference draws it with ``jax.random`` on the host's
   CPU device.
3. The rounds loop. Greedy MIS by rank over the eligible-induced graph, in
   rounds: every undecided vertex whose rank is below all its undecided
   neighbours' joins the MIS, and its undecided neighbours leave. ``rounds``
   counts the rounds in which some vertex was undecided. The MIS is the
   sequential greedy MIS of the same ranks (Fischer–Noever).
4. Capture. An MIS vertex labels itself; every other eligible vertex takes
   the MIS neighbour of least rank; an ineligible vertex labels itself. This
   is sequential PIVOT on the eligible-induced graph.
5. The cost pass. Disagreements over the full positive graph: positive
   edges cut plus negative pairs inside a cluster.
6. Best-of-``k``: the first sample of least cost wins.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

import numpy as np

INF = np.int64(2**31 - 1)


def pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def canonical_edges(n: int, edges) -> np.ndarray:
    """Undirected edges ``u < v``, without self-loops or duplicates."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
    keep = lo != hi
    code = np.unique(lo[keep] * np.int64(n) + hi[keep])
    return np.stack([code // n, code % n], axis=1)


def degrees(n: int, und: np.ndarray) -> np.ndarray:
    return np.bincount(und.ravel(), minlength=n).astype(np.int64)


def degeneracy(n: int, und: np.ndarray) -> int:
    """Exact degeneracy: the largest ``k`` whose k-core is not empty.

    Peels, in batches, every vertex whose degree among those left is at
    most ``k``, raising ``k`` to the least degree left when none is.
    """
    deg = degrees(n, und)
    alive = np.ones(n, dtype=bool)
    k = 0
    while alive.any():
        k = max(k, int(deg[alive].min()))
        while True:
            out = alive & (deg <= k)
            if not out.any():
                break
            alive &= ~out
            cut = out[und[:, 0]] | out[und[:, 1]]
            hit = und[cut].ravel()
            deg -= np.bincount(hit, minlength=n)
    return k


def doubling_degeneracy_bound(n: int, und: np.ndarray) -> int:
    """The control's bound: strip every vertex of degree at most ``k`` and
    double ``k`` whenever nothing is stripped — an upper bound below
    ``2d``, not the exact degeneracy the configuration states."""
    deg = degrees(n, und)
    alive = np.ones(n, dtype=bool)
    k = 1
    while alive.any():
        out = alive & (deg <= k)
        if not out.any():
            k *= 2
            continue
        alive &= ~out
        cut = out[und[:, 0]] | out[und[:, 1]]
        deg -= np.bincount(und[cut].ravel(), minlength=n)
    return k


@dataclasses.dataclass
class Plan:
    n: int
    m: int
    und: np.ndarray             # (m, 2) full positive graph, u < v
    lam: int
    threshold: float
    eligible: np.ndarray        # (n,) bool
    src: np.ndarray             # eligible-induced directed edges, by src
    dst: np.ndarray
    starts: np.ndarray          # (n + 1,) offsets into src/dst
    R: int
    W: int

    @property
    def kept(self) -> int:
        return len(self.src) // 2

    @property
    def high_degree(self) -> int:
        return int((~self.eligible).sum())


def plan(n: int, edges, eps: float,
         bound: Callable[[int, np.ndarray], int] = degeneracy) -> Plan:
    und = canonical_edges(n, edges)
    lam = max(1, bound(n, und))
    threshold = 8.0 * (1.0 + eps) / eps * lam
    eligible = ~(degrees(n, und) > threshold)
    kept = und[eligible[und[:, 0]] & eligible[und[:, 1]]]
    src = np.concatenate([kept[:, 0], kept[:, 1]])
    dst = np.concatenate([kept[:, 1], kept[:, 0]])
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    counts = np.bincount(src, minlength=n)
    starts = np.concatenate([[0], np.cumsum(counts)])
    wreq = int(counts.max()) if len(src) else 0
    return Plan(n=n, m=len(und), und=und, lam=lam, threshold=threshold,
                eligible=eligible, src=src, dst=dst, starts=starts,
                R=max(8, pow2(max(1, n))), W=max(4, pow2(max(1, wreq))))


def sample_ranks(jax, keys_by_n: Dict[int, List], k: int) -> Dict[int, np.ndarray]:
    """For each vertex count ``n``, the ``(len(keys), k, n)`` ranks of
    every request key's ``k`` samples, drawn on the CPU device. One program
    per ``n``, compiled side by side."""
    from concurrent.futures import ThreadPoolExecutor

    from jax.sharding import SingleDeviceSharding

    on_cpu = SingleDeviceSharding(jax.devices("cpu")[0])

    def draw(key, n):
        subs = [key] if k <= 1 else [jax.random.fold_in(key, i)
                                     for i in range(k)]
        return jax.numpy.stack([jax.random.permutation(s, n) for s in subs])

    def ranks_for(item):
        n, keys = item
        raw = np.stack([np.asarray(x) for x in keys])
        fn = jax.jit(jax.vmap(lambda key: draw(key, n))).lower(
            jax.ShapeDtypeStruct(raw.shape, raw.dtype, sharding=on_cpu)
        ).compile()
        perms = np.asarray(fn(jax.device_put(raw, on_cpu))).astype(np.int64)
        ranks = np.empty(perms.shape, dtype=np.int64)
        idx = np.broadcast_to(np.arange(n, dtype=np.int64), perms.shape)
        np.put_along_axis(ranks, perms, idx, axis=2)
        return n, ranks

    with ThreadPoolExecutor(min(8, max(1, len(keys_by_n)))) as pool:
        return dict(pool.map(ranks_for, keys_by_n.items()))


def _neighbour_min(p: Plan, ranks: np.ndarray, active: np.ndarray):
    """Per vertex: least rank over its active eligible-induced neighbours."""
    out = np.full(p.n, INF, dtype=np.int64)
    if not len(p.src):
        return out
    vals = np.where(active[p.dst], ranks[p.dst], INF)
    rows = np.flatnonzero(p.starts[1:] > p.starts[:-1])
    out[rows] = np.minimum.reduceat(vals, p.starts[rows])
    return out


def pivot(p: Plan, ranks: np.ndarray):
    """Labels and rounds of one sample's ranks (steps 3 and 4)."""
    undecided = p.eligible.copy()
    in_mis = np.zeros(p.n, dtype=bool)
    rounds = 0
    while undecided.any():
        winners = undecided & (ranks < _neighbour_min(p, ranks, undecided))
        hit = undecided & ~winners & (_neighbour_min(p, ranks, winners) < INF)
        in_mis |= winners
        undecided &= ~(winners | hit)
        rounds += 1
    labels = np.arange(p.n, dtype=np.int64)
    wmin = _neighbour_min(p, ranks, in_mis)
    vertex_of_rank = np.empty(p.n, dtype=np.int64)
    vertex_of_rank[ranks] = np.arange(p.n)
    take = p.eligible & ~in_mis & (wmin < INF)
    labels[take] = vertex_of_rank[wmin[take]]
    return labels, rounds


def cost(p: Plan, labels: np.ndarray) -> int:
    """Disagreements over the full positive graph (step 5)."""
    intra_pos = int((labels[p.und[:, 0]] == labels[p.und[:, 1]]).sum())
    sizes = np.bincount(labels, minlength=p.n).astype(np.int64)
    intra_pairs = int((sizes * (sizes - 1) // 2).sum())
    return (p.m - intra_pos) + (intra_pairs - intra_pos)


def solve(p: Plan, ranks_k: np.ndarray) -> Dict:
    """Best of the samples' answers (step 6)."""
    best = None
    for i, ranks in enumerate(ranks_k):
        labels, rounds = pivot(p, ranks)
        c = cost(p, labels)
        if best is None or c < best["cost"]:
            best = {"labels": labels, "cost": c, "picked": i,
                    "rounds": rounds}
    return best


def sequential_pivot(p: Plan, ranks: np.ndarray) -> np.ndarray:
    """Sequential PIVOT on the eligible-induced graph, for the self-checks:
    each unlabelled eligible vertex in rank order becomes a pivot and takes
    its unlabelled eligible neighbours."""
    labels = np.full(p.n, -1, dtype=np.int64)
    for v in np.argsort(ranks, kind="stable"):
        if not p.eligible[v]:
            labels[v] = v
        elif labels[v] < 0:
            labels[v] = v
            for u in p.dst[p.starts[v]:p.starts[v + 1]]:
                if labels[u] < 0:
                    labels[u] = v
    return labels
