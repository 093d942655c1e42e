"""Arboricity estimation via degeneracy peeling.

The degeneracy ``d`` of a graph satisfies ``λ ≤ d ≤ 2λ − 1`` (Nash–Williams),
so it is a 2-approximation of arboricity usable in the Algorithm 4 degree
threshold — only the constant in ``O(λ/ε)`` moves.

Two implementations:
* :func:`degeneracy_peel` — the exact degeneracy: the largest ``k`` whose
  k-core is not empty, by a level-synchronous k-core peel in numpy. The
  k-core does not depend on the order in which vertices are stripped, so
  each level ``k`` strips every live vertex of degree ≤ k at once (one CSR
  gather and one ``bincount`` per round) instead of one min-degree vertex
  at a time. A frontier too small to pay for a round — the tail of a level,
  a chain that peels one vertex per round — cascades vertex by vertex over
  Python lists of the CSR slices within the same level. It returns the
  counts of both steps, which the serving layer records. It replaced a
  heap-based min-degree peel, one Python step per directed edge, which
  the tests keep as the oracle it must equal.
* :func:`degeneracy_parallel` — round-parallel doubling peeling: repeatedly
  strip all vertices of degree ≤ k, doubling k when the graph stops
  shrinking; returns an upper bound ≤ 2d in O(log²) rounds (standard MPC
  peeling; each strip round is one convergecast).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph


# Frontier size (vertices plus the CSR entries they read) below which the
# cascade strips vertex by vertex instead of paying a numpy round: a round
# costs tens of microseconds whatever its size, a vertex about one.
SMALL_FRONTIER = 64


class Peel(NamedTuple):
    """Exact degeneracy and how the peel reached it."""

    d: int        # degeneracy: the largest k whose k-core is not empty
    rounds: int   # vectorized strip rounds
    single: int   # vertices stripped one at a time by the cascade


def degeneracy_peel(g: Graph) -> Peel:
    """Exact degeneracy by a level-synchronous k-core peel.

    At level ``k`` every live vertex of degree ≤ k is stripped; the
    vertices whose degree the strip drops to ≤ k form the next frontier of
    the same level, so only touched neighbours are examined. When a level
    has nothing left, ``k`` rises to the least live degree; the answer is
    the last ``k`` at which anything was stripped. A frontier of at most
    :data:`SMALL_FRONTIER` vertices plus CSR entries cascades vertex by
    vertex, as long as its pending part stays that small.
    """
    n = g.n
    deg = np.asarray(g.deg).astype(np.int64)
    row = np.asarray(g.row_offsets)
    dst = np.asarray(g.dst)
    lens = np.diff(row[:n + 1]).astype(np.int64)
    alive = np.ones(n + 1, dtype=bool)
    alive[n] = False                  # the pad vertex of the CSR rows
    live = np.arange(n)
    front = live[:0]
    row_l = None
    k = rounds = single = 0
    left = n
    while left:
        if not front.size:
            # The level is spent: raise k to the least live degree.
            live = live[alive[live]]
            dl = deg[live]
            k = int(dl.min())
            front = live[dl <= k]
        ln = lens[front]
        vol = int(ln.sum())
        if front.size + vol > SMALL_FRONTIER:
            rounds += 1
            alive[front] = False
            left -= front.size
            idx = np.arange(vol) + np.repeat(
                row[front] - (np.cumsum(ln) - ln), ln)
            nb = dst[idx]
            nb = nb[alive[nb]]
            deg -= np.bincount(nb, minlength=n)
            front = np.unique(nb[deg[nb] <= k])
            continue
        if row_l is None:
            row_l = row.tolist()
        # Cascade: a vertex joins the stack when its degree falls to k,
        # and counts as stripped from then on (its degree no longer
        # matters). ``cur`` holds the degrees this cascade changed, -1
        # for a stripped vertex; they go back to ``deg`` at the end.
        stack = front.tolist()
        cur = dict.fromkeys(stack, -1)
        out = []
        while stack and len(stack) + vol <= SMALL_FRONTIER:
            v = stack.pop()
            out.append(v)
            a, b = row_l[v], row_l[v + 1]
            vol -= b - a
            for u in dst[a:b].tolist():
                du = cur.get(u)
                if du is None:
                    if not alive[u]:
                        continue
                    du = int(deg[u])
                elif du < 0:
                    continue
                du -= 1
                if du == k:
                    stack.append(u)
                    vol += row_l[u + 1] - row_l[u]
                    du = -1
                cur[u] = du
        alive[out] = False
        left -= len(out)
        single += len(out)
        deg[np.fromiter(cur, np.int64, len(cur))] = np.fromiter(
            cur.values(), np.int64, len(cur))
        front = np.array(stack, dtype=np.int64)
    return Peel(k, rounds, single)


@partial(jax.jit, static_argnames=("max_iters",))
def _peel(g: Graph, max_iters: int = 128) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Doubling peel: returns (k_bound, rounds). λ ≤ degeneracy ≤ k_bound."""
    n = g.n

    def live_deg(alive):
        dst_ok = g.dst < n
        dst_idx = jnp.minimum(g.dst, n - 1)
        contrib = (dst_ok & alive[dst_idx]).astype(jnp.int32)
        return jnp.zeros((n + 1,), jnp.int32).at[jnp.minimum(g.src, n)].add(
            contrib
        )[:n]

    def body(state):
        alive, k, rounds, _ = state
        deg = live_deg(alive)
        strip = alive & (deg <= k)
        new_alive = alive & ~strip
        stalled = ~jnp.any(strip)
        new_k = jnp.where(stalled, k * 2, k)
        return new_alive, new_k, rounds + 1, jnp.any(new_alive)

    def cond(state):
        _, _, rounds, more = state
        return more & (rounds < max_iters)

    alive0 = jnp.ones((n,), bool)
    _, k, rounds, _ = jax.lax.while_loop(
        cond, body, (alive0, jnp.int32(1), jnp.int32(0), jnp.bool_(n > 0))
    )
    return k, rounds


def degeneracy_parallel(g: Graph) -> Tuple[int, int]:
    """(upper bound on degeneracy, peel rounds used)."""
    k, rounds = _peel(g)
    return int(k), int(rounds)


def arboricity_bounds(g: Graph, exact: bool = True) -> Tuple[int, int]:
    """Return (lower, upper) bounds on arboricity λ.

    With ``exact`` degeneracy d: ceil((d+1)/2) ≤ λ ≤ d.
    """
    d = degeneracy_peel(g).d if exact else degeneracy_parallel(g)[0]
    lo = (d + 1 + 1) // 2
    return max(1, lo), max(1, d)


__all__ = [
    "Peel",
    "degeneracy_peel",
    "degeneracy_parallel",
    "arboricity_bounds",
]
