"""LFR benchmark graphs (Lancichinetti, Fortunato and Radicchi, Phys. Rev. E
78, 046110, 2008), over the grid of Lancichinetti and Fortunato, Phys. Rev.
E 80, 056117 (2009), Figs. 2-3.

One graph follows the 2008 construction:

1. Degrees from a power law of exponent ``tau1`` on ``[k_min, k_max]``,
   ``k_min`` solved so that the law's mean is ``k_avg``; each draw is
   rounded to the nearest integer.
2. Community sizes from a power law of exponent ``tau2`` on
   ``[s_min, s_max]`` (log-uniform at ``tau2 = 1``), rounded, drawn until
   they cover ``n``; the last is cut to fit, and a remainder too small to
   be a community is spread one vertex at a time over communities below
   ``s_max``.
3. Each vertex keeps ``round((1 - mu) k)`` of its ``k`` links inside its
   community and the rest outside. Vertices are placed in descending
   internal degree (random ties), each on a free place drawn uniformly among
   the communities larger than its internal degree.
4. Inside each community the internal degrees are wired by Havel-Hakimi
   and then mixed by degree-preserving double-edge swaps; between
   communities, a configuration model over the external stubs, whose
   self-loops, repeated edges and edges inside one community are rewired
   by double-edge swaps with random external edges.

What the construction cannot honour is dropped, never added: see
``ASSUMED``, which the configuration file copies. Vertex ids are permuted at
the end, so no id order carries the communities.
"""

from __future__ import annotations

import numpy as np

ASSUMED = {
    "k_min": "solved by bisection so the continuous power law on [k_min, k_max] has mean k_avg; draws rounded to the nearest integer",
    "sizes": "continuous power law (log-uniform at tau2 1) on [s_min, s_max], rounded; drawn until they cover n, the last cut to fit; a remainder below s_min is spread one vertex at a time over communities below s_max",
    "parity": "a community whose internal degrees sum odd moves one stub of a random member outside; an odd external sum drops one stub of a random vertex",
    "assignment": "vertices in descending internal degree, random ties, each to a uniform free place among communities larger than its internal degree; with none free, the largest community with room, internal degree cut to its size - 1 and the rest moved outside",
    "internal wiring": "Havel-Hakimi per community (stubs a non-graphical sequence leaves are dropped), then 10 rounds of degree-preserving double-edge swaps inside each community",
    "external wiring": "configuration model; self-loops, repeats and edges inside one community rewired by up to 50 rounds of double-edge swaps with random external edges; what is left of them is dropped",
    "ids": "vertex ids permuted at the end; communities are not reported",
}

MIX_ROUNDS = 10
REWIRE_ROUNDS = 50


def solve_k_min(k_avg: float, k_max: float, tau: float) -> float:
    """The ``k_min`` whose power law on ``[k_min, k_max]`` has mean
    ``k_avg``."""
    def mean(a: float) -> float:
        x = np.linspace(a, k_max, 20001)
        p = x ** -tau
        return float(np.trapezoid(x * p, x) / np.trapezoid(p, x))

    lo, hi = 1e-3, k_avg
    for _ in range(100):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if mean(mid) < k_avg else (lo, mid)
    return 0.5 * (lo + hi)


def power_law(lo: float, hi: float, tau: float, size: int, rng):
    """Continuous power-law draws ``p(x) ∝ x^-tau`` on ``[lo, hi]``."""
    u = rng.random(size)
    if abs(tau - 1.0) < 1e-12:
        return lo * (hi / lo) ** u
    a, b = lo ** (1.0 - tau), hi ** (1.0 - tau)
    return (a + u * (b - a)) ** (1.0 / (1.0 - tau))


def community_sizes(n: int, s_min: int, s_max: int, tau: float, rng):
    draws = np.rint(power_law(s_min, s_max, tau, 2 * n // s_min + 2, rng))
    sizes = draws.astype(np.int64)
    cut = int(np.searchsorted(np.cumsum(sizes), n)) + 1
    sizes = sizes[:cut]
    sizes[-1] -= int(sizes.sum()) - n
    if sizes[-1] < s_min:
        rest, sizes = int(sizes[-1]), sizes[:-1]
        for _ in range(rest):
            sizes[rng.choice(np.flatnonzero(sizes < s_max))] += 1
    return sizes


def assign(d_in: np.ndarray, sizes: np.ndarray, rng):
    """Community of each vertex, and internal degrees cut where no
    community can hold them."""
    d_in = d_in.copy()
    by_size = np.argsort(-sizes, kind="stable")
    sorted_sizes = sizes[by_size]
    free = sorted_sizes.copy()
    comm = np.empty(len(d_in), dtype=np.int64)
    for v in np.lexsort((rng.random(len(d_in)), -d_in)):
        fits = int(np.searchsorted(-sorted_sizes, -d_in[v], side="left"))
        room = np.cumsum(free[:fits])
        if fits and room[-1] > 0:
            c = int(np.searchsorted(room, rng.integers(room[-1]),
                                    side="right"))
        else:
            c = int(np.flatnonzero(free)[0])
            d_in[v] = min(d_in[v], int(sorted_sizes[c]) - 1)
        free[c] -= 1
        comm[v] = by_size[c]
    return comm, d_in


def havel_hakimi(members: np.ndarray, deg: np.ndarray, rng):
    """Edges of a simple graph on ``members`` with degrees ``deg`` (as
    near as the sequence allows), ties broken at random."""
    res = deg.astype(np.int64).copy()
    out = []
    while True:
        v = int(np.argmax(res + 0.5 * rng.random(len(res))))
        d = int(res[v])
        if d <= 0:
            break
        res[v] = -1
        others = np.flatnonzero(res > 0)
        order = np.lexsort((rng.random(len(others)), -res[others]))
        pick = others[order[:d]]
        res[pick] -= 1
        res[v] = 0
        out.append(np.stack([np.full(len(pick), v), pick], axis=1))
    if not out:
        return np.zeros((0, 2), dtype=np.int64)
    return members[np.concatenate(out)]


def canonical(e: np.ndarray) -> np.ndarray:
    return np.stack([e.min(axis=1), e.max(axis=1)], axis=1)


def swap(e: np.ndarray, i: np.ndarray, j: np.ndarray, n: int, rng,
         ok=lambda a: np.ones(len(a), dtype=bool)):
    """Double-edge swaps of edge pairs ``(i, j)``: ``(u, v), (x, y)`` become
    ``(u, x), (v, y)`` or ``(u, y), (v, x)``, kept where both new edges are
    simple, pass ``ok``, are absent and are proposed once. Returns the
    edges and which pairs swapped."""
    u, v = e[i, 0], e[i, 1]
    flip = rng.random(len(i)) < 0.5
    x = np.where(flip, e[j, 1], e[j, 0])
    y = np.where(flip, e[j, 0], e[j, 1])
    a = canonical(np.stack([u, x], axis=1))
    b = canonical(np.stack([v, y], axis=1))
    ca, cb = a[:, 0] * n + a[:, 1], b[:, 0] * n + b[:, 1]
    codes = np.sort(e[:, 0] * n + e[:, 1])
    new = np.concatenate([ca, cb])
    seen, count = np.unique(new, return_counts=True)
    once = count[np.searchsorted(seen, new)] == 1
    good = ((a[:, 0] != a[:, 1]) & (b[:, 0] != b[:, 1]) & (ca != cb)
            & ok(a) & ok(b)
            & ~np.isin(ca, codes) & ~np.isin(cb, codes)
            & once[: len(i)] & once[len(i):])
    e = e.copy()
    e[i[good]], e[j[good]] = a[good], b[good]
    return e, good


def internal_edges(comm: np.ndarray, d_in: np.ndarray, n_comm: int, rng):
    n = len(comm)
    by_comm = np.argsort(comm, kind="stable")
    bounds = np.cumsum(np.bincount(comm, minlength=n_comm))[:-1]
    parts = [havel_hakimi(members, d_in[members], rng)
             for members in np.split(by_comm, bounds)]
    e = canonical(np.concatenate(parts))
    for _ in range(MIX_ROUNDS):
        order = np.lexsort((rng.random(len(e)), comm[e[:, 0]]))
        i, j = order[0:-1:2], order[1::2]
        same = comm[e[i, 0]] == comm[e[j, 0]]
        e, _ = swap(e, i[same], j[same], n, rng)
    return e


def external_edges(comm: np.ndarray, d_ex: np.ndarray, rng):
    n = len(comm)
    stubs = np.repeat(np.arange(n), d_ex)
    if len(stubs) % 2:
        stubs = np.delete(stubs, rng.integers(len(stubs)))
    e = canonical(rng.permutation(stubs).reshape(-1, 2))
    apart = lambda a: comm[a[:, 0]] != comm[a[:, 1]]

    def bad_edges(e):
        code = e[:, 0] * n + e[:, 1]
        first = np.zeros(len(e), dtype=bool)
        first[np.unique(code, return_index=True)[1]] = True
        return np.flatnonzero(~first | ~apart(e))

    for _ in range(REWIRE_ROUNDS):
        bad = bad_edges(e)
        if not len(bad):
            break
        fine = np.setdiff1d(np.arange(len(e)), bad)
        partner = rng.choice(fine, size=min(len(bad), len(fine)),
                             replace=False)
        e, _ = swap(e, bad[: len(partner)], partner, n, rng, ok=apart)
    return np.delete(e, bad_edges(e), axis=0)


def lfr(n: int, mu: float, k_avg: float, k_max: int, tau1: float,
        tau2: float, s_min: int, s_max: int, rng):
    """One LFR graph: ``(edges, communities)``, edges as ``(m, 2)`` int64
    ``u < v``, simple, communities as one id per vertex."""
    k_min = solve_k_min(k_avg, k_max, tau1)
    k = np.rint(power_law(k_min, k_max, tau1, n, rng)).astype(np.int64)
    d_in = np.rint((1.0 - mu) * k).astype(np.int64)
    sizes = community_sizes(n, s_min, s_max, tau2, rng)
    comm, d_in = assign(d_in, sizes, rng)
    odd = np.flatnonzero(np.bincount(comm, weights=d_in,
                                     minlength=len(sizes)) % 2)
    for c in odd:
        members = np.flatnonzero((comm == c) & (d_in > 0))
        d_in[rng.choice(members)] -= 1
    d_ex = k - d_in
    e = np.concatenate([internal_edges(comm, d_in, len(sizes), rng),
                        external_edges(comm, d_ex, rng)])
    perm = rng.permutation(n)
    labels = np.empty_like(comm)
    labels[perm] = comm
    return canonical(perm[e]), labels


def grid(p: dict):
    """The grid's points in pool order: ``(n, community range name, mu)``."""
    return [(n, name, mu) for n in p["sizes"]
            for name in sorted(p["communities"]) for mu in p["mu"]
            for _ in range(p["realizations"])]


def make_pool(p: dict, rng) -> list:
    pool = []
    for n, name, mu in grid(p):
        s_min, s_max = p["communities"][name]
        edges, _ = lfr(n, mu, p["k_avg"], p["k_max"], p["tau1"], p["tau2"],
                       s_min, s_max, rng)
        pool.append((n, edges))
    return pool
