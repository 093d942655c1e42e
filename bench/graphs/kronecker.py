"""Graph500 Kronecker graphs (graph500.org specification, section 3).

``edge_factor · 2**scale`` edges, each placed by ``scale`` independent
quadrant draws with initiator probabilities ``A``, ``B``, ``C`` (and
``D = 1 − A − B − C``), then vertex labels permuted and edge order
shuffled, as the reference generator does. Self-loops and duplicate edges
are dropped by the engine's own graph builder (and by the benchmark's
reference). All ``2**scale`` vertices are kept, isolated ones included.

The pool holds ``pool`` graphs drawn from one seeded stream.
"""

from __future__ import annotations

import numpy as np


def kronecker(scale: int, edge_factor: int, a: float, b: float, c: float,
              rng) -> np.ndarray:
    m = edge_factor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((m, 2), dtype=np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[:, 0] += ii.astype(np.int64) << bit
        ij[:, 1] += jj.astype(np.int64) << bit
    perm = rng.permutation(1 << scale)
    return perm[ij][rng.permutation(m)]


def make_pool(p: dict, rng) -> list:
    n = 1 << p["scale"]
    return [(n, kronecker(p["scale"], p["edge_factor"], p["A"], p["B"],
                          p["C"], rng)) for _ in range(p["pool"])]
