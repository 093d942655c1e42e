"""Quickstart: cluster a signed graph with the paper's algorithms.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import numpy as np

from repro.core import build_graph, correlation_cluster
from repro.core.graph import random_arboric
from repro.util import enable_compile_cache


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    n, lam = 2_000, 3
    edges, _ = random_arboric(n, lam, rng)
    g = build_graph(n, edges)
    print(f"graph: n={n} m={g.m} (λ ≤ {lam} by construction)")

    # Corollary 28: degree-cap (Thm 26, ε=2) + PIVOT → 3-approx in expectation
    res = correlation_cluster(g, method="pivot", lam=lam,
                              key=jax.random.PRNGKey(0))
    print(f"pivot        cost={res.cost}  high-degree singletons="
          f"{res.info['high_degree']}  depth={res.info['depth']}")

    # Same, with Algorithm 1's phase scheduling + MPC round ledger
    res = correlation_cluster(g, method="pivot_phased", lam=lam,
                              key=jax.random.PRNGKey(0))
    print(f"pivot_phased cost={res.cost}  MPC rounds="
          f"{res.info['mpc_rounds']:.0f}  ledger={res.info['ledger']}")

    # Corollary 32: deterministic O(λ²) in O(1) rounds
    res = correlation_cluster(g, method="cliques")
    print(f"cliques      cost={res.cost}")

    # Distributed engine (edge-sharded shard_map over available devices)
    res = correlation_cluster(g, method="pivot", lam=lam,
                              key=jax.random.PRNGKey(0), distributed=True)
    print(f"distributed  cost={res.cost}  rounds={res.info['depth']}")


if __name__ == "__main__":
    main()
