"""Forest (λ=1) special case: matchings ⇒ optimum correlation clustering.

    PYTHONPATH=src python examples/forest_clustering.py
"""

import jax
import numpy as np

from repro.core import (build_graph, correlation_cluster, matching_size,
                        max_matching_forest)
from repro.core.graph import random_forest
from repro.util import enable_compile_cache


def main():
    enable_compile_cache()
    rng = np.random.default_rng(1)
    g = build_graph(5_000, random_forest(5_000, rng))
    exact = correlation_cluster(g, method="forest_exact")
    approx = correlation_cluster(g, method="forest_approx",
                                 key=jax.random.PRNGKey(0))
    m_star = matching_size(max_matching_forest(g))
    print(f"forest n=5000 m={g.m}, max matching = {m_star}")
    print(f"exact   cost={exact.cost}  (= m − |M*| = {g.m - m_star})")
    print(f"approx  cost={approx.cost}  ratio="
          f"{approx.cost / max(1, exact.cost):.4f}  "
          f"rounds={approx.info['rounds']}")


if __name__ == "__main__":
    main()
