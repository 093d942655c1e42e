"""Pure-jnp oracles for every Pallas kernel (no Pallas imports).

These are the correctness references the kernel tests sweep against
(`tests/test_kernels.py` asserts allclose across shapes/dtypes) and the
fallbacks used on platforms without the kernels.
"""

from __future__ import annotations

import jax.numpy as jnp

INF_I32 = jnp.int32(2**31 - 1)


def neighbor_min_ref(ell: jnp.ndarray, ranks: jnp.ndarray,
                     active: jnp.ndarray) -> jnp.ndarray:
    """Oracle for kernels.neighbor_min: min over active neighbours per row.

    ell: (n, W) neighbour ids, pad entries point at the last slot of
    ranks/active (which must be INF/inactive).
    """
    vals = jnp.take(ranks, ell, axis=0, fill_value=2**31 - 1)
    act = jnp.take(active.astype(jnp.bool_), ell, axis=0, fill_value=False)
    return jnp.min(jnp.where(act, vals, INF_I32), axis=1)


def label_agree_ref(ell: jnp.ndarray, labels_p: jnp.ndarray) -> jnp.ndarray:
    """Oracle for kernels.label_agree_ell_batch on one graph slice.

    ell: (n, W) neighbour ids, pad entries = n; labels_p: (n+1,) labels
    with slot n = -1 sentinel (never equal to a real label). Returns the
    per-vertex count of ELL neighbours sharing the vertex's label.
    """
    nbr = jnp.take(labels_p, ell, axis=0, fill_value=-1)
    own = labels_p[: ell.shape[0]]
    return jnp.sum((nbr == own[:, None]).astype(jnp.int32), axis=1)


__all__ = ["neighbor_min_ref", "label_agree_ref", "INF_I32"]
