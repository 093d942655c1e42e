"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the wrappers lower the Mosaic kernels; on any other
backend (the CPU test runs) they run the same kernels in the Pallas
interpreter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import neighbor_min as _nm


# Resolved ONCE at import: ``interpret`` is a jit static arg on every
# kernel below, so re-probing the backend per call would let a mid-process
# backend flip silently retrace the hot path. A process's backend is fixed
# after jax initializes; tests override explicitly via set_interpret_mode.
_INTERPRET = jax.default_backend() != "tpu"


def interpret_mode() -> bool:
    """The interpret flag every kernel wrapper passes (import-time fixed)."""
    return _INTERPRET


def set_interpret_mode(interpret: bool | None) -> bool:
    """Override the import-time interpret resolution (tests only); returns
    the previous value. ``None`` re-resolves from the current backend."""
    global _INTERPRET
    prev = _INTERPRET
    _INTERPRET = (jax.default_backend() != "tpu") if interpret is None \
        else bool(interpret)
    return prev


def neighbor_min(g, ranks: jnp.ndarray, active: jnp.ndarray,
                 width: int | None = None) -> jnp.ndarray:
    """Graph-facing neighbour-min (contract of core.mis.neighbor_min_ranks).

    Builds the ELL view once per (graph, width); jit caching makes repeated
    MIS rounds reuse the compiled kernel.
    """
    ell = _nm.ell_from_graph(g, width=width)
    ranks_p, active_p = _nm.pad_state(jnp.asarray(ranks, jnp.int32), active)
    return _nm.neighbor_min_ell(ell, ranks_p, active_p,
                                interpret=_INTERPRET)


def neighbor_min_ell(ell, ranks_p, active_p, block_rows: int = 256):
    return _nm.neighbor_min_ell(ell, ranks_p, active_p,
                                block_rows=block_rows,
                                interpret=_INTERPRET)


def neighbor_min_ell_batch(ell, ranks_p, active_p, block_rows: int = 256):
    """Batched (B, R, W) neighbour-min — per-round hot loop of core.batch."""
    return _nm.neighbor_min_ell_batch(ell, ranks_p, active_p,
                                      block_rows=block_rows,
                                      interpret=_INTERPRET)


def label_agree_ell_batch(ell, labels_p, block_rows: int = 256):
    """Batched (B, R, W) same-label neighbour count — the device cost pass
    of core.batch (2·intra_pos when summed per graph)."""
    return _nm.label_agree_ell_batch(ell, labels_p, block_rows=block_rows,
                                     interpret=_INTERPRET)


__all__ = ["neighbor_min", "neighbor_min_ell", "neighbor_min_ell_batch",
           "label_agree_ell_batch", "interpret_mode", "set_interpret_mode"]
