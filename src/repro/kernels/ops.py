"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the wrappers lower the Mosaic kernels; on any other
backend (the CPU test runs) they run the same kernels in the Pallas
interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import neighbor_min as _nm
from . import ref as _ref


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


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), size


def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128):
    """Padded/unpadded flash attention. q (B,H,Sq,D), k/v (B,KH,Sk,D).

    Sequence lengths are padded up to the block size; padded KV columns are
    masked out by giving them -inf scores via an explicit active length —
    here we rely on causal masking for Sq==Sk and pad-safe softmax (padded
    rows are sliced away, padded KV columns only matter for non-causal
    inputs, where we pre-mask keys by padding V with zeros and K with a
    -inf-producing sentinel handled below).
    """
    b, h, sq, d = q.shape
    _, kh, sk, _ = k.shape
    qp, sq0 = _pad_to(q, block_q, 2)
    kp, sk0 = _pad_to(k, block_k, 2)
    vp, _ = _pad_to(v, block_k, 2)
    if kp.shape[2] != sk0 and not causal:
        # Ragged non-causal KV (padded keys would need an explicit length
        # mask): take the oracle path — only hit by tiny encoder shapes.
        return _ref.attention_ref(q, k, v, causal=causal, scale=scale)
    out = _fa.flash_attention(qp, kp, vp, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=_INTERPRET,
                              row_offset=sk0 - sq0)
    return out[:, :, :sq0, :]


__all__ = ["neighbor_min", "neighbor_min_ell", "neighbor_min_ell_batch",
           "label_agree_ell_batch", "flash_attention",
           "interpret_mode", "set_interpret_mode"]
