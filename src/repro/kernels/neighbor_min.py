"""Pallas TPU kernels: masked neighbour-min and same-label counts over ELL.

Contract: given an ELL adjacency (each vertex's neighbour list padded to a
fixed width ``W``, pad id ``R``), per-vertex ``ranks`` and an ``active``
mask, compute for every vertex the minimum rank over its *active*
neighbours (INF if none). This is the per-round hot loop of the paper's
greedy-MIS engine — executed O(log n) times per PIVOT call on the full
edge set. ``label_agree_ell_batch`` is the cost pass over the same layout:
per vertex, how many neighbours carry its own label.

TPU layout. The paper's Theorem 26 bounds the degree of the clustered
subgraph by ``O(λ/ε)`` (12λ at ε=2), which keeps ``W`` small, so the hot
loop is a dense tile pipeline through VMEM instead of a data-dependent CSR
walk. Mosaic lowers an in-kernel gather only inside one vreg row (a
``take_along_axis`` over 128 lanes), so:

* the ELL is fed **lane-major**: ``(B, W, R)``, vertices on lanes, so a
  row block's result is a lane row written straight to a ``(B, 1, R)``
  output;
* the per-graph state table is staged whole in VMEM as ``(C, 128)`` lane
  chunks (``R·4`` bytes: 128 KiB at ``R = 2^15``), and a neighbour id
  ``v`` is read as lane ``v % 128`` of chunk ``v // 128`` — one 128-lane
  gather per chunk, selected by the chunk id. That costs ``O(R/128)``
  vector ops per ELL entry, where a one-hot compare over the whole row
  would cost ``O(R)``.

Neighbour ids ``>= R`` (the pad id) read a fixed fill value (INF for the
min, the ``-1`` sentinel for labels), so the state's pad slot ``R`` is
never staged.

Grid: ``(B, R_lanes / rb)`` with ``rb`` a multiple of 128 lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

INF_VAL = 2**31 - 1  # int32 max; Python int so pallas kernels don't capture arrays
INF = jnp.int32(INF_VAL)
LANES = 128


def _lanes(n: int) -> int:
    """Vertex axis padded to whole 128-lane rows."""
    return max(LANES, pl.cdiv(n, LANES) * LANES)


def lane_tile(block_rows: int, r: int) -> int:
    """Vertices per grid step of an R-vertex bucket: ``block_rows`` rounded
    up to whole 128-lane rows (the only tile Mosaic accepts on the lane
    axis), at most the padded row. Block sizes with one lane tile are one
    program; the autotuner and the program cache key by this tile."""
    return min(_lanes(r), pl.cdiv(max(1, block_rows), LANES) * LANES)


def _lane_major(ell: jnp.ndarray, r_lanes: int) -> jnp.ndarray:
    """(B, R, W) → (B, W, R_lanes); pad lanes hold the pad id R."""
    r = ell.shape[1]
    t = jnp.swapaxes(ell, 1, 2)
    if r_lanes > r:
        t = jnp.pad(t, ((0, 0), (0, 0), (0, r_lanes - r)), constant_values=r)
    return t


def _chunk_table(state: jnp.ndarray, r: int, r_lanes: int,
                 fill: int) -> jnp.ndarray:
    """(B, >= R) state → (B, R_lanes/128, 128): ids < R read ``state``,
    ids in [R, R_lanes) read ``fill``."""
    t = state[:, :r].astype(jnp.int32)
    if r_lanes > r:
        t = jnp.pad(t, ((0, 0), (0, r_lanes - r)), constant_values=fill)
    return t.reshape(t.shape[0], r_lanes // LANES, LANES)


def _gather_tile(table_ref, cols: jnp.ndarray, fill: int) -> jnp.ndarray:
    """``table[cols]`` for one (W, 128) tile of neighbour ids.

    One 128-lane gather per table chunk, kept where the id's chunk matches;
    ids past the table (the pad id when R is a multiple of 128) keep
    ``fill``.
    """
    hi = cols >> 7
    lo = cols & (LANES - 1)

    def body(c, acc):
        row = table_ref[0, pl.ds(c, 1), :]                  # (1, 128)
        got = jnp.take_along_axis(jnp.broadcast_to(row, cols.shape), lo,
                                  axis=1, mode="promise_in_bounds")
        return jnp.where(hi == c, got, acc)

    return jax.lax.fori_loop(0, table_ref.shape[1], body,
                             jnp.full(cols.shape, fill, jnp.int32))


def _neighbor_min_kernel(ell_ref, table_ref, out_ref):
    """One (graph, lane block): ell (1, W, rb), table (1, C, 128),
    out (1, 1, rb)."""
    for j in range(ell_ref.shape[2] // LANES):
        lanes = slice(j * LANES, (j + 1) * LANES)
        vals = _gather_tile(table_ref, ell_ref[0, :, lanes], INF_VAL)
        out_ref[0, :, lanes] = jnp.min(vals, axis=0, keepdims=True)


def _label_agree_kernel(ell_ref, table_ref, own_ref, out_ref):
    """One (graph, lane block): count neighbours whose label equals the
    vertex's own. Pad ids read the -1 sentinel, never a real label."""
    for j in range(ell_ref.shape[2] // LANES):
        lanes = slice(j * LANES, (j + 1) * LANES)
        nbr = _gather_tile(table_ref, ell_ref[0, :, lanes], -1)
        same = (nbr == own_ref[0, :, lanes]).astype(jnp.int32)
        out_ref[0, :, lanes] = jnp.sum(same, axis=0, keepdims=True)


def _specs(w: int, rb: int, c: int):
    """BlockSpecs of the (B, W, R) ELL, the (B, C, 128) table and a
    (B, 1, R) lane row, for grid point (graph, lane block)."""
    return (pl.BlockSpec((1, w, rb), lambda bi, i: (bi, 0, i)),
            pl.BlockSpec((1, c, LANES), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, 1, rb), lambda bi, i: (bi, 0, i)))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def neighbor_min_ell_batch(ell: jnp.ndarray, ranks: jnp.ndarray,
                           active: jnp.ndarray, block_rows: int = 256,
                           interpret: bool = True) -> jnp.ndarray:
    """Batched neighbour-min over shape-bucketed ELL adjacencies.

    The multi-graph PIVOT engine (``core.batch``) packs ``B`` graphs of one
    shape bucket into a single ``(B, R, W)`` ELL tensor; this kernel runs the
    per-round hot loop for the whole bucket with a 2-D ``(batch, lane_block)``
    grid, so one Mosaic program serves every graph in the bucket and the
    round loop stays on device end to end.

    Args:
      ell: (B, R, W) int32 neighbour ids; pad entries == R (per-graph pad
        slot, see ``core.batch``).
      ranks: (B, R+1) int32 — slot R is the INF pad slot.
      active: (B, R+1) bool/int32 — slot R inactive.
      block_rows: vertices per grid step, rounded up to 128 lanes.
    Returns (B, R) int32 per-vertex mins.
    """
    b, r, w = ell.shape
    r_lanes = _lanes(r)
    rb = lane_tile(block_rows, r)
    masked = jnp.where(active[:, :r].astype(bool), ranks[:, :r], INF_VAL)
    table = _chunk_table(masked, r, r_lanes, INF_VAL)
    ell_spec, table_spec, row_spec = _specs(w, rb, table.shape[1])
    out = pl.pallas_call(
        _neighbor_min_kernel,
        grid=(b, pl.cdiv(r_lanes, rb)),
        in_specs=[ell_spec, table_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, r_lanes), jnp.int32),
        interpret=interpret,
        name="neighbor_min",
    )(_lane_major(ell, r_lanes), table)
    return out[:, 0, :r]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def neighbor_min_ell(ell: jnp.ndarray, ranks: jnp.ndarray, active: jnp.ndarray,
                     block_rows: int = 256, interpret: bool = True
                     ) -> jnp.ndarray:
    """Single-graph neighbour-min: the batched kernel at ``B = 1``.

    Args:
      ell: (n_rows, W) int32 neighbour ids; pad entries == n_rows (the
        padded rank slot, see :func:`pad_state`).
      ranks: (n_rows+1,) int32 — last slot is the INF pad slot.
      active: (n_rows+1,) bool/int32 — last slot False.
    Returns (n_rows,) int32 mins.
    """
    return neighbor_min_ell_batch(ell[None], ranks[None], active[None],
                                  block_rows=block_rows,
                                  interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def label_agree_ell_batch(ell: jnp.ndarray, labels_p: jnp.ndarray,
                          block_rows: int = 256, interpret: bool = True
                          ) -> jnp.ndarray:
    """Batched same-label neighbour count over shape-bucketed ELL tensors.

    The device cost pass of ``core.batch``: one ``(batch, lane_block)`` grid
    program computes per-vertex agreement counts for every graph of a
    bucket, in the same lane-major layout as :func:`neighbor_min_ell_batch`.

    Args:
      ell: (B, R, W) int32 neighbour ids; pad entries == R.
      labels_p: (B, R+1) int32 cluster labels; slot R holds the -1 sentinel.
    Returns (B, R) int32 per-vertex same-label neighbour counts.
    """
    b, r, w = ell.shape
    r_lanes = _lanes(r)
    rb = lane_tile(block_rows, r)
    table = _chunk_table(labels_p, r, r_lanes, -1)
    own = table.reshape(b, 1, r_lanes)
    ell_spec, table_spec, row_spec = _specs(w, rb, table.shape[1])
    out = pl.pallas_call(
        _label_agree_kernel,
        grid=(b, pl.cdiv(r_lanes, rb)),
        in_specs=[ell_spec, table_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, r_lanes), jnp.int32),
        interpret=interpret,
        name="label_agree",
    )(_lane_major(ell, r_lanes), table, own)
    return out[:, 0, :r]


def pad_state(ranks: jnp.ndarray, active: jnp.ndarray):
    """Append the INF/inactive pad slot (ELL pad entries point at it)."""
    ranks_p = jnp.concatenate([ranks, jnp.array([INF], jnp.int32)])
    active_p = jnp.concatenate([active.astype(jnp.int32), jnp.zeros((1,), jnp.int32)])
    return ranks_p, active_p


def ell_from_graph(g, width: int | None = None,
                   allow_truncate: bool = False) -> jnp.ndarray:
    """Build the (n, W) ELL neighbour table from a core Graph (jnp ops).

    Pad entries point at slot ``n`` (the pad slot added by pad_state).

    A ``width`` smaller than the graph's max degree silently dropped the
    overflow neighbours historically, which corrupts neighbour-min (and with
    it the greedy MIS): a vertex can win a round only because its true
    minimum-rank neighbour fell off the row. Now this raises unless the
    caller explicitly opts in with ``allow_truncate=True`` (legitimate only
    when the dropped columns are provably never active, e.g. rows the degree
    cap already singled out). Under tracing (``g.deg`` is abstract) the check
    is skipped — jit callers are expected to pass a concrete safe width, as
    ``core.mis`` does.
    """
    n = g.n
    max_deg = None
    if not isinstance(g.deg, jax.core.Tracer):
        max_deg = int(np.asarray(g.deg).max()) if n else 0
    if width is None:
        if max_deg is None:
            raise ValueError("ell_from_graph: pass an explicit width when "
                             "the graph degrees are traced")
        width = max(1, max_deg)
    elif max_deg is not None and width < max_deg and not allow_truncate:
        raise ValueError(
            f"ell_from_graph: width={width} < max degree {max_deg} would "
            "silently drop neighbours and corrupt neighbour-min / MIS "
            "results; pass width >= max degree or allow_truncate=True")
    slot = jnp.arange(g.src.shape[0], dtype=jnp.int32) - g.row_offsets[
        jnp.minimum(g.src, n)
    ]
    ell = jnp.full((n + 1, width), n, jnp.int32)
    valid = (g.src < n) & (slot < width)
    rows = jnp.where(valid, g.src, n)
    cols = jnp.where(valid, slot, 0)
    ell = ell.at[rows, cols].set(jnp.where(valid, g.dst, n))
    return ell[:n]


__all__ = ["neighbor_min_ell", "neighbor_min_ell_batch",
           "label_agree_ell_batch", "ell_from_graph", "pad_state", "INF"]
