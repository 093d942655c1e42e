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

Ragged, degree-ordered sweep. A power-law graph's ELL is almost all pad:
``W`` is set by the widest row, and most rows are far shorter. Sweeping
every slot costs the same whatever the data (44.46 ms a call at
``(4, 16384, 1024)`` on a TPU v5e, bound by the vector ops of the
128-chunk loop over pad, not by HBM). So :func:`prepare_ell`, run once
per program on the device, orders each graph's rows by width (the
highest non-pad slot + 1; pads may sit in the middle of a row) with a
stable descending sort, gathers the rows in that order, makes them
lane-major, and records for each 128-lane group ``ceil(width of its
first row / 8)``: the sublane tiles of 8 rows that hold every real id of
the group. The kernels read those counts by scalar prefetch and loop
over a group's first ``count`` tiles only; a graph of uniform degree
gets ``W / 8`` and sweeps everything, as before. The state table stays
indexed by the original vertex id — only the rows move, so the ids in
them are unchanged — and the ``(B, R)`` result is sorted back into
vertex order. Min and sum do not depend on order, so the
results are bit-identical to the full sweep. Every ELL block is still
read from HBM whole; only the vector work skips the pad. At
``(4, 16384, 1024)`` on a TPU v5e a Graph500 SCALE-14 graph sweeps 2.8%
of the tiles and a kernel call takes about 1.1 ms of device time (44.5
before); a dense ELL of that shape sweeps every tile, a call then
taking about 37 ms on the host's clock against the full sweep's 46.

Grid: ``(B, R_lanes / rb)`` with ``rb`` a multiple of 128 lanes; each
step sweeps its ``rb / 128`` lane groups to their own tile counts.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF_VAL = 2**31 - 1  # int32 max; Python int so pallas kernels don't capture arrays
# numpy, not jnp: the module holds no jax array, so a first import inside a
# trace (the program's tile count on the jnp path) stages nothing.
INF = np.int32(INF_VAL)
LANES = 128
# Table chunks one step of the gather loop takes (see _gather_tile).
CHUNK_UNROLL = 32


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


def _tile_rows(w: int) -> int:
    """ELL rows in one swept tile: a sublane tile of 8, or the whole row
    when ``W`` is not a multiple of 8 (the 4-wide bucket, test widths)."""
    return 8 if w % 8 == 0 else w


class EllLayout(NamedTuple):
    """A ``(B, R, W)`` ELL prepared for the ragged sweep (:func:`prepare_ell`).

    ``ell`` is (B, W, R_lanes) lane-major with the rows in sweep order
    (widest first), ``tiles`` (B, R_lanes/128) the tiles of
    :func:`_tile_rows` rows each 128-lane group holds real ids in, and
    ``order`` (B, R) the vertex at each position of the sweep.
    """

    ell: jnp.ndarray
    tiles: jnp.ndarray
    order: jnp.ndarray


def prepare_ell(ell: jnp.ndarray) -> EllLayout:
    """Order a (B, R, W) ELL's rows by width for the ragged kernels.

    A row's width is its highest non-pad slot + 1 (ids ``>= R`` are pad,
    wherever they sit). The sort is stable and descending, per batch entry,
    and the rows are gathered whole. Run it once per program and pass the
    layout to every kernel call over the same ELL.
    """
    b, r, w = ell.shape
    r_lanes = _lanes(r)
    slot = jnp.arange(1, w + 1, dtype=jnp.int32)
    width = jnp.max(jnp.where(ell < r, slot, 0), axis=2)            # (B, R)
    order = jnp.argsort(-width, axis=1, stable=True).astype(jnp.int32)
    rows = jax.vmap(lambda e, o: e[o])(ell, order)
    heads = jnp.take_along_axis(width, order, axis=1)
    if r_lanes > r:
        heads = jnp.pad(heads, ((0, 0), (0, r_lanes - r)))
    tiles = pl.cdiv(heads[:, ::LANES], _tile_rows(w)).astype(jnp.int32)
    return EllLayout(_lane_major(rows, r_lanes), tiles, order)


def tile_counts(ell: jnp.ndarray,
                layout: Optional[EllLayout] = None) -> jnp.ndarray:
    """(B, 2) int32 per batch entry: the ELL tiles the sweep covers, and
    all of them (``R_lanes/128 · W/8``). Without a layout the sweep is the
    full one, so both are the full count."""
    b, r, w = ell.shape
    full = jnp.full((b,), (_lanes(r) // LANES) * (w // _tile_rows(w)),
                    jnp.int32)
    swept = full if layout is None else jnp.sum(layout.tiles, axis=1)
    return jnp.stack([swept, full], axis=1)


def _chunk_table(state: jnp.ndarray, r: int, r_lanes: int,
                 fill: int) -> jnp.ndarray:
    """(B, >= R) state → (B, R_lanes/128, 128): ids < R read ``state``,
    ids in [R, R_lanes) read ``fill``."""
    t = state[:, :r].astype(jnp.int32)
    if r_lanes > r:
        t = jnp.pad(t, ((0, 0), (0, r_lanes - r)), constant_values=fill)
    return t.reshape(t.shape[0], r_lanes // LANES, LANES)


def _gather_tile(table_ref, cols: jnp.ndarray, fill: int) -> jnp.ndarray:
    """``table[cols]`` for one (rows, 128) tile of neighbour ids.

    One 128-lane gather per table chunk, kept where the id's chunk matches;
    ids past the table (the pad id when R is a multiple of 128) keep
    ``fill``. A loop step takes ``CHUNK_UNROLL`` chunks (Mosaic unrolls a
    loop whole or not at all), so the loads and gathers of one step
    overlap: one chunk a step waits out each gather's latency, about eight
    times slower at ``(4, 16384, 1024)`` on a TPU v5e. Each chunk of a
    step is a copy of the loop body to lower, so a larger step makes every
    program build (and every warm start, which lowers before it reads the
    compile cache) slower.
    """
    hi = cols >> 7
    lo = cols & (LANES - 1)
    n = table_ref.shape[1]
    step = math.gcd(n, CHUNK_UNROLL)

    def body(i, acc):
        for u in range(step):
            c = i * step + u
            row = table_ref[0, pl.ds(c, 1), :]              # (1, 128)
            got = jnp.take_along_axis(jnp.broadcast_to(row, cols.shape), lo,
                                      axis=1, mode="promise_in_bounds")
            acc = jnp.where(hi == c, got, acc)
        return acc

    return jax.lax.fori_loop(0, n // step, body,
                             jnp.full(cols.shape, fill, jnp.int32))


def _sweep_group(tiles_ref, ell_ref, j: int, fold, init):
    """Fold ``fold(acc, ids)`` over the swept tiles of lane group ``j`` of
    this grid step's (1, W, rb) ELL block; ``tiles_ref`` holds every
    group's tile count, flat in grid order."""
    groups = ell_ref.shape[2] // LANES
    sub = _tile_rows(ell_ref.shape[1])
    block = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    lanes = pl.ds(j * LANES, LANES)

    def body(t, acc):
        rows = pl.ds(pl.multiple_of(t * sub, sub), sub)
        return fold(acc, ell_ref[0, rows, lanes])

    return jax.lax.fori_loop(0, tiles_ref[block * groups + j], body, init)


def _neighbor_min_kernel(tiles_ref, ell_ref, table_ref, out_ref):
    """One (graph, lane block): ell (1, W, rb), table (1, C, 128),
    out (1, 1, rb)."""
    sub = _tile_rows(ell_ref.shape[1])
    for j in range(ell_ref.shape[2] // LANES):
        acc = _sweep_group(
            tiles_ref, ell_ref, j,
            lambda acc, ids: jnp.minimum(
                acc, _gather_tile(table_ref, ids, INF_VAL)),
            jnp.full((sub, LANES), INF_VAL, jnp.int32))
        out_ref[0, :, pl.ds(j * LANES, LANES)] = jnp.min(
            acc, axis=0, keepdims=True)


def _label_agree_kernel(tiles_ref, ell_ref, table_ref, own_ref, out_ref):
    """One (graph, lane block): count neighbours whose label equals the
    vertex's own. Pad ids read the -1 sentinel, never a real label."""
    sub = _tile_rows(ell_ref.shape[1])
    for j in range(ell_ref.shape[2] // LANES):
        lanes = pl.ds(j * LANES, LANES)
        own = own_ref[0, :, lanes]
        acc = _sweep_group(
            tiles_ref, ell_ref, j,
            lambda acc, ids: acc + (
                _gather_tile(table_ref, ids, -1) == own).astype(jnp.int32),
            jnp.zeros((sub, LANES), jnp.int32))
        out_ref[0, :, lanes] = jnp.sum(acc, axis=0, keepdims=True)


def _ragged_call(kernel, layout: EllLayout, tables, block_rows: int,
                 interpret: bool, name: str) -> jnp.ndarray:
    """Run ``kernel`` over a prepared layout on a ``(B, R_lanes/rb)`` grid
    and map its (B, 1, R_lanes) lane row back to (B, R) vertex order, by
    sorting it on ``order`` (on a TPU a sort of the row is several times
    cheaper than a gather through the inverse permutation). ``tables``
    are the (B, C, 128) state table, indexed by vertex id, and any
    (B, 1, R_lanes) lane rows after it, in sweep order."""
    b, w, r_lanes = layout.ell.shape
    r = layout.order.shape[1]
    rb = lane_tile(block_rows, r)
    n_blocks = pl.cdiv(r_lanes, rb)
    # One count per lane group of the grid, a partial last block's
    # groups past the row reading 0.
    groups = n_blocks * (rb // LANES)
    tiles = jnp.pad(layout.tiles, ((0, 0), (0, groups - r_lanes // LANES)))
    table, *rows = tables
    in_specs = [pl.BlockSpec((1, w, rb), lambda bi, i, t: (bi, 0, i)),
                pl.BlockSpec((1, table.shape[1], LANES),
                             lambda bi, i, t: (bi, 0, 0))]
    row_spec = pl.BlockSpec((1, 1, rb), lambda bi, i, t: (bi, 0, i))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, n_blocks),
            in_specs=in_specs + [row_spec] * len(rows),
            out_specs=row_spec),
        out_shape=jax.ShapeDtypeStruct((b, 1, r_lanes), jnp.int32),
        interpret=interpret,
        name=name,
    )(tiles.reshape(-1), layout.ell, table, *rows)
    return jax.lax.sort((layout.order, out[:, 0, :r]), dimension=1,
                        num_keys=1)[1]


def _as_layout(ell) -> EllLayout:
    return ell if isinstance(ell, EllLayout) else prepare_ell(ell)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def neighbor_min_ell_batch(ell, ranks: jnp.ndarray, active: jnp.ndarray,
                           block_rows: int = 256,
                           interpret: bool = True) -> jnp.ndarray:
    """Batched neighbour-min over shape-bucketed ELL adjacencies.

    The multi-graph PIVOT engine (``core.batch``) packs ``B`` graphs of one
    shape bucket into a single ``(B, R, W)`` ELL tensor; this kernel runs the
    per-round hot loop for the whole bucket with a 2-D ``(batch, lane_block)``
    grid, so one Mosaic program serves every graph in the bucket and the
    round loop stays on device end to end.

    Args:
      ell: (B, R, W) int32 neighbour ids, pad entries == R (per-graph pad
        slot, see ``core.batch``); or its :func:`prepare_ell` layout, which
        a caller that sweeps the same ELL many times prepares once.
      ranks: (B, R+1) int32 — slot R is the INF pad slot.
      active: (B, R+1) bool/int32 — slot R inactive.
      block_rows: vertices per grid step, rounded up to 128 lanes.
    Returns (B, R) int32 per-vertex mins.
    """
    layout = _as_layout(ell)
    r = layout.order.shape[1]
    r_lanes = layout.ell.shape[2]
    masked = jnp.where(active[:, :r].astype(bool), ranks[:, :r], INF_VAL)
    table = _chunk_table(masked, r, r_lanes, INF_VAL)
    return _ragged_call(_neighbor_min_kernel, layout, (table,), block_rows,
                        interpret, "neighbor_min")


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
def label_agree_ell_batch(ell, labels_p: jnp.ndarray, block_rows: int = 256,
                          interpret: bool = True) -> jnp.ndarray:
    """Batched same-label neighbour count over shape-bucketed ELL tensors.

    The device cost pass of ``core.batch``: one ``(batch, lane_block)`` grid
    program computes per-vertex agreement counts for every graph of a
    bucket, in the same ragged layout as :func:`neighbor_min_ell_batch`.

    Args:
      ell: (B, R, W) int32 neighbour ids, pad entries == R; or its
        :func:`prepare_ell` layout.
      labels_p: (B, R+1) int32 cluster labels; slot R holds the -1 sentinel.
    Returns (B, R) int32 per-vertex same-label neighbour counts.
    """
    layout = _as_layout(ell)
    b = labels_p.shape[0]
    r = layout.order.shape[1]
    r_lanes = layout.ell.shape[2]
    table = _chunk_table(labels_p, r, r_lanes, -1)
    own = _chunk_table(jnp.take_along_axis(labels_p, layout.order, axis=1),
                       r, r_lanes, -1).reshape(b, 1, r_lanes)
    return _ragged_call(_label_agree_kernel, layout, (table, own),
                        block_rows, interpret, "label_agree")


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
           "label_agree_ell_batch", "EllLayout", "prepare_ell", "tile_counts",
           "ell_from_graph", "pad_state", "INF"]
