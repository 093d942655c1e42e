"""Distributed PIVOT/greedy-MIS via ``shard_map`` — the MPC ⇒ mesh mapping.

MPC machine ⇔ mesh device. The padded COO edge array is partitioned
contiguously across devices (each machine holds ``O(m/M)`` edges — the MPC
input distribution); per-vertex state is replicated (it is the ``O(n)``
aggregate message stream the broadcast/convergecast trees of §2.1.5 carry).

One MPC round ⇔ one collective phase:

* each device segment-reduces its local edge slab into a length-(n+1)
  candidate vector  (local computation — free in MPC),
* ``jax.lax.pmin`` across the mesh combines candidates (the convergecast
  tree; on a TPU torus XLA lowers this to an S-ary reduction exactly like
  Goodrich et al.'s broadcast trees),
* the replicated status update is the broadcast.

The whole while-loop lives inside a single ``shard_map`` so the lowered
program is one SPMD module whose collective schedule is inspectable by the
roofline tooling (`repro.launch.roofline` counts these collectives).

Output is bit-identical to the single-device engine (tested), because the
round dynamics are deterministic given π.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .graph import Graph
from .mis import IN_MIS, INF_RANK, UNDECIDED, assign_to_min_rank_mis_neighbor


def edge_shard_mesh(num_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over available devices for edge-parallel clustering."""
    devs = np.array(jax.devices() if num_devices is None
                    else jax.devices()[:num_devices])
    return Mesh(devs, axis_names=("shard",))


def pow2_device_mesh(num_devices: Optional[int] = None,
                     axis_name: str = "shard") -> Mesh:
    """1-D mesh over the largest power-of-two prefix of local devices.

    The batch engine's group axis is always padded to a power of two, so a
    data-parallel split of that axis only divides evenly across a
    power-of-two device count. ``ShardedExecutor`` builds its mesh here: on
    an 8-device host this is all 8, on a 6-device host the first 4.
    """
    devs = jax.devices()
    limit = len(devs) if num_devices is None else max(1, min(num_devices,
                                                             len(devs)))
    count = 1 << (limit.bit_length() - 1)
    return Mesh(np.array(devs[:count]), axis_names=(axis_name,))


def _pad_edges_for_mesh(g: Graph, num_shards: int) -> Graph:
    """Re-pad the COO arrays so their length divides the shard count."""
    e = g.num_directed
    target = ((e + num_shards - 1) // num_shards) * num_shards
    if target == e:
        return g
    pad = target - e
    src = jnp.concatenate([g.src, jnp.full((pad,), g.n, jnp.int32)])
    dst = jnp.concatenate([g.dst, jnp.full((pad,), g.n, jnp.int32)])
    eid = jnp.concatenate([g.eid, jnp.full((pad,), g.m, jnp.int32)])
    row = g.row_offsets.at[g.n + 1].set(target)
    return Graph(n=g.n, m=g.m, src=src, dst=dst, row_offsets=row,
                 deg=g.deg, eid=eid)


def _local_segment_min(src, dst, vals_at_dst, mask_at_dst, n):
    """Per-device partial: min over the local edge slab, length n+1."""
    dst_ok = dst < n
    dst_idx = jnp.minimum(dst, n - 1)
    vals = jnp.where(dst_ok & mask_at_dst[dst_idx], vals_at_dst[dst_idx],
                     INF_RANK)
    return jnp.full((n + 1,), INF_RANK, jnp.int32).at[
        jnp.minimum(src, n)
    ].min(vals)


@partial(jax.jit, static_argnames=("n", "mesh", "packed"))
def _dist_mis_program(src, dst, ranks, n: int, mesh: Mesh,
                      packed: bool = False):
    """SPMD greedy-MIS: src/dst sharded over 'shard', state replicated.

    ``packed``: the hit-detection collective carries an int8 flag vector
    (pmax) instead of a second int32 rank pmin — the winner set is already
    globally known after the first pmin (every shard recomputes it from the
    replicated state), so only *adjacency to a winner* must cross the
    network. 8 → 5 bytes/vertex/round (§Perf H3 beyond-paper step).
    """

    def spmd(src_l, dst_l, ranks_r):
        def nbr_min(mask):
            local = _local_segment_min(src_l, dst_l, ranks_r, mask, n)
            return jax.lax.pmin(local, "shard")[:n]  # MPC convergecast

        def nbr_any(mask):
            """int8 OR-convergecast: does v have a neighbour in ``mask``."""
            dst_ok = dst_l < n
            dst_idx = jnp.minimum(dst_l, n - 1)
            vals = (dst_ok & mask[dst_idx]).astype(jnp.int8)
            local = jnp.zeros((n + 1,), jnp.int8).at[
                jnp.minimum(src_l, n)
            ].max(vals)
            return jax.lax.pmax(local, "shard")[:n] > 0

        def body(state):
            status, rounds = state
            und = status == UNDECIDED
            nmin = nbr_min(und)
            winners = und & (ranks_r < nmin)
            if packed:
                hit = und & (~winners) & nbr_any(winners)
            else:
                wmin = nbr_min(winners)
                hit = und & (~winners) & (wmin < INF_RANK)
            status = jnp.where(winners, jnp.int32(1), status)
            status = jnp.where(hit, jnp.int32(2), status)
            return status, rounds + 1

        def cond(state):
            status, _ = state
            return jnp.any(status == UNDECIDED)

        status0 = jnp.zeros((n,), jnp.int32)
        status, rounds = jax.lax.while_loop(cond, body, (status0, jnp.int32(0)))

        # PIVOT capture pass (one more convergecast round).
        in_mis = status == 1
        local = _local_segment_min(src_l, dst_l, ranks_r, in_mis, n)
        wmin = jax.lax.pmin(local, "shard")[:n]
        return status, rounds, wmin

    # Every out spec is replicated by construction (pmin / pmax collectives
    # close each round), which the replication checker cannot see through
    # the while loop.
    return jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(P("shard"), P("shard"), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )(src, dst, ranks)


def distributed_pivot(g: Graph, ranks, mesh: Optional[Mesh] = None,
                      packed: bool = False
                      ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Edge-parallel PIVOT. Returns (labels, in_mis, rounds).

    ``packed=True`` switches the hit-detection collective to the int8
    OR-convergecast (see ``_dist_mis_program``): 8 → 5 bytes/vertex/round on
    the wire, bit-identical output (tested against the unpacked engine).
    """
    mesh = mesh or edge_shard_mesh()
    nshards = mesh.devices.size
    gp = _pad_edges_for_mesh(g, nshards)
    n = g.n
    ranks = jnp.asarray(ranks, jnp.int32)
    status, rounds, wmin = _dist_mis_program(gp.src, gp.dst, ranks, n, mesh,
                                             packed=packed)
    in_mis = status == 1

    rank_to_v = jnp.zeros((n,), jnp.int32).at[ranks].set(
        jnp.arange(n, dtype=jnp.int32))
    own = jnp.arange(n, dtype=jnp.int32)
    pivot_v = rank_to_v[jnp.minimum(wmin, n - 1)]
    labels = jnp.where(in_mis, own,
                       jnp.where(wmin < INF_RANK, pivot_v, own))
    return np.asarray(labels), np.asarray(in_mis), int(rounds)


__all__ = ["edge_shard_mesh", "pow2_device_mesh", "distributed_pivot"]
