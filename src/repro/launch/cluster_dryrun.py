import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run + roofline for the paper's OWN workload: distributed PIVOT
correlation clustering on the production mesh (§Perf H3).

Method: the per-round SPMD program (one MIS round) is lowered/compiled on a
256-way edge-sharded mesh and its collective bytes extracted from the HLO;
round *counts* are measured by running the full algorithm eagerly on the
host at the same graph size (they are data-dependent, so the while loop
carries no static trip count). Total collective bytes = rounds ×
bytes/round (+ capture pass). Variants:

  raw        — PIVOT without the degree cap (Chierichetti-style baseline)
  capped     — Theorem 26 degree cap first (the paper's contribution)
  packed     — + int8 hit-flag collective instead of the 2nd rank pmin
               (beyond-paper; winner set is recomputable from the 1st pmin)
  phased     — + Algorithm 1 prefix scheduling: phase i communicates
               O(t_i)-sized state, bytes = Σ_i depth_i · bytes(t_i)
"""

import argparse
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import (algorithm1, build_graph, degree_threshold,
                        greedy_mis_parallel, random_permutation_ranks)
from repro.core.dist import _dist_mis_program, _pad_edges_for_mesh
from repro.core.graph import scale_free
from repro.launch import roofline as rl


def _flat_mesh(chips: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:chips]), ("shard",))


def _round_program_bytes(n: int, edges_per_shard: int, mesh: Mesh,
                         packed: bool) -> dict:
    """Lower ONE MIS round on the mesh; return collective bytes per round."""
    chips = mesh.devices.size
    e_total = edges_per_shard * chips

    def one_round(src, dst, ranks, status):
        def spmd(src_l, dst_l, ranks_r, status_r):
            from repro.core.dist import _local_segment_min
            und = status_r == 0
            local = _local_segment_min(src_l, dst_l, ranks_r, und, n)
            nmin = jax.lax.pmin(local, "shard")[:n]
            winners = und & (ranks_r < nmin)
            if packed:
                dst_ok = dst_l < n
                dst_idx = jnp.minimum(dst_l, n - 1)
                vals = (dst_ok & winners[dst_idx]).astype(jnp.int8)
                loc = jnp.zeros((n + 1,), jnp.int8).at[
                    jnp.minimum(src_l, n)].max(vals)
                hit_any = jax.lax.pmax(loc, "shard")[:n] > 0
                hit = und & (~winners) & hit_any
            else:
                local2 = _local_segment_min(src_l, dst_l, ranks_r, winners, n)
                wmin = jax.lax.pmin(local2, "shard")[:n]
                hit = und & (~winners) & (wmin < 2**31 - 1)
            status_r = jnp.where(winners, 1, status_r)
            status_r = jnp.where(hit, 2, status_r)
            return status_r

        return jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(P("shard"), P("shard"), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(src, dst, ranks, status)

    sds = jax.ShapeDtypeStruct
    sh_e = NamedSharding(mesh, P("shard"))
    sh_r = NamedSharding(mesh, P())
    fn = jax.jit(one_round,
                 in_shardings=(sh_e, sh_e, sh_r, sh_r),
                 out_shardings=sh_r)
    with mesh:
        lowered = fn.lower(sds((e_total,), jnp.int32),
                           sds((e_total,), jnp.int32),
                           sds((n,), jnp.int32), sds((n,), jnp.int32))
        compiled = lowered.compile()
    coll = rl.collective_stats(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "bytes_per_round": coll.total_bytes,
        "by_kind": coll.bytes_by_kind,
        "per_device_bytes": mem.argument_size_in_bytes
        + mem.temp_size_in_bytes,
    }


def run(n: int = 1 << 17, attach: int = 8, chips: int = 256,
        seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    edges, lam = scale_free(n, attach, rng)
    g = build_graph(n, edges)
    delta = g.max_degree()
    key = jax.random.PRNGKey(seed)
    ranks = random_permutation_ranks(n, key)

    # --- measured round counts (data-dependent) --------------------------
    depth_raw = int(greedy_mis_parallel(g, ranks).rounds)
    thresh = degree_threshold(lam, 2.0)
    high = np.asarray(g.deg) > thresh
    eligible = jnp.asarray(~high)
    depth_capped = int(greedy_mis_parallel(g, ranks, eligible=eligible).rounds)

    # Algorithm 1 phase stats on the capped subgraph (for the phased model).
    from repro.core.degree_cap import degree_capped_pivot
    capped = degree_capped_pivot(g, lam=lam, key=key, eps=2.0,
                                 engine="phased")
    ledger = capped.inner.ledger
    phases = [(p.prefix_end - p.prefix_start, max(1, p.depth))
              for p in ledger.phases]

    # --- per-round collective bytes from the compiled SPMD program -------
    mesh = _flat_mesh(chips)
    m_eff = int((~high[np.asarray(g.src[: 2 * g.m])]).sum())  # capped edges
    eps_raw = math.ceil(2 * g.m / chips)
    eps_cap = math.ceil(m_eff / chips)
    r_raw = _round_program_bytes(n, eps_raw, mesh, packed=False)
    r_packed = _round_program_bytes(n, eps_cap, mesh, packed=True)
    r_unpacked_cap = _round_program_bytes(n, eps_cap, mesh, packed=False)

    def total(bpr, rounds):
        return bpr * rounds + bpr / 2  # + capture pass (one pmin)

    # Phased: bytes scale with the phase's prefix size (state vectors are
    # O(t_i)); use packed per-round bytes scaled by t_i/n.
    phased_bytes = sum(
        r_packed["bytes_per_round"] * (t / n) * depth for t, depth in phases)

    variants = {
        "raw_unpacked": total(r_raw["bytes_per_round"], depth_raw),
        "capped_unpacked": total(r_unpacked_cap["bytes_per_round"],
                                 depth_capped),
        "capped_packed": total(r_packed["bytes_per_round"], depth_capped),
        "capped_packed_phased": phased_bytes + r_packed["bytes_per_round"],
    }
    seg_flops = 2.0 * 2 * g.m  # compare+select per directed edge per round
    out = {
        "n": n, "m": int(g.m), "lambda": lam, "delta": int(delta),
        "threshold": thresh, "high_degree": int(high.sum()),
        "depth_raw": depth_raw, "depth_capped": depth_capped,
        "phases": phases,
        "bytes_per_round_unpacked": r_raw["bytes_per_round"],
        "bytes_per_round_packed": r_packed["bytes_per_round"],
        "per_device_bytes": r_raw["per_device_bytes"],
        "variants_total_collective_bytes": variants,
        "t_collective_s": {k: v / (chips * rl.ICI_BW)
                           for k, v in variants.items()},
        "t_compute_s": seg_flops * depth_raw / (chips * rl.PEAK_FLOPS_BF16),
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 17)
    ap.add_argument("--attach", type=int, default=8)
    ap.add_argument("--chips", type=int, default=256)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    res = run(n=args.n, attach=args.attach, chips=args.chips)
    print(json.dumps(res, indent=2, default=float))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(res, indent=2, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
