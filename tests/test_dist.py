"""Distributed (shard_map) engine ≡ sequential; multi-device via subprocess."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import (
    build_graph,
    correlation_cluster,
    distributed_pivot,
    pivot_sequential,
    random_permutation_ranks,
)
from repro.core.graph import random_arboric


def test_distributed_matches_sequential_one_device(rng):
    edges, _ = random_arboric(200, 3, rng)
    g = build_graph(200, edges)
    ranks = random_permutation_ranks(200, jax.random.PRNGKey(4))
    labels, in_mis, rounds = distributed_pivot(g, ranks)
    assert (labels == pivot_sequential(g, np.asarray(ranks))).all()
    assert rounds >= 1


def test_distributed_capped_api(rng):
    edges, lam = random_arboric(150, 2, rng)
    g = build_graph(150, edges)
    res_d = correlation_cluster(g, method="pivot", lam=lam,
                                key=jax.random.PRNGKey(9), distributed=True)
    res_s = correlation_cluster(g, method="pivot", lam=lam,
                                key=jax.random.PRNGKey(9), distributed=False)
    # same permutation (same key) ⇒ identical clustering
    assert (res_d.labels == res_s.labels).all()
    assert res_d.cost == res_s.cost


def test_distributed_packed_matches_unpacked(rng):
    """packed=True (int8 OR-convergecast hit detection) ≡ unpacked engine ≡
    sequential oracle — the previously untested _dist_mis_program path."""
    edges, _ = random_arboric(220, 3, rng)
    g = build_graph(220, edges)
    ranks = random_permutation_ranks(220, jax.random.PRNGKey(11))
    lab_p, mis_p, rounds_p = distributed_pivot(g, ranks, packed=True)
    lab_u, mis_u, rounds_u = distributed_pivot(g, ranks, packed=False)
    assert (lab_p == lab_u).all()
    assert (mis_p == mis_u).all()
    assert rounds_p == rounds_u
    assert (lab_p == pivot_sequential(g, np.asarray(ranks))).all()


@pytest.mark.slow
def test_distributed_packed_multidevice_subprocess(rng):
    """int8 OR-convergecast on a real 8-device CPU mesh: the packed
    collective must stay bit-exact when pmax actually crosses shards."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.core import (build_graph, distributed_pivot,
                                pivot_sequential, random_permutation_ranks,
                                edge_shard_mesh)
        from repro.core.graph import random_arboric
        rng = np.random.default_rng(3)
        edges, _ = random_arboric(400, 4, rng)
        g = build_graph(400, edges)
        ranks = random_permutation_ranks(400, jax.random.PRNGKey(6))
        mesh = edge_shard_mesh()
        assert mesh.devices.size == 8, mesh.devices.size
        lab_p, _, r_p = distributed_pivot(g, ranks, mesh=mesh, packed=True)
        lab_u, _, r_u = distributed_pivot(g, ranks, mesh=mesh, packed=False)
        ref = pivot_sequential(g, np.asarray(ranks))
        assert (lab_p == lab_u).all(), "packed != unpacked on 8 shards"
        assert (lab_p == ref).all(), "packed != sequential oracle"
        assert r_p == r_u
        print("OK rounds=", r_p)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


@pytest.mark.slow
def test_distributed_eight_devices_subprocess(rng, tmp_path):
    """Bit-equality of the edge-sharded engine across 8 host devices —
    proves the MPC mapping's collectives are semantics-preserving."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.core import (build_graph, distributed_pivot,
                                pivot_sequential, random_permutation_ranks,
                                edge_shard_mesh)
        from repro.core.graph import random_arboric
        rng = np.random.default_rng(0)
        edges, _ = random_arboric(500, 3, rng)
        g = build_graph(500, edges)
        ranks = random_permutation_ranks(500, jax.random.PRNGKey(1))
        mesh = edge_shard_mesh()
        assert mesh.devices.size == 8, mesh.devices.size
        labels, _, rounds = distributed_pivot(g, ranks, mesh=mesh)
        ref = pivot_sequential(g, np.asarray(ranks))
        assert (labels == ref).all(), "8-shard mismatch"
        print("OK rounds=", rounds)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


@pytest.mark.slow
def test_ep_local_moe_matches_sort_subprocess():
    """ep_local (shard_map EP, §Perf H1 iter 4-5) ≡ sort dispatch, incl.
    gradients, on a 2×4 device mesh."""
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke
        from repro.models.common import KeyGen, split_params
        from repro.models.mlp import init_moe, moe_sort, moe_ep_local
        from repro.models.sharding import ShardingPlan
        cfg = get_smoke("olmoe-1b-7b")
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        plan = ShardingPlan(axes={"experts": "model", "batch": "data",
                                  "embed": None, "ff": None,
                                  "expert_ff": None, "expert_embed": None})
        p_pm = init_moe(cfg, KeyGen(jax.random.PRNGKey(0)), jnp.float32, plan)
        p, _ = split_params(p_pm)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
        y_ref = moe_sort(p, x, cfg, capacity_factor=100.0)
        with jax.set_mesh(mesh):
            y_ep = moe_ep_local(p, x, cfg, 100.0, plan, mesh)
        assert float(jnp.max(jnp.abs(y_ref - y_ep))) < 1e-4

        def loss(pp):
            return jnp.sum(moe_ep_local(pp, x, cfg, 100.0, plan, mesh)**2)
        with jax.set_mesh(mesh):
            g = jax.grad(loss)(p)
        assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g))
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
