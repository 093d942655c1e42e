"""Warmup-time kernel autotuner: cache persistence/invalidation, sweep
mechanics, program-key plumbing, bit-exactness for every tuned shape, and
the learned compile/service costs it feeds the serving cost model."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_graph, correlation_cluster,
                        correlation_cluster_batch)
from repro.core import executor as exec_mod
from repro.core.graph import random_arboric
from repro.core.plan import plan_graph
from repro.kernels import autotune as at
from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest
from repro.serve.costmodel import FlushCostModel
from repro.serve.engine import serve_all
from repro.serve.scheduler import FlushTelemetry


@pytest.fixture(autouse=True)
def fresh_cache(monkeypatch):
    """Every test runs against its own in-memory tuning cache: tuned
    winners are process-global state that would otherwise leak program-key
    resolution between tests."""
    monkeypatch.delenv("REPRO_TUNING_CACHE", raising=False)
    prev = at.set_tuning_cache(at.TuningCache(path=None))
    yield
    at.set_tuning_cache(prev)


def _graphs(n_graphs=4, lo=8, hi=30, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_graphs):
        n = int(rng.integers(lo, hi))
        edges, _ = random_arboric(n, 2, rng)
        out.append(build_graph(n, edges))
    return out


def _seed_all_buckets(graphs, block_rows, k=1):
    """Force ``block_rows`` as the cached winner for every bucket/tier a
    run of ``graphs`` can hit — the hook the bit-exactness sweep uses to
    route each candidate through the real resolution path."""
    cache = at.tuning_cache()
    buckets = {plan_graph(g).bucket for g in graphs}
    for (r, w) in buckets:
        tier = 1
        while tier <= at.MAX_BATCH_TIER:
            for kern in at.KERNELS:
                cache.put(kern, r, w, tier, min(block_rows, r))
            tier *= 2
    return buckets


# --- cache mechanics -------------------------------------------------------


def test_batch_tier_and_candidates():
    assert at.batch_tier(1) == 1
    assert at.batch_tier(5) == 8
    assert at.batch_tier(64) == 64
    assert at.batch_tier(10 ** 9) == at.MAX_BATCH_TIER
    # Resolved to lane tiles (128-lane multiples, at most the padded row),
    # deduplicated, default always present.
    assert at.candidate_blocks(512) == (128, 256, 512)
    assert at.candidate_blocks(128) == (128,)
    assert at.candidate_blocks(32) == (128,)
    assert at.candidate_blocks(100, candidates=(48, 512)) == (128,)
    assert at.candidate_blocks(1024, candidates=(48, 64, 512)) == (128, 512,
                                                                   256)
    # One lane tile is one program key; the default tile is the legacy key.
    assert at.lane_tiles(1024, (48, 100)) == (128, 128)
    assert at.lane_tiles(1024, (256, 200)) is None
    assert at.lane_tiles(64, (8, 512)) is None


def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "tuning.json")
    cache = at.TuningCache(path=path)
    cache.put("neighbor_min", 64, 8, 4, 32,
              meta={"speedup_vs_default": 1.5})
    cache.save()
    loaded = at.TuningCache(path=path)
    assert loaded.get("neighbor_min", 64, 8, 4) == 32
    assert loaded.hits == 1
    assert loaded.get("neighbor_min", 64, 8, 8) is None   # other tier
    assert loaded.misses == 1
    blob = json.loads(open(path).read())
    assert blob["version"] == 1
    (key, entry), = blob["entries"].items()
    assert key == f"{jax.default_backend()}/neighbor_min/64x8/b4"
    assert entry["jax_version"] == jax.__version__


def test_cache_stale_entries_ignored(tmp_path):
    """The invalidation rule: entries from another backend or jax version
    are counted stale and treated as misses — ignored, never trusted."""
    path = str(tmp_path / "tuning.json")
    backend = jax.default_backend()
    blob = {"version": 1, "entries": {
        f"{backend}/neighbor_min/64x8/b4": {
            "block_rows": 32, "backend": backend,
            "jax_version": "0.0.0-stale"},
        f"tpu-v9/label_agree/64x8/b4": {
            "block_rows": 64, "backend": "tpu-v9",
            "jax_version": jax.__version__},
    }}
    with open(path, "w") as f:
        json.dump(blob, f)
    cache = at.TuningCache(path=path)
    assert cache.get("neighbor_min", 64, 8, 4) is None
    assert cache.stale == 1 and cache.misses == 1
    # The wrong-backend entry is simply not found under this backend's key.
    assert cache.get("label_agree", 64, 8, 4) is None
    assert cache.misses == 2


def test_cache_corrupt_file_ignored(tmp_path):
    path = str(tmp_path / "tuning.json")
    with open(path, "w") as f:
        f.write("{not json")
    cache = at.TuningCache(path=path)        # must not raise
    assert cache.get("neighbor_min", 8, 4, 1) is None


def test_cache_env_var_path(tmp_path, monkeypatch):
    path = str(tmp_path / "env-tuning.json")
    cache = at.TuningCache(path=path)
    cache.put("label_agree", 32, 4, 2, 16)
    cache.save()
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)
    env_cache = at.TuningCache()
    assert env_cache.path == path
    assert env_cache.get("label_agree", 32, 4, 2) == 16


def test_resolve_block_rows_untuned_is_none():
    assert at.resolve_block_rows((8, 512, 8)) is None
    at.tuning_cache().put("neighbor_min", 512, 8, 8, 128)
    # Partial tuning: the untuned kernel falls back to the default tile.
    assert at.resolve_block_rows((8, 512, 8)) == (128, 256)
    # A winner with the default's lane tile keeps the untuned key.
    at.tuning_cache().put("neighbor_min", 64, 8, 8, 32)
    assert at.resolve_block_rows((8, 64, 8)) is None


# --- sweep mechanics -------------------------------------------------------


def _packed_bucket(graphs, g_pad=None, k=1):
    from repro.core.api import sample_keys
    from repro.core.plan import pack_bucket

    plans = [plan_graph(g) for g in graphs]
    keys = [sample_keys(jax.random.PRNGKey(i), k)
            for i in range(len(plans))]
    return pack_bucket(plans, keys, k=k, g_pad=g_pad)


def test_sweep_records_winner_and_cache():
    graphs = _graphs(2, lo=20, hi=21, seed=3)     # one bucket
    ell, ranks, elig, _m, _pad = _packed_bucket(graphs, g_pad=2)
    cache = at.tuning_cache()
    records = at.sweep_bucket(ell, ranks, elig, candidates=(8, 16),
                              repeats=1)
    assert {r["kernel"] for r in records} == set(at.KERNELS)
    b, r, w = (int(s) for s in ell.shape)
    tier = at.batch_tier(b)
    for rec in records:
        assert rec["winner"] in rec["candidates"]
        assert rec["winner_ms"] <= rec["default_ms"] + 1e-9
        assert rec["speedup_vs_default"] >= 1.0 - 1e-9
        assert cache.get(rec["kernel"], r, w, tier) == rec["winner"]
    assert cache.sweeps == 2
    assert len(cache.sweep_log) == 2
    info = at.tuning_info()
    assert info["sweeps"] == 2 and len(info["sweep_log"]) == 2


def test_warmup_autotune_caches_and_reuses(tmp_path):
    """The CI autotune smoke: a 2-candidate sweep on one small bucket must
    cache a winner, and a second warmup against the populated cache file
    must perform zero sweep timings (hit counters prove it)."""
    path = str(tmp_path / "tuning.json")
    graphs = _graphs(3, lo=10, hi=24, seed=1)
    at.set_tuning_cache(at.TuningCache(path=path))
    eng = ClusterBatcher(max_batch=2, use_kernel=True)
    eng.warmup(graphs, autotune=True, candidates=(16, 32), repeats=1)
    first = at.tuning_cache()
    assert first.sweeps > 0
    assert os.path.exists(path)
    assert eng.stats.tuning is not None
    assert eng.stats.tuning["sweeps"] == first.sweeps
    assert len(eng.stats.tuning["sweep_log"]) == first.sweeps

    # "Second process": a fresh cache object loaded from the same file.
    at.set_tuning_cache(at.TuningCache(path=path))
    second = at.tuning_cache()
    eng2 = ClusterBatcher(max_batch=2, use_kernel=True)
    eng2.warmup(graphs, autotune=True, candidates=(16, 32), repeats=1)
    assert second.sweeps == 0, "populated cache must skip all sweeps"
    assert second.hits > 0, "reuse must be visible in the hit counters"
    assert second.stale == 0


def test_program_key_carries_block_shape():
    """Distinct lane-tile pairs are distinct compiled programs (re-tuning
    can never mutate a compiled one), with identical outputs; block pairs
    with one lane tile share one program; the jnp path ignores block shape
    entirely."""
    ell = jnp.full((2, 384, 4), 384, jnp.int32)
    ranks = jnp.full((2, 385), np.iinfo(np.int32).max, jnp.int32)
    elig = jnp.zeros((2, 385), bool)
    m = jnp.zeros((2,), jnp.int32)
    args = (ell, ranks, elig, m)
    before = exec_mod.program_cache_size()
    outs = [exec_mod.run_bucket_program(*args, k=2, use_kernel=True,
                                        block_rows=br)
            for br in [(128, 128), (384, 384), None]]
    assert exec_mod.program_cache_size() - before == 3
    for got in outs[1:]:
        for a, b in zip(outs[0], got):
            assert (np.asarray(a) == np.asarray(b)).all()
    # The probe resolves block shape identically to the run: (8, 8) and
    # (100, 128) are the 128-lane program, (256, 256) the default one.
    for br in [(128, 128), (8, 8), (100, 128), (512, 512), (256, 256)]:
        assert exec_mod.program_cache_contains((2, 384, 4), 2,
                                               use_kernel=True,
                                               block_rows=br)
    assert not exec_mod.program_cache_contains((2, 384, 4), 2,
                                               use_kernel=True,
                                               block_rows=(128, 384))
    # use_kernel=False: block shape is normalized out of the key.
    before = exec_mod.program_cache_size()
    exec_mod.run_bucket_program(*args, k=2, block_rows=(128, 128))
    exec_mod.run_bucket_program(*args, k=2)
    assert exec_mod.program_cache_size() - before <= 1


def test_tuned_cache_winner_drives_run_and_probe():
    """An untuned run and a tuned run of the same bucket are different
    programs, and the cost model's probe tracks the tuning cache."""
    ell = jnp.full((2, 320, 4), 320, jnp.int32)
    ranks = jnp.full((2, 321), np.iinfo(np.int32).max, jnp.int32)
    elig = jnp.zeros((2, 321), bool)
    m = jnp.zeros((2,), jnp.int32)
    args = (ell, ranks, elig, m)
    exec_mod.run_bucket_program(*args, k=1, use_kernel=True)
    assert exec_mod.program_cache_contains((2, 320, 4), 1, use_kernel=True)
    for kern in at.KERNELS:
        at.tuning_cache().put(kern, 320, 4, at.batch_tier(2), 128)
    # The tuned program is not resident yet; default resolution now points
    # at the tuned key.
    assert not exec_mod.program_cache_contains((2, 320, 4), 1,
                                               use_kernel=True)
    before = exec_mod.program_cache_size()
    exec_mod.run_bucket_program(*args, k=1, use_kernel=True)
    assert exec_mod.program_cache_size() - before == 1
    assert exec_mod.program_cache_contains((2, 320, 4), 1, use_kernel=True)


# --- bit-exactness: every candidate and the cached winner ------------------


@pytest.mark.parametrize("executor", ["sync", "async", "sharded"])
@pytest.mark.parametrize("block_rows", [32, 64, 256])
def test_bit_exact_for_every_tuned_candidate(executor, block_rows):
    """The acceptance contract: for every swept candidate, batch results
    on the kernel path under tuned block shapes are bit-identical to the
    per-graph engine, across all three executors."""
    graphs = _graphs(5, lo=8, hi=40, seed=7)
    keys = [jax.random.PRNGKey(i) for i in range(len(graphs))]
    _seed_all_buckets(graphs, block_rows)
    results = correlation_cluster_batch(graphs, keys=keys, use_kernel=True,
                                        executor=executor)
    for g, key, got in zip(graphs, keys, results):
        ref = correlation_cluster(g, key=key)
        assert (got.labels == ref.labels).all()
        assert got.cost == ref.cost


def test_bit_exact_jnp_path_with_tuned_cache():
    """Tuned winners must not perturb the jnp (use_kernel=False) path."""
    graphs = _graphs(4, seed=9)
    keys = [jax.random.PRNGKey(i) for i in range(len(graphs))]
    _seed_all_buckets(graphs, 32)
    results = correlation_cluster_batch(graphs, keys=keys, use_kernel=False)
    for g, key, got in zip(graphs, keys, results):
        ref = correlation_cluster(g, key=key)
        assert (got.labels == ref.labels).all() and got.cost == ref.cost


def test_bit_exact_served_after_autotune_warmup():
    """Cached-winner path end to end: warmup(autotune=True) then serve on
    the kernel path — results match the per-graph engine."""
    graphs = _graphs(4, seed=11)
    eng = ClusterBatcher(max_batch=4, use_kernel=True)
    eng.warmup(graphs, autotune=True, candidates=(16, 64), repeats=1)
    reqs = [ClusterRequest(uid=i, graph=g, key=jax.random.PRNGKey(i))
            for i, g in enumerate(graphs)]
    done = {r.uid: r for r in serve_all(eng, reqs)}
    for i, g in enumerate(graphs):
        ref = correlation_cluster(g, key=jax.random.PRNGKey(i))
        assert (done[i].result.labels == ref.labels).all()
        assert done[i].result.cost == ref.cost


# --- learned compile walls + cost-model integration ------------------------


def test_compile_wall_stamped_and_surfaced():
    """A program-cache miss stamps its compile wall on the handle and into
    program_cache_info; hits stamp None."""
    ex = exec_mod.SyncExecutor()
    ell = np.full((3, 48, 4), 48, dtype=np.int32)
    ranks = np.full((3, 49), np.iinfo(np.int32).max, dtype=np.int32)
    elig = np.zeros((3, 49), dtype=bool)
    m = np.zeros((3,), dtype=np.int32)
    h1 = ex.submit(ell, ranks, elig, m, k=3)
    assert h1.compile_seconds is not None and h1.compile_seconds > 0
    h2 = ex.submit(ell, ranks, elig, m, k=3)
    assert h2.compile_seconds is None
    info = exec_mod.program_cache_info()
    assert "48x4" in info["compile_wall_ewma_ms"]
    assert info["compile_wall_ewma_ms"]["48x4"] > 0


def test_batcher_feeds_compile_walls_into_telemetry():
    """Harvest threads the executor's compile stamps into FlushTelemetry:
    per-shape compile stream + summary fields."""
    g = _graphs(1, lo=12, hi=13, seed=21)[0]
    eng = ClusterBatcher(max_batch=1, num_samples=3)
    done = eng.admit(ClusterRequest(uid=0, graph=g,
                                    key=jax.random.PRNGKey(0)))
    done += eng.flush()
    assert done and done[0].result is not None
    tele = eng.stats.latency
    bucket = plan_graph(g).queue_key     # telemetry keys are (method, R, W)
    assert tele.bucket_ewma_compile(bucket) is not None
    assert tele.ewma_compile is not None
    rec = tele.summary()[f"{bucket[0]}:{bucket[1]}x{bucket[2]}"]
    assert rec["compiles_total"] >= 1
    assert rec["compile_wall_ewma_ms"] > 0
    # Compile-free wall is maintained and below the raw (compile-heavy)
    # first wall.
    assert tele.bucket_ewma_wall_xc(bucket) is not None
    assert tele.bucket_ewma_wall_xc(bucket) <= tele.bucket_ewma_wall(bucket)


def test_cost_model_learned_compile_charge():
    """compile_charge prefers the observed per-shape compile EWMA, then
    the global compile EWMA, then the static prior — and still returns 0
    for resident programs."""
    bucket = (16384, 2048)          # never compiled anywhere in the suite
    model = FlushCostModel(compile_cost_s=0.1)
    model.bind_engine(num_samples=1)
    tele = FlushTelemetry(alpha=1.0)
    assert model.compile_charge(bucket, 4, tele) == pytest.approx(0.1)
    tele.record_compile((8, 4), 0.7)        # other shape: global fallback
    assert model.compile_charge(bucket, 4, tele) == pytest.approx(0.7)
    tele.record_compile(bucket, 0.4)        # this shape: learned
    assert model.compile_charge(bucket, 4, tele) == pytest.approx(0.4)
    assert model.compile_charge(bucket, 4, None) == pytest.approx(0.1)


def test_price_steal_uses_learned_compile_and_own_flush_credit():
    bucket = (16384, 2048)
    src = (8, 4)
    model = FlushCostModel(compile_cost_s=0.1)
    model.bind_engine(num_samples=1)
    tele = FlushTelemetry(alpha=1.0)
    tele.record(bucket, wall_s=0.08)
    # Steal 8→16 groups inflates the batch: learned compile charged.
    tele.record_compile(bucket, 0.4)
    cost = model.price_steal(bucket, 8, [(src, 0.01)], 0.1, tele)
    assert cost.compile_cost_s == pytest.approx(0.4)
    # Cold source: no own-flush credit (never the floor/global fallback).
    assert cost.own_flush_credit_s == 0.0
    assert cost.benefit_s == pytest.approx(0.1 - 0.01)
    # Observed source flush: its compile-free wall is credited once per
    # distinct source bucket.
    tele.record(src, wall_s=0.05)
    cost = model.price_steal(bucket, 8, [(src, 0.01), (src, 0.02)], 0.1,
                             tele)
    assert cost.own_flush_credit_s == pytest.approx(0.05)
    assert cost.benefit_s == pytest.approx((0.1 - 0.01) + (0.1 - 0.02)
                                           + 0.05)
    # The credit excludes compile walls: a compile-inflated flush of the
    # source must not inflate the credit.
    tele2 = FlushTelemetry(alpha=1.0)
    tele2.record(bucket, wall_s=0.08)
    tele2.record(src, wall_s=0.5, compile_s=0.48)
    cost2 = model.price_steal(bucket, 8, [(src, 0.01)], 0.1, tele2)
    assert cost2.own_flush_credit_s == pytest.approx(0.02)
