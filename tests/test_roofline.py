"""Roofline tooling: HLO collective walker (trip counts, async starts,
participants), the chip peaks table and the batched ELL kernel models (the
autotuner's hardware lower bound)."""

import textwrap

import pytest

from repro.launch.roofline import (
    ELL_KERNELS,
    PEAKS,
    chip_peaks,
    collective_stats,
    ell_kernel_bytes,
    ell_kernel_flops,
    ell_kernel_roofline,
)

HLO = textwrap.dedent("""\
    HloModule test

    %body (p: (s32[], f32[16,16])) -> (s32[], f32[16,16]) {
      %p = (s32[], f32[16,16]) parameter(0)
      %ar = f32[16,16]{1,0} all-reduce(%gte), channel_id=1, replica_groups=[4,8]<=[32], to_apply=%add
      ROOT %t = (s32[], f32[16,16]) tuple(%iv, %ar)
    }

    %cond (p2: (s32[], f32[16,16])) -> pred[] {
      %p2 = (s32[], f32[16,16]) parameter(0)
      ROOT %lt = pred[] compare(%iv2, %c), direction=LT
    }

    ENTRY %main (a: f32[16,16]) -> f32[16,16] {
      %a = f32[16,16]{1,0} parameter(0)
      %ag = f32[64,16]{1,0} all-gather(%a), channel_id=2, replica_groups=[8,4]<=[32], dimensions={0}
      %w = (s32[], f32[16,16]) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
      %cps = (f32[16,16], f32[16,16]) collective-permute-start(%a), channel_id=3, source_target_pairs={{0,1},{1,0}}
      %cpd = f32[16,16]{1,0} collective-permute-done(%cps)
      ROOT %out = f32[16,16]{1,0} add(%cpd, %a)
    }
""")


def test_collective_walker_trip_counts_and_async():
    cs = collective_stats(HLO, default_participants=32)
    # all-gather: 64*16*4 bytes × 4 participants = 16384
    assert cs.bytes_by_kind["all-gather"] == 64 * 16 * 4 * 4
    # all-reduce inside while ×10 trips, 8 participants
    assert cs.bytes_by_kind["all-reduce"] == 16 * 16 * 4 * 8 * 10
    assert cs.count_by_kind["all-reduce"] == 10
    # collective-permute-start counted once (max tuple element), done
    # skipped; participants = number of source_target_pairs (2 here)
    assert cs.bytes_by_kind["collective-permute"] == 16 * 16 * 4 * 2
    assert cs.count_by_kind["collective-permute"] == 1


# --- batched ELL kernel models ---------------------------------------------


def test_ell_kernel_models_scale_and_validate():
    for kern in ELL_KERNELS:
        # Linear in every axis of the swept (B, R, W) volume.
        assert ell_kernel_flops(kern, 8, 64, 8) \
            == 2 * ell_kernel_flops(kern, 4, 64, 8)
        assert ell_kernel_bytes(kern, 4, 128, 8) \
            > ell_kernel_bytes(kern, 4, 64, 8)
        assert ell_kernel_bytes(kern, 4, 64, 16) \
            > ell_kernel_bytes(kern, 4, 64, 8)
    # neighbor_min gathers two tables, label_agree one.
    assert ell_kernel_bytes("neighbor_min", 4, 64, 8) \
        > ell_kernel_bytes("label_agree", 4, 64, 8)
    with pytest.raises(ValueError):
        ell_kernel_flops("fused_softmax", 4, 64, 8)
    with pytest.raises(ValueError):
        ell_kernel_bytes("fused_softmax", 4, 64, 8)


def test_ell_kernel_roofline_bottleneck_and_dict():
    # ~3.5 element-ops/byte max: on any real FLOPS/BW ratio these kernels
    # are memory bound; force the opposite with a tiny peak to check both
    # branches.
    r = ell_kernel_roofline("neighbor_min", 8, 128, 16,
                            device_kind="TPU v5 lite")
    assert r.t_model == max(r.t_compute, r.t_memory)
    assert r.bottleneck == "memory"
    slow = ell_kernel_roofline("neighbor_min", 8, 128, 16,
                               peak_flops=1e6, mem_bw=1e15)
    assert slow.bottleneck == "compute"
    d = r.as_dict()
    assert d["shape"] == [8, 128, 16]
    assert d["t_model_s"] == r.t_model
    assert d["bottleneck"] == "memory"


def test_chip_peaks_table_refuses_unknown_kinds():
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                           16e9)
    assert all(p.source for p in PEAKS.values())
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        ell_kernel_roofline("neighbor_min", 8, 128, 16, device_kind="cpu")
    with pytest.raises(ValueError, match="device_kind"):
        ell_kernel_roofline("neighbor_min", 8, 128, 16)
    with pytest.raises(ValueError, match="not both"):
        ell_kernel_roofline("neighbor_min", 8, 128, 16,
                            device_kind="TPU v5 lite", mem_bw=1e9)


@pytest.mark.slow
def test_measured_kernel_walls_respect_roofline():
    """The tentpole's closed loop: sweep real packed bucket tensors, then
    assert (a) every measured wall is >= the hardware model bound — the
    TPU-v5e roofline, passed by kind, is a lower bound for any slower
    backend, so a wall beating it means the timing or the model is broken
    — and (b) a fresh
    best-of-repeats re-measurement of the tuned block is no slower than
    the 256-default beyond timing noise."""
    import time

    import jax
    import numpy as np

    from repro.core import build_graph
    from repro.core.api import sample_keys
    from repro.core.graph import random_arboric
    from repro.core.plan import build_packed_rows, pack_bucket, plan_graph
    from repro.kernels import autotune as at
    from repro.kernels.ops import label_agree_ell_batch, neighbor_min_ell_batch

    prev = at.set_tuning_cache(at.TuningCache(path=None))
    try:
        rng = np.random.default_rng(5)
        graphs = []
        for _ in range(4):
            edges, _ = random_arboric(48, 2, rng)
            graphs.append(build_graph(48, edges))
        plans = [plan_graph(g) for g in graphs]
        for i, plan in enumerate(plans):
            plan.rows = build_packed_rows(
                plan, sample_keys(jax.random.PRNGKey(i), 1))
        ell, ranks, elig, _m, _pad = pack_bucket(plans, k=1, g_pad=4)
        b, r, w = (int(s) for s in ell.shape)

        records = at.sweep_bucket(ell, ranks, elig, candidates=(16, 32),
                                  repeats=2)
        assert len(records) == len(ELL_KERNELS)
        for rec in records:
            bound = ell_kernel_roofline(rec["kernel"], b, r, w,
                                        device_kind="TPU v5 lite").t_model
            for ms in rec["timings_ms"].values():
                assert ms * 1e-3 >= bound, (
                    f"{rec['kernel']} measured {ms:.4f}ms beats the "
                    f"roofline bound {bound * 1e3:.4f}ms")

        # Re-measure default vs tuned fresh (sweep winners are argmin by
        # construction; a fresh timing is the meaningful comparison).
        labels_p = jax.numpy.broadcast_to(
            jax.numpy.arange(r + 1, dtype=jax.numpy.int32), (b, r + 1))
        calls = {
            "neighbor_min": lambda br: neighbor_min_ell_batch(
                ell, ranks, elig, block_rows=br),
            "label_agree": lambda br: label_agree_ell_batch(
                ell, labels_p, block_rows=br),
        }
        cache = at.tuning_cache()
        tier = at.batch_tier(b)
        for kern, call in calls.items():
            tuned = cache.get(kern, r, w, tier, count=False)
            assert tuned is not None

            def best_of(br, n=2):
                call(br).block_until_ready()      # compile untimed
                walls = []
                for _ in range(n):
                    t0 = time.perf_counter()
                    call(br).block_until_ready()
                    walls.append(time.perf_counter() - t0)
                return min(walls)

            t_tuned = best_of(tuned)
            t_default = best_of(min(at.DEFAULT_BLOCK_ROWS, r))
            assert t_tuned <= t_default * 1.3 + 1e-3, (
                f"{kern}: tuned block {tuned} ({t_tuned * 1e3:.3f}ms) "
                f"slower than default ({t_default * 1e3:.3f}ms)")
    finally:
        at.set_tuning_cache(prev)
