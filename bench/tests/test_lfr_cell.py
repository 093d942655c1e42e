"""The LFR cell, shrunk to a CPU's size, served through ``harness.run``:
every answer equals the reference, and flushes of several graphs come from
both of its buckets. Also the scheduler readers on a hand-made context."""

import copy
import time

import pytest

from bench import harness, peaks, run

CELL = "lfr_lf09.closed64"


def tiny():
    """Two sizes of a few hundred vertices, two mixings, both community
    ranges (8 graphs); the jnp path, 4 graphs a flush, 2·(4 − 1) + 1
    requests outstanding, a CPU's rate cap."""
    bench, cell, config, traffic = run.load_cell(CELL)
    config = copy.deepcopy(config)
    config["graphs"].update(sizes=[200, 600], mu=[0.2, 0.6])
    config["engine"].update(use_kernel=False, max_batch=4)
    traffic = dict(traffic, outstanding=7, max_rate_per_s=400)
    return bench, cell, config, traffic


def test_shrunk_cell_is_correct_with_batched_flushes_in_both_buckets(
        monkeypatch):
    from repro.core import executor

    monkeypatch.setattr(executor, "_program_cache", type(
        executor._program_cache)())
    # The full policy never flushes a partial bucket; the drain's final
    # engine flush does, after this wait.
    monkeypatch.setattr(harness, "DRAIN_S", 0.5)
    made = []

    def recording(name):
        made.append(harness_recording(name))
        return made[-1]

    harness_recording = harness.recording_executor
    monkeypatch.setattr(harness, "recording_executor", recording)
    bench, cell, config, traffic = tiny()
    out = harness.run(cell, config, traffic, bench, 2**31 + 21, 3.0, False,
                      time.perf_counter())
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
    batched = {f.shape[1:] for f in made[0].flushes if len(f.uids) > 1}
    assert batched == {(256, 64), (1024, 64)}


class Plan:
    n, kept = 100, 150


def context(flushes, k=4):
    return harness.Context(
        cell={"name": CELL}, config={}, k=k, t_start=10.0, t_end=20.0,
        setup_s=1.0, records=[], flushes=list(flushes),
        stats={"start": {}, "end": {}}, plans={0: Plan()}, trace=None,
        peaks=peaks.chip_peaks("TPU v5 lite"))


def flush(g_pad, graphs, k=4, r=1024):
    return harness.Flush(at=0.0, shape=(g_pad * k, r, 64),
                         uids=list(range(graphs)))


@pytest.mark.parametrize("flushes, pad, per_flush", [
    ([flush(16, 16), flush(16, 16, r=8192)], 0.0, 16.0),
    ([flush(16, 16), flush(4, 3)], 100.0 * 4 / 80, 9.5),
    ([flush(8, 5), flush(1, 1), flush(2, 2, r=8192)], 100.0 * 12 / 44,
     8 / 3),
])
def test_scheduler_readers(flushes, pad, per_flush):
    ctx = context(flushes)
    assert harness.load_reader("pad_share")(ctx) == pytest.approx(pad)
    assert harness.load_reader("graphs_per_flush")(ctx) == pytest.approx(
        per_flush)


def test_scheduler_readers_read_the_samples_per_graph():
    ctx = context([harness.Flush(at=0.0, shape=(8, 256, 64), uids=[0, 1, 2])],
                  k=2)
    assert harness.load_reader("pad_share")(ctx) == pytest.approx(25.0)


def test_no_flush_gives_no_reading():
    ctx = context([])
    assert harness.load_reader("pad_share")(ctx) is None
    assert harness.load_reader("graphs_per_flush")(ctx) is None
