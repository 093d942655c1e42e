"""Metric arithmetic on hand-made spans, counters and traces."""

import json
from pathlib import Path

import pytest

from bench import harness, peaks, trace, work

FIXTURES = Path(__file__).resolve().parent / "data"


def rec(uid, graph=0, due=None, start=None, end=None, retired=None):
    return harness.Record(uid=uid, graph=graph, key=None, due=due,
                          admit_start=start, admit_end=end,
                          retired_at=retired)


class Plan:
    n, kept = 100, 150


def context(records, flushes=(), stats=None, reduced=None):
    stats = stats or {"start": {}, "end": {}}
    return harness.Context(
        cell={"name": "x"}, config={}, k=4, t_start=10.0, t_end=20.0,
        setup_s=33.5, records=list(records), flushes=list(flushes),
        stats=stats, plans={0: Plan()}, trace=reduced,
        peaks=peaks.chip_peaks("TPU v5 lite"))


def read(name, ctx):
    return harness.load_reader(name)(ctx)


def test_rate_counts_answers_inside_the_window():
    ctx = context([rec(0, retired=11.0), rec(1, retired=19.9),
                   rec(2, retired=20.5), rec(3)])
    assert read("graphs_per_s", ctx) == pytest.approx(0.2)
    assert read("setup_s", ctx) == 33.5


def test_admit_ms_is_the_mean_admit_wall_started_in_the_window():
    recs = [rec(i, start=10.0 + i, end=10.0 + i + 0.01 * (i + 1))
            for i in range(3)]
    recs.append(rec(3, start=20.5, end=21.5))
    ctx = context(recs)
    assert read("admit_ms", ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["admit_ms.replay", "admit_ms.a.b"])
def test_a_name_split_by_cell_falls_back_to_its_quantity(name):
    ctx = context([rec(0, start=10.0, end=10.02)])
    assert read(name, ctx) == pytest.approx(20.0)


def test_a_metric_with_no_reader_is_refused():
    with pytest.raises(ValueError):
        harness.load_reader("no_such_metric.replay")


def test_counters_read_as_window_deltas():
    ctx = context([], stats={"start": {"flushes": 5}, "end": {"flushes": 7}})
    assert ctx.counter("flushes") == 2


def hand_trace():
    ms = 1e6
    ops = [["fusion.1", "", 1 * ms, 3 * ms],
           ["neighbor_min", "", 3 * ms, 5 * ms],
           ["fusion.2", "", 12 * ms, 13 * ms],
           ["neighbor_min", "", 13 * ms, 15 * ms],
           ["after", "", 40 * ms, 60 * ms]]
    modules = [["jit__unknown(1)", "", 1 * ms, 5 * ms],
               ["jit__lambda(2)", "", 8 * ms, 9 * ms],
               ["jit__unknown(1)", "", 12 * ms, 15 * ms]]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": modules}},
            "host": [["bench.window", 0.0, 20 * ms],
                     ["bench.admit", 4 * ms, 11 * ms],
                     ["bench.sleep", 15 * ms, 20 * ms]]}


def test_trace_reduction_on_a_hand_made_trace():
    out = trace.reduce(hand_trace())
    assert out["window_s"] == pytest.approx(0.020)
    assert out["busy_s"] == pytest.approx(0.007)
    assert out["device_ops"][0] == ["neighbor_min", pytest.approx(0.004)]
    # gaps: 5..12 ms (admit), 15..20 (sleep), 0..1 (nothing)
    assert out["idle_gaps"] == [["admit", pytest.approx(0.007)],
                                ["sleep", pytest.approx(0.005)],
                                ["host-other", pytest.approx(0.001)]]
    mods = out["devices"][0]["modules"]
    assert [len(m["ops"]) for m in mods] == [2, 0, 2]


def test_program_metrics_pair_executions_with_flushes():
    reduced = trace.reduce(hand_trace())
    flushes = [harness.Flush(at=1.0, shape=(4, 128, 8), uids=[0]),
               harness.Flush(at=2.0, shape=(4, 128, 8), uids=[1])]
    recs = [rec(0, retired=11.0), rec(1, retired=12.0)]
    ctx = context(recs, flushes, reduced=reduced)
    assert read("program_ms_per_graph", ctx) == pytest.approx(3.5)
    least = 2 * work.graph_bytes(100, 150, 4) / 819e9
    assert read("program_roofline", ctx) == pytest.approx(
        100 * least / 0.007)
    kernel = 2 * work.neighbor_min_call_bytes(4, 128, 8) / 819e9
    assert read("neighbor_min_roofline", ctx) == pytest.approx(
        100 * kernel / 0.004)


def test_no_trace_gives_no_reading():
    ctx = context([rec(0, retired=11.0)])
    for name in ("program_ms_per_graph", "program_roofline",
                 "neighbor_min_roofline"):
        assert read(name, ctx) is None


def test_unknown_chip_has_no_peaks():
    with pytest.raises(ValueError):
        peaks.chip_peaks("TPU v9")


def test_trace_reduction_on_a_recorded_trace():
    """1.5 s of a traced ``kron_g500_s14.replay`` window on a TPU v5e."""
    raw = json.loads((FIXTURES / "trace_kron_replay.json").read_text())
    out = trace.reduce(raw)
    lo, hi = trace.window(raw)
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    ops = raw["devices"]["/device:TPU:0"]["XLA Ops"]
    cuts = sorted((max(a, lo), min(b, hi)) for *_, a, b in ops if b > lo)
    busy, end = 0.0, lo
    for a, b in cuts:
        busy += max(0.0, b - max(a, end))
        end = max(end, b)
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < out["busy_s"] <= out["window_s"]
    names = [name for name, _ in out["device_ops"]]
    assert names[0].startswith("neighbor_min") and "while.5" not in names[:4]
    programs = [m for m in out["devices"][0]["modules"]
                if "jit__unknown" in m["name"]]
    assert programs and all(
        any(op.startswith("%neighbor_min") for op, _, _ in m["ops"])
        for m in programs)
    assert all(label in ("admit", "poll", "sleep", "host-other")
               for label, _ in out["idle_gaps"])
