"""One run of one cell: set-up, the measured window, the check, the metrics.

The engine is driven only through its public entry points:
``ClusterBatcher(...)``, ``warmup``, ``admit``, ``poll``, ``retire`` and
``flush``, with an executor instance that records each flush it submits
(shape, requests, time) and otherwise is the configured executor.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import check
from bench import traffic as traffic_gen
from bench.compile_watch import CompileWatch

HERE = Path(__file__).resolve().parent
POLL_S = 0.001          # how long the driving loop sleeps when idle
DRAIN_S = 60.0          # how long answers due in the window are waited for
REHEARSAL = 2           # requests served in set-up, after the warm-up


@dataclasses.dataclass
class Record:
    uid: int
    graph: int                          # index into the pool
    key: Any
    due: Optional[float] = None         # open loop: when it was to be sent
    admit_start: Optional[float] = None
    admit_end: Optional[float] = None
    retired_at: Optional[float] = None
    req: Any = None
    result: Any = None


@dataclasses.dataclass
class Flush:
    at: float
    shape: tuple                        # packed (B, R, W)
    uids: List[int]


@dataclasses.dataclass
class Context:
    """What the metric readers read (``metrics/<name>.py``)."""

    cell: dict
    config: dict
    k: int
    t_start: float
    t_end: float
    setup_s: float
    records: List[Record]
    flushes: List[Flush]
    stats: Dict[str, Dict[str, float]]
    plans: dict                         # reference plans by pool index
    trace: Optional[dict]
    peaks: Any

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def retired_in_window(self) -> List[Record]:
        return [r for r in self.records
                if r.retired_at is not None and r.retired_at <= self.t_end]

    def counter(self, name: str) -> float:
        return self.stats["end"][name] - self.stats["start"][name]


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", file=sys.stderr, flush=True)


def load_reader(name: str) -> Callable:
    """The reader of metric ``name``: ``metrics/<name>.py``, else, for a
    name split by the cells it is reported in (``admit_ms.steady``), the
    reader of the name before its first dot (``metrics/admit_ms.py``)."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file() and "." in name:
        path = HERE / "metrics" / f"{name.split('.', 1)[0]}.py"
    if not path.is_file():
        raise ValueError(f"metric {name!r} has no reader under "
                         f"{path.parent}")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def recording_executor(name: str):
    """The configured executor, recording every flush it is given."""
    from repro.core import executor as ex

    base = {"sync": ex.SyncExecutor, "async": ex.AsyncExecutor,
            "sharded": ex.ShardedExecutor}[name]

    class Recording(base):
        def __init__(self):
            super().__init__()
            self.flushes: List[Flush] = []

        def submit(self, ell, *args, payload=None, **kwargs):
            handle = super().submit(ell, *args, payload=payload, **kwargs)
            self.flushes.append(Flush(
                at=time.perf_counter(), shape=tuple(np.shape(ell)),
                uids=[r.uid for r in payload or ()]))
            return handle

    return Recording()


def stats_numbers(engine) -> Dict[str, float]:
    s = engine.stats
    return {"flushes": s.flushes, "deadline_flushes": s.deadline_flushes,
            "clustered": s.clustered, "padded_slots": s.padded_slots,
            "rejected": s.rejected,
            "harvested_flushes": s.latency.total_flushes,
            "assemble_s": s.latency.total_assemble_s,
            "build_s": s.latency.total_build_s}


class Driver:
    """Sends a cell's requests to the engine and stamps them."""

    def __init__(self, engine, records: List[Record], graphs, traffic: dict,
                 span):
        from repro.serve.cluster_batcher import ClusterRequest
        from repro.serve.engine import AdmissionRejected

        self.engine = engine
        self.records = records
        self.graphs = graphs
        self.traffic = traffic
        self.span = span
        self.by_uid = {r.uid: r for r in records}
        self.request = ClusterRequest
        self.rejected = AdmissionRejected
        self.sent = 0
        self.done = 0

    def _retired(self, out) -> None:
        now = time.perf_counter()
        for req in out:
            rec = self.by_uid.get(req.uid)
            if rec is not None and rec.retired_at is None:
                rec.retired_at = now
                self.done += 1

    def _admit(self, rec: Record) -> None:
        rec.req = self.request(uid=rec.uid, graph=self.graphs[rec.graph],
                               key=rec.key)
        rec.admit_start = time.perf_counter()
        while True:
            try:
                with self.span("bench.admit"):
                    out = self.engine.admit(rec.req)
                break
            except self.rejected:
                with self.span("bench.poll"):
                    self._retired(self.engine.poll())
        rec.admit_end = time.perf_counter()
        self.sent += 1
        self._retired(out)

    def _idle(self, until: float) -> None:
        with self.span("bench.poll"):
            self._retired(self.engine.poll())
        pause = min(POLL_S, until - time.perf_counter())
        if pause > 0:
            with self.span("bench.sleep"):
                time.sleep(pause)

    def window(self, t_start: float, t_end: float) -> None:
        kind = self.traffic["arrivals"]
        recs = self.records
        if kind == "poisson":
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    break
                if self.sent < len(recs) and recs[self.sent].due <= now:
                    self._admit(recs[self.sent])
                else:
                    nxt = recs[self.sent].due if self.sent < len(recs) \
                        else t_end
                    self._idle(min(nxt, t_end))
        elif kind == "closed":
            outstanding = self.traffic["outstanding"]
            while time.perf_counter() < t_end:
                if self.sent - self.done < outstanding \
                        and self.sent < len(recs):
                    self._admit(recs[self.sent])
                else:
                    self._idle(t_end)
        elif kind == "saturate":
            while time.perf_counter() < t_end and self.sent < len(recs):
                self._admit(recs[self.sent])
        else:
            raise ValueError(f"unknown arrivals {kind!r}")
        if kind != "poisson" and self.sent == len(recs) \
                and time.perf_counter() < t_end:
            raise RuntimeError(
                f"all {len(recs)} requests the traffic's max_rate_per_s "
                "allows were sent before the window closed")

    def drain(self, t_end: float) -> None:
        """Send what fell due in the window and was not sent, then wait
        for every answer, ``DRAIN_S`` at most."""
        if self.traffic["arrivals"] == "poisson":
            while self.sent < len(self.records) \
                    and self.records[self.sent].due <= t_end:
                self._admit(self.records[self.sent])
        deadline = time.perf_counter() + DRAIN_S
        while self.done < self.sent and time.perf_counter() < deadline:
            self._idle(deadline)
        if self.done < self.sent:
            self._retired(self.engine.flush())


def run(cell: dict, config: dict, traffic: dict, bench: dict, seed: int,
        seconds: float, trace: bool, t0: float) -> dict:
    import jax

    from repro.core import build_graph
    from repro.serve.cluster_batcher import ClusterBatcher, ClusterRequest

    from bench import peaks as peaks_mod
    from bench import trace as trace_mod

    device = jax.devices()[0]
    watch = CompileWatch(jax)
    eng_cfg = dict(config["engine"])
    k = eng_cfg["num_samples"]

    # Set-up 1: traffic from the seed.
    t = time.perf_counter()
    pool = traffic_gen.make_pool(config, seed)
    graphs = [build_graph(n, e) for n, e in pool]
    budget = traffic_gen.request_budget(traffic, seconds)
    order = traffic_gen.graph_order(len(pool), budget, seed)
    keys = traffic_gen.request_keys(budget, seed)
    records = [Record(uid=i, graph=int(order[i]),
                      key=jax.device_put(keys[i])) for i in range(budget)]
    if traffic["arrivals"] == "poisson":
        offsets = traffic_gen.poisson_offsets(traffic["rate_per_s"],
                                              seconds, seed)
    say("set-up traffic", seconds=time.perf_counter() - t, pool=len(pool),
        distinct_n=len({n for n, _ in pool}), requests=budget,
        edges=sum(len(e) for _, e in pool))

    # Set-up 2: the engine, warmed for this pool's buckets and sizes only.
    executor = recording_executor(eng_cfg.pop("executor"))
    engine = ClusterBatcher(executor=executor, **eng_cfg)
    t, snap = time.perf_counter(), watch.snapshot()
    programs = engine.warmup(graphs)
    counts, spans = watch.since(snap), watch.spans(snap)
    first = min((a for a, _ in spans.values()), default=time.perf_counter())
    say("set-up warm-up", seconds=time.perf_counter() - t,
        planning_s=first - t, bucket_programs=programs,
        build_walls_s={name: round(b - a, 3)
                       for name, (a, b) in spans.items()}, **counts)

    # Set-up 3: a few requests through admit and flush, with keys no
    # window request has, so that nothing the path needs is first built in
    # the window.
    t, snap = time.perf_counter(), watch.snapshot()
    extra = traffic_gen.request_keys(REHEARSAL, seed, stream=4)
    for i in range(REHEARSAL):
        engine.admit(ClusterRequest(uid=-1 - i, graph=graphs[i % len(graphs)],
                                    key=jax.device_put(extra[i])))
    engine.flush()
    say("set-up rehearsal", seconds=time.perf_counter() - t,
        **watch.since(snap))
    executor.flushes.clear()
    gc.collect()

    # The window.
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())
    driver = Driver(engine, records, graphs, traffic, span)
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    snap = watch.snapshot()
    stats = {"start": stats_numbers(engine)}
    t_start = time.perf_counter()
    setup_s = t_start - t0
    t_end = t_start + seconds
    if traffic["arrivals"] == "poisson":
        for rec, off in zip(records, offsets):
            rec.due = t_start + float(off)
    with span("bench.window"):
        driver.window(t_start, t_end)
        t_end = max(t_end, time.perf_counter())
    stats["end"] = stats_numbers(engine)
    flushes = [f for f in executor.flushes if f.at <= t_end]
    in_window = watch.since(snap)
    if trace:
        jax.profiler.stop_trace()
    driver.drain(t_end)
    attempted = [r for r in records if r.admit_start is not None
                 and (r.due if r.due is not None else r.admit_start) <= t_end]
    say("window", seconds=t_end - t_start, sent=driver.sent,
        answered=driver.done, attempted=len(attempted),
        flushes=len(flushes), **in_window)
    if in_window["executables"]:
        raise RuntimeError(
            f"{in_window['executables']} executables were built inside the "
            f"measured window: {in_window['by_name']}")
    memory_peak = int((device.memory_stats() or {}).get(
        "peak_bytes_in_use", 0))

    # Free the program's state before the reference runs.
    for rec in attempted:
        rec.result = rec.req.result if rec.req.done else None
    for rec in records:
        rec.req = None
    del engine, driver, executor, graphs
    gc.collect()

    reduced = None
    if trace:
        t = time.perf_counter()
        reduced = trace_mod.reduce(trace_mod.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        say("trace", seconds=time.perf_counter() - t,
            window_s=reduced["window_s"], busy_s=reduced["busy_s"])

    t = time.perf_counter()
    plans, answers = check.reference_answers(
        jax, attempted, pool, config["engine"].get("eps", 2.0), k)
    numbers, failed = check.compare(attempted, plans, answers)
    say("reference", seconds=time.perf_counter() - t,
        compared=len(attempted))

    ctx = Context(cell=cell, config=config, k=k, t_start=t_start,
                  t_end=t_end, setup_s=setup_s, records=attempted,
                  flushes=flushes, stats=stats, plans=plans, trace=reduced,
                  peaks=peaks_mod.chip_peaks(device.device_kind)
                  if device.platform == "tpu" else None)
    metrics = {}
    for spec in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" in spec and cell["name"] not in spec["workloads"]:
            continue
        value = load_reader(spec["name"])(ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

    checks = {name: {"value": v, "limit": 0} for name, v in numbers.items()}
    correct = len(attempted) > 0 and all(v == 0 for v in numbers.values())
    out = {"correct": correct, "attempted": len(attempted), "failed": failed,
           "metrics": metrics,
           "device": {"platform": device.platform, "kind": device.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": memory_peak}}
    if trace:
        out["device"].update(busy_s=reduced["busy_s"],
                             window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out
