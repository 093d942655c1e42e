"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

:func:`load` turns the file into plain lists — one per device plane and
line, plus the benchmark's own host spans — and everything else here works
on those lists, so the self-checks can feed a small recorded trace without
the profiler.

* The traced window is the host span ``bench.window`` that the harness
  opens around the measured window; device events are clipped to it.
* Busy time of a device is the union of its op intervals (line
  ``XLA Ops``); ``busy_s`` averages it over the devices that ran anything.
* ``modules`` are the executions of compiled programs (line
  ``XLA Modules``), each with the ops and kernels that ran inside it.
* Idle gaps are the stretches of the window with no op on the device,
  each named by the benchmark host span that overlapped it most.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str) -> dict:
    """``{"devices": {plane: {line: [[name, text, start_ns, end_ns]]}},
    "host": [[name, start_ns, end_ns]]}`` from the newest trace file."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                events = []
                for ev in line.events:
                    stats = " ".join(str(v) for _, v in ev.stats
                                     if isinstance(v, str))
                    events.append([ev.name, stats, float(ev.start_ns),
                                   float(ev.end_ns)])
                lines[line.name] = events
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.end_ns)])
    return {"devices": devices, "host": host}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(events, lo: float, hi: float):
    for ev in events:
        a, b = max(ev[-2], lo), min(ev[-1], hi)
        if b > a:
            yield ev, a, b


def window(raw: dict) -> Tuple[float, float]:
    spans = [(a, b) for name, a, b in raw["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    return spans[0]


def reduce(raw: dict) -> dict:
    lo, hi = window(raw)
    per_device = []
    for plane, lines in sorted(raw["devices"].items()):
        ops = [(ev, a, b) for ev, a, b in clip(lines.get(OPS_LINE, []),
                                               lo, hi)]
        if not ops:
            continue
        busy = union([(a, b) for _, a, b in ops])
        modules = []
        for ev, a, b in clip(lines.get(MODULES_LINE, []), lo, hi):
            modules.append({"name": ev[0], "start": ev[2], "end": ev[3],
                            "seconds": (b - a) / 1e9, "ops": []})
        modules.sort(key=lambda mod: mod["start"])
        starts = [mod["start"] for mod in modules]
        for ev, a, b in ops:
            at = bisect.bisect_right(starts, ev[2]) - 1
            if at >= 0 and ev[2] < modules[at]["end"]:
                modules[at]["ops"].append((ev[0], ev[1], (b - a) / 1e9))
        per_device.append({"plane": plane, "ops": ops, "busy": busy,
                           "modules": modules})
    span_s = (hi - lo) / 1e9
    out = {"window_s": span_s, "devices": per_device}
    if not per_device:
        out.update(busy_s=0.0, device_ops=[], idle_gaps=[])
        return out
    out["busy_s"] = sum(sum(b - a for a, b in d["busy"])
                        for d in per_device) / len(per_device) / 1e9
    totals: Dict[str, float] = defaultdict(float)
    for d in per_device:
        for name, seconds in self_times(d["ops"]):
            totals[name] += seconds
    out["device_ops"] = [[k, v] for k, v in sorted(
        totals.items(), key=lambda kv: -kv[1])[:10]]
    out["idle_gaps"] = idle_gaps(per_device[0]["busy"], raw["host"], lo, hi)
    return out


def short_name(name: str) -> str:
    """``%neighbor_min.3 = s32[...] custom-call(...)`` → ``neighbor_min.3``."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def self_times(ops):
    """``(short name, seconds)`` of each op less the ops nested in it (a
    ``while`` holds the kernels of its body on the same line)."""
    out, stack = [], []
    for ev, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= (b - a) / 1e9
        out.append([short_name(ev[0]), (b - a) / 1e9])
        stack.append((len(out) - 1, b))
    return out


def idle_gaps(busy, host, lo: float, hi: float, top: int = 10):
    """The ``top`` longest stretches with no op on the device, each named
    by the benchmark host span (other than the window) that overlapped it
    most, or ``host-other``."""
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda ab: ab[0] - ab[1])
    spans = [s for s in host if s[0] != WINDOW]
    out = []
    for a, b in gaps[:top]:
        share: Dict[str, float] = defaultdict(float)
        for name, s, e in spans:
            if e > a and s < b:
                share[name[len("bench."):]] += min(b, e) - max(a, s)
        label = max(share, key=share.get) if share else "host-other"
        out.append([label, (b - a) / 1e9])
    return out
