"""Arithmetic the metric readers share: means of host spans, and the
pairing of traced program executions with the flushes that caused them."""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

# The engine's bucket program: its jitted function's name, or (the
# function is a ``functools.partial``, which XLA names ``jit__unknown``)
# the kernels only it runs.
BUCKET_PROGRAM = ("bucket_impl", "jit__unknown")
BUCKET_KERNELS = ("label_agree", "neighbor_min")


def mean(values) -> Optional[float]:
    values = list(values)
    return float(np.mean(values)) if values else None


def admit_ms(ctx) -> List[float]:
    return [1e3 * (r.admit_end - r.admit_start) for r in ctx.records
            if r.admit_start is not None and r.admit_end is not None
            and r.admit_start <= ctx.t_end]


def program_runs(ctx) -> Optional[List[Tuple[dict, object]]]:
    """Each traced execution of a bucket program, with the flush it ran:
    the window starts with the engine empty and one device runs programs in
    the order they were submitted, so the i-th execution is the i-th flush.
    None when there is no trace or more executions than flushes."""
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    modules = [m for m in ctx.trace["devices"][0]["modules"]
               if is_bucket_program(m)]
    if not modules or len(modules) > len(ctx.flushes):
        return None
    flushes = sorted(ctx.flushes, key=lambda f: f.at)
    return list(zip(modules, flushes))


def is_bucket_program(module: dict) -> bool:
    if any(name in module["name"] for name in BUCKET_PROGRAM):
        return True
    return any(k in op or k in stats for op, stats, _ in module["ops"]
               for k in BUCKET_KERNELS)


def least_bytes(ctx, flushes, bytes_of_graph) -> float:
    """``bytes_of_graph(n, kept edges, k)`` summed over every graph the
    flushes ran, from the reference's plan of each."""
    graph_of = {r.uid: r.graph for r in ctx.records}
    return sum(bytes_of_graph(plan.n, plan.kept, ctx.k)
               for f in flushes for plan in
               (ctx.plans[graph_of[uid]] for uid in f.uids))
