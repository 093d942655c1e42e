"""Least time of the bucket programs' work over their device time, in %.

The least time is :func:`work.graph_bytes` of every graph a traced program
ran — its kept edges, its samples' ranks, its labels — at the chip's peak
HBM bandwidth: a bound no layout or loop can beat, so the share survives a
change of how the rounds are implemented.
"""

from bench import readings, work


def read(ctx):
    runs = readings.program_runs(ctx)
    if not runs or ctx.peaks is None:
        return None
    least = readings.least_bytes(ctx, [f for _, f in runs],
                                 work.graph_bytes) / ctx.peaks.hbm_bw
    return 100.0 * least / sum(m["seconds"] for m, _ in runs)
