"""Least time of the ``neighbor_min`` kernel calls over their device time,
in %: :func:`work.neighbor_min_call_bytes` at the call's packed shape (its
program's flush) at the chip's peak HBM bandwidth."""

from bench import readings, work

KERNEL = "neighbor_min"


def read(ctx):
    runs = readings.program_runs(ctx)
    if not runs or ctx.peaks is None:
        return None
    least = spent = 0.0
    for module, flush in runs:
        for name, stats, seconds in module["ops"]:
            if KERNEL in name or KERNEL in stats:
                least += work.neighbor_min_call_bytes(*flush.shape)
                spent += seconds
    if not spent:
        return None
    return 100.0 * least / ctx.peaks.hbm_bw / spent
