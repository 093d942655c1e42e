"""Mean host wall of one ``ClusterBatcher.admit`` call in the window:
plan, fingerprint, row build and rank dispatch, plus the flushes and
harvests the call ran."""

from bench import readings


def read(ctx):
    return readings.mean(readings.admit_ms(ctx))
