"""Device time of the bucket programs in the traced window per graph
answered in it."""

from bench import readings


def read(ctx):
    runs = readings.program_runs(ctx)
    graphs = len(ctx.retired_in_window())
    if not runs or not graphs:
        return None
    return 1e3 * sum(m["seconds"] for m, _ in runs) / graphs
