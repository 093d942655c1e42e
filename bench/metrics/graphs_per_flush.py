"""Mean number of graphs (requests) in one of the window's flushes."""


def read(ctx):
    if not ctx.flushes:
        return None
    return sum(len(f.uids) for f in ctx.flushes) / len(ctx.flushes)
