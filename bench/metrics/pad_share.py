"""Padded batch entries over all batch entries of the window's flushes, in
%: a flush of ``g`` graphs packs ``B = g_pad · k`` entries (``Flush.shape``),
of which ``k`` per graph are real (``Flush.uids``)."""


def read(ctx):
    entries = sum(f.shape[0] for f in ctx.flushes)
    if not entries:
        return None
    real = sum(ctx.k * len(f.uids) for f in ctx.flushes)
    return 100.0 * (entries - real) / entries
