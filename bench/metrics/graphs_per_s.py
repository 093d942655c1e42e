"""Graphs answered inside the window over the window's seconds."""


def read(ctx):
    return len(ctx.retired_in_window()) / ctx.seconds
