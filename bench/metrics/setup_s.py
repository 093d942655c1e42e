"""Seconds from the process's start to the window's: imports, the chip's
start-up, traffic generation, the engine's warm-up (compiles or cache
loads) and the set-up rehearsal."""


def read(ctx):
    return ctx.setup_s
