"""Seconds from process start to window start: imports, device start,
population, problem, compile or compile-cache read, and the warm-up grid
call (host clock)."""

LAYER = "end to end"
UNIT = "s"
MOVES = None


def read(ctx):
    return ctx["setup_s"]
