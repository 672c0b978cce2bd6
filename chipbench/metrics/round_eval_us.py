"""Device microseconds per grid round under the ``eval`` scope (the
per-round global loss, and the final one), summed over the cell's chips and
divided by profiled grid calls x rounds (profiler trace,
``chipbench/program_trace.py``). None where no op carries the scope."""

from chipbench import program_trace

LAYER = "round program (the whole scan)"
UNIT = "us"
MOVES = "cell_rounds_per_s"


def read(ctx):
    return program_trace.scope_us_per_round(ctx, ("eval",))
