"""Device microseconds per grid round under the ``local`` scope (client
computation: oracles and local steps), summed over the cell's chips and
divided by profiled grid calls x rounds (profiler trace,
``chipbench/program_trace.py``). None where no op carries the scope."""

from chipbench import program_trace

LAYER = "round program (the whole scan)"
UNIT = "us"
MOVES = "cell_rounds_per_s"


def read(ctx):
    return program_trace.scope_us_per_round(ctx, ("local",))
