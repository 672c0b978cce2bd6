"""Device microseconds per grid round under the ``downlink`` and ``uplink``
scopes (compression, error feedback, the fused aggregate-apply kernel),
summed over the cell's chips and divided by profiled grid calls x rounds
(profiler trace, ``chipbench/program_trace.py``). None where no op carries
either scope."""

from chipbench import program_trace

LAYER = "round program (the whole scan)"
UNIT = "us"
MOVES = "cell_rounds_per_s"


def read(ctx):
    return program_trace.scope_us_per_round(ctx, ("downlink", "uplink"))
