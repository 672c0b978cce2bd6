"""Milliseconds a grid call spends building its comm mask schedule (or a
policy grid's selection keys): the program's ``repro.sweep.masks`` span,
summed over the profiled grid calls and averaged per call (profiler trace,
``chipbench/program_trace.py``). None where the program opens no such
span."""

from chipbench import program_trace

LAYER = "host path (core.sweep.run)"
UNIT = "ms"
MOVES = "cell_rounds_per_s"


def read(ctx):
    return program_trace.span_ms_per_call(ctx, "repro.sweep.masks")
