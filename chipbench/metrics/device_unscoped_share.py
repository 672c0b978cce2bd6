"""Share of device busy time in ops under no round-body scope, in percent:
how much of the device time the scopes leave without an owner (profiler
trace, ``chipbench/program_trace.py``). None where no op carries any
scope."""

from chipbench import program_trace

LAYER = "device"
UNIT = "%"
MOVES = "cell_rounds_per_s"


def read(ctx):
    scope_s = program_trace.measure(ctx).get("scope_s") or {}
    total = sum(scope_s.values())
    if not total or set(scope_s) <= {program_trace.NONE}:
        return None
    return 100.0 * scope_s.get(program_trace.NONE, 0.0) / total
