"""Milliseconds from entering ``sweep.run`` to its return, averaged over
the window's grid calls (host clock): operand build (per-seed keys, the
comm mask schedule or selection keys, stacked operands), executor cache
lookup and dispatch. Dispatch is asynchronous, so this is host work unless
something inside waits on the device."""

import statistics

LAYER = "host path (core.sweep.run)"
UNIT = "ms"
MOVES = "cell_rounds_per_s"


def read(ctx):
    if not ctx["calls"]:
        return None
    return 1e3 * statistics.fmean(c.t_return - c.t_start
                                  for c in ctx["calls"])
