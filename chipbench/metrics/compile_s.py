"""The first grid call's wall time minus a warm call's (the median of the
window's calls): tracing, lowering and compiling the executor, or reading
it from the persistent cache (host clock)."""

import statistics

LAYER = "executor (core.runner cache, jax.jit)"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    if not ctx["calls"]:
        return None
    warm = statistics.median(c.t_done - c.t_start for c in ctx["calls"])
    return ctx["warmup_s"] - warm
