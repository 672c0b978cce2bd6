"""Share of the traced window in which the device runs no operation, the
largest over the cell's chips, in percent (profiler trace)."""

LAYER = "device"
UNIT = "%"
MOVES = "cell_rounds_per_s"


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else 100.0 * tr["idle_share"]
