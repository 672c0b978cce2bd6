"""Grid cells x rounds completed per second: all cell-rounds of the grid
calls completed in the window over the time from window start to the last
completion (host clock). What a sweep user pays for."""

LAYER = "end to end"
UNIT = "cell-rounds/s"
MOVES = None


def read(ctx):
    if not ctx["calls"]:
        return None
    return ctx["cell_rounds"] / ctx["window_s"]
