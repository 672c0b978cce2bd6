"""Model FLOP utilisation of the whole round program, in percent: the
model operations a cell-round requires (``chipbench/flops.py``, from the
configuration's shapes) times the window's cell-rounds per second, over
the chips' bf16 peak (``chipbench/peaks.py``)."""

from chipbench import flops, peaks

LAYER = "round program (the whole scan)"
UNIT = "%"
MOVES = "cell_rounds_per_s"


def read(ctx):
    if not ctx["calls"]:
        return None
    cell = ctx["cell"]
    rate = ctx["cell_rounds"] / ctx["window_s"]
    need = flops.per_cell_round(cell.config, cell.traffic, cell.family)
    peak = peaks.peaks(ctx["device"]["kind"])["flops"]
    return 100.0 * need * rate / (ctx["chips"] * peak)
