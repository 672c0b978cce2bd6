"""Share of its roofline that ``qsgd_dequantize`` reaches in the traced window,
in percent (see ``chipbench/roofline.py``). None where the cell's
executor runs no such kernel."""

from chipbench import roofline

LAYER = "kernels (Pallas)"
UNIT = "%"
MOVES = "cell_rounds_per_s"


def read(ctx):
    return roofline.share(ctx, "qsgd_dequantize")
