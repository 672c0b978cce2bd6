"""Share of its roofline that ``toy_embed_grad`` reaches in the traced
window, in percent (see ``chipbench/roofline.py``)."""

from chipbench import roofline

LAYER = "kernels (Pallas)"
UNIT = "%"
MOVES = "cell_rounds_per_s"


def read(ctx):
    return roofline.share(ctx, "toy_embed_grad")
