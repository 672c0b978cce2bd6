"""Operations and bytes of one ``qsgd_dequantize`` call on [cells, S, d].

Per cell it reads the rows v and the uniforms u (S d floats each), the S row
norms and the level count, and writes S d floats. Per element it does 11
operations: |v|, / norm, * L, floor, scaled - lo, the comparison with u, the
add, sign, * norm, q / L and the product.
"""


def cost(cells: int, rows: int, width: int):
    """(flops, bytes) of one call."""
    n = rows * width
    return cells * 11 * n, cells * 4 * (3 * n + rows + 1)
