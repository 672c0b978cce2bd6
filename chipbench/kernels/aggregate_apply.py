"""Operations and bytes of one ``aggregate_apply`` call on [cells, S, d].

Per cell it reads four [S, d] tables (wire rows, feedback input,
compressed rows, residuals), the [d] iterate and two [S] columns (weights,
mask), and writes the [d] iterate and the [S, d] residuals. The weighted
sum is 2 S d operations and the subtraction from x d more; the residual
m (di - co) + (1 - m) rs is 4 S d and S more.
"""


def cost(cells: int, rows: int, width: int):
    n = rows * width
    flops = 2 * n + width + 4 * n + rows
    return cells * flops, cells * 4 * (5 * n + 2 * width + 2 * rows)
