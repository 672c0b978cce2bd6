"""Operations and bytes of one ``weighted_mean_over_clients`` call on
[cells, S, d].

Per cell it reads the [S, d] rows and the [S] weights and writes the [d]
mean: S d products, S d additions and d divisions.
"""


def cost(cells: int, rows: int, width: int):
    n = rows * width
    return cells * (2 * n + width), cells * 4 * (n + rows + width)
