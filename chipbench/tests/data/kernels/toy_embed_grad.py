"""Operations and bytes of one ``toy_embed_grad`` call on [cells, rows,
width]: a multiply-add per element, reading the table and the update and
writing the table."""


def cost(cells: int, rows: int, width: int):
    n = rows * width
    return cells * 2 * n, cells * 4 * 3 * n
