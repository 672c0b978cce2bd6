"""Peak device memory after the window, largest over the cell's chips
(``memory_stats()["peak_bytes_in_use"]``), in GB. It sets how large a grid
fits on a chip."""

LAYER = "end to end"
UNIT = "GB"
MOVES = None


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
