"""The least bytes a density request moves, counted from the query and
the data, never from the implementation.

Every row inside the request's envelope and window has to be read to be
counted: at least its x and y at 4 bytes each (float32 places a row in a
cell of a 256-cell axis; the float64 columns are needed only for the few
rows within rounding of a cell edge). The time column adds nothing to the
least count (a time-partitioned layout selects the window by position),
and rows outside the envelope or window add nothing (pruning may skip
them). The grid is written once, 4 bytes a cell.
"""

BYTES_PER_ROW = 8
BYTES_PER_CELL = 4


def least_bytes(rows_in: int, width: int, height: int) -> int:
    return int(rows_in) * BYTES_PER_ROW + int(width) * int(height) * BYTES_PER_CELL
