"""Pallas TPU kernels for the graph engine, each with ops.py + ref.py."""


def row_tiling(n_rows: int, max_rows: int) -> tuple[int, int]:
    """``(padded_rows, rows_per_block)`` for a lane-dense ``[n_rows, L]``.

    The TPU lowering accepts a block whose last two dims are multiples of
    (8, 128) or equal to the whole array's.  Up to ``max_rows`` rows go
    in one whole-array block; longer arrays pad to a multiple of 8 rows
    and take the largest multiple of 8 <= ``max_rows`` dividing that.
    """
    if n_rows <= max_rows:
        return n_rows, n_rows
    n_pad = -(-n_rows // 8) * 8
    r = max_rows - max_rows % 8
    while n_pad % r:
        r -= 8
    return n_pad, r
