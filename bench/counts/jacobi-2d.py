"""jacobi-2d on r rows of 48 x 48: two sweeps of four adds and one
multiply per point; the least traffic reads the grid once and writes the
result once (float32)."""


def counts(rows: int) -> tuple[float, float]:
    n = rows * 48 * 48
    return float(2 * 5 * n), float(2 * 4 * n)
