"""vecadd on r rows of 256: one add per element; reads a and b, writes
the sum (float32)."""


def counts(rows: int) -> tuple[float, float]:
    n = rows * 256
    return float(n), float(3 * 4 * n)
