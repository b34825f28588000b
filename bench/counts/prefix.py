"""prefix on r rows of 2048: an inclusive scan needs one add per element
after the first of each row; reads and writes every element once
(float32)."""


def counts(rows: int) -> tuple[float, float]:
    return float(rows * 2047), float(2 * 4 * rows * 2048)
