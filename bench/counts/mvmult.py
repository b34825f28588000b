"""mvmult on r rows of 768: a multiply and an add per matrix element;
reads the matrix and the vector, writes one float32 per row."""


def counts(rows: int) -> tuple[float, float]:
    return float(2 * rows * 768), float(4 * (rows * 768 + 768 + rows))
