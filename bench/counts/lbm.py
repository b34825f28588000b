"""lbm on r rows of 9 x 32 x 32: per cell, 8 adds for the density, 9
multiplies for the equilibria and 3 operations per direction for the
relaxation (subtract, scale, add): 44 per cell; reads and writes the 9
distributions once (float32)."""


def counts(rows: int) -> tuple[float, float]:
    cells = rows * 32 * 32
    return float(44 * cells), float(2 * 4 * 9 * cells)
