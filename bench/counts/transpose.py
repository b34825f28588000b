"""transpose on r rows of 64 x 64: no arithmetic (the kernel's * 1.0 is
an identity); reads and writes every element once (float32)."""


def counts(rows: int) -> tuple[float, float]:
    n = rows * 64 * 64
    return 0.0, float(2 * 4 * n)
