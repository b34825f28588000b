"""gemm: (1.5 * A) @ B + 1.2 * C."""


def kernel(P, c, s):
    return P.mm(1.5 * c["A"], s["B"]) + 1.2 * c["C"]
