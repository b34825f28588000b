"""sgemm: A @ B."""


def kernel(P, c, s):
    return P.mm(c["A"], s["B"])
