"""mvmult: A @ v."""


def kernel(P, c, s):
    return P.mm(c["A"], s["v"])
