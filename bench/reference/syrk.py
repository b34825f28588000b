"""syrk: A @ Afull.T."""


def kernel(P, c, s):
    return P.mm(c["A"], s["Afull"].T)
