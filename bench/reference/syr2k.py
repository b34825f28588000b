"""syr2k: A @ Bfull.T + B @ Afull.T."""


def kernel(P, c, s):
    return P.mm(c["A"], s["Bfull"].T) + P.mm(c["B"], s["Afull"].T)
