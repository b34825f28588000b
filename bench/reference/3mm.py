"""3mm: (A @ B) @ (C @ D)."""


def kernel(P, c, s):
    return P.mm(P.mm(c["A"], s["B"]), P.mm(s["C"], s["D"]))
