"""2mm: (1.5 * A @ B) @ C + 1.2 * D."""


def kernel(P, c, s):
    return P.mm(1.5 * P.mm(c["A"], s["B"]), s["C"]) + 1.2 * c["D"]
