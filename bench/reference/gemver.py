"""gemver: A2 = A + u1 v1^T + u2 v2^T; 1.2 * A2 + (A2 @ y) as a column."""


def kernel(P, c, s):
    xp = P.xp
    A = c["A"] + xp.outer(c["u1"], s["v1"]) + xp.outer(c["u2"], s["v2"])
    return A * 1.2 + P.mm(A, s["y"])[:, None]
