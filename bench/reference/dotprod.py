"""dotprod: per-row dot product of a and b."""


def kernel(P, c, s):
    return P.xp.sum(c["a"] * c["b"], axis=1)
