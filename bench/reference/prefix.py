"""prefix: inclusive prefix sum along each row."""


def kernel(P, c, s):
    return P.xp.cumsum(c["x"], axis=1)
