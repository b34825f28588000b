"""jacobi-1d: 0.333 * (left + self + right), periodic."""


def kernel(P, c, s):
    xp = P.xp
    x = c["x"]
    return 0.333 * (xp.roll(x, 1, 1) + x + xp.roll(x, -1, 1))
