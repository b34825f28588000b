"""jacobi-2d: two 5-point Jacobi sweeps (0.2 * sum), periodic."""


def kernel(P, c, s):
    xp = P.xp
    u = c["u"]
    for _ in range(2):
        u = 0.2 * (u + xp.roll(u, 1, 1) + xp.roll(u, -1, 1)
                   + xp.roll(u, 1, 2) + xp.roll(u, -1, 2))
    return u
