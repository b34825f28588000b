"""heat-3d: two explicit 7-point heat steps (0.1), periodic."""


def kernel(P, c, s):
    xp = P.xp
    u = c["u"]
    for _ in range(2):
        lap = (xp.roll(u, 1, 1) + xp.roll(u, -1, 1)
               + xp.roll(u, 1, 2) + xp.roll(u, -1, 2)
               + xp.roll(u, 1, 3) + xp.roll(u, -1, 3) - 6 * u)
        u = u + 0.1 * lap
    return u
