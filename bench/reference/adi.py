"""adi: two sweeps of explicit diffusion along columns then rows, periodic."""


def kernel(P, c, s):
    xp = P.xp
    u = c["u"]
    for _ in range(2):
        u = u + 0.1 * (xp.roll(u, 1, axis=2) - 2 * u + xp.roll(u, -1, axis=2))
        u = u + 0.1 * (xp.roll(u, 1, axis=1) - 2 * u + xp.roll(u, -1, axis=1))
    return u
