"""binomial: 48-step binomial lattice price of a call, per row."""

import numpy as np


def kernel(P, c, s):
    xp = P.xp
    T, K, r = 48, 100.0, 0.05
    dt = 1.0 / T
    u = float(np.exp(0.2 * np.sqrt(dt)))
    d = 1.0 / u
    p = (float(np.exp(r * dt)) - d) / (u - d)
    disc = float(np.exp(-r * dt))
    j = P.arr(np.arange(T + 1, dtype=np.float32))
    st = c["S0"][:, None] * (u ** j) * (d ** (T - j))
    v = xp.maximum(st - K, 0.0)
    for _ in range(T):
        v = disc * (p * v[:, 1:] + (1 - p) * v[:, :-1])
        v = xp.pad(v, ((0, 0), (0, 1)))
    return v[:, 0]
