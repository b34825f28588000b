"""montecarlo: discounted mean European call payoff over each row's paths."""

import numpy as np


def kernel(P, c, s):
    xp = P.xp
    S0, K, r, sig, T = 100.0, 100.0, 0.05, 0.2, 1.0
    st = S0 * xp.exp((r - 0.5 * sig ** 2) * T + sig * T ** 0.5 * c["z"])
    payoff = xp.maximum(st - K, 0.0)
    return float(np.exp(-r * T)) * xp.mean(payoff, axis=1)
