"""blackscholes: Black-Scholes call and put, stacked on a new last axis."""

import numpy as np


def kernel(P, c, s):
    xp = P.xp
    S, K, T = c["S"], c["K"], c["T"]
    r, sig = 0.05, 0.2
    d1 = (xp.log(S / K) + (r + 0.5 * sig ** 2) * T) / (sig * xp.sqrt(T))
    d2 = d1 - sig * xp.sqrt(T)
    cdf = lambda x: 0.5 * (1.0 + P.erf(x / float(np.sqrt(2.0))))  # noqa: E731
    call = S * cdf(d1) - K * xp.exp(-r * T) * cdf(d2)
    put = K * xp.exp(-r * T) * cdf(-d2) - S * cdf(-d1)
    return xp.stack([call, put], axis=1)
