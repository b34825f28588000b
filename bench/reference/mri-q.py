"""mri-q: Q matrix: sums of phi*cos and phi*sin of 2*pi*(x @ k.T)."""

import numpy as np


def kernel(P, c, s):
    xp = P.xp
    phase = 2 * float(np.pi) * P.mm(c["x"], s["k"].T)
    return xp.stack([xp.sum(s["phi"] * xp.cos(phase), axis=1),
                     xp.sum(s["phi"] * xp.sin(phase), axis=1)], axis=1)
