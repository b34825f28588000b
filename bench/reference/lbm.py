"""lbm: one D2Q9 stream and BGK relax step (omega 0.6), periodic."""

import numpy as np


def kernel(P, c, s):
    xp = P.xp
    f = c["f"]
    w = P.arr(np.array([4 / 9] + [1 / 9] * 4 + [1 / 36] * 4, np.float32))
    shifts = [(0, 0), (1, 0), (-1, 0), (0, 1), (0, -1),
              (1, 1), (-1, -1), (1, -1), (-1, 1)]
    rho = xp.sum(f, axis=1, keepdims=True)
    streamed = xp.stack([xp.roll(f[:, i], sh, axis=(1, 2))
                         for i, sh in enumerate(shifts)], axis=1)
    feq = w[None, :, None, None] * rho
    return streamed + 0.6 * (feq - streamed)
