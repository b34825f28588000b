"""transpose: each row's 64x64 tile transposed."""


def kernel(P, c, s):
    return P.xp.swapaxes(c["x"], 1, 2)
