"""scalarprod: sum of a*b over the chunk, shape (1,); chunks add up."""


def kernel(P, c, s):
    return P.xp.reshape(P.xp.sum(c["a"] * c["b"]), (1,))
