"""correlation: correlation matrix of the chunk's columns (chunk-local)."""


def kernel(P, c, s):
    xp = P.xp
    x = c["x"]
    xm = x - xp.mean(x, axis=0, keepdims=True)
    sd = xp.sqrt(xp.mean(xm ** 2, axis=0, keepdims=True)) + 1e-6
    xn = xm / sd
    return P.mm(xn.T, xn) / x.shape[0]
