"""covariance: covariance matrix of the chunk's columns (chunk-local)."""


def kernel(P, c, s):
    xp = P.xp
    x = c["x"]
    xc = x - xp.mean(x, axis=0, keepdims=True)
    return P.mm(xc.T, xc) / x.shape[0]
