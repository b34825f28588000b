"""fwt: natural-order fast Walsh-Hadamard transform of each row."""


def kernel(P, c, s):
    xp = P.xp
    x = c["x"]
    n, m = x.shape
    h = 1
    while h < m:
        x = x.reshape(n, -1, 2, h)
        a, b = x[:, :, 0, :], x[:, :, 1, :]
        x = xp.stack([a + b, a - b], axis=2).reshape(n, m)
        h *= 2
    return x
