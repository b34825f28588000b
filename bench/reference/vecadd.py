"""vecadd: c.a + c.b."""


def kernel(P, c, s):
    return c["a"] + c["b"]
