"""gesummv: 1.5 * A @ x + 1.2 * B @ x."""


def kernel(P, c, s):
    return 1.5 * P.mm(c["A"], s["x"]) + 1.2 * P.mm(c["B"], s["x"])
