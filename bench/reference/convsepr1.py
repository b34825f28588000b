"""convsepr1: separable convolution of radius 1, rows then columns, periodic."""

R = 1


def kernel(P, c, s):
    xp = P.xp
    img, k = c["img"], s["k"]
    out = xp.zeros_like(img)
    for i in range(-R, R + 1):
        out = out + k[i + R] * xp.roll(img, i, axis=2)
    out2 = xp.zeros_like(out)
    for i in range(-R, R + 1):
        out2 = out2 + k[i + R] * xp.roll(out, i, axis=1)
    return out2
