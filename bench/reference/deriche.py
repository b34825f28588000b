"""deriche: first-order IIR smoothing (a = 0.7) along rows, forward then backward."""


def kernel(P, c, s):
    xp = P.xp
    x = c["img"]
    a = 0.7
    y = xp.zeros_like(x[..., 0])
    fwd = []
    for w in range(x.shape[-1]):
        y = a * y + (1 - a) * x[..., w]
        fwd.append(y)
    y = xp.zeros_like(x[..., 0])
    bwd = []
    for w in reversed(range(x.shape[-1])):
        y = a * y + (1 - a) * fwd[w]
        bwd.append(y)
    return xp.stack(bwd[::-1], axis=-1)
