"""dct: D @ img @ D.T for each 32x32 image."""


def kernel(P, c, s):
    D = s["D"]
    return P.mm(P.mm(D, c["img"]), D.T)
