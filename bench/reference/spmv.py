"""spmv: row sums of val * v[idx]."""


def kernel(P, c, s):
    return P.xp.sum(c["val"] * s["v"][c["idx"]], axis=1)
