"""histo: 256-bin histogram of each row."""


def kernel(P, c, s):
    return P.row_hist(c["x"], 256)
