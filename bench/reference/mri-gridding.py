"""mri-gridding: values scatter-added onto a 64x64 grid, shape (1, 4096); chunks add up."""


def kernel(P, c, s):
    return P.scatter_add(64 * 64, c["idx"].reshape(-1),
                         c["val"].reshape(-1))[None]
