"""mvt: concat(A @ y1, A.T @ y2[:rows]) (chunk-local)."""


def kernel(P, c, s):
    A = c["A"]
    return P.xp.concatenate([P.mm(A, s["y1"]),
                             P.mm(A.T, s["y2"][:A.shape[0]])])
