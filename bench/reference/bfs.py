"""bfs: four levels of frontier expansion over adj, clipped to {0, 1}."""


def kernel(P, c, s):
    xp = P.xp
    frontier = c["frontier"]
    visited = frontier
    for _ in range(4):
        frontier = xp.clip(P.mm(frontier, s["adj"]), 0.0, 1.0) * (1.0 - visited)
        visited = xp.clip(visited + frontier, 0.0, 1.0)
    return visited
