"""Plain sequential Hierholzer: one Euler circuit of a connected graph
whose degrees are all even, as arrival stubs in walk order."""
from __future__ import annotations

import numpy as np


def hierholzer(graph) -> np.ndarray:
    """An Euler circuit of ``graph`` (see ``checker`` for the stub
    convention); raises ``ValueError`` when the graph has none."""
    E, V = graph.num_edges, graph.num_vertices
    if E == 0:
        return np.zeros(0, dtype=np.int64)
    u = graph.edge_u.tolist()
    v = graph.edge_v.tolist()
    incident = [[] for _ in range(V)]       # the stubs at each vertex
    for e in range(E):
        incident[u[e]].append(2 * e)
        incident[v[e]].append(2 * e + 1)
    if any(len(inc) % 2 for inc in incident):
        raise ValueError("the graph has an odd-degree vertex")
    used = [False] * E
    start = u[0]
    stack = [(start, -1)]                   # (vertex, stub that arrived)
    out = []
    while stack:
        x, arrived = stack[-1]
        inc = incident[x]
        while inc and used[inc[-1] >> 1]:
            inc.pop()
        if inc:
            s = inc.pop() ^ 1               # leave x, arrive at stub s
            used[s >> 1] = True
            stack.append((v[s >> 1] if s & 1 else u[s >> 1], s))
        else:
            stack.pop()
            if arrived >= 0:
                out.append(arrived)
    if len(out) != E:
        raise ValueError(f"the walk covers {len(out)} of {E} edges: "
                         "the graph is not connected")
    return np.array(out[::-1], dtype=np.int64)
