"""Graph500 Kronecker graphs (specification, "Graph Generation", Kernel 0).

Mirrors the reference ``kronecker_generator.m``: ``edgefactor · 2^scale``
edges, each endpoint bit drawn from the initiator (A, B, C, D), vertex
labels permuted at random and the edge list shuffled.  Kernel 1's graph
drops the self-loops and duplicate edges (the specification lets kernels
ignore them) and lists the simple graph's edges in sorted ``(u, v)``
order, as a compressed-row build of it does.  The Euler input then takes
the paper's pipeline (arXiv:1903.06950 §4.2): the largest component,
eulerized.
"""
from __future__ import annotations

import numpy as np

from ..core.graph import Graph
from .eulerize import eulerize, largest_component


def kronecker_graph(scale: int, edgefactor: int = 16, a: float = 0.57,
                    b: float = 0.19, c: float = 0.19, seed: int = 0) -> Graph:
    """A simple undirected Graph500 Kronecker graph on ``2**scale``
    vertices (Kernel 0, then Kernel 1's self-loop and duplicate drop)."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    u = np.zeros(m, dtype=np.int64)
    v = np.zeros(m, dtype=np.int64)
    for ib in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        u += ii.astype(np.int64) << ib
        v += jj.astype(np.int64) << ib
    perm = rng.permutation(n)
    u, v = perm[u], perm[v]
    order = rng.permutation(m)
    u, v = u[order], v[order]

    lo, hi = np.minimum(u, v), np.maximum(u, v)
    keep = lo != hi
    lo, hi = lo[keep], hi[keep]
    _, first = np.unique(lo * n + hi, return_index=True)
    return Graph(num_vertices=n, edge_u=lo[first], edge_v=hi[first])


def eulerian_kronecker(scale: int, edgefactor: int = 16, a: float = 0.57,
                       b: float = 0.19, c: float = 0.19,
                       seed: int = 0) -> Graph:
    """Kronecker graph → largest component → eulerize (seed + 1)."""
    g = kronecker_graph(scale, edgefactor=edgefactor, a=a, b=b, c=c,
                        seed=seed)
    return eulerize(largest_component(g), seed=seed + 1)
