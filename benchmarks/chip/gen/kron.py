"""Graph500 Kronecker graphs, then the paper's §4.2 pipeline.

A copy of ``repro.graphgen.kronecker`` kept with the benchmark, so that
no change to the program can change the benchmark's graphs; it yields
byte-identical graphs for every seed (the harness tests check it).
Kernel 0 follows the Graph500 reference ``kronecker_generator.m``:
``edgefactor · 2^scale`` edges drawn bit by bit from the initiator
(A, B, C, D), vertex labels permuted, the edge list shuffled.  Kernel 1
drops self-loops and duplicate edges and lists the simple graph's edges
in sorted ``(u, v)`` order.  Then the largest component, eulerized with
``seed + 1``.
"""
from __future__ import annotations

import numpy as np

from . import EdgeList
from .eulerize import eulerize, largest_component


def kronecker_graph(scale: int, edgefactor: int = 16, a: float = 0.57,
                    b: float = 0.19, c: float = 0.19,
                    seed: int = 0) -> EdgeList:
    """A simple undirected Kronecker graph on ``2**scale`` vertices."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    u = np.zeros(m, dtype=np.int64)
    v = np.zeros(m, dtype=np.int64)
    for ib in range(scale):
        ii = rng.random(m) > ab                 # row bit: 1 with C + D
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        u += ii.astype(np.int64) << ib
        v += jj.astype(np.int64) << ib
    perm = rng.permutation(n)                   # vertex labels permuted
    u, v = perm[u], perm[v]
    order = rng.permutation(m)                  # edge list shuffled
    u, v = u[order], v[order]

    lo, hi = np.minimum(u, v), np.maximum(u, v)
    keep = lo != hi
    lo, hi = lo[keep], hi[keep]
    _, first = np.unique(lo * n + hi, return_index=True)
    return EdgeList(n, lo[first], hi[first])


def eulerian_kronecker(seed: int, scale: int, edgefactor: int = 16,
                       a: float = 0.57, b: float = 0.19,
                       c: float = 0.19) -> EdgeList:
    """Kronecker graph, then its largest component, then eulerize
    (seed + 1)."""
    g = kronecker_graph(scale, edgefactor=edgefactor, a=a, b=b, c=c,
                        seed=seed)
    return eulerize(largest_component(g), seed=seed + 1)
