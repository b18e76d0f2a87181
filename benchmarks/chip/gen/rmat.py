"""R-MAT power-law graph generator (paper §4.2 uses parallel RMAT).

A copy of ``repro.graphgen.rmat.rmat_graph`` kept with the benchmark, so
that no change to the program can change the benchmark's graphs.  It
yields byte-identical graphs for every seed (the harness tests check it).
"""
from __future__ import annotations

import numpy as np

from . import EdgeList


def rmat_graph(scale: int, avg_degree: int = 5, a: float = 0.57,
               b: float = 0.19, c: float = 0.19, seed: int = 0) -> EdgeList:
    """An undirected R-MAT graph with 2**scale vertices and average
    undirected degree ``avg_degree``; self-loops and duplicate edges are
    removed."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * avg_degree // 2
    m_try = int(m * 1.35) + 16      # oversample to survive the dedup

    u = np.zeros(m_try, dtype=np.int64)
    v = np.zeros(m_try, dtype=np.int64)
    d = 1.0 - a - b - c
    for _ in range(scale):
        u <<= 1
        v <<= 1
        r1 = rng.random(m_try)
        r2 = rng.random(m_try)
        # quadrant probabilities: (0,0)=a, (0,1)=b, (1,0)=c, (1,1)=d
        row = r1 < (c + d)
        col_p = np.where(row, d / max(c + d, 1e-12), b / max(a + b, 1e-12))
        col = r2 < col_p
        u |= row.astype(np.int64)
        v |= col.astype(np.int64)

    lo = np.minimum(u, v)
    hi = np.maximum(u, v)
    keep = lo != hi
    lo, hi = lo[keep], hi[keep]
    _, idx = np.unique(lo * n + hi, return_index=True)
    lo, hi = lo[idx], hi[idx]
    if len(lo) > m:
        sel = rng.permutation(len(lo))[:m]
        lo, hi = lo[sel], hi[sel]
    return EdgeList(n, lo.astype(np.int64), hi.astype(np.int64))
