"""The paper's §4.2 pipeline: RMAT, largest component, eulerize.

A copy of ``repro.graphgen.eulerize`` kept with the benchmark.  Eulerize
pairs odd-degree vertices, preferring pairs not already adjacent, and
adds one edge per pair (the paper reports about 5% added edges).
"""
from __future__ import annotations

import numpy as np

from . import EdgeList
from .rmat import rmat_graph


def largest_component(graph: EdgeList) -> EdgeList:
    """The subgraph induced on the largest connected component, with its
    vertices relabelled densely."""
    V = graph.num_vertices
    label = np.arange(V, dtype=np.int64)
    for _ in range(64):             # min-label propagation, early exit
        m = np.minimum(label[graph.edge_u], label[graph.edge_v])
        new = label.copy()
        np.minimum.at(new, graph.edge_u, m)
        np.minimum.at(new, graph.edge_v, m)
        new = new[new]
        if np.array_equal(new, label):
            break
        label = new
    roots, counts = np.unique(label, return_counts=True)
    keep_v = label == roots[np.argmax(counts)]
    remap = -np.ones(V, dtype=np.int64)
    remap[keep_v] = np.arange(keep_v.sum(), dtype=np.int64)
    keep_e = keep_v[graph.edge_u] & keep_v[graph.edge_v]
    return EdgeList(int(keep_v.sum()), remap[graph.edge_u[keep_e]],
                    remap[graph.edge_v[keep_e]])


def eulerize(graph: EdgeList, seed: int = 0) -> EdgeList:
    """Add a matching over the odd-degree vertices so every degree is even."""
    rng = np.random.default_rng(seed)
    odd = np.nonzero(graph.degrees() % 2 == 1)[0]
    if len(odd) % 2:
        raise ValueError("odd number of odd-degree vertices")
    if len(odd) == 0:
        return graph
    existing = set(zip(np.minimum(graph.edge_u, graph.edge_v).tolist(),
                       np.maximum(graph.edge_u, graph.edge_v).tolist()))
    stack = list(rng.permutation(odd))
    new_u, new_v, spare = [], [], []
    while stack:
        x = stack.pop()
        matched = False
        for _ in range(min(len(stack), 8)):   # a few tries to avoid duplicates
            y = stack.pop()
            key = (min(int(x), int(y)), max(int(x), int(y)))
            if key not in existing and x != y:
                existing.add(key)
                new_u.append(key[0])
                new_v.append(key[1])
                matched = True
                break
            spare.append(y)
        stack.extend(spare)
        spare.clear()
        if not matched and stack:     # forced multi-edge
            y = stack.pop()
            new_u.append(min(int(x), int(y)))
            new_v.append(max(int(x), int(y)))
        elif not matched:
            raise ValueError("odd vertex left unpaired")
    out = EdgeList(graph.num_vertices,
                   np.concatenate([graph.edge_u, np.array(new_u, np.int64)]),
                   np.concatenate([graph.edge_v, np.array(new_v, np.int64)]))
    if not out.is_eulerian():
        raise ValueError("eulerize left an odd-degree vertex")
    return out


def eulerian_rmat(seed: int, scale: int, avg_degree: int = 5,
                  a: float = 0.57, b: float = 0.19, c: float = 0.19) -> EdgeList:
    """RMAT, then its largest component, then eulerize (seed + 1)."""
    g = rmat_graph(scale, avg_degree=avg_degree, a=a, b=b, c=c, seed=seed)
    return eulerize(largest_component(g), seed=seed + 1)
