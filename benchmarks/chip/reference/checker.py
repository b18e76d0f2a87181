"""Plain Euler-circuit checker.

Edge ``e`` has two stubs, ``2e`` at ``edge_u[e]`` and ``2e + 1`` at
``edge_v[e]``.  A circuit lists, in walk order, the stub at which each
step arrives: stub ``2e`` walks ``e`` from ``edge_v[e]`` to ``edge_u[e]``.  The guarantees checked are the ones a configuration states: every
edge is walked exactly once, each step leaves from the vertex the step
before arrived at, and the walk ends where it began.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def circuit_fault(graph, circuit) -> Optional[str]:
    """``None`` when ``circuit`` is an Euler circuit of ``graph``, else
    the first broken guarantee, in words."""
    E = graph.num_edges
    c = np.asarray(circuit)
    if c.shape != (E,):
        return f"circuit has shape {c.shape}, the graph {E} edges"
    if E == 0:
        return None
    if not np.issubdtype(c.dtype, np.integer):
        return f"circuit has dtype {c.dtype}"
    c = c.astype(np.int64)
    if c.min() < 0 or c.max() >= 2 * E:
        return "a stub lies outside the graph"
    walked = np.bincount(c >> 1, minlength=E)
    if not np.all(walked == 1):
        return f"{int(np.sum(walked != 1))} edges not walked exactly once"
    e = c >> 1
    at_v = (c & 1).astype(bool)
    head = np.where(at_v, graph.edge_v[e], graph.edge_u[e])   # arrives at
    tail = np.where(at_v, graph.edge_u[e], graph.edge_v[e])   # leaves from
    broken = np.nonzero(head[:-1] != tail[1:])[0]
    if len(broken):
        return f"the walk breaks after step {int(broken[0])}"
    if head[-1] != tail[0]:
        return "the walk is not closed"
    return None
