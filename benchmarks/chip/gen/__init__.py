"""The benchmark's own graph generators (copies, independent of ``repro``).

A configuration names its generator as ``"<module>.<function>"`` of this
package; the function takes ``seed`` and the configuration's generator
parameters and returns an :class:`EdgeList`.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass
class EdgeList:
    """An undirected multigraph: edge ``e`` joins ``edge_u[e]`` and
    ``edge_v[e]``; its stubs are ``2e`` (at ``u``) and ``2e + 1`` (at ``v``)."""

    num_vertices: int
    edge_u: np.ndarray  # [E] int64
    edge_v: np.ndarray  # [E] int64

    @property
    def num_edges(self) -> int:
        return int(self.edge_u.shape[0])

    def degrees(self) -> np.ndarray:
        deg = np.zeros(self.num_vertices, dtype=np.int64)
        np.add.at(deg, self.edge_u, 1)
        np.add.at(deg, self.edge_v, 1)
        return deg

    def is_eulerian(self) -> bool:
        return bool(np.all(self.degrees() % 2 == 0))


def generator(name: str):
    """The generator function ``"<module>.<function>"`` of this package."""
    module, _, func = name.rpartition(".")
    if not module:
        raise ValueError(f"generator {name!r} is not '<module>.<function>'")
    return getattr(importlib.import_module(f"{__name__}.{module}"), func)
