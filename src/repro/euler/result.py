"""The unified result type of the public Euler API (DESIGN.md §7).

One ``EulerResult`` is returned by every backend (``device`` engine, fused
or eager, and the ``host`` reference engine), replacing the old split
between ``HostEngine``'s dataclass and the distributed engine's bare
``(circuit, metrics)`` tuples.  Per-level memory-state metrics are
normalized into :class:`repro.core.memory.LevelStats` regardless of which
execution path produced them, and circuit validation is a method on the
result (``res.validate()``) instead of an ad-hoc ``validate=True`` flag
threaded through every engine call.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..core.graph import Graph
from ..core.memory import LevelStats, PartitionState
from ..core.phase2 import MergeTree


@dataclasses.dataclass
class CacheStats:
    """Compiled-program cache accounting of a solver session.

    ``bucket``/``hit``/``batch`` describe the solve that produced this
    snapshot; the counters are cumulative over the owning
    :class:`EulerSolver`.  Programs are cached per ``(bucket, batch)``:
    the single-graph program and each batched width compile separately
    (DESIGN.md §8), and each counts once in ``traces``.

    >>> CacheStats(hits=3, misses=1, traces=1).compiles
    1
    """

    bucket: Optional[Tuple] = None   # shape-bucket key of this solve
    hit: bool = False                # this solve reused a cached program
    batch: int = 1                   # batch width B of this solve's program
    hits: int = 0                    # cumulative (bucket, B) cache hits
    misses: int = 0                  # cumulative (bucket, B) cache misses
    traces: int = 0                  # times a whole-run program was traced
    evictions: int = 0               # (bucket, B) programs dropped by LRU
    prewarms: int = 0                # programs compiled by prewarm()
    state_uploads: int = 0           # host→device EngineState transfers

    @property
    def compiles(self) -> int:
        """Programs actually lowered (= traces of the jitted entry)."""
        return self.traces


@dataclasses.dataclass
class EulerResult:
    """Everything a solve produces, shared by both backends.

    ``circuit`` is the Euler circuit of ``graph`` as arrival stubs in walk
    order (stub ``2e`` = edge ``e`` traversed u→v, ``2e+1`` = v→u).  When
    the device backend padded the graph into a shape bucket
    (``padded_edges > 0``), ``circuit`` is already stripped back to the
    original edge set while ``mate`` still covers the padded stub space.
    """

    circuit: np.ndarray              # [E] arrival stubs in walk order
    mate: np.ndarray                 # [2E′] post-splice mate permutation
    tree: MergeTree
    levels: List[LevelStats]         # per-level Int64 state, both backends
    supersteps: int
    backend: str = "host"            # "host" | "device"
    fused: bool = False              # device: scan-fused vs eager supersteps
    graph: Optional[Graph] = None    # the (unpadded) input graph
    padded_edges: int = 0            # dummy edges added for shape bucketing
    phase3_converged: bool = True
    phase3_rounds: Optional[int] = None  # device: pivot-splice rounds run
    timings: Dict[str, float] = dataclasses.field(default_factory=dict)
    cache: CacheStats = dataclasses.field(default_factory=CacheStats)
    valid: Optional[bool] = None     # set by validate(); None = unchecked

    def validate(self) -> "EulerResult":
        """Assert ``circuit`` is an Euler circuit of ``graph``; returns
        self so ``solve(g).validate()`` chains.

        >>> import numpy as np
        >>> from repro.core.graph import Graph
        >>> from repro.euler import solve
        >>> tri = Graph(3, np.array([0, 1, 2]), np.array([1, 2, 0]))
        >>> solve(tri, backend="host", n_parts=1).validate().valid
        True
        """
        from ..core.hierholzer import InvalidCircuitError, validate_circuit

        if self.graph is None:
            raise ValueError("result carries no graph to validate")
        try:
            validate_circuit(self.graph, np.asarray(self.circuit,
                                                    dtype=np.int64))
        except InvalidCircuitError:
            self.valid = False
            raise
        self.valid = True
        return self

    # ------------------------------------------------------------------
    # metric normalization (device engines) / back-compat raw view
    # ------------------------------------------------------------------
    @staticmethod
    def levels_from_metrics(metrics_per_level: Iterable[np.ndarray],
                            ) -> List[LevelStats]:
        """Normalize the device engine's per-level ``[n, 4]`` Int64-count
        arrays (``[2·parked, 3·opens, 4·touch, 4·components]`` per
        partition) into the same :class:`LevelStats` the host engine
        reports, so both backends expose one metrics shape.  A fifth,
        sixth and seventh column, where present, are the Phase 1 hook,
        splice vote and splice forest rounds the partition ran (``[n, 7]``,
        the engine's layout)."""
        out: List[LevelStats] = []
        for lvl, m in enumerate(metrics_per_level):
            m = np.asarray(m)
            states = [
                PartitionState(
                    pid=pid, level=lvl,
                    remote_copies=int(row[0]) // 2,
                    boundary=0,
                    open_stubs=int(row[1]) // 3,
                    touch=int(row[2]) // 4,
                    components=int(row[3]) // 4,
                    hook_rounds=int(row[4]) if len(row) > 4 else None,
                    splice_rounds=int(row[5]) if len(row) > 5 else None,
                    forest_rounds=int(row[6]) if len(row) > 6 else None,
                )
                for pid, row in enumerate(m)
            ]
            out.append(LevelStats(level=lvl, states=states, phase1_cost={},
                                  phase1_seconds={}, comm_longs={}))
        return out

    def metrics_arrays(self) -> List[np.ndarray]:
        """Back-compat raw view: per-level ``[n, 4]`` Int64-count arrays
        (inverse of :meth:`levels_from_metrics` on the Int64 counts; the
        loop rounds stay on the levels; device-backend levels only — host
        levels additionally carry boundary counts)."""
        return [
            np.array(
                [[2 * s.remote_copies, 3 * s.open_stubs, 4 * s.touch,
                  4 * s.components] for s in ls.states],
                dtype=np.int32,
            )
            for ls in self.levels
        ]
