"""Closed loop of one-shot solves: one user hands the solver one graph,
waits for its circuit, then hands it the next.

The loop goes round the pool in cycles, each cycle every pool graph once
in an order drawn from the run's seed, and the window closes when the
cycle in flight at ``seconds`` completes: every run does the same work,
whatever its seed.  The window opens at the first solve's start.  Every
request is a new graph object.
"""
from __future__ import annotations

import dataclasses
import time
import traceback
from typing import Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    index: int                    # pool graph solved
    start: float                  # host clock (perf_counter), seconds
    end: float
    edges: int
    circuit: Optional[np.ndarray]
    prepare_s: Optional[float]    # the solver's own host-prep timing
    error: Optional[str] = None   # the exception of a solve that failed


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_window(solve: Callable, request: Callable, seconds: float,
               mix: dict, seed: int) -> List[Record]:
    """Solve whole cycles of the pool until ``seconds`` have passed."""
    rng = np.random.default_rng(abs(int(seed)))
    records: List[Record] = []
    t0 = time.perf_counter()
    with _annotate("chipbench.window"):
        while True:
            for k in rng.permutation(mix["pool_graphs"]).tolist():
                g = request(k)
                start = time.perf_counter()
                try:
                    with _annotate("chipbench.solve"):
                        res = solve(g)
                    rec = Record(k, start, time.perf_counter(), g.num_edges,
                                 np.asarray(res.circuit),
                                 res.timings.get("prepare_s"))
                except Exception:  # counted as failed; the window goes on
                    rec = Record(k, start, time.perf_counter(), g.num_edges,
                                 None, None, traceback.format_exc(limit=4))
                records.append(rec)
            if records[-1].end - t0 >= seconds:
                return records


def window_stats(records: List[Record]) -> dict:
    """Edges completed, solves and the window's length (first start to
    last end)."""
    done = [r for r in records if r.error is None]
    return {"solves": len(records), "completed": len(done),
            "edges": sum(r.edges for r in done),
            "window_s": records[-1].end - records[0].start}
