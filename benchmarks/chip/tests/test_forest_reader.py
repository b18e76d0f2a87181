"""The reader of the Phase 1 splice's spanning-forest rounds
(``splice_forest_rounds.oneshot``), on a synthetic span ring, run on the
CPU.

It reads the ``forest_rounds`` counter off the root ``solve`` span of
each window solve: per solve, the sum over levels of the most rounds any
partition ran, averaged over the window.  A program whose roots carry no
such counter reads as nothing (None).
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.traffic.closed_oneshot import Record  # noqa: E402
from repro import obs  # noqa: E402

#: two solves' counters: 2 levels x 2 partitions
SOLVES = [
    {"splice_rounds": [[2, 2], [1, 1]], "forest_rounds": [[1, 0], [2, 1]]},
    {"splice_rounds": [[3, 1], [1, 1]], "forest_rounds": [[0, 0], [1, 3]]},
]


@pytest.fixture
def ring(monkeypatch):
    """The process-default trace log, replaced by one on a fake clock."""
    t = [0.0]
    log = obs.TraceLog(clock=lambda: t[0])
    monkeypatch.setattr(obs.trace, "DEFAULT", log)
    return log, t


def _solve(log, t, counters):
    root = log.request("solve").start()
    root.set(**counters)
    t[0] += 1.0
    root.end()


def _window(log, t, solves):
    """A solve before the window, the window's solves, one after it;
    returns the window's records."""
    _solve(log, t, SOLVES[1])                  # a warm solve: outside
    t[0] = 100.0
    records = []
    for i, s in enumerate(solves):
        start = t[0]
        _solve(log, t, s)
        records.append(Record(i, start, t[0], 10, None, 0.1))
        t[0] += 0.5
    _solve(log, t, SOLVES[0])                  # the seed graph: outside
    return records


def _read(name, records):
    return harness.load_metric(name)(harness.Ctx(records=records, trace=None))


def test_forest_rounds_are_the_mean_of_per_level_maxima(ring):
    log, t = ring
    records = _window(log, t, SOLVES)
    # solve 0: levels max 1 + 2 = 3; solve 1: 0 + 3 = 3
    assert _read("splice_forest_rounds.oneshot", records) == pytest.approx(
        ((1 + 2) + (0 + 3)) / 2)


def test_forest_rounds_of_a_program_without_the_forest_read_as_nothing(ring):
    """An older program's roots carry the other counters but no
    ``forest_rounds``: the forest's reader finds nothing to read."""
    log, t = ring
    older = [{k: v for k, v in s.items() if k != "forest_rounds"}
             for s in SOLVES]
    records = _window(log, t, older)
    assert _read("splice_forest_rounds.oneshot", records) is None
    assert _read("splice_rounds.oneshot", records) is not None


def test_no_program_spans_read_as_nothing(ring):
    records = [Record(0, 0.0, 1.0, 10, None, 0.1)]
    assert _read("splice_forest_rounds.oneshot", records) is None
