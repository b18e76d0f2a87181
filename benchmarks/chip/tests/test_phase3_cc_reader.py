"""The reader of the share of solves whose Phase 3 ran its CC and pivot
splice (``phase3_cc_runs.oneshot``), on a synthetic span ring, run on
the CPU, beside the splice-round reader it complements.

It reads the ``phase3_cc_runs`` counter (0 or 1) off the root ``solve``
span of each window solve and averages it over the window.  A program
whose roots carry no such counter reads as nothing (None).
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

#: three solves: one spliced in two rounds, two found one circuit already
SOLVES = [
    {"phase3_rounds": 2, "phase3_cc_runs": 1},
    {"phase3_rounds": 0, "phase3_cc_runs": 0},
    {"phase3_rounds": 0, "phase3_cc_runs": 0},
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
    _solve(log, t, SOLVES[0])                  # a warm solve: outside
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


def test_cc_runs_are_the_share_of_window_solves(ring):
    log, t = ring
    records = _window(log, t, SOLVES)
    assert _read("phase3_cc_runs.oneshot", records) == pytest.approx(1 / 3)
    assert _read("phase3_splice_rounds.oneshot", records) == pytest.approx(
        2 / 3)


def test_cc_runs_read_zero_where_every_splice_was_skipped(ring):
    log, t = ring
    records = _window(log, t, SOLVES[1:])
    assert _read("phase3_cc_runs.oneshot", records) == 0.0
    assert _read("phase3_splice_rounds.oneshot", records) == 0.0


def test_cc_runs_of_a_program_without_the_counter_read_as_nothing(ring):
    """An older program's roots carry ``phase3_rounds`` but no
    ``phase3_cc_runs``: the reader finds nothing to read."""
    log, t = ring
    older = [{"phase3_rounds": 1} for _ in SOLVES]
    records = _window(log, t, older)
    assert _read("phase3_cc_runs.oneshot", records) is None
    assert _read("phase3_splice_rounds.oneshot", records) == 1.0


def test_no_program_spans_read_as_nothing(ring):
    records = [Record(0, 0.0, 1.0, 10, None, 0.1)]
    assert _read("phase3_cc_runs.oneshot", records) is None
