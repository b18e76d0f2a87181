"""The per-layer metrics that read the program's own spans and counters
(``program_spans.py`` and its readers), on a synthetic span ring, and
the program's spans in a profile, run on the CPU.

Each reader takes the root ``solve`` spans that started inside the
window: spans from before or after it are ignored, and a window whose
roots are not one per solve reads as nothing (None), never a partial
mean.
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

READERS = ("hook_rounds.oneshot", "splice_rounds.oneshot",
           "phase3_splice_rounds.oneshot", "level_round_imbalance.oneshot",
           "host_dispatch_s.oneshot")

#: two solves' counters: 2 levels x 2 partitions
SOLVES = [
    {"hook_rounds": [[4, 6], [3, 1]], "splice_rounds": [[2, 2], [1, 1]],
     "phase3_rounds": 5, "stage": 0.25, "launch": 0.5},
    {"hook_rounds": [[5, 5], [2, 2]], "splice_rounds": [[3, 1], [1, 1]],
     "phase3_rounds": 7, "stage": 0.5, "launch": 0.25},
]


def _solve(log, t, solve, skip=()):
    """One solve's span tree at fake time ``t[0]``: root, stage, launch."""
    root = log.request("solve").start()
    with root.scope():
        for name in ("stage", "launch"):
            with log.span(name):
                if name not in skip:
                    t[0] += solve[name]
    root.set(**{k: solve[k] for k in ("hook_rounds", "splice_rounds",
                                      "phase3_rounds")})
    t[0] += 1.0
    root.end()


@pytest.fixture
def ring(monkeypatch):
    """The process-default trace log, replaced by one on a fake clock."""
    t = [0.0]
    log = obs.TraceLog(clock=lambda: t[0])
    monkeypatch.setattr(obs.trace, "DEFAULT", log)
    return log, t


def _window(log, t, solves, drop=None):
    """Spans before the window, the window's solves, spans after it;
    returns the window's records.  ``drop`` leaves a span out."""
    _solve(log, t, SOLVES[1])                  # a warm solve: outside
    t[0] = 100.0
    records = []
    for i, s in enumerate(solves):
        start = t[0]
        if drop == ("root", i):
            with log.span("stage"):
                t[0] += s["stage"]
            t[0] += 1.0
        else:
            _solve(log, t, s)
        records.append(Record(i, start, t[0], 10, None, 0.1))
        t[0] += 0.5
    _solve(log, t, SOLVES[0])                  # the seed graph: outside
    return records


def _read(name, records):
    return harness.load_metric(name)(harness.Ctx(records=records, trace=None))


def test_readers_on_a_window_ignore_spans_outside_it(ring):
    log, t = ring
    records = _window(log, t, SOLVES)
    assert _read("hook_rounds.oneshot", records) == pytest.approx(
        ((6 + 3) + (5 + 2)) / 2)
    assert _read("splice_rounds.oneshot", records) == pytest.approx(
        ((2 + 1) + (3 + 1)) / 2)
    assert _read("phase3_splice_rounds.oneshot", records) == 6.0
    # solve 0: levels (6+2, 3+1) most 8 + 4 = 12 over means 7 + 3 = 10;
    # solve 1: most 8 + 3 = 11 over means 7 + 3 = 10
    assert _read("level_round_imbalance.oneshot", records) == pytest.approx(
        (12 / 10 + 11 / 10) / 2)
    assert _read("host_dispatch_s.oneshot", records) == pytest.approx(0.75)


def test_a_missing_root_reads_as_nothing(ring):
    log, t = ring
    records = _window(log, t, SOLVES, drop=("root", 1))
    for name in READERS:
        assert _read(name, records) is None, name


def test_a_missing_child_or_counter_reads_as_nothing(ring):
    log, t = ring
    records = _window(log, t, SOLVES)
    # strip the stage child of the second root, and a counter of the first
    spans = log.spans()
    roots = [s for s in spans if s["name"] == "solve"
             and records[0].start <= s["t0"] <= records[-1].end]
    kept = [s for s in spans
            if not (s["name"] == "stage" and s["parent"] == roots[1]["id"])]
    log.clear()
    for s in kept:
        if s["id"] == roots[0]["id"]:
            s = dict(s, attrs={k: v for k, v in s["attrs"].items()
                               if k != "phase3_rounds"})
        log._ring.append(s)
    assert _read("host_dispatch_s.oneshot", records) is None
    assert _read("phase3_splice_rounds.oneshot", records) is None
    assert _read("hook_rounds.oneshot", records) is not None


def test_no_program_spans_read_as_nothing(ring):
    """A program without the span tree (an older one) gives no roots."""
    records = [Record(0, 0.0, 1.0, 10, None, 0.1)]
    for name in READERS:
        assert _read(name, records) is None, name


def test_program_spans_sit_inside_the_benchmark_solve_in_a_profile(tmp_path):
    """Traced like a ``--trace 1`` run, on the CPU: the solver's spans are
    ``repro.*`` annotations on the host plane, on the profiler's clock,
    inside the loop's ``chipbench.solve``."""
    import jax

    from benchmarks.chip.traffic.closed_oneshot import run_window
    from benchmarks.chip.xspace import read_xspace
    from repro.core.graph import Graph
    from repro.euler import EulerSolver
    from repro.graphgen.eulerize import eulerian_rmat

    g = eulerian_rmat(6, avg_degree=4, seed=0)
    solver = EulerSolver(n_parts=1, trace=obs.TraceLog())
    solver.solve(g)                           # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run_window(solver.solve,
                   lambda k: Graph(g.num_vertices, g.edge_u, g.edge_v),
                   0.0, {"pool_graphs": 2}, 7)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    space = read_xspace(str(path))
    events = []
    for plane in space.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                name = plane.event_metadata[e.metadata_id].name
                t0 = line.timestamp_ns * 1000 + e.offset_ps
                events.append((name, t0, t0 + e.duration_ps))
    solves = [e for e in events if e[0] == "chipbench.solve"]
    assert len(solves) == 2
    ours = [e for e in events if e[0].startswith("repro.")]
    names = {e[0] for e in ours}
    assert names >= {"repro.solve", "repro.prepare", "repro.partition",
                     "repro.stage", "repro.upload", "repro.launch",
                     "repro.wait", "repro.strip"}, names
    assert "repro.fetch" not in names
    for name, t0, t1 in ours:
        assert any(s0 <= t0 and t1 <= s1 for _, s0, s1 in solves), name
    assert sum(e[0] == "repro.solve" for e in ours) == 2
