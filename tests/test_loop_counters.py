"""Device loop counters and the solve's span tree (DESIGN.md §13).

Every data-dependent ``while_loop`` on the solve path returns the rounds
it ran: Phase 1's hook/jump and splice loops per partition and level (in
the per-level ``metrics``), Phase 3's pivot splice per solve.  They come
back with the run's one fetch and land on ``EulerResult`` and on the
solve's root span.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices
from repro import obs
from repro.core.phase1 import (NewEdges, Phase1Caps, empty_open, empty_touch,
                               forest_round_budget, hook_round_budget,
                               phase1_local)
from repro.euler import EulerSolver
from repro.graphgen.eulerize import eulerian_rmat
from test_core_euler import run_phase1_whole_graph, small_graph


def counters(res):
    return ([ls.hook_rounds for ls in res.levels],
            [ls.splice_rounds for ls in res.levels], res.phase3_rounds,
            [ls.forest_rounds for ls in res.levels])


def check_bounds(res, caps):
    """Every count lies within its static bound: CC rounds at least 2
    (at P=1 the pairs join the stubs' comps and the splice relabels some,
    so each CC call has an edge to hook); the splice's vote and forest
    loops may run none (no path meets a cycle; no two cycles share a
    vertex), and so may Phase 3's splice (the rank's orbit already
    covers every edge)."""
    pool = 2 * caps.new_cap + caps.open_cap
    hook_max = hook_round_budget(caps.phase1(), pool, caps.touch_cap)
    forest_max = forest_round_budget(pool, caps.touch_cap)
    hooks, splices, p3, forests = counters(res)
    for level in hooks:
        assert all(2 <= h <= hook_max for h in level), (level, hook_max)
    for level in splices:
        assert all(0 <= s <= caps.splice_rounds for s in level), level
    for level in forests:
        assert all(0 <= f <= forest_max for f in level), (level, forest_max)
    assert 0 <= p3 <= caps.phase3_rounds


@pytest.fixture(scope="module")
def graphs():
    return [eulerian_rmat(8, avg_degree=5, seed=s) for s in (3, 4)]


def test_counters_agree_fused_eager_and_stay_in_bounds(graphs):
    solver = EulerSolver(n_parts=1, trace=obs.NullTraceLog())
    for g in graphs:
        fused = solver.solve(g).validate()
        eager = solver.solve(g, fused=False).validate()
        assert counters(fused) == counters(eager)
        check_bounds(fused, solver.bucket_of(g)[3])
        assert len(fused.levels[0].hook_rounds) == 1


def test_batch_member_counters_match_solo_solves(graphs):
    solver = EulerSolver(n_parts=1, trace=obs.NullTraceLog())
    # seed 2's cycles share no vertex (no forest round); seed 3's do
    pair = graphs + [eulerian_rmat(8, avg_degree=5, seed=2)]
    solo = [counters(solver.solve(g)) for g in pair]
    batched = [counters(r) for r in solver.solve_batch(pair)]
    assert batched == solo
    # the members differ, so each kept its own count under vmap
    assert solo[0] != solo[2]


def test_host_backend_has_no_device_counters(graphs):
    res = EulerSolver(n_parts=2, backend="host").solve(graphs[0])
    assert res.phase3_rounds is None
    assert all(ls.hook_rounds is None and ls.splice_rounds is None
               and ls.forest_rounds is None for ls in res.levels)


def test_static_unrolls_report_their_fixed_counts():
    g = small_graph(0)
    loops = run_phase1_whole_graph(g)       # while loops, default caps
    E = g.num_edges
    new = NewEdges(eid=jnp.arange(E, dtype=jnp.int32),
                   u=jnp.asarray(g.edge_u, jnp.int32),
                   v=jnp.asarray(g.edge_v, jnp.int32),
                   lau=jnp.zeros(E, jnp.int32), lav=jnp.zeros(E, jnp.int32),
                   mask=jnp.ones(E, bool))
    caps = Phase1Caps(open_cap=8, touch_cap=8, splice_rounds=6,
                      static_splice=True, stub_space=2 * E)
    out = jax.jit(phase1_local, static_argnames="caps")(
        new, empty_open(8), empty_touch(8), jnp.int32(0), caps)
    assert int(out.splice_rounds) == 6
    assert int(out.hook_rounds) == hook_round_budget(caps, 2 * E + 8, 8)
    assert int(out.forest_rounds) == forest_round_budget(2 * E + 8, 8)
    # the while loops stop at their fixpoint, within the same budgets: no
    # open path, so the forest splices every cycle and no vote runs
    assert int(loops.splice_rounds) == 0
    assert 1 <= int(loops.forest_rounds) < int(out.forest_rounds)
    assert 2 <= int(loops.hook_rounds) < int(out.hook_rounds)


def test_p4_counters_match_the_eager_oracle():
    """P=4: per-partition Phase 1 counts and the sharded Phase 3's splice
    rounds equal the eager path's (replicated Phase 3)."""
    out = run_with_devices("""
        from repro import obs
        from repro.euler import EulerSolver
        from repro.graphgen.eulerize import eulerian_rmat
        solver = EulerSolver(n_parts=4, trace=obs.NullTraceLog())
        assert solver.sharded_phase3
        votes = 0
        for seed in (3, 4):
            g = eulerian_rmat(8, avg_degree=5, seed=seed)
            f = solver.solve(g).validate()
            e = solver.solve(g, fused=False).validate()
            cf = ([l.hook_rounds for l in f.levels],
                  [l.splice_rounds for l in f.levels], f.phase3_rounds,
                  [l.forest_rounds for l in f.levels])
            ce = ([l.hook_rounds for l in e.levels],
                  [l.splice_rounds for l in e.levels], e.phase3_rounds,
                  [l.forest_rounds for l in e.levels])
            assert cf == ce, (cf, ce)
            assert all(len(h) == 4 for h in cf[0])
            votes += sum(map(sum, cf[1]))
            print(cf)
        # seed 3 has a vertex where a cycle meets an open path
        assert votes > 0
    """, n=4)
    assert len(out.strip().splitlines()) == 2


# ---------------------------------------------------------------------------
# the span tree of one solve
# ---------------------------------------------------------------------------

def test_solve_span_tree_shares_one_req(graphs):
    log = obs.TraceLog()
    solver = EulerSolver(n_parts=1, trace=log)
    solver.solve(graphs[0])                 # compiles: a retrace event
    log.clear()
    res = solver.solve(graphs[1])
    spans = log.spans()
    (root,) = [s for s in spans if s["name"] == "solve"]
    assert root["parent"] is None
    by_id = {s["id"]: s for s in spans}
    assert {s["req"] for s in spans} == {root["req"]}

    def parent(name):
        (s,) = [s for s in spans if s["name"] == name]
        return by_id[s["parent"]]["name"]

    assert parent("prepare") == "solve"
    assert parent("partition") == "prepare"
    assert parent("stage") == "solve"
    assert parent("upload") == "stage"
    assert parent("launch") == "solve"
    assert parent("wait") == "solve"
    assert parent("strip") == "solve"
    assert "fetch" not in {s["name"] for s in spans}
    # one measurement, read two ways
    (prep,) = [s for s in spans if s["name"] == "prepare"]
    assert res.timings["prepare_s"] == prep["dur_s"]
    # the counters ride on the root span
    hooks, splices, p3, forests = counters(res)
    assert root["attrs"]["hook_rounds"] == hooks
    assert root["attrs"]["splice_rounds"] == splices
    assert root["attrs"]["phase3_rounds"] == p3
    assert root["attrs"]["phase3_cc_runs"] == int(p3 > 0)
    assert root["attrs"]["forest_rounds"] == forests
    # children lie inside the root, in order
    t0, t1 = root["t0"], root["t0"] + root["dur_s"]
    kids = sorted((s for s in spans if s["parent"] == root["id"]),
                  key=lambda s: s["t0"])
    assert [s["name"] for s in kids] == ["prepare", "stage", "launch",
                                         "wait", "strip"]
    assert all(t0 <= s["t0"] and s["t0"] + s["dur_s"] <= t1 for s in kids)


def test_batch_span_tree_and_counters_per_member(graphs):
    log = obs.TraceLog()
    solver = EulerSolver(n_parts=1, trace=log)
    results = solver.solve_batch(graphs)
    (root,) = [s for s in log.spans() if s["name"] == "solve_batch"]
    assert root["attrs"]["width"] == 2
    assert root["attrs"]["phase3_rounds"] == [r.phase3_rounds
                                              for r in results]
    assert root["attrs"]["phase3_cc_runs"] == [int(r.phase3_rounds > 0)
                                               for r in results]
    assert root["attrs"]["hook_rounds"] == [counters(r)[0] for r in results]
    names = {s["name"] for s in log.spans() if s.get("req") == root["req"]}
    assert names >= {"solve_batch", "prepare", "partition", "stage",
                     "upload", "launch", "wait", "strip"}


def test_prepare_s_is_the_span_duration_with_tracing_off(graphs):
    res = EulerSolver(n_parts=1, trace=obs.NullTraceLog()).solve(graphs[0])
    assert res.timings["prepare_s"] > 0


def test_a_failed_prepare_ends_the_root_span_as_an_error():
    log = obs.TraceLog()
    solver = EulerSolver(n_parts=4, trace=log)
    tiny = eulerian_rmat(4, avg_degree=4, seed=0)
    with pytest.raises(ValueError):
        solver.solve_async(tiny, part_of_vertex=np.zeros(tiny.num_vertices,
                                                         dtype=np.int64))
    (root,) = [s for s in log.spans() if s["name"] == "solve"]
    assert root["status"] == "error" and root["attrs"]["error"] == "ValueError"
