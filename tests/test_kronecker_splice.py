"""Graph500 Kronecker inputs and the Phase 1 splice's spanning forest.

Kronecker graphs in Kernel 1's sorted edge order put many Phase-1 cycles
through the same dense vertices.  The splice's vote rounds merge each
cycle at one vertex a round, so they ran 12, 32, 42, 64 and 87 rounds at
scales 8-12 and ran out of their budget at scale 9.  The spanning forest
(DESIGN.md §2) splices every cycle in one pass after Borůvka rounds,
whose number grows with the log of the cycle count.
"""
import numpy as np
import pytest

from conftest import run_with_devices
from repro import obs
from repro.core.phase1 import forest_round_budget
from repro.euler import EulerSolver
from repro.graphgen.kronecker import eulerian_kronecker, kronecker_graph


def counters(res):
    return ([ls.hook_rounds for ls in res.levels],
            [ls.splice_rounds for ls in res.levels],
            [ls.forest_rounds for ls in res.levels], res.phase3_rounds)


def splice_total(res):
    """Splice rounds of one solve: vote plus forest, the most any
    partition ran at each level, summed over the levels."""
    return sum(max(v) + max(f) for v, f in zip(counters(res)[1],
                                               counters(res)[2]))


@pytest.fixture(scope="module")
def solver():
    return EulerSolver(n_parts=1, trace=obs.NullTraceLog())


@pytest.mark.parametrize("static", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_spanning_forest_spans_each_component_without_a_cycle(seed, static):
    """The picked edges form a spanning forest (one tree per component,
    no cycle), the labels are each component's least node, and the
    rounds stay within ceil(log2 T) for T nodes with an edge."""
    import jax.numpy as jnp
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    from repro.core.phase1 import _spanning_forest

    rng = np.random.default_rng(seed)
    K, E = 300, 500
    a, b = rng.integers(0, K, E), rng.integers(0, K, E)
    b[:100] = a[:100] + 1                      # a path through low ids
    b = np.minimum(b, K - 1)
    mask = rng.random(E) < 0.8
    least, tree, ok, ran = _spanning_forest(
        jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
        jnp.asarray(mask), K, rounds=12, static=static)
    least, tree = np.asarray(least), np.asarray(tree)
    g = coo_matrix((np.ones(mask.sum()), (a[mask], b[mask])), shape=(K, K))
    n_comp, comp = connected_components(g, directed=False)
    first = {c: np.flatnonzero(comp == c).min() for c in range(n_comp)}
    assert bool(ok) and (least == [first[c] for c in comp]).all()
    assert not tree[~mask].any()
    # a forest spanning every component: K - n_comp edges, same components
    t = coo_matrix((np.ones(tree.sum()), (a[tree], b[tree])), shape=(K, K))
    n_t, comp_t = connected_components(t, directed=False)
    assert n_t == n_comp and tree.sum() == K - n_comp
    assert (comp_t == comp).all()
    touched = len(np.unique(np.concatenate([a[mask], b[mask]])))
    assert static or int(ran) <= np.ceil(np.log2(touched))


def test_kronecker_graph_is_simple_sorted_and_seeded():
    g = kronecker_graph(8, seed=3)
    assert (g.edge_u < g.edge_v).all()                  # no self-loops
    key = g.edge_u * g.num_vertices + g.edge_v
    assert (np.diff(key) > 0).all()                     # sorted, no dups
    again = kronecker_graph(8, seed=3)
    assert key.tobytes() == (again.edge_u * again.num_vertices
                             + again.edge_v).tobytes()
    e = eulerian_kronecker(8, seed=3)
    assert e.is_eulerian() and e.num_edges > g.num_edges // 2


@pytest.mark.parametrize("scale", [8, 9, 10])
def test_p1_fused_eager_batched_and_host_agree(solver, scale):
    graphs = [eulerian_kronecker(scale, seed=s) for s in (0, 1)]
    assert solver.bucket_of(graphs[0]) == solver.bucket_of(graphs[1])
    fused = [solver.solve(g).validate() for g in graphs]
    batched = solver.solve_batch(graphs)
    for g, f, b in zip(graphs, fused, batched):
        e = solver.solve(g, fused=False).validate()
        b.validate()
        assert (f.circuit == e.circuit).all() and (f.circuit == b.circuit).all()
        assert counters(f) == counters(e) == counters(b)
        # one partition, no path: the forest splices, no vote round runs
        assert counters(f)[1] == [[0]] and counters(f)[2][0][0] >= 1
        EulerSolver(n_parts=1, backend="host").solve(g).validate()


def test_scale9_no_longer_runs_out_of_splice_rounds(solver):
    """The vote rounds alone needed 32 here, past their budget of 16."""
    g = eulerian_kronecker(9, seed=0)
    res = solver.solve(g).validate()
    caps = solver.bucket_of(g)[3]
    assert caps.splice_rounds == 16
    assert splice_total(res) <= 2


def test_splice_rounds_grow_with_log_of_cycles_not_with_density(solver):
    """Scales 8-12: the splice's total rounds rise by at most one a scale
    step.  A Borůvka round at least halves the trees, so a graph with at
    most twice the cycles needs at most one round more; the vote rounds
    run none (no open path at P=1)."""
    totals = []
    for scale in range(8, 13):
        g = eulerian_kronecker(scale, seed=0)
        res = solver.solve(g).validate()
        caps = solver.bucket_of(g)[3]
        pool = 2 * caps.new_cap + caps.open_cap
        assert splice_total(res) <= forest_round_budget(pool, caps.touch_cap)
        totals.append(splice_total(res))
    assert all(b - a <= 1 for a, b in zip(totals, totals[1:])), totals
    assert max(totals) <= 3, totals


def test_p4_fused_eager_batched_and_host_agree():
    out = run_with_devices("""
        from repro import obs
        from repro.euler import EulerSolver
        from repro.graphgen.kronecker import eulerian_kronecker

        def counters(r):
            return ([l.hook_rounds for l in r.levels],
                    [l.splice_rounds for l in r.levels],
                    [l.forest_rounds for l in r.levels], r.phase3_rounds)

        solver = EulerSolver(n_parts=4, trace=obs.NullTraceLog())
        for scale in (8, 9):
            graphs = [eulerian_kronecker(scale, seed=s) for s in (0, 1)]
            fused = [solver.solve(g).validate() for g in graphs]
            keys = {solver.bucket_of(g) for g in graphs}
            batched = (solver.solve_batch(graphs) if len(keys) == 1
                       else fused)
            for g, f, b in zip(graphs, fused, batched):
                e = solver.solve(g, fused=False).validate()
                b.validate()
                assert (f.circuit == e.circuit).all()
                assert (f.circuit == b.circuit).all()
                assert counters(f) == counters(e) == counters(b)
                EulerSolver(n_parts=4, backend="host").solve(g).validate()
                print(scale, len(keys), counters(f))
    """, n=4)
    lines = out.strip().splitlines()
    assert len(lines) == 4
    assert any(line.split()[1] == "1" for line in lines)   # one batched
