"""Core Euler engine: oracle, host BSP engine, jitted Phase 1, Phase 3."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.graph import Graph, partition_graph
from repro.core.hierholzer import hierholzer_circuit, validate_circuit
from repro.core.makki import makki_tour
from repro.euler import solve
from repro.core.phase1 import (BIG, NewEdges, Phase1Caps, empty_open,
                               empty_touch, phase1_local)
from repro.core.phase2 import generate_merge_tree
from repro.core.phase3 import circuit_from_mate_jnp, circuit_from_mate_np, \
    splice_components_np
from repro.graphgen.eulerize import eulerian_rmat, eulerize
from repro.graphgen.partition import partition_vertices
from repro.graphgen.rmat import rmat_graph


def small_graph(seed=0, scale=7, deg=4):
    return eulerian_rmat(scale, avg_degree=deg, seed=seed)


# ---------------------------------------------------------------------------
# oracle
# ---------------------------------------------------------------------------

def test_hierholzer_triangle():
    g = Graph(3, np.array([0, 1, 2]), np.array([1, 2, 0]))
    validate_circuit(g, hierholzer_circuit(g))


def test_hierholzer_rejects_non_eulerian():
    g = Graph(3, np.array([0, 1]), np.array([1, 2]))
    with pytest.raises(ValueError):
        hierholzer_circuit(g)


def test_hierholzer_rejects_disconnected():
    g = Graph(6, np.array([0, 1, 2, 3, 4, 5]), np.array([1, 2, 0, 4, 5, 3]))
    with pytest.raises(ValueError):
        hierholzer_circuit(g)


@pytest.mark.parametrize("seed", range(4))
def test_hierholzer_random(seed):
    g = small_graph(seed)
    validate_circuit(g, hierholzer_circuit(g))


# ---------------------------------------------------------------------------
# host BSP engine (paper semantics)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nparts", [2, 3, 4, 8])
def test_host_engine_valid_circuit(nparts):
    g = small_graph(seed=nparts, scale=8, deg=5)
    # §5 heuristics off: the baseline host path keeps its only
    # nparts-parametrized coverage (heuristics-on is covered below)
    res = solve(g, backend="host", n_parts=nparts, partition_seed=1,
                remote_dedup=False, deferred_transfer=False).validate()
    assert res.supersteps == res.tree.height + 1


@pytest.mark.parametrize("dedup,defer", [(True, False), (True, True),
                                         (False, True)])
def test_host_engine_heuristics(dedup, defer):
    g = small_graph(seed=3, scale=8, deg=5)
    part = partition_vertices(g, 4, seed=2)
    base = solve(g, part_of_vertex=part, backend="host", n_parts=4,
                 remote_dedup=False, deferred_transfer=False).validate()
    opt = solve(g, part_of_vertex=part, backend="host", n_parts=4,
                remote_dedup=dedup, deferred_transfer=defer).validate()
    # §5: heuristics never increase the level-0 cumulative state
    assert opt.levels[0].cumulative <= base.levels[0].cumulative
    # and the circuits cover the same edge multiset
    assert sorted(base.circuit >> 1) == sorted(opt.circuit >> 1)


def test_supersteps_log_n():
    """Coordination cost = ⌈log₂ n⌉ + 1 (paper §3.5)."""
    import math

    for nparts in (2, 4, 8):
        g = small_graph(seed=nparts, scale=9, deg=5)
        pg = partition_graph(g, partition_vertices(g, nparts, seed=0))
        tree = generate_merge_tree(pg.meta)
        assert tree.supersteps() == math.ceil(math.log2(nparts)) + 1


def test_makki_coordination_cost():
    """Makki baseline needs O(|E|) supersteps vertex-centric and
    #crossings partition-centric — both far beyond ⌈log n⌉+1."""
    g = small_graph(seed=5, scale=8, deg=5)
    pg = partition_graph(g, partition_vertices(g, 4, seed=0))
    res = makki_tour(pg)
    tree = generate_merge_tree(pg.meta)
    assert res.supersteps_vertex_centric == g.num_edges
    assert res.supersteps_partition_centric > 4 * tree.supersteps()


# ---------------------------------------------------------------------------
# jitted Phase 1
# ---------------------------------------------------------------------------

def run_phase1_whole_graph(g):
    E = g.num_edges
    new = NewEdges(
        eid=jnp.arange(E, dtype=jnp.int32),
        u=jnp.asarray(g.edge_u, jnp.int32),
        v=jnp.asarray(g.edge_v, jnp.int32),
        lau=jnp.zeros(E, jnp.int32),
        lav=jnp.zeros(E, jnp.int32),
        mask=jnp.ones(E, bool),
    )
    caps = Phase1Caps(open_cap=8, touch_cap=8, stub_space=2 * E)
    return jax.jit(phase1_local, static_argnames="caps")(
        new, empty_open(8), empty_touch(8), jnp.int32(0), caps
    )


@pytest.mark.parametrize("seed", range(3))
def test_phase1_produces_valid_circuit(seed):
    g = small_graph(seed)
    out = run_phase1_whole_graph(g)
    assert np.array(out.flags).all(), "convergence/capacity flags"
    mate = np.full(2 * g.num_edges, -1, dtype=np.int64)
    m = np.array(out.log_mask)
    s1 = np.array(out.log_s1)[m]
    s2 = np.array(out.log_s2)[m]
    mate[s1] = s2
    mate[s2] = s1
    assert (mate >= 0).all()
    sv = np.empty(2 * g.num_edges, dtype=np.int64)
    sv[0::2] = g.edge_u
    sv[1::2] = g.edge_v
    mate = splice_components_np(mate, sv, mate >= 0)
    validate_circuit(g, circuit_from_mate_np(mate))


def test_phase3_jnp_matches_np():
    g = small_graph(1)
    out = run_phase1_whole_graph(g)
    mate = np.full(2 * g.num_edges, -1, dtype=np.int64)
    m = np.array(out.log_mask)
    mate[np.array(out.log_s1)[m]] = np.array(out.log_s2)[m]
    mate[np.array(out.log_s2)[m]] = np.array(out.log_s1)[m]
    sv = np.empty(2 * g.num_edges, dtype=np.int64)
    sv[0::2] = g.edge_u
    sv[1::2] = g.edge_v
    mate = splice_components_np(mate, sv, mate >= 0)
    c_np = circuit_from_mate_np(mate, start_stub=int(mate[0] ^ 1))
    c_j = circuit_from_mate_jnp(jnp.asarray(mate, jnp.int32),
                                jnp.int32(mate[0] ^ 1))
    c_j = np.array(c_j)
    assert (c_j >= 0).all()
    validate_circuit(g, c_j.astype(np.int64))


# ---------------------------------------------------------------------------
# graphgen
# ---------------------------------------------------------------------------

def test_eulerize_makes_even():
    g = rmat_graph(9, avg_degree=5, seed=0)
    ge = eulerize(g, seed=1)
    assert ge.is_eulerian()
    # degree distribution roughly preserved (≤ ~10% extra edges, paper: ~5%)
    assert ge.num_edges <= g.num_edges * 1.15


def test_partitioner_balance():
    g = small_graph(2, scale=10, deg=5)
    part = partition_vertices(g, 8, seed=0)
    pg = partition_graph(g, part)
    assert pg.vertex_imbalance() < 1.0
    assert 0.0 < pg.cut_fraction() < 0.95
    assert all(len(p.odd_boundary) % 2 == 0 for p in pg.parts), \
        "handshake lemma per partition"
