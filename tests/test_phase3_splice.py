"""Phase 3 pivot splice: multi-cycle graphs where partitions leave ≥3
edge-disjoint cycles sharing pivot vertices.

Cross-checks ``splice_components_jnp`` (the device path used by the fused
engine) against ``splice_components_np`` (the scipy host oracle) and the
Hierholzer oracle: both splices must turn the same multi-cycle perfect
matching into a single orbit covering every edge exactly once.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.graph import Graph
from repro.core.hierholzer import hierholzer_circuit, validate_circuit
from repro.core.phase3 import (
    circuit_from_mate_jnp,
    circuit_from_mate_np,
    phase3_device,
    splice_components_jnp,
    splice_components_np,
)
from repro.graphgen.eulerize import eulerian_rmat


def stub_vertices(g: Graph) -> np.ndarray:
    sv = np.empty(2 * g.num_edges, dtype=np.int64)
    sv[0::2] = g.edge_u
    sv[1::2] = g.edge_v
    return sv


def graph_of_cycles(n_vertices, cycles):
    """Build a multigraph from vertex cycles plus a mate array that pairs
    each cycle independently (one component per cycle) — the state an
    engine partition leaves behind before the final pivot splice."""
    eu, ev = [], []
    mate_pairs = []
    for cyc in cycles:
        first_eid = len(eu)
        k = len(cyc)
        for i in range(k):
            eu.append(cyc[i])
            ev.append(cyc[(i + 1) % k])
        # pair arrival stub of edge i with departure stub of edge i+1:
        # edge i's v-stub (2e+1) meets edge i+1's u-stub (2e') at cyc[i+1]
        for i in range(k):
            e_in = first_eid + i
            e_out = first_eid + (i + 1) % k
            mate_pairs.append((2 * e_in + 1, 2 * e_out))
    g = Graph(n_vertices, np.array(eu, dtype=np.int64),
              np.array(ev, dtype=np.int64))
    mate = np.full(2 * g.num_edges, -1, dtype=np.int64)
    for a, b in mate_pairs:
        mate[a] = b
        mate[b] = a
    assert (mate >= 0).all()
    return g, mate


def check_both_splices(g, mate):
    sv = stub_vertices(g)
    # host oracle
    m_np = splice_components_np(mate.copy(), sv, mate >= 0)
    c_np = circuit_from_mate_np(m_np)
    validate_circuit(g, c_np)
    # device path
    m_j, ok, rounds = jax.jit(splice_components_jnp)(
        jnp.asarray(mate, jnp.int32), jnp.asarray(sv, jnp.int32),
        jnp.asarray(mate >= 0),
    )
    assert bool(ok), "device splice did not converge"
    assert 1 <= int(rounds) <= 64
    m_j = np.asarray(m_j, dtype=np.int64)
    # still a perfect matching over the same stubs
    assert (m_j >= 0).all()
    assert (m_j[m_j] == np.arange(2 * g.num_edges)).all()
    c_j = circuit_from_mate_np(m_j)
    validate_circuit(g, c_j)
    # both circuits traverse the same edge multiset as the Hierholzer oracle
    oracle = hierholzer_circuit(g)
    assert sorted(c_np >> 1) == sorted(oracle >> 1)
    assert sorted(c_j >> 1) == sorted(oracle >> 1)


def test_three_triangles_one_pivot():
    """Flower: 3 edge-disjoint triangles sharing pivot vertex 0."""
    g, mate = graph_of_cycles(7, [[0, 1, 2], [0, 3, 4], [0, 5, 6]])
    check_both_splices(g, mate)


def test_five_cycles_one_pivot():
    g, mate = graph_of_cycles(
        11, [[0, 1, 2], [0, 3, 4], [0, 5, 6], [0, 7, 8], [0, 9, 10]]
    )
    check_both_splices(g, mate)


def test_cycle_chain_distinct_pivots():
    """c0—v1—c1—v4—c2—v7—c3: each adjacent pair shares one pivot."""
    g, mate = graph_of_cycles(
        10,
        [[0, 1, 2], [1, 3, 4], [4, 5, 6], [6, 7, 8],
         [8, 9, 0]],
    )
    check_both_splices(g, mate)


def test_cycles_sharing_multiple_pivots():
    """≥3 cycles through the SAME two pivot vertices (multigraph)."""
    g, mate = graph_of_cycles(
        8, [[0, 2, 1, 3], [0, 4, 1, 5], [0, 6, 1, 7]]
    )
    check_both_splices(g, mate)


@pytest.mark.parametrize("seed", range(3))
def test_random_per_vertex_pairing(seed):
    """Stress: arbitrary per-vertex stub pairing of an Eulerian graph —
    many components crossing at many pivots — must splice to one orbit."""
    g = eulerian_rmat(7, avg_degree=4, seed=seed)
    sv = stub_vertices(g)
    n_stubs = 2 * g.num_edges
    order = np.argsort(sv, kind="stable")
    vs = sv[order]
    idx = np.arange(n_stubs)
    start = np.maximum.accumulate(
        np.where(np.r_[True, vs[1:] != vs[:-1]], idx, 0)
    )
    pos = idx - start
    first = pos % 2 == 0            # even degrees → every stub pairs
    a = order[first]
    b = order[~first]
    mate = np.full(n_stubs, -1, dtype=np.int64)
    mate[a] = b
    mate[b] = a
    check_both_splices(g, mate)


def test_phase3_device_end_to_end():
    """phase3_device = splice + list-rank in one jitted program."""
    g, mate = graph_of_cycles(7, [[0, 1, 2], [0, 3, 4], [0, 5, 6]])
    sv = stub_vertices(g)
    circ, m2, ok, rounds = jax.jit(phase3_device)(
        jnp.asarray(mate, jnp.int32), jnp.asarray(sv, jnp.int32)
    )
    assert bool(ok)
    assert int(rounds) >= 2      # three cycles at one pivot: a rotation
    circ = np.asarray(circ, dtype=np.int64)
    assert (circ >= 0).all()
    validate_circuit(g, circ)


def test_circuit_pallas_backend_byte_identical():
    """The XLA doubling rounds of circuit_from_mate_jnp (the backend that
    replaced the Pallas kernel) emit the NumPy list-ranking oracle's
    circuit byte for byte."""
    g, mate = graph_of_cycles(7, [[0, 1, 2], [0, 3, 4], [0, 5, 6]])
    sv = stub_vertices(g)
    m = splice_components_np(mate.copy(), sv, mate >= 0)
    start = int(m[0]) ^ 1
    c_np = circuit_from_mate_np(m, start)
    c_jnp = circuit_from_mate_jnp(jnp.asarray(m, jnp.int32),
                                  jnp.int32(start))
    assert (np.asarray(c_jnp) == c_np).all()
    validate_circuit(g, np.asarray(c_jnp, dtype=np.int64))
