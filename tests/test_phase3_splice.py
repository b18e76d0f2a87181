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
    check_both_splices(g, per_vertex_pairing(g))


def per_vertex_pairing(g: Graph) -> np.ndarray:
    """Pair each vertex's stubs in stub order: an arbitrary perfect
    matching, whose cycles cross at many pivots."""
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
    return mate


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


# ---------------------------------------------------------------------------
# rank first: the CC and splice run only when the orbit misses edges
# ---------------------------------------------------------------------------

def splice_first(mate, sv):
    """Phase 3 in the splice-first order: CC labels and pivot splice on
    every mate, then the rank of the spliced mate."""
    valid = mate >= 0
    m2, ok, ran = splice_components_jnp(mate, sv, valid)
    start = jnp.argmax(valid).astype(jnp.int32)
    return circuit_from_mate_jnp(m2, start), m2, ok, ran


CYCLE_SETS = {
    "three_triangles": (7, [[0, 1, 2], [0, 3, 4], [0, 5, 6]]),
    "five_cycles": (11, [[0, 1, 2], [0, 3, 4], [0, 5, 6], [0, 7, 8],
                         [0, 9, 10]]),
    "chain": (10, [[0, 1, 2], [1, 3, 4], [4, 5, 6], [6, 7, 8], [8, 9, 0]]),
    "two_pivots": (8, [[0, 2, 1, 3], [0, 4, 1, 5], [0, 6, 1, 7]]),
}


def phase3_case(name, one_cycle):
    """A multi-cycle mate (the cycle sets above, or a per-vertex pairing
    of an RMAT graph), or with ``one_cycle`` the same graph's mate after
    the host splice: one closed walk over every edge."""
    if name == "rmat_pairing":
        g = eulerian_rmat(7, avg_degree=4, seed=0)
        mate = per_vertex_pairing(g)
    else:
        g, mate = graph_of_cycles(*CYCLE_SETS[name])
    sv = stub_vertices(g)
    if one_cycle:
        mate = splice_components_np(mate, sv, mate >= 0)
    return g, jnp.asarray(mate, jnp.int32), jnp.asarray(sv, jnp.int32)


@pytest.mark.parametrize("one_cycle", [False, True],
                         ids=["multi_cycle", "one_cycle"])
@pytest.mark.parametrize("name", [*CYCLE_SETS, "rmat_pairing"])
def test_rank_first_is_byte_identical_to_splice_first(name, one_cycle):
    """The same circuit, mate and ``ok`` as the splice-first order; the
    same splice rounds where the orbit misses edges, and 0 (CC and splice
    skipped) where the mate is one circuit already."""
    g, mate, sv = phase3_case(name, one_cycle)
    circ, m2, ok, rounds = jax.jit(phase3_device)(mate, sv)
    c_old, m_old, ok_old, r_old = jax.jit(splice_first)(mate, sv)
    assert np.array_equal(np.asarray(circ), np.asarray(c_old))
    assert np.array_equal(np.asarray(m2), np.asarray(m_old))
    assert bool(ok) and bool(ok_old)
    if one_cycle:
        assert int(rounds) == 0 and int(r_old) == 1
        assert np.array_equal(np.asarray(m2), np.asarray(mate))
    else:
        assert int(rounds) == int(r_old) >= 2
    validate_circuit(g, np.asarray(circ, dtype=np.int64))


def test_batched_phase3_matches_each_graph_alone():
    """A one-circuit mate batched with a multi-cycle one: the batch gives,
    graph by graph, what the unbatched calls give (the multi-cycle member
    runs the splice; the other keeps its skip)."""
    cases = [phase3_case("three_triangles", one) for one in (True, False)]
    mates = jnp.stack([m for _, m, _ in cases])
    svs = jnp.stack([sv for _, _, sv in cases])
    batched = jax.jit(jax.vmap(phase3_device))(mates, svs)
    for b, (_, mate, sv) in enumerate(cases):
        alone = jax.jit(phase3_device)(mate, sv)
        for x, y in zip(batched, alone):
            assert np.array_equal(np.asarray(x[b]), np.asarray(y))
    assert [int(r) for r in batched[3]] == [0, 2]


def _loops(jaxpr, depth=0, out=None):
    """``(primitive, while-depth, name stack)`` of every loop eqn."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("scan", "while"):
            out.append((name, depth, str(eqn.source_info.name_stack)))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _loops(sub, depth + (name == "while"), out)
    return out


def test_batched_cc_stays_inside_a_loop():
    """Under ``vmap`` the CC doubling loop sits in the splice's gate loop,
    one ``while`` deeper than the rank: it is not lifted into a ``select``
    that runs it for every batch."""
    _, mate, sv = phase3_case("three_triangles", True)
    jaxpr = jax.make_jaxpr(jax.vmap(phase3_device))(
        jnp.stack([mate, mate]), jnp.stack([sv, sv]))
    loops = _loops(jaxpr.jaxpr)
    (cc,) = [d for prim, d, scope in loops
             if prim == "scan" and scope.endswith("cc")]
    (rank,) = [d for prim, d, scope in loops
               if prim == "scan" and scope.endswith("rank")]
    assert cc == rank + 1, loops
