"""Sharded Phase 3 (DESIGN.md §11): parity and fuzz layer.

The sharded path must be *byte-identical* to the replicated device
oracle — same mate permutation after splicing, same emitted circuit —
across partition counts, multi-cycle pivot densities, batch widths, and
both emission modes (device ``all_gather`` and ``gather_circuit=False``
host-side emission).  Three layers:

  * function-level parity: ``phase3_sharded`` under ``shard_map`` vs a
    jitted ``phase3_device`` on the gathered mate, P ∈ {1, 2, 4, 8},
    plus the host ``circuit_from_mate_np`` rank oracle on the spliced
    mate (subprocess, 8 fake devices);
  * solver-level parity: replicated / sharded / no-gather solvers on the
    same graphs, single and B=4 batched, warm repeat, and the eager
    (non-fused) oracle — every result also passes ``res.validate()``
    (full Euler-circuit check against the input graph);
  * seeded fuzz (Hypothesis when installed, the ``_hypofallback`` shim
    otherwise) over random multi-trail Eulerian graphs in-process on a
    single-device mesh, where the sharded rings still run (n=1).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypofallback import given, settings, st

from conftest import run_with_devices
from repro.core.graph import Graph


def random_eulerian_np(n_vertices, n_trails, trail_len, seed):
    """Random Eulerian multigraph: ``n_trails`` closed walks that share
    vertices (higher ``n_trails`` -> more disjoint cycles per vertex ->
    denser pivot splicing in Phase 3)."""
    rng = np.random.default_rng(seed)
    eu, ev, used = [], [], [0]
    for _ in range(max(1, n_trails)):
        start = int(rng.choice(used))
        cur = start
        for _ in range(max(2, trail_len)):
            nxt = int(rng.integers(0, n_vertices))
            eu.append(cur)
            ev.append(nxt)
            used.append(nxt)
            cur = nxt
        eu.append(cur)
        ev.append(start)
    return Graph(n_vertices, np.asarray(eu, np.int64),
                 np.asarray(ev, np.int64))


# shared subprocess preamble: graph generator + solver-mode comparator
_GEN = '''
import numpy as np
from repro.core.graph import Graph

def random_eulerian(n_vertices, n_trails, trail_len, seed):
    rng = np.random.default_rng(seed)
    eu, ev, used = [], [], [0]
    for _ in range(max(1, n_trails)):
        start = int(rng.choice(used)); cur = start
        for _ in range(max(2, trail_len)):
            nxt = int(rng.integers(0, n_vertices))
            eu.append(cur); ev.append(nxt); used.append(nxt); cur = nxt
        eu.append(cur); ev.append(start)
    return Graph(n_vertices, np.asarray(eu, np.int64),
                 np.asarray(ev, np.int64))
'''


# ----------------------------------------------------------------------
# function-level parity: phase3_sharded vs phase3_device + host oracle
# ----------------------------------------------------------------------
def test_phase3_sharded_function_parity():
    out = run_with_devices('''
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.phase3 import (circuit_from_mate_np, phase3_device,
                               phase3_sharded, shard_width)
from repro.parallel.compat import make_mesh, shard_map

rng = np.random.default_rng(0)

def random_cycle_cover(n_vertices, n_trails, trail_len):
    """Union of closed trails sharing vertices -> (mate, sv, E): the
    exact post-Phase-2 state (per-cycle successor matching)."""
    edges, cycles, used = [], [], [0]
    for _ in range(n_trails):
        start = int(rng.choice(used))
        L = int(rng.integers(2, trail_len + 1))
        mids = rng.integers(0, n_vertices, size=L - 1).tolist()
        walk = [start] + mids + [start]
        ids = []
        for a, b in zip(walk[:-1], walk[1:]):
            ids.append(len(edges)); edges.append((a, b))
        cycles.append(ids); used.extend(mids)
    E = len(edges)
    mate = np.full(2 * E, -1, np.int32)
    sv = np.zeros(2 * E, np.int32)
    for e, (a, b) in enumerate(edges):
        sv[2 * e] = a; sv[2 * e + 1] = b
    for ids in cycles:
        for i, e in enumerate(ids):
            nxt_e = ids[(i + 1) % len(ids)]
            mate[2 * e + 1] = 2 * nxt_e
            mate[2 * nxt_e] = 2 * e + 1
    return mate, sv, E

def check(mate, sv, E, n, label):
    """Parity on one mate; returns the spliced mate (one circuit)."""
    n_stubs = 2 * E
    c_rep, m_rep, ok_rep, r_rep = jax.jit(phase3_device)(
        jnp.asarray(mate), jnp.asarray(sv))
    assert bool(ok_rep), f"{label}: replicated did not converge"

    S = shard_width(E, n)
    pad = n * S - n_stubs
    mate_p = np.concatenate([mate, np.full(pad, -1, np.int32)])
    sv_p = np.concatenate([sv, np.zeros(pad, np.int32)])
    mesh = make_mesh((n,), ("x",))
    deg = np.bincount(sv, minlength=1)
    owners = np.arange(len(deg)) % n
    p3v = int(max(np.bincount(owners, weights=deg, minlength=n))) + 8

    def f(m_sh, s_sh):
        return phase3_sharded(m_sh, s_sh, "x", n, n_stubs, p3v)

    with mesh:
        fn = jax.jit(shard_map(f, mesh, (P("x"), P("x")),
                               (P(None), P(None), P(), P())))
        c_sh, m_sh, ok_sh, r_sh = fn(jnp.asarray(mate_p), jnp.asarray(sv_p))
    assert bool(ok_sh), f"{label}: sharded did not converge"
    assert int(r_sh) == int(r_rep), f"{label}: splice rounds differ"
    assert np.array_equal(np.asarray(m_rep), np.asarray(m_sh)), (
        f"{label}: mate mismatch")
    assert np.array_equal(np.asarray(c_rep), np.asarray(c_sh)), (
        f"{label}: circuit mismatch")
    # host rank oracle on the spliced mate (same start/halt rule)
    circ_np = circuit_from_mate_np(np.asarray(m_sh))
    assert np.array_equal(np.asarray(c_sh), circ_np.astype(np.int32)), (
        f"{label}: host circuit mismatch")
    return np.asarray(m_sh), int(r_sh)

for trial in range(4):
    nv = int(rng.integers(2, 9))
    nt = int(rng.integers(1, 5))
    tl = int(rng.integers(2, 7))
    mate, sv, E = random_cycle_cover(nv, nt, tl)
    for n in (1, 2, 4, 8):
        one, _ = check(mate, sv, E, n, f"trial{trial}-P{n}")
        # the spliced mate is one circuit: both paths skip the splice
        _, r_one = check(one, sv, E, n, f"trial{trial}-P{n}-one-cycle")
        assert r_one == 0, r_one
print("FUNCTION_PARITY_OK")
''', n=8)
    assert "FUNCTION_PARITY_OK" in out


# ----------------------------------------------------------------------
# solver-level parity: replicated vs sharded vs no-gather, B in {1, 4}
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_parts", [2, 4])
def test_solver_parity_matrix(n_parts):
    out = run_with_devices(_GEN + f'''
from repro.euler import EulerSolver

P = {n_parts}
ref = EulerSolver(n_parts=P, sharded_phase3=False)
sh = EulerSolver(n_parts=P)                      # default: sharded
ng = EulerSolver(n_parts=P, gather_circuit=False)
assert sh.sharded_phase3 and not ref.sharded_phase3

graphs = [random_eulerian(10, 1, 12, 7), random_eulerian(18, 3, 8, 8),
          random_eulerian(24, 6, 5, 9)]
for g in graphs:
    r0 = ref.solve(g).validate()
    r1 = sh.solve(g).validate()
    r2 = ng.solve(g).validate()
    assert r0.valid and r1.valid and r2.valid
    for r in (r1, r2):
        assert np.array_equal(r0.circuit, r.circuit), "circuit mismatch"
        assert np.array_equal(r0.mate, r.mate), "mate mismatch"

# B=4 batched serving: find a second graph in the SAME ladder bucket
# (caps round off the degree profile, so sibling seeds can drift)
ga = random_eulerian(24, 3, 8, 70)
key = ref.bucket_of(ga)
gb = ga
for s in range(71, 200):
    cand = random_eulerian(24, 3, 8, s)
    if ref.bucket_of(cand) == key:
        gb = cand
        break
batch = [ga, gb, ga, gb]
b0 = ref.solve_batch(batch)
b1 = sh.solve_batch(batch)
b2 = ng.solve_batch(batch)
for x, y, z in zip(b0, b1, b2):
    y.validate(); z.validate()
    assert y.valid and z.valid
    assert np.array_equal(x.circuit, y.circuit)
    assert np.array_equal(x.circuit, z.circuit)
    assert np.array_equal(x.mate, y.mate)

# warm repeat (device-resident) and the eager (non-fused) oracle
again = sh.solve(graphs[1])
assert np.array_equal(again.circuit, sh.solve(graphs[1]).circuit)
eager = sh.solve(graphs[1], fused=False)
assert np.array_equal(again.circuit, eager.circuit), "eager/fused drift"
print("SOLVER_PARITY_OK")
''', n=8)
    assert "SOLVER_PARITY_OK" in out


# ----------------------------------------------------------------------
# seeded fuzz, in-process (single-device mesh still runs the ring code)
# ----------------------------------------------------------------------
@st.composite
def eulerian_params(draw):
    return (draw(st.integers(4, 28)),     # vertices
            draw(st.integers(1, 6)),      # trails (pivot density)
            draw(st.integers(3, 10)),     # trail length
            draw(st.integers(0, 2 ** 31 - 1)))


@given(eulerian_params())
@settings(max_examples=8, deadline=None)
def test_sharded_fuzz_single_device(params):
    from repro.euler import EulerSolver

    nv, trails, tlen, seed = params
    g = random_eulerian_np(nv, trails, tlen, seed)
    ref = EulerSolver(n_parts=1, sharded_phase3=False).solve(g).validate()
    sh = EulerSolver(n_parts=1, sharded_phase3=True).solve(g).validate()
    assert ref.valid and sh.valid
    assert np.array_equal(ref.circuit, sh.circuit)
    assert np.array_equal(ref.mate, sh.mate)
    # every edge appears exactly once in the emitted circuit
    assert sorted(np.asarray(sh.circuit) >> 1) == list(range(g.num_edges))
