"""Phase 1's id lookups: the stub-space path against binary search.

Every stub or component id Phase 1 looks up lies below the stub space
``2·num_edges``.  Where the program knows that bound, the CC runs over
the ids themselves and each exact-match lookup is one gather from a table
indexed by the id; otherwise (the eager per-level program) the lookups
are ``jnp.searchsorted`` on sorted values.  Both must give the same
answers, and ``phase1_local`` the same outputs.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import phase1
from repro.core.engine import DistributedEngine
from repro.core.graph import partition_graph
from repro.core.phase1 import (BIG, NewEdges, OpenTable, Phase1Caps,
                               TouchTable, empty_open, empty_touch,
                               phase1_local)
from repro.graphgen.eulerize import eulerian_rmat
from repro.graphgen.partition import partition_vertices

SPACE = 512


def _ids(rng, n, lo=0, hi=SPACE):
    return rng.integers(lo, hi, size=n).astype(np.int32)


def _search(sorted_vals, q):
    """The exact-match binary search the tables replace."""
    K = len(sorted_vals)
    j = np.clip(np.asarray(jnp.searchsorted(jnp.asarray(sorted_vals),
                                            jnp.asarray(q))), 0, K - 1)
    return j, (sorted_vals[j] == q) & (q < BIG)


def _queries(rng, values):
    """Present ids, ids absent from ``values``, and BIG padding."""
    absent = np.setdiff1d(np.arange(SPACE), values)[:40]
    return np.concatenate([rng.choice(values[values < BIG], 60), absent,
                           np.full(8, BIG)]).astype(np.int32)


def _sorted_universe(rng):
    """Sorted ids with duplicates, then BIG padding."""
    return np.sort(np.concatenate([
        np.repeat(_ids(rng, 50), rng.integers(1, 4, 50)),
        np.full(30, BIG)])).astype(np.int32)


def _check_cc(rng, static=False, rounds=0):
    """The CC over the id space equals the CC over the sorted universe's
    slots: roots of present, absent and BIG ids, the converged flag and
    the rounds run, also when the budget stops it early."""
    universe = _sorted_universe(rng)
    rng.shuffle(universe)
    present = universe[universe < BIG]
    n_e = 120
    ca, cb = rng.choice(present, n_e), rng.choice(present, n_e)
    emask = rng.random(n_e) < 0.7
    ca, cb = (np.where(emask, x, BIG).astype(np.int32) for x in (ca, cb))
    rounds = rounds or math.ceil(math.log2(len(universe))) + 2
    args = [jnp.asarray(x) for x in (ca, cb, emask, universe)]
    table = phase1._cc_hook_jump(*args, rounds, static=static, space=SPACE)
    search = phase1._cc_hook_jump(*args, rounds, static=static)
    q = jnp.asarray(_queries(rng, universe))
    np.testing.assert_array_equal(table[0](q), search[0](q))
    assert bool(table[3]) == bool(search[3])
    assert int(table[4]) == int(search[4])
    # the vote's segments: distinct values, distinct ids below n_seg
    seg = np.asarray(table[1](jnp.asarray(present)))
    assert (seg < table[2]).all()
    assert len(np.unique(seg)) == len(np.unique(present))


def _check_member(rng):
    values = np.concatenate([_ids(rng, 80), np.full(20, BIG)]).astype(
        np.int32)
    rng.shuffle(values)
    q = _queries(rng, values)
    _, expect = _search(np.sort(values), q)
    table = phase1.member_table(jnp.asarray(values), SPACE)
    got = np.asarray(phase1._take(table, jnp.asarray(q), 0)) > 0
    np.testing.assert_array_equal(got, expect)


def _check_relabel(rng):
    mfrom = np.concatenate([rng.choice(SPACE, 60, replace=False),
                            np.full(30, BIG)]).astype(np.int32)
    mto = np.where(mfrom < BIG, _ids(rng, 90), BIG).astype(np.int32)
    perm = rng.permutation(90)
    mfrom, mto = mfrom[perm], mto[perm]
    q = _queries(rng, mfrom)
    mo = np.argsort(mfrom, kind="stable")
    j, hit = _search(mfrom[mo], q)
    expect = np.where(hit, mto[mo][j], q)
    table = phase1.relabel_table(jnp.asarray(mfrom), jnp.asarray(mto), SPACE)
    got = np.asarray(phase1._take(table, jnp.asarray(q), jnp.asarray(q)))
    np.testing.assert_array_equal(got, expect)
    assert (got[q == BIG] == BIG).all()


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("check", [_check_member, _check_relabel],
                         ids=["member", "relabel"])
def test_table_helper_equals_search(check, seed):
    check(np.random.default_rng(seed))


@pytest.mark.parametrize("seed,static,rounds", [
    (0, False, 0), (1, False, 0), (2, True, 0), (3, False, 1)])
def test_cc_over_ids_equals_cc_over_slots(seed, static, rounds):
    _check_cc(np.random.default_rng(seed), static, rounds)


# ---------------------------------------------------------------------------
# phase1_local: table path == search path
# ---------------------------------------------------------------------------

def _new_edges(g, eids, la, width):
    """``eids`` of ``g`` as a NewEdges table padded to ``width``."""
    k = len(eids)

    def pad(x, fill=BIG):
        out = np.full(width, fill, np.int32)
        out[:k] = x
        return jnp.asarray(out)

    mask = np.zeros(width, bool)
    mask[:k] = True
    u, v = g.edge_u[eids], g.edge_v[eids]
    return NewEdges(pad(eids), pad(u), pad(v), pad(la[u]), pad(la[v]),
                    jnp.asarray(mask))


def _merge(tables, cap, cls):
    """Concatenate level outputs' tables, valid rows first, to ``cap``."""
    cols = [np.concatenate([np.asarray(getattr(t, f)) for t in tables])
            for f in cls._fields]
    keep = np.flatnonzero(cols[-1])
    assert len(keep) <= cap
    out = []
    for c in cols:
        fill = False if c.dtype == bool else BIG
        x = np.full(cap, fill, c.dtype)
        x[:len(keep)] = c[keep]
        out.append(jnp.asarray(x))
    return cls(*out)


def _run(caps, args, on_relabel=None):
    """phase1_local under a fresh jit; ``on_relabel`` sees each splice
    round's relabel ``mfrom`` on the table path."""
    if on_relabel is None:
        return jax.jit(lambda *a: phase1_local(*a, caps))(*args)
    orig = phase1.relabel_table

    def spy(mfrom, mto, space):
        jax.debug.callback(on_relabel, mfrom)
        return orig(mfrom, mto, space)

    phase1.relabel_table = spy
    try:
        return jax.block_until_ready(
            jax.jit(lambda *a: phase1_local(*a, caps))(*args))
    finally:
        phase1.relabel_table = orig


def _both_paths(args, caps, num_edges):
    """Run ``args`` on the table and the search path; assert equal
    outputs and unique active ``mfrom`` rows in every splice round."""
    table_caps = dataclasses.replace(caps, stub_space=2 * num_edges)
    search_caps = dataclasses.replace(caps, stub_space=0)
    rounds = []

    def unique(mfrom):
        live = np.asarray(mfrom)[np.asarray(mfrom) < BIG]
        rounds.append((len(live), len(np.unique(live))))

    table = _run(table_caps, args, unique)
    search = _run(search_caps, args)
    leaves = zip(jax.tree.leaves(table), jax.tree.leaves(search))
    for i, (a, b) in enumerate(leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"Phase1Out leaf {i}")
    assert np.asarray(table.flags).all()
    assert len(rounds) == int(table.splice_rounds)
    assert all(n == u for n, u in rounds), rounds
    return table, rounds


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_phase1_table_path_equals_search_whole_graph(seed):
    """P=1: the whole graph in one Phase 1."""
    g = eulerian_rmat(7, avg_degree=4, seed=seed)
    E = g.num_edges
    new = _new_edges(g, np.arange(E), np.zeros(g.num_vertices, np.int64), E)
    caps = Phase1Caps(open_cap=8, touch_cap=8)
    out, rounds = _both_paths((new, empty_open(8), empty_touch(8),
                               jnp.int32(0)), caps, E)
    # the cycles were spliced: along the forest (no path, so no vote)
    assert int(out.forest_rounds) >= 1 and not rounds


def _partitioned(seed):
    """Both level-0 partitions of a two-way split, then the merge level
    that takes their opens and touch pairs and the cut edges, each on both
    paths.  Returns, per partition and level, the vote rounds' active
    ``mfrom`` rows and the forest rounds."""
    g = eulerian_rmat(8, avg_degree=5, seed=seed)
    pg = partition_graph(g, partition_vertices(g, 2, seed=seed))
    tree, act, la, cut_ids, _ = DistributedEngine.plan(pg)
    assert tree.height == 1 and len(cut_ids)
    E, cap = g.num_edges, 512
    caps = Phase1Caps(open_cap=cap, touch_cap=cap)
    outs, level0 = [], []
    for p in pg.parts:
        new = _new_edges(g, p.local_eids, la, E)
        out, rounds = _both_paths((new, empty_open(cap), empty_touch(cap),
                                   jnp.int32(0)), caps, E)
        outs.append(out)
        level0.append((sum(n for n, _ in rounds), int(out.forest_rounds)))
    opens = _merge([o.opens for o in outs], cap, OpenTable)
    touch = _merge([o.touch for o in outs], cap, TouchTable)
    assert np.asarray(opens.mask).any() and np.asarray(touch.mask).any()
    new = _new_edges(g, cut_ids[act[cut_ids] == 0], la, E)
    out, rounds = _both_paths((new, opens, touch, jnp.int32(1)), caps, E)
    return level0, (sum(n for n, _ in rounds), int(out.forest_rounds))


@pytest.mark.parametrize("seed", [0, 1])
def test_phase1_table_path_equals_search_partitioned(seed):
    """The merge level's cycles meet at its vertices: it splices them
    along the forest.  (No level-0 vertex of these splits holds both a
    cycle and a path, so no vote round runs here; the next test's splits
    have one.)"""
    _, merge = _partitioned(seed)
    assert merge[1] >= 1, merge


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_phase1_table_path_equals_search_where_a_cycle_meets_a_path(seed):
    """In these splits a level-0 partition holds a vertex where a cycle
    meets an open path, so the vote rounds rotate there, and the spy of
    ``_both_paths`` saw their ``mfrom`` rows unique."""
    level0, _ = _partitioned(seed)
    assert sum(rows for rows, _ in level0) > 0, level0
