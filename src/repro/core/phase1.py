"""Jitted, vectorized Phase 1 — the per-partition superstep body.

TPU-native replacement for the paper's sequential Hierholzer walk
(Alg. 1; the stub representation and phase mapping are DESIGN.md §2):

  1. *pair* the stub pool (new local edges' stubs + inherited open path
     endpoints) per vertex — sort + parity pairing.  Odd leftovers are the
     OB path endpoints of Lemma 1; components with no leftovers are the
     EB/internal cycles of Lemma 2.
  2. *label* components: connected components over the component-merge
     graph induced by the new pairs, by the Borůvka hook/jump rounds the
     splice's spanning forest uses too.
  3. *splice* components sharing an owned vertex (Lemma 3 / MERGEINTO) by
     mate rotations: first every cycle along a spanning forest of the
     cycles-and-vertices graph, in one pass (Borůvka rounds, their number
     growing with the log of the cycle count, not with the degrees); then
     the open paths by a voting scheme that gives each component at most
     one rotation per round, at most one path per rotation.

Everything is static-shape and jit-compatible: masked fixed-capacity
tables, sort-based grouping, ``segment_min`` label propagation, and
bounded round counts with convergence flags (asserted in tests and checked
at runtime by the engine).

Component ids are *min member stub id* — globally unique and stable across
levels and devices, so pathMaps merge without coordination.  Every id
Phase 1 looks up (a stub or a component) therefore lies in the stub space
``[0, 2·num_edges)``; where the caller gives that bound, the CC runs over
the ids themselves and each exact-match lookup is one gather from a table
indexed by the id (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import bounded

I32 = jnp.int32
BIG = jnp.iinfo(jnp.int32).max


@dataclasses.dataclass(frozen=True)
class Phase1Caps:
    open_cap: int           # max carried-forward path endpoints
    touch_cap: int          # max representative pairs at boundary vertices
    hook_rounds: int = 0    # 0 → ceil(log2(comp universe)) + 1
    splice_rounds: int = 12  # vote rounds (open paths); the spanning
                             # forest's budget is forest_round_budget
    static_splice: bool = False  # unroll splice and hook rounds (roofline
                                 # analysis: while-loop bodies are
                                 # cost-counted once)
    stub_space: int = 0     # every stub/comp id is below this (2·num_edges):
                            # lookups are tables over it; 0 → unknown, binary
                            # search.  Derived, not a setting


class OpenTable(NamedTuple):
    stub: jnp.ndarray   # [OC] stub id
    vert: jnp.ndarray   # [OC] vertex the stub is incident on
    la: jnp.ndarray     # [OC] last-activation level of the vertex
    comp: jnp.ndarray   # [OC] component id (min member stub id)
    mask: jnp.ndarray   # [OC] bool


class TouchTable(NamedTuple):
    s1: jnp.ndarray     # [TC]
    s2: jnp.ndarray     # [TC] current mate of s1 (same vertex)
    vert: jnp.ndarray   # [TC]
    la: jnp.ndarray     # [TC]
    comp: jnp.ndarray   # [TC]
    mask: jnp.ndarray   # [TC] bool


class NewEdges(NamedTuple):
    eid: jnp.ndarray    # [NE] global edge id
    u: jnp.ndarray      # [NE]
    v: jnp.ndarray      # [NE]
    lau: jnp.ndarray    # [NE] last-activation level of u
    lav: jnp.ndarray    # [NE] last-activation level of v
    mask: jnp.ndarray   # [NE] bool


class Phase1Out(NamedTuple):
    opens: OpenTable
    touch: TouchTable
    log_s1: jnp.ndarray        # [PC] mate-log: mate[log_s1] = log_s2
    log_s2: jnp.ndarray
    log_mask: jnp.ndarray
    n_components: jnp.ndarray  # [] live components touching this partition
    flags: jnp.ndarray         # [3] bool: cc converged, splice converged, no overflow
    hook_rounds: jnp.ndarray   # [] Borůvka rounds run, both CC calls
    splice_rounds: jnp.ndarray  # [] splice vote rounds run
    forest_rounds: jnp.ndarray  # [] splice spanning-forest rounds run


def pair_table_cap(pool: int, touch_cap: int) -> int:
    """Width of Phase 1's compacted pair table: at most half the stub pool
    can pair, plus the inherited touch pairs.  Shared with
    ``EngineCaps.pair_cap`` so the engine's mate-log lane sizing can never
    drift from the table the log is emitted from."""
    return pool // 2 + touch_cap


def _boruvka_budget(nodes: int) -> int:
    """Round budget of a :func:`_spanning_forest` over ``nodes`` nodes:
    Borůvka rounds at least halve the trees of each component, so
    ``ceil(log2 nodes)`` rounds always suffice; one more for slack."""
    return int(math.ceil(math.log2(max(2, nodes)))) + 1


def _hook_budgets(caps: Phase1Caps, pool: int, touch_cap: int):
    """Round budgets of Phase 1's two CC calls.  A nonzero
    ``caps.hook_rounds`` fixes both; else each is the Borůvka budget of
    its universe: the component values (``pool + touch_cap``) for the
    first, twice as many for the second (that universe joined with the
    pair table's post-splice comps)."""
    if caps.hook_rounds:
        return caps.hook_rounds, caps.hook_rounds
    k = pool + touch_cap
    return _boruvka_budget(k), _boruvka_budget(2 * k)


def hook_round_budget(caps: Phase1Caps, pool: int, touch_cap: int) -> int:
    """Most CC rounds one ``phase1_local`` call can report: the sum of
    both CC calls' budgets (``pool`` = stub-pool width, ``2·new_cap +
    open_cap``)."""
    return sum(_hook_budgets(caps, pool, touch_cap))


def forest_round_budget(pool: int, touch_cap: int) -> int:
    """Round budget of the splice's spanning forest, whose nodes are the
    cycles of the pair table (at most ``pair_table_cap`` of them)."""
    return _boruvka_budget(pair_table_cap(pool, touch_cap))


def _in_space(values, space: int):
    """Scatter indices: ids below ``space`` as they are, the rest (BIG
    padding) at ``space``, which ``mode="drop"`` skips."""
    return jnp.where(values < space, values, space)


def _take(table, q, fill):
    """``table[q]`` for ids inside the table, ``fill`` for the rest."""
    inside = q < table.shape[0]
    return jnp.where(inside, table[jnp.where(inside, q, 0)], fill)


def member_table(values, space: int):
    """``t[d]`` = 1 if id ``d`` is among ``values`` (BIG entries
    ignored), else 0.  Int32: a TPU scatter into a narrower type
    compiles to about 1.8 MB more program code."""
    return jnp.zeros((space,), I32).at[_in_space(values, space)].set(
        1, mode="drop")


def relabel_table(mfrom, mto, space: int):
    """``t[d] = mto[i]`` where ``mfrom[i] == d``, else ``d``.  The ids
    of ``mfrom`` below BIG must be distinct."""
    return jnp.arange(space, dtype=I32).at[_in_space(mfrom, space)].set(
        mto, mode="drop")


def _searcher(sorted_vals):
    """``q ↦ (slot, found)`` by binary search on sorted ``sorted_vals``:
    the first slot holding ``q`` (clipped insertion point when absent)."""
    K = sorted_vals.shape[0]

    def find(q):
        j = jnp.clip(jnp.searchsorted(sorted_vals, q), 0, K - 1).astype(I32)
        return j, sorted_vals[j] == q

    return find


def empty_open(cap: int) -> OpenTable:
    z = jnp.full((cap,), BIG, dtype=I32)
    return OpenTable(z, z, z, z, jnp.zeros((cap,), bool))


def empty_touch(cap: int) -> TouchTable:
    z = jnp.full((cap,), BIG, dtype=I32)
    return TouchTable(z, z, z, z, z, jnp.zeros((cap,), bool))


def _compact(arrays, mask, cap: int):
    """Move valid entries to the front and truncate to ``cap``."""
    order = bounded.argsort(~mask)
    overflow = jnp.sum(mask) > cap
    outs = tuple(a[order][:cap] for a in arrays)
    return outs, mask[order][:cap], overflow


def _seg_starts(sorted_keys, idx_dtype=I32):
    """Index of each element's segment start, for sorted keys."""
    n = sorted_keys.shape[0]
    idx = jnp.arange(n, dtype=idx_dtype)
    newseg = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]]
    )
    return bounded.cummax(jnp.where(newseg, idx, 0))


def _jump(lab):
    """Pointer jumping to the fixpoint: every label becomes its tree's
    root."""
    def body(st):
        lab, _ = st
        nlab = lab[lab]
        return nlab, jnp.any(nlab != lab)

    return jax.lax.while_loop(lambda st: st[1], body,
                              (lab, jnp.array(True)))[0]


def _spanning_forest(na, nb, emask, K: int, rounds: int,
                     static: bool = False, pick: bool = True):
    """A spanning forest of the graph on nodes ``[0, K)`` whose edges are
    ``(na[i], nb[i])`` where ``emask[i]``, by Borůvka rounds.

    Each round every tree with an edge to another tree picks the
    lowest-indexed such edge and hooks its root onto the other end's
    root; where two trees picked the same edge, the smaller root stays.
    Edges carry distinct indices, so the picks close no cycle but those
    two-tree ones, and the picked edges stay a forest.  Every tree with an
    edge leaving it merges each round, so the trees of a component at
    least halve: the forest is spanning after ``ceil(log2 T)`` rounds for
    T trees at the start.  Pointer jumping (:func:`_jump`) makes every
    label a root again before the next round.  Stops at the first round
    that finds no edge between trees; ``static`` unrolls all ``rounds``
    (the jumps inside a round stay a loop).

    Returns ``(least, tree, converged, rounds run)``: ``least[x]`` is the
    least node of ``x``'s tree, ``tree`` marks the picked edges (bool,
    elementwise: no scatter into a narrow type; None without ``pick``,
    for a caller that wants the components alone).
    """
    E = na.shape[0]
    idx = jnp.arange(E, dtype=I32)
    node = jnp.arange(K, dtype=I32)
    na = jnp.where(emask, na, 0)    # a masked edge joins node 0 to itself
    nb = jnp.where(emask, nb, 0)

    def round_(lab, ea, eb, tree):
        cand = jnp.where(ea != eb, idx, BIG)
        best = jax.ops.segment_min(jnp.concatenate([cand, cand]),
                                   jnp.concatenate([ea, eb]),
                                   num_segments=K)
        has = best < BIG
        e = jnp.minimum(best, E - 1)
        other = (ea ^ eb)[e] ^ node             # the picked edge's far root
        mutual = has & (_take(best, other, BIG) == best)
        lab = _jump(jnp.where(has & ~(mutual & (node < other)), other, lab))
        if pick:
            tree = tree | (best[ea] == idx) | (best[eb] == idx)
        return lab, lab[na], lab[nb], tree

    state = (node, na, nb, jnp.zeros((E,), bool) if pick else None)
    if static:
        for _ in range(rounds):
            state = round_(*state)
        ran = jnp.array(rounds, I32)
    else:
        def body(st):
            nst = round_(*st[:4])
            return nst + (jnp.any(nst[1] != nst[2]), st[5] + 1)

        st = jax.lax.while_loop(
            lambda st: st[4] & (st[5] < rounds), body,
            state + (jnp.any(na != nb), jnp.array(0, I32)))
        state, ran = st[:4], st[5]
    lab, ea, eb, tree = state
    least = jax.ops.segment_min(node, lab, num_segments=K)[lab]
    return least, tree, ~jnp.any(ea != eb), ran


def _cc_hook_jump(ca, cb, emask, universe, rounds: int,
                  static: bool = False, space: int = 0):
    """Min-label connected components over a value-keyed graph.

    Nodes are the values in ``universe`` ([K], BIG-padded); edges are
    (ca[i], cb[i]) where ``emask[i]``, each endpoint a universe value.
    Returns ``(roots, seg, n_seg, converged, rounds run, value)``:
    ``roots`` maps values to their component's least *value* (identity
    for values outside the universe, so BIG stays BIG); ``seg`` maps
    universe values to distinct ids below ``n_seg`` (the splice's node
    ids), in value order, and everything else to ids the segment ops drop
    or that no vote reads; ``value`` maps a universe value's ``seg`` id
    back.

    With ``space`` (every value below it) the nodes are the ids below
    ``space`` themselves; an id outside the universe is an isolated node.
    Without it the nodes are the slots of the sorted universe, found by
    binary search.  Node order is value order in both, so both give the
    same roots, flag and round count.  The components are the trees of
    :func:`_spanning_forest` (at most ``rounds`` Borůvka rounds; ``static``
    unrolls them).
    """
    if space:
        K, ia, ib = space, ca, cb
    else:
        K = universe.shape[0]
        uniq = bounded.sort(universe)
        find = _searcher(uniq)
        ia, ib = find(ca)[0], find(cb)[0]
    least, _, converged, ran = _spanning_forest(ia, ib, emask, K, rounds,
                                                static=static, pick=False)
    if space:
        return (lambda v: _take(least, v, v),
                lambda v: _in_space(v, space), space, converged, ran,
                lambda s: s)
    root_val = uniq[least]

    def lookup(v):
        j, found = find(v)      # a BIG slot is its own root: BIG → BIG
        return jnp.where(found, root_val[j], v)

    return (lookup, lambda v: find(v)[0], K, converged, ran,
            lambda s: uniq[s])


def _next_marked(mark):
    """For each position ``p``, the least marked position ``≥ p`` (BIG
    where none): a reversed running minimum, no gather."""
    n = mark.shape[0]
    neg = jnp.where(mark, -jnp.arange(n, dtype=I32), -BIG)
    return -bounded.cummax(neg[::-1])[::-1]


def phase1_local(
    new: NewEdges,
    opens: OpenTable,
    touch: TouchTable,
    level: jnp.ndarray,
    caps: Phase1Caps,
) -> Phase1Out:
    """One partition's Phase 1 at one level.  Fully jittable."""
    # ------------------------------------------------------------------
    # 1. stub pool = new edges' stubs + inherited open endpoints
    # ------------------------------------------------------------------
    nm, om = new.mask, opens.mask
    pool_stub = jnp.concatenate(
        [jnp.where(nm, 2 * new.eid, BIG), jnp.where(nm, 2 * new.eid + 1, BIG),
         jnp.where(om, opens.stub, BIG)]
    )
    pool_vert = jnp.concatenate(
        [jnp.where(nm, new.u, BIG), jnp.where(nm, new.v, BIG),
         jnp.where(om, opens.vert, BIG)]
    )
    pool_la = jnp.concatenate(
        [jnp.where(nm, new.lau, 0), jnp.where(nm, new.lav, 0),
         jnp.where(om, opens.la, 0)]
    )
    pool_comp = jnp.concatenate(
        [jnp.where(nm, 2 * new.eid, BIG), jnp.where(nm, 2 * new.eid, BIG),
         jnp.where(om, opens.comp, BIG)]
    )
    pool_mask = jnp.concatenate([nm, nm, om])
    P = pool_stub.shape[0]
    hook1, hook2 = _hook_budgets(caps, P, touch.mask.shape[0])
    space = caps.stub_space

    # ------------------------------------------------------------------
    # 2. pair per vertex: sort by (vertex, stub), pair consecutive
    # ------------------------------------------------------------------
    vkey = jnp.where(pool_mask, pool_vert, BIG)
    # §Perf (euler H-E1'): drop the stub tiebreak key — stable argsort is
    # already deterministic — one sort pass instead of lexsort's two
    order = bounded.argsort(vkey)
    sv, ss = vkey[order], pool_stub[order]
    sc, sl, sm = pool_comp[order], pool_la[order], pool_mask[order]
    pos = jnp.arange(P, dtype=I32) - _seg_starts(sv)
    nxt_same = jnp.concatenate([sv[1:] == sv[:-1], jnp.zeros((1,), bool)])
    has_partner = (pos % 2 == 0) & sm & (sv < BIG) & nxt_same
    pr_a = jnp.where(has_partner, ss, BIG)
    pr_b = jnp.where(has_partner, jnp.roll(ss, -1), BIG)
    pr_v = jnp.where(has_partner, sv, BIG)
    pr_la = jnp.where(has_partner, sl, 0)
    pr_ca = jnp.where(has_partner, sc, BIG)
    pr_cb = jnp.where(has_partner, jnp.roll(sc, -1), BIG)
    pr_mask = has_partner
    paired = has_partner | jnp.concatenate([jnp.zeros((1,), bool), has_partner[:-1]])
    left_mask = sm & ~paired & (sv < BIG)

    # ------------------------------------------------------------------
    # 3. component labels after pairing (CC over comp values)
    # ------------------------------------------------------------------
    universe = jnp.concatenate(
        [jnp.where(sm, sc, BIG), jnp.where(touch.mask, touch.comp, BIG)]
    )
    roots, comp_seg, n_seg, cc_ok, hook_ran, seg_value = _cc_hook_jump(
        pr_ca, pr_cb, pr_mask, universe, hook1, static=caps.static_splice,
        space=space,
    )
    # the id space drops ids at or past its end: count one as overflow
    ids_ok = jnp.all((universe < space) | (universe == BIG)) if space \
        else jnp.array(True)
    open_comp = roots(jnp.where(left_mask, sc, BIG))
    pair_comp = roots(pr_ca)
    touch_comp = roots(jnp.where(touch.mask, touch.comp, BIG))

    # ------------------------------------------------------------------
    # 4. unified pair table (this level's pairs + inherited touch pairs)
    # ------------------------------------------------------------------
    q_s1 = jnp.concatenate([pr_a, jnp.where(touch.mask, touch.s1, BIG)])
    q_s2 = jnp.concatenate([pr_b, jnp.where(touch.mask, touch.s2, BIG)])
    q_v = jnp.concatenate([pr_v, jnp.where(touch.mask, touch.vert, BIG)])
    q_la = jnp.concatenate([pr_la, jnp.where(touch.mask, touch.la, 0)])
    q_c = jnp.concatenate([pair_comp, touch_comp])
    q_m = jnp.concatenate([pr_mask, touch.mask])
    # §Perf (euler H-E2): at most half the pool can pair, so compact the
    # pair table to P//2 + TC before the splice loop — every subsequent
    # round (sorts, segment ops, relabels) streams half the rows.
    (q_s1, q_s2, q_v, q_la, q_c), q_m, _ = _compact(
        (q_s1, q_s2, q_v, q_la, q_c), q_m,
        pair_table_cap(pool_stub.shape[0], touch.mask.shape[0]),
    )
    PC = q_s1.shape[0]
    q_c_pre = q_c          # pre-splice comps of the compacted pair table

    # the open comps as a set: a member_table over the stub space, or
    # sorted (BIG-padded) for the search
    if space:
        oc = member_table(open_comp, space)

        def is_path(comps, oc):
            return _take(oc, comps, 0) > 0
    else:
        oc = bounded.sort(open_comp)

        def is_path(comps, oc):
            return _searcher(oc)(comps)[1] & (comps < BIG)

    def relabeler(mfrom, mto):
        """(comp relabel, open-set relabel) for the map mfrom → mto;
        ``mfrom`` holds each comp once (one rotation per comp a round)."""
        if space:
            table = relabel_table(mfrom, mto, space)
            return (lambda vals: _take(table, vals, vals),
                    lambda oc: member_table(jnp.where(oc, table, BIG), space))
        mo = bounded.argsort(mfrom)
        find, to = _searcher(mfrom[mo]), mto[mo]

        def relabel(vals):
            j, found = find(vals)
            return jnp.where(found, to[j], vals)

        return relabel, lambda oc: bounded.sort(relabel(oc))

    # ------------------------------------------------------------------
    # 5a. splice the cycles along a spanning forest, in one pass
    # ------------------------------------------------------------------
    # Nodes are the cycles; at each vertex its first cycle row (the
    # anchor) has an edge to every other cycle row there.  A spanning
    # forest of that graph, rotated at every vertex among the anchor and
    # the rows its forest edges reach, makes each tree one circuit
    # (Atallah & Vishkin, JCSS 1984): the chosen passages of a vertex
    # belong to distinct circuits however the other vertices' rotations
    # went, or the forest would hold a cycle.  Paths stay out; the vote
    # rounds below give each merge at most one.
    vm = jnp.where(q_m, q_v, BIG)
    order2 = bounded.argsort(vm)
    gv, gc, gs2 = vm[order2], q_c[order2], q_s2[order2]
    n = gv.shape[0]
    pos = jnp.arange(n, dtype=I32)
    seg = _seg_starts(gv)
    live = q_m[order2] & (gv < BIG)
    path = live & is_path(gc, oc)
    cyc = live & ~path
    anchor = jax.ops.segment_min(jnp.where(cyc, pos, BIG), seg,
                                 num_segments=n)[seg]
    anchor_c = gc[jnp.minimum(anchor, n - 1)]
    least, tree, forest_ok, forest_ran = _spanning_forest(
        comp_seg(anchor_c), comp_seg(gc), cyc & (gc != anchor_c), n_seg,
        forest_round_budget(P, touch.mask.shape[0]),
        static=caps.static_splice)
    used = jax.ops.segment_max(tree.astype(I32), seg, num_segments=n)
    act = tree | ((used[seg] > 0) & (pos == anchor))
    # rotate each vertex's act rows circularly, in row order
    first = _next_marked(act)
    nxt = jnp.concatenate([first[1:], jnp.full((1,), BIG, I32)])
    nxt_in = (nxt < n) & (gv[jnp.minimum(nxt, n - 1)] == gv)
    rot = gs2[jnp.minimum(jnp.where(nxt_in, nxt, first[seg]), n - 1)]
    q_s2 = q_s2.at[jnp.where(act, order2, n)].set(rot, mode="drop")
    # a cycle's comp becomes its tree's least comp (comps are min member
    # stubs, so that is the circuit's); paths keep theirs
    node = comp_seg(q_c)
    q_c = jnp.where(q_c < BIG, seg_value(_take(least, node, 0)), q_c)
    # after a spanning forest no vertex holds two cycles: the votes start
    # only where a cycle meets a path (its budget always suffices, so
    # ``forest_ok`` is a flag, like the CC's)
    has_path = jax.ops.segment_max(path.astype(I32), seg, num_segments=n)
    pending = jnp.any(cyc & (has_path[seg] > 0))

    # ------------------------------------------------------------------
    # 5b. vote rounds: the open paths
    # ------------------------------------------------------------------
    def splice_round(state):
        s2, cmp_, oc, _, rounds_left = state
        order2 = bounded.lexsort((cmp_, vm))  # H-E1': no s1 tiebreak
        gv, gc = vm[order2], cmp_[order2]
        gs2 = s2[order2]
        gm = q_m[order2]
        dup = jnp.concatenate(
            [jnp.zeros((1,), bool), (gv[1:] == gv[:-1]) & (gc[1:] == gc[:-1])]
        )
        rep = gm & ~dup & (gv < BIG)
        seg = _seg_starts(gv)
        n = gv.shape[0]
        gpath = is_path(gc, oc) & rep
        n_rep = jax.ops.segment_sum(rep.astype(I32), seg, num_segments=n)
        n_cyc = jax.ops.segment_sum((rep & ~gpath).astype(I32), seg,
                                    num_segments=n)
        cand = rep & (n_rep[seg] >= 2) & (n_cyc[seg] >= 1)
        # each comp votes for its min candidate vertex
        ci = comp_seg(gc)
        vote = jax.ops.segment_min(jnp.where(cand, gv, BIG), ci,
                                   num_segments=n_seg)
        voted = cand & (_take(vote, ci, BIG) == gv)
        # at most one path per vertex: cycles + the min-comp voted path
        pthmin = jax.ops.segment_min(
            jnp.where(voted & gpath, gc, BIG), seg, num_segments=n
        )
        take = voted & (~gpath | (gc == pthmin[seg]))
        n_take = jax.ops.segment_sum(take.astype(I32), seg, num_segments=n)
        act = take & (n_take[seg] >= 2)
        # rotation among act members, circular within vertex segment
        akey = jnp.where(act, gv, BIG)
        o4 = bounded.argsort(akey)
        hv, hs2, hc = akey[o4], gs2[o4], gc[o4]
        hm = act[o4]
        hstart = _seg_starts(hv)
        hlast = jnp.concatenate([hv[1:] != hv[:-1], jnp.ones((1,), bool)])
        hnxt = jnp.clip(jnp.where(hlast, hstart, jnp.arange(n, dtype=I32) + 1),
                        0, n - 1)
        rot_s2 = jnp.where(hm, hs2[hnxt], hs2)
        minc = jax.ops.segment_min(jnp.where(hm, hc, BIG), hstart, num_segments=n)
        rot_c = jnp.where(hm, minc[hstart], hc)
        changed = jnp.any(hm)
        # single unsort of the rotated rows: active-space position p ↦
        # original index order2[o4[p]] (int32 only: no bool scatter)
        orig = order2[o4]
        s2_new = s2.at[jnp.where(hm, orig, n)].set(rot_s2, mode="drop")
        # comp relabel map (from → min comp at its rotation vertex)
        relabel, reopen = relabeler(jnp.where(hm, hc, BIG),
                                    jnp.where(hm, rot_c, BIG))
        return s2_new, relabel(cmp_), reopen(oc), changed, rounds_left - 1

    def cond(state):
        return state[3] & (state[4] > 0)

    init = (q_s2, q_c, oc, pending, jnp.array(caps.splice_rounds, I32))
    if caps.static_splice:
        state = init
        for _ in range(caps.splice_rounds):
            state = splice_round(state)
        q_s2, q_c, oc, still_changing, _ = state
        splice_ok = jnp.array(True)   # fixed rounds; flag checked by tests
        splice_ran = jnp.array(caps.splice_rounds, I32)
    else:
        q_s2, q_c, oc, still_changing, left = jax.lax.while_loop(
            cond, splice_round, init
        )
        splice_ok = ~still_changing & forest_ok
        splice_ran = caps.splice_rounds - left

    # ------------------------------------------------------------------
    # 6. rebuild tables
    # ------------------------------------------------------------------
    # Recover per-stub open comps: splice relabels are strictly decreasing
    # (from → min of merged set), so CC over (pre-splice comp → final comp)
    # pairs has the final label as its min — a single CC pass maps
    # every original comp to its final id.
    roots3, _, _, cc3_ok, hook3_ran, _ = _cc_hook_jump(
        q_c_pre,
        q_c,
        q_m,
        jnp.concatenate([universe, jnp.where(q_m, q_c, BIG)]),
        hook2,
        static=caps.static_splice,
        space=space,
    )
    open_comp_final = roots3(open_comp)

    (o_stub, o_vert, o_la, o_comp), o_mask, open_of = _compact(
        (jnp.where(left_mask, ss, BIG), jnp.where(left_mask, sv, BIG),
         jnp.where(left_mask, sl, 0), open_comp_final),
        left_mask, caps.open_cap,
    )
    new_opens = OpenTable(o_stub, o_vert, o_la, o_comp, o_mask)

    # touch = pairs at vertices that still activate later, dedup (v, comp)
    keep = q_m & (q_la > level)
    tv = jnp.where(keep, q_v, BIG)
    tc = jnp.where(keep, q_c, BIG)
    ot = bounded.lexsort((tc, tv))        # H-E1': s1 tiebreak dropped
    dv, dc = tv[ot], tc[ot]
    dup2 = jnp.concatenate(
        [jnp.zeros((1,), bool), (dv[1:] == dv[:-1]) & (dc[1:] == dc[:-1])]
    )
    tm = keep[ot] & ~dup2
    (t_s1, t_s2, t_v, t_la, t_c), t_m, touch_of = _compact(
        (q_s1[ot], q_s2[ot], q_v[ot], q_la[ot], q_c[ot]), tm, caps.touch_cap
    )
    new_touch = TouchTable(t_s1, t_s2, t_v, t_la, t_c, t_m)

    live = bounded.sort(jnp.concatenate(
        [jnp.where(o_mask, o_comp, BIG), jnp.where(t_m, t_c, BIG)]
    ))
    n_comp = jnp.sum(
        (live < BIG)
        & jnp.concatenate([jnp.ones((1,), bool), live[1:] != live[:-1]])
    )

    flags = jnp.stack([cc_ok & cc3_ok, splice_ok,
                       ~(open_of | touch_of) & ids_ok])
    return Phase1Out(
        opens=new_opens,
        touch=new_touch,
        log_s1=jnp.where(q_m, q_s1, BIG),
        log_s2=jnp.where(q_m, q_s2, BIG),
        log_mask=q_m,
        n_components=n_comp.astype(I32),
        flags=flags,
        hook_rounds=hook_ran + hook3_ran,
        splice_rounds=splice_ran,
        forest_rounds=forest_ran,
    )
