"""Phase 3: unroll the pairing structure into the final Euler circuit.

The paper defers Phase 3 to future work; we implement it.  After all merge
levels, every stub has a mate (perfect matching per vertex) and the
(sibling ∘ mate) permutation's orbit through any stub is the full circuit.
Emission is *list ranking* by pointer doubling — O(log E) depth, fully
vectorized — rather than the paper's sequential disk unroll.

Both a NumPy (host/oracle) and a JAX (device) implementation live here;
they share semantics and are cross-checked in tests.  The device path
(:func:`phase3_device`: the list rank first, then
:func:`splice_components_jnp` and a second rank only where the ranked
orbit misses edges) is fully jittable and runs inside the fused engine
program (DESIGN.md §4): the scipy ``connected_components`` call becomes
pointer-doubling min-label propagation over the cycle structure (the XLA
gather rounds of ``repro.kernels.ref``, the same program on every backend)
and the per-vertex rotation becomes the same sort + segment voting scheme
Phase 1 uses for its splice rounds.
"""
from __future__ import annotations

import math
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels import ref as _kref
from . import bounded
from .phase1 import BIG, I32, _seg_starts


def circuit_from_mate_np(mate: np.ndarray, start_stub: int = -1) -> np.ndarray:
    """NumPy list-ranking: emit the circuit as arrival stubs in walk order.

    ``mate[s]`` is the stub paired with ``s`` at their shared vertex; the
    walk arriving at stub ``s`` departs via ``mate[s]`` and next arrives at
    ``mate[s] ^ 1``.  Requires a single orbit covering E stubs (one circuit).
    """
    n_stubs = mate.shape[0]
    E = n_stubs // 2
    valid = mate >= 0
    if start_stub < 0:
        start_stub = int(np.nonzero(valid)[0][0])
    nxt = np.where(valid, mate ^ 1, np.arange(n_stubs))

    # Halt node: predecessor of start — t such that nxt[t] == start.
    t = int(mate[start_stub ^ 1])
    ptr = nxt.copy()
    ptr[t] = t
    dist = np.ones(n_stubs, dtype=np.int64)
    dist[t] = 0
    reach = np.zeros(n_stubs, dtype=bool)
    reach[t] = True
    rounds = int(np.ceil(np.log2(max(2, n_stubs)))) + 1
    for _ in range(rounds):
        dist = dist + dist[ptr]
        reach = reach | reach[ptr]
        ptr = ptr[ptr]

    orbit = np.nonzero(reach & valid)[0]
    order = orbit[np.argsort(-dist[orbit], kind="stable")]
    return order.astype(np.int64)


def circuit_from_mate_jnp(mate: jnp.ndarray,
                          start_stub: jnp.ndarray) -> jnp.ndarray:
    """JAX list-ranking twin of :func:`circuit_from_mate_np`.

    Returns arrival stubs in walk order, padded with -1 where ``mate`` is
    invalid (padding slots).  Static shapes: output has ``len(mate)//2``
    entries (E slots).
    """
    dist, reach = _rank(mate, start_stub)
    return emit_circuit(mate >= 0, dist, reach)


@jax.named_scope("rank")
def _rank(mate: jnp.ndarray,
          start_stub: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """List ranking of the walk orbit that starts at ``start_stub``:
    ``(dist, reach)``, each stub's distance to the halt stub and whether
    it lies on that orbit.  The doubling rounds are
    :func:`repro.kernels.ref.pointer_double_rank_ref` under one
    ``fori_loop``, so the program's size does not grow with the round
    count."""
    n_stubs = mate.shape[0]
    iota = jnp.arange(n_stubs, dtype=mate.dtype)
    nxt = jnp.where(mate >= 0, mate ^ 1, iota)
    t = mate[start_stub ^ 1]
    ptr = nxt.at[t].set(t).astype(I32)
    dist = jnp.ones(n_stubs, dtype=jnp.int32).at[t].set(0)
    reach = jnp.zeros(n_stubs, dtype=I32).at[t].set(1)
    rounds = int(np.ceil(np.log2(max(2, n_stubs)))) + 1
    _, dist, reach = jax.lax.fori_loop(
        0, rounds, lambda _, c: _kref.pointer_double_rank_ref(*c),
        (ptr, dist, reach))
    return dist, reach


def _stubs_off_circuit(valid: jnp.ndarray, reach: jnp.ndarray) -> jnp.ndarray:
    """Mated stubs whose edge the ranked orbit does not walk (int32).

    On a perfect matching each cycle splits into a forward and a reverse
    orbit, each holding one stub of every edge of the cycle.  So the
    orbit walks every mated edge exactly when it holds half of the mated
    stubs, and the mate is one closed walk exactly when this is 0.  A
    count, not a per-edge test: it adds the least code to the program,
    whose size the device's peak memory follows."""
    on = jnp.sum((valid & (reach > 0)).astype(I32))
    return jnp.sum(valid.astype(I32)) - 2 * on


@jax.named_scope("emit")
def emit_circuit(valid: jnp.ndarray, dist: jnp.ndarray,
                 reach: jnp.ndarray) -> jnp.ndarray:
    """Rank → walk-order emission shared by every Phase 3 backend.

    Sorts stubs by descending halt distance among orbit members (stable,
    so non-members keep index order), keeps the first E slots, and blanks
    slots that are not on the orbit.  The sharded path runs the exact
    same function on the gathered (or host-fetched) rank arrays, which is
    what makes its circuits byte-identical to the replicated oracle's.
    """
    on_orbit = (reach > 0) & valid
    key = jnp.where(on_orbit, -dist, jnp.iinfo(jnp.int32).max)
    order = bounded.argsort(key)
    E = valid.shape[0] // 2
    out = order[:E].astype(jnp.int32)
    member = on_orbit[out]
    return jnp.where(member, out, -1)


def emit_circuit_np(valid: np.ndarray, dist: np.ndarray,
                    reach: np.ndarray) -> np.ndarray:
    """NumPy twin of :func:`emit_circuit` for the ``gather_circuit=False``
    result mode: the engine fetches the still-sharded rank triple and the
    host emits the walk order.  Same int32 keys, same stable sort, same
    tie order — byte-identical output to the device emission."""
    valid = np.asarray(valid)
    on_orbit = (np.asarray(reach) > 0) & valid
    dist = np.asarray(dist).astype(np.int32, copy=False)
    key = np.where(on_orbit, -dist,
                   np.iinfo(np.int32).max).astype(np.int32)
    order = np.argsort(key, kind="stable")
    E = valid.shape[0] // 2
    out = order[:E].astype(np.int32)
    member = on_orbit[out]
    return np.where(member, out, np.int32(-1))


def splice_components_np(
    mate: np.ndarray,
    stub_vertex: np.ndarray,
    valid: np.ndarray,
) -> np.ndarray:
    """Final pivot splice (host): merge remaining edge-disjoint cycles that
    cross only at already-consumed vertices, by mate rotations — the same
    operation the paper's Phase 3 performs when it "switches to a different
    cycle at the pivot vertex".  Returns the updated mate array."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    mate = mate.copy()
    n_stubs = mate.shape[0]
    idx = np.nonzero(valid)[0]
    for _ in range(64):
        # components over sibling + mate links
        sib_u = idx
        sib_v = idx ^ 1
        mat_u = idx
        mat_v = mate[idx]
        rows = np.concatenate([sib_u, mat_u])
        cols = np.concatenate([sib_v, mat_v])
        g = coo_matrix(
            (np.ones(len(rows), np.int8), (rows, cols)), shape=(n_stubs, n_stubs)
        )
        ncomp, labels = connected_components(g, directed=False)
        live = np.unique(labels[idx])
        if len(live) <= 1:
            break
        # one representative pair per (component, vertex); rotate per vertex
        s = idx[mate[idx] > idx]  # one canonical stub per mate-pair
        v = stub_vertex[s]
        comp = labels[s]
        order = np.lexsort((comp, v))
        s, v, comp = s[order], v[order], comp[order]
        first = np.ones(len(s), dtype=bool)
        first[1:] = (v[1:] != v[:-1]) | (comp[1:] != comp[:-1])
        s, v, comp = s[first], v[first], comp[first]
        # vertices hosting >= 2 distinct comps
        vstart = np.ones(len(v), dtype=bool)
        vstart[1:] = v[1:] != v[:-1]
        vseg = np.cumsum(vstart) - 1
        seg_sizes = np.bincount(vseg)
        merged_any = False
        done = set()
        for seg in np.nonzero(seg_sizes >= 2)[0]:
            members = np.nonzero(vseg == seg)[0]
            comps = comp[members]
            if any(c in done for c in comps):
                continue  # one rotation per comp per round
            done.update(int(c) for c in comps)
            reps = s[members]
            mates = mate[reps]
            # rotate: mate[a_i] <- b_{i+1}
            for i in range(len(reps)):
                a = reps[i]
                b = mates[(i + 1) % len(reps)]
                mate[a] = b
                mate[b] = a
            merged_any = True
        if not merged_any:
            break
    return mate


# ---------------------------------------------------------------------------
# device Phase 3 (jittable; runs inside the fused engine program)
# ---------------------------------------------------------------------------

@jax.named_scope("cc")
def _cc_cycle_labels(mate: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Component labels (min member stub id) of the sibling∘mate cycle
    structure, by pointer-doubling min-label propagation.

    Requires every valid stub to be mated (perfect matching), so each
    component is a closed cycle and splits into two pointer orbits — the
    forward and reverse traversals.  Doubling converges each orbit to its
    own min in O(log) rounds; one final min with the sibling's label merges
    the two orbits into the cycle id.
    """
    n = mate.shape[0]
    iota = jnp.arange(n, dtype=I32)
    nxt = jnp.where(valid, mate ^ 1, iota).astype(I32)  # walk successor
    rounds = int(math.ceil(math.log2(max(2, n)))) + 1
    _, lab = jax.lax.fori_loop(
        0, rounds, lambda _, c: _kref.pointer_double_ref(*c), (nxt, iota))
    return jnp.minimum(lab, lab[iota ^ 1])


def splice_components_jnp(
    mate: jnp.ndarray,
    stub_vertex: jnp.ndarray,
    valid: jnp.ndarray,
    rounds: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Jittable twin of :func:`splice_components_np` for perfect matchings.

    Merges the remaining edge-disjoint cycles that cross at shared (pivot)
    vertices by mate rotations, exactly the operation the paper's Phase 3
    performs when it "switches to a different cycle at the pivot vertex".
    The scipy CC call becomes :func:`_cc_cycle_labels`; the per-round
    rotation set is chosen by the same voting scheme as Phase 1's splice
    rounds (each component votes its min candidate vertex, so a component
    rotates at most once per round — safe concurrent merging with
    guaranteed progress at the globally-min candidate vertex).

    Requires every valid stub to be mated (true after all merge levels;
    the engine asserts it).  Invalid slots (padding) are ignored.  Returns
    ``(mate', converged, rounds_run)``; non-convergence within ``rounds``
    only happens on disconnected inputs, which downstream validation
    rejects anyway.
    """
    n = mate.shape[0]
    iota = jnp.arange(n, dtype=I32)
    mate = mate.astype(I32)
    sv = stub_vertex.astype(I32)
    lab0 = _cc_cycle_labels(mate, valid)

    def round_fn(state):
        mate, lab, _, r = state
        cm = valid & (mate > iota)                 # canonical stub per pair
        vkey = jnp.where(cm, sv, BIG)
        ckey = jnp.where(cm, lab, BIG)
        order = bounded.lexsort((ckey, vkey))
        gv, gc = vkey[order], ckey[order]
        gs = jnp.where(cm, iota, BIG)[order]
        gm = cm[order]
        # one representative pair per (vertex, component)
        dup = jnp.concatenate(
            [jnp.zeros((1,), bool), (gv[1:] == gv[:-1]) & (gc[1:] == gc[:-1])]
        )
        rep = gm & ~dup & (gv < BIG)
        seg = _seg_starts(gv)
        n_rep = jax.ops.segment_sum(rep.astype(I32), seg, num_segments=n)
        cand = rep & (n_rep[seg] >= 2)             # ≥2 cycles at this pivot
        # each component votes for its min candidate vertex (≤1 rotation
        # per component per round)
        cseg = jnp.where(cand, gc, n).astype(I32)  # comp ids are stub ids < n
        vote = jax.ops.segment_min(jnp.where(cand, gv, BIG), cseg,
                                   num_segments=n + 1)
        voted = cand & (vote[jnp.clip(gc, 0, n)] == gv)
        n_take = jax.ops.segment_sum(voted.astype(I32), seg, num_segments=n)
        act = voted & (n_take[seg] >= 2)
        # circular mate rotation within each pivot vertex's act group
        akey = jnp.where(act, gv, BIG)
        o2 = bounded.argsort(akey)
        hv, hs, hc = akey[o2], gs[o2], gc[o2]
        hm = act[o2]
        hstart = _seg_starts(hv)
        hlast = jnp.concatenate([hv[1:] != hv[:-1], jnp.ones((1,), bool)])
        hnxt = jnp.clip(
            jnp.where(hlast, hstart, jnp.arange(n, dtype=I32) + 1), 0, n - 1
        )
        b = mate[jnp.clip(hs[hnxt], 0, n - 1)]     # mate of the next rep
        # rotate: mate[a_i] ← b_{i+1}, mate[b_{i+1}] ← a_i.  a's are
        # canonical reps, b's their (larger) mates at the same vertex —
        # provably disjoint index sets, so the scatters never collide.
        mpad = jnp.concatenate([mate, jnp.full((1,), -1, I32)])
        mpad = mpad.at[jnp.where(hm, hs, n)].set(jnp.where(hm, b, -1))
        mpad = mpad.at[jnp.where(hm, b, n)].set(jnp.where(hm, hs, -1))
        mate_new = mpad[:n]
        # relabel merged components to the min label at their pivot
        minc = jax.ops.segment_min(jnp.where(hm, hc, BIG), hstart,
                                   num_segments=n)
        rot_c = minc[hstart]
        lmap = jnp.concatenate([iota, jnp.zeros((1,), I32)])
        lmap = lmap.at[jnp.where(hm, hc, n)].set(jnp.where(hm, rot_c, 0))
        lab_new = lmap[jnp.clip(lab, 0, n - 1)]
        changed = jnp.any(hm)
        return mate_new, lab_new, changed, r - 1

    def cond(state):
        return state[2] & (state[3] > 0)

    init = (mate, lab0, jnp.array(True), jnp.array(rounds, I32))
    with jax.named_scope("splice"):
        mate, _, still_changing, left = jax.lax.while_loop(
            cond, round_fn, init)
    return mate, ~still_changing, rounds - left


def _rank_first(splice, rank, misses, mate):
    """Phase 3's order, shared by the replicated and the sharded path.

    Pass 0 ranks the mate as handed over.  Only where the ranked orbit
    misses edges (``misses(reach)``) does pass 1 run ``splice`` (CC
    labels plus the pivot-splice loop) and rank the spliced mate.  When
    the orbit covers every edge the mate is one cycle, so the splice
    would find no pivot with two components and change nothing: the
    circuit is the one the splice-first order gives, byte for byte.

    The splice sits in a ``while_loop`` that runs at most once, not in a
    ``lax.cond``: under ``vmap`` a batched ``cond`` becomes a ``select``
    that runs both branches, while the loop runs only when some graph of
    the batch needs it.  The rank is traced once, in the pass loop's body.

    Returns ``(mate', dist, reach, ok, splice_rounds_run)``; on the skip
    ``ok`` is true and the rounds are 0.
    """
    def gated_splice(c):
        m, _, _, _ = c
        m, ok, ran = splice(m)
        return m, ok, ran, jnp.array(False)

    def one_pass(c):
        m, ok, ran, _, _, pending, passes = c
        m, ok, ran, _ = jax.lax.while_loop(
            lambda s: s[3], gated_splice, (m, ok, ran, pending))
        dist, reach = rank(m)
        return m, ok, ran, dist, reach, misses(reach), passes + 1

    def again(c):
        passes = c[6]
        return (passes == 0) | (c[5] & (passes < 2))

    zero = jnp.zeros(mate.shape, I32)
    init = (mate.astype(I32), jnp.array(True), jnp.array(0, I32), zero, zero,
            jnp.array(False), jnp.array(0, I32))
    mate, ok, ran, dist, reach, _, _ = jax.lax.while_loop(
        again, one_pass, init)
    return mate, dist, reach, ok, ran


def phase3_device(mate: jnp.ndarray, stub_vertex: jnp.ndarray,
                  splice_rounds: int = 64):
    """Full on-device Phase 3: list-rank emission, with the pivot splice
    and a second rank only where the first orbit misses edges
    (:func:`_rank_first`).

    Shared by the fused engine program (where it runs replicated inside the
    same shard_map as the level scan) and the eager oracle path (where it
    runs on the host-replayed mate), so the two paths produce byte-identical
    circuits whenever their mate arrays agree.

    The batched fused program wraps this whole function in ``jax.vmap``
    (one call per graph in the batch, DESIGN.md §8).

    Returns ``(circuit [E], mate', splice_converged, splice_rounds_run)``.
    """
    valid = mate >= 0
    start = jnp.argmax(valid).astype(I32)
    mate2, dist, reach, ok, ran = _rank_first(
        lambda m: splice_components_jnp(m, stub_vertex, valid,
                                        rounds=splice_rounds),
        lambda m: _rank(m, start),
        lambda reach: _stubs_off_circuit(valid, reach) > 0,
        mate)
    return emit_circuit(valid, dist, reach), mate2, ok, ran


# ---------------------------------------------------------------------------
# sharded Phase 3 (DESIGN.md §11): CC + splice + rank over stub shards
# ---------------------------------------------------------------------------
#
# The replicated device Phase 3 above needs the whole mate[2E] on every
# device (an all_gather right after the level scan).  The sharded twin
# below keeps Phase 3 itself distributed: each device owns the [S] slice
# of the stub space with global ids [me·S, me·S + S), S = shard_width(E,n)
# ≈ 2E/n, and every remote pointer is resolved by rotating *table shards*
# around the device ring (ppermute) while queries stay home — a
# deterministic O(S)-memory schedule with no per-pair lane skew, unlike
# all_to_all query routing whose (src,dst) receive buffers are unbounded
# for adversarial pointer distributions.  S is even, so a stub's sibling
# s^1 always lives on the same shard and the sibling-merge/next-pointer
# steps stay local.
#
# Byte-identity with the replicated oracle holds by construction:
#   · CC doubling gathers the same round-start snapshots, runs ≥ the
#     oracle's round count (extra rounds past the fixpoint are idempotent
#     for min-label propagation), and ends with the same local sibling
#     merge;
#   · each splice round ships the canonical (s, v, comp, mate) records to
#     the vertex-owner device (owner(v) = v mod n) and re-runs the
#     oracle's exact lexsort / rep-dedup / vote / rotate logic there —
#     every vertex group is wholly owned by one device, so the per-vertex
#     decisions (and hence the global rotation set) are identical;
#   · rank doubling mirrors CC, and emission runs the shared
#     ``emit_circuit`` on the same (valid, dist, reach) values.

def shard_width(num_edges: int, n_parts: int) -> int:
    """Per-device stub-shard width of the sharded Phase 3: the smallest
    EVEN S with n·S ≥ 2E.  Evenness keeps each stub's sibling s^1 on the
    same shard (global ids are [me·S, me·S+S)), so sibling lookups never
    leave the device.

    >>> shard_width(128, 8), shard_width(100, 8), shard_width(3, 4)
    (32, 26, 2)
    """
    return max(2, 2 * math.ceil(num_edges / max(1, n_parts)))


def sharded_phase3_schedule(num_edges: int, n_parts: int,
                            gather_circuit: bool = True) -> dict:
    """The sharded Phase 3's static collective schedule, counted in jaxpr
    *eqns* (ring loops trace one ppermute eqn each; the runtime executes
    each ``n_parts`` times per loop, and each doubling ring once per
    round).  Shared by the engine's published budget
    (``fused_collective_budget``) and the analysis cost model so the two
    can never drift.

      · rank (traced once, in the pass loop of ``_rank_first``): 1
        ring-min for the start stub, 1 ``psum`` fetching the halt stub's
        mate, one rotation ring inside the R-round loop, and 1 ``psum``
        of the mated stubs off the orbit's circuit;
      · CC doubling (run only when that count is not 0): one
        table-rotation ring inside the R-round loop;
      · pivot splice (inside the while body, traced once): 6 rings —
        record ship, vote scatter, vote readback, mate write, relabel
        scatter, relabel readback — plus 1 ``psum`` for the global
        `changed` flag;
      · emission: 1 ``all_gather`` (elided when ``gather_circuit=False``,
        where the rank shards leave the program still sharded).

    At run time a mate that is already one circuit runs R+1 rings (the
    rank's); one that needs splicing runs 2R+1 more (CC, second rank)
    and 6 for each splice round.
    """
    S = shard_width(num_edges, n_parts)
    total = n_parts * S
    rounds = int(math.ceil(math.log2(max(2, total)))) + 1
    return {
        "shard_width": S,
        "stub_space": total,
        "doubling_rounds": rounds,
        "splice_rings": 6,
        "ppermute": 2 + 6 + 1,
        "psum": 3,
        "all_gather": 1 if gather_circuit else 0,
    }


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


@jax.named_scope("cc")
def _cc_labels_sharded(mate_sh: jnp.ndarray, axes, n: int) -> jnp.ndarray:
    """Sharded twin of :func:`_cc_cycle_labels`: min-label propagation by
    pointer doubling where each round resolves remote pointers with one
    full ring rotation of the (nxt, lab) table shards."""
    S = mate_sh.shape[0]
    me = jax.lax.axis_index(axes).astype(I32)
    gid = me * S + jnp.arange(S, dtype=I32)
    valid = mate_sh >= 0
    nxt = jnp.where(valid, mate_sh ^ 1, gid).astype(I32)
    perm = _ring_perm(n)
    rounds = int(math.ceil(math.log2(max(2, n * S)))) + 1

    def round_(_, carry):
        nxt, lab = carry

        def step(k, c):
            tbl, a_nxt, a_lab = c
            base = ((jnp.mod(me - k, n)) * S).astype(I32)[None]
            a_nxt, a_lab = _kref.pointer_double_shard_ref(
                nxt, a_nxt, a_lab, base, tbl[0], tbl[1], s_real=S)
            tbl = jax.lax.ppermute(tbl, axes, perm)
            return tbl, a_nxt, a_lab

        _, a_nxt, a_lab = jax.lax.fori_loop(
            0, n, step,
            (jnp.stack([nxt, lab]), nxt, jnp.full((S,), BIG, I32)))
        return a_nxt, jnp.minimum(lab, a_lab)

    _, lab = jax.lax.fori_loop(0, rounds, round_, (nxt, gid))
    iota = jnp.arange(S, dtype=I32)
    return jnp.minimum(lab, lab[iota ^ 1])


def splice_components_sharded(
    mate_sh: jnp.ndarray,
    sv_sh: jnp.ndarray,
    axes,
    n: int,
    p3v_cap: int,
    rounds: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sharded twin of :func:`splice_components_jnp`.

    Per round: canonical (stub, vertex, comp, mate) records ring-ship to
    their vertex-owner device (owner(v) = v mod n) into a [p3v_cap]
    table, where the oracle's per-vertex rep/vote/rotate logic runs
    verbatim on the locally-sorted records; mate rotations and component
    relabels ring back to the stub/label owners.  Returns
    ``(mate_sh', ok, rounds_run)`` — ``ok`` is convergence AND no
    vertex-table overflow (``p3v_cap`` is sized from the degree profile,
    so overflow only means undersized caps, never silent corruption);
    the round count is the same on every device (``changed`` is a
    ``psum``).
    """
    S = mate_sh.shape[0]
    me = jax.lax.axis_index(axes).astype(I32)
    iota = jnp.arange(S, dtype=I32)
    gid = me * S + iota
    mate_sh = mate_sh.astype(I32)
    sv_sh = sv_sh.astype(I32)
    perm = _ring_perm(n)
    lab0 = _cc_labels_sharded(mate_sh, axes, n)
    lo, hi = me * S, me * S + S

    def round_fn(state):
        mate, lab, _, r, of = state
        valid = mate >= 0
        cm = valid & (mate > gid)                 # canonical stub per pair

        # ---- ring 1: ship canonical records to their vertex owner ----
        def ship_step(k, carry):
            buf, tbl, cnt, of_t = carry
            bs, bv, bc, bm, bmk = buf
            take = (bmk > 0) & (jnp.mod(bv, n) == me)
            pos = cnt + bounded.cumsum(take.astype(I32)) - 1
            okw = take & (pos < p3v_cap)
            slot = jnp.where(okw, pos, p3v_cap)
            vals = jnp.stack([bv, bc, bs, bm])
            tbl = tbl.at[:, slot].set(jnp.where(okw, vals, BIG))
            cnt = cnt + jnp.sum(take.astype(I32))
            of_t = of_t | (cnt > p3v_cap)
            buf = jax.lax.ppermute(buf, axes, perm)
            return buf, tbl, cnt, of_t

        buf0 = jnp.stack([jnp.where(cm, gid, BIG), jnp.where(cm, sv_sh, BIG),
                          jnp.where(cm, lab, BIG), jnp.where(cm, mate, BIG),
                          cm.astype(I32)])
        _, tbl, _, of_t = jax.lax.fori_loop(
            0, n, ship_step,
            (buf0, jnp.full((4, p3v_cap + 1), BIG, I32),
             jnp.zeros((), I32), jnp.zeros((), bool)))
        tv, tc, ts, tm = (tbl[i, :p3v_cap] for i in range(4))

        # ---- local per-vertex logic (the oracle's, verbatim) ----
        order = bounded.lexsort((ts, tc, tv))
        gv, gc, gs, gm = tv[order], tc[order], ts[order], tm[order]
        gmk = gv < BIG
        dup = jnp.concatenate(
            [jnp.zeros((1,), bool), (gv[1:] == gv[:-1]) & (gc[1:] == gc[:-1])]
        )
        rep = gmk & ~dup
        vseg = _seg_starts(gv)
        n_rep = jax.ops.segment_sum(rep.astype(I32), vseg,
                                    num_segments=p3v_cap)
        cand = rep & (n_rep[vseg] >= 2)

        # ---- ring 2: scatter-min votes onto the comp-label owners ----
        def vote_step(k, carry):
            vbuf, vote = carry
            qc, qv, qm = vbuf
            own = (qm > 0) & (qc >= lo) & (qc < hi)
            idx = jnp.where(own, qc - lo, S)
            vote = vote.at[idx].min(jnp.where(own, qv, BIG))
            vbuf = jax.lax.ppermute(vbuf, axes, perm)
            return vbuf, vote

        vbuf0 = jnp.stack([jnp.where(cand, gc, BIG),
                           jnp.where(cand, gv, BIG), cand.astype(I32)])
        _, vote = jax.lax.fori_loop(
            0, n, vote_step, (vbuf0, jnp.full((S + 1,), BIG, I32)))

        # ---- ring 3: read each record's comp vote back ----
        def read_step(k, rbuf):
            qc, ans = rbuf
            own = (qc >= lo) & (qc < hi)
            idx = jnp.where(own, qc - lo, 0)
            ans = jnp.where(own, vote[idx], ans)
            return jax.lax.ppermute(jnp.stack([qc, ans]), axes, perm)

        rbuf = jax.lax.fori_loop(
            0, n, read_step,
            jnp.stack([jnp.where(gmk, gc, BIG),
                       jnp.full((p3v_cap,), BIG, I32)]))
        va = rbuf[1]

        voted = cand & (va == gv)
        n_take = jax.ops.segment_sum(voted.astype(I32), vseg,
                                     num_segments=p3v_cap)
        act = voted & (n_take[vseg] >= 2)

        # circular rotation pairs within each pivot vertex's act group
        akey = jnp.where(act, gv, BIG)
        o2 = bounded.argsort(akey)
        hv, hs, hc = akey[o2], gs[o2], gc[o2]
        hmate = gm[o2]
        hm = act[o2]
        hstart = _seg_starts(hv)
        hlast = jnp.concatenate([hv[1:] != hv[:-1], jnp.ones((1,), bool)])
        hnxt = jnp.clip(
            jnp.where(hlast, hstart, jnp.arange(p3v_cap, dtype=I32) + 1),
            0, p3v_cap - 1)
        b = hmate[hnxt]                            # mate of the next rep
        minc = jax.ops.segment_min(jnp.where(hm, hc, BIG), hstart,
                                   num_segments=p3v_cap)
        rot_c = minc[hstart]

        # ---- ring 4: deliver mate[a_i] ← b_{i+1}, mate[b_{i+1}] ← a_i ----
        def write_step(k, carry):
            wbuf, mpad = carry
            wa, wb, wm = wbuf
            own_a = (wm > 0) & (wa >= lo) & (wa < hi)
            ia = jnp.where(own_a, wa - lo, S)
            mpad = mpad.at[ia].set(jnp.where(own_a, wb, -1))
            own_b = (wm > 0) & (wb >= lo) & (wb < hi)
            ib = jnp.where(own_b, wb - lo, S)
            mpad = mpad.at[ib].set(jnp.where(own_b, wa, -1))
            wbuf = jax.lax.ppermute(wbuf, axes, perm)
            return wbuf, mpad

        wbuf0 = jnp.stack([jnp.where(hm, hs, BIG), jnp.where(hm, b, BIG),
                           hm.astype(I32)])
        _, mpad = jax.lax.fori_loop(
            0, n, write_step,
            (wbuf0, jnp.concatenate([mate, jnp.full((1,), -1, I32)])))
        mate_new = mpad[:S]

        # ---- ring 5: deliver comp relabels to the label owners ----
        def lmap_step(k, carry):
            mbuf, lmap_p = carry
            mo, mn, mm = mbuf
            own = (mm > 0) & (mo >= lo) & (mo < hi)
            idx = jnp.where(own, mo - lo, S)
            lmap_p = lmap_p.at[idx].set(jnp.where(own, mn, 0))
            mbuf = jax.lax.ppermute(mbuf, axes, perm)
            return mbuf, lmap_p

        mbuf0 = jnp.stack([jnp.where(hm, hc, BIG),
                           jnp.where(hm, rot_c, BIG), hm.astype(I32)])
        _, lmap_p = jax.lax.fori_loop(
            0, n, lmap_step,
            (mbuf0, jnp.concatenate([gid, jnp.zeros((1,), I32)])))
        lmap = lmap_p[:S]

        # ---- ring 6: every stub reads lmap[lab] from the label owner ----
        def lq_step(k, qbuf):
            ql, ans = qbuf
            own = (ql >= lo) & (ql < hi)
            idx = jnp.where(own, ql - lo, 0)
            ans = jnp.where(own, lmap[idx], ans)
            return jax.lax.ppermute(jnp.stack([ql, ans]), axes, perm)

        qbuf = jax.lax.fori_loop(0, n, lq_step, jnp.stack([lab, lab]))
        lab_new = qbuf[1]

        changed = jax.lax.psum(jnp.sum(hm.astype(I32)), axes) > 0
        return mate_new, lab_new, changed, r - 1, of | of_t

    def cond(state):
        return state[2] & (state[3] > 0)

    init = (mate_sh, lab0, jnp.array(True), jnp.array(rounds, I32),
            jnp.array(False))
    with jax.named_scope("splice"):
        mate_sh, _, still_changing, left, of = jax.lax.while_loop(
            cond, round_fn, init)
    return mate_sh, ~still_changing & ~of, rounds - left


@jax.named_scope("rank")
def _rank_sharded(mate_sh: jnp.ndarray, axes,
                  n: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sharded list ranking: the doubling loop of
    :func:`_rank` over rotating (ptr, dist, reach) table
    shards.  Returns the local (dist, reach) slices."""
    S = mate_sh.shape[0]
    me = jax.lax.axis_index(axes).astype(I32)
    gid = me * S + jnp.arange(S, dtype=I32)
    valid = mate_sh >= 0
    nxt = jnp.where(valid, mate_sh ^ 1, gid).astype(I32)
    perm = _ring_perm(n)

    # global start stub = min valid gid, by a scalar ring-min
    def min_step(k, carry):
        rot, acc = carry
        rot = jax.lax.ppermute(rot, axes, perm)
        return rot, jnp.minimum(acc, rot)

    local_min = jnp.min(jnp.where(valid, gid, BIG))[None]
    _, acc = jax.lax.fori_loop(0, n, min_step, (local_min, local_min))
    start = acc[0]

    # halt stub t = mate[start ^ 1], fetched from its owner via one psum
    q = start ^ 1
    t = jax.lax.psum(jnp.sum(jnp.where(gid == q, mate_sh, 0)), axes)

    ptr = jnp.where(gid == t, gid, nxt)
    dist = jnp.where(gid == t, 0, 1).astype(jnp.int32)
    reach = (gid == t).astype(I32)
    rounds = int(math.ceil(math.log2(max(2, n * S)))) + 1
    zero = jnp.zeros((S,), I32)

    def round_(_, carry):
        ptr, dist, reach = carry

        def step(k, c):
            tbl, a_ptr, a_dist, a_reach = c
            base = ((jnp.mod(me - k, n)) * S).astype(I32)[None]
            a_ptr, a_dist, a_reach = _kref.pointer_double_rank_shard_ref(
                ptr, a_ptr, a_dist, a_reach, base,
                tbl[0], tbl[1], tbl[2], s_real=S)
            tbl = jax.lax.ppermute(tbl, axes, perm)
            return tbl, a_ptr, a_dist, a_reach

        _, a_ptr, a_dist, a_reach = jax.lax.fori_loop(
            0, n, step, (jnp.stack([ptr, dist, reach]), ptr, zero, zero))
        return a_ptr, dist + a_dist, jnp.maximum(reach, a_reach)

    _, dist, reach = jax.lax.fori_loop(0, rounds, round_, (ptr, dist, reach))
    return dist, reach


def phase3_sharded(mate_sh: jnp.ndarray, sv_sh: jnp.ndarray, axes, n: int,
                   n_stubs: int, p3v_cap: int,
                   splice_rounds: int = 64,
                   gather_circuit: bool = True):
    """Full sharded Phase 3 for one device's [S] stub shard, in the
    order of :func:`phase3_device` (:func:`_rank_first`): rank, then the
    CC and pivot splice and a second rank only where the orbit misses
    edges.  A shard holds both stubs of each of its edges (S is even).

    With ``gather_circuit=True`` (the default) the run's ONE
    ``all_gather`` happens here — at the very end, on the post-rank
    (mate, dist, reach) triple — and the function returns the replicated
    ``(circuit [E], mate [2E], ok, rounds)`` exactly like
    :func:`phase3_device`.  With ``gather_circuit=False`` nothing is
    gathered: the triple comes back still sharded (``(mate_sh, dist_sh,
    reach_sh, ok, rounds)``) and the caller (the engine's
    :class:`PendingRun`) emits the circuit host-side from the fetched
    shards via the same :func:`emit_circuit` ordering.
    """
    valid = mate_sh >= 0
    mate2_sh, dist_sh, reach_sh, ok, ran = _rank_first(
        lambda m: splice_components_sharded(m, sv_sh, axes, n, p3v_cap,
                                            rounds=splice_rounds),
        lambda m: _rank_sharded(m, axes, n),
        # one psum: every device takes the same branch
        lambda reach: jax.lax.psum(_stubs_off_circuit(valid, reach),
                                    axes) > 0,
        mate_sh)
    if not gather_circuit:
        return mate2_sh, dist_sh, reach_sh, ok, ran
    with jax.named_scope("emit"):
        packed = jnp.stack([mate2_sh, dist_sh, reach_sh], axis=1)  # [S, 3]
        g = jax.lax.all_gather(packed, axes, tiled=True)           # [n·S, 3]
    mate2 = g[:n_stubs, 0]
    circuit = emit_circuit(mate2 >= 0, g[:n_stubs, 1], g[:n_stubs, 2])
    return circuit, mate2, ok, ran
