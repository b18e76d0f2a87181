"""Geometric shape buckets for the multi-graph serving path (DESIGN.md §7/§9).

The fused whole-run program is compiled for static shapes: the padded
per-device tables (:class:`EngineCaps`), the number of scan levels, and
the global stub space ``2E``.  To amortize one lowered program across many
request graphs, a graph is *padded* into the smallest geometric bucket
that fits it:

  · ``E`` rounds up to the next power of two (``e_cap``) by appending a
    dummy edge cycle anchored at one real vertex — degrees stay even, the
    graph stays connected, and the dummy section of the resulting circuit
    is contiguous, so stripping it back out leaves a valid Euler circuit
    of the original graph;
  · every table capacity from ``size_caps`` is quantized onto a *shared
    cap ladder* keyed off ``e_cap`` (:func:`ladder_caps`) — independent
    pow2 rounding per cap (:func:`round_caps`, the pre-ladder scheme)
    fragments same-scale pools whenever any one field straddles its own
    pow2 boundary;
  · the scan length ``n_levels`` rounds up to a power of two
    (:func:`ladder_levels`) — the extra supersteps past the real merge
    tree's height are no-ops (all tables are empty after the final real
    level), so heterogeneous tree heights share one program.

The bucket key is ``(e_cap, n_parts, n_levels, caps)``; any two graphs
sharing a key run through the *same* compiled program with zero retrace.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import numpy as np

from ..core.engine import EngineCaps
from ..core.graph import Graph


def ceil_pow2(x: int, lo: int = 1) -> int:
    """Smallest power of two ≥ max(x, lo).

    >>> [ceil_pow2(x) for x in (1, 3, 8, 9)]
    [1, 4, 8, 16]
    >>> ceil_pow2(3, lo=64)
    64
    """
    v = max(int(x), int(lo), 1)
    return 1 << (v - 1).bit_length()


def round_caps(caps: EngineCaps, lo: int = 16) -> EngineCaps:
    """Round every table capacity up to a power of two (geometric bucket).
    Round budgets and flags are kept verbatim; zero lane overrides stay
    zero (they already default to the rounded table width).

    >>> caps = EngineCaps(edge_cap=100, park_cap=3, ship_cap=17,
    ...                   new_cap=130, open_cap=48, touch_cap=96)
    >>> r = round_caps(caps)
    >>> r.edge_cap, r.park_cap, r.new_cap
    (128, 16, 256)
    >>> round_caps(r) == r                    # idempotent
    True
    """

    def r(v: int) -> int:
        return ceil_pow2(v, lo) if v else 0

    return dataclasses.replace(
        caps,
        edge_cap=r(caps.edge_cap),
        park_cap=r(caps.park_cap),
        ship_cap=r(caps.ship_cap),
        new_cap=r(caps.new_cap),
        open_cap=r(caps.open_cap),
        touch_cap=r(caps.touch_cap),
        open_ship_cap=r(caps.open_ship_cap),
        touch_ship_cap=r(caps.touch_ship_cap),
        mate_ship_cap=r(caps.mate_ship_cap),
        p3v_cap=r(caps.p3v_cap),
    )


# ---------------------------------------------------------------------------
# the shared cap-quantization ladder (DESIGN.md §9)
# ---------------------------------------------------------------------------

#: Ladder floor rungs as divisors of the bucket scale ``e_cap``: each cap
#: field is raised at least to ``e_cap // divisor`` (then pow2-rounded only
#: if it *exceeds* its floor — the rare outlier escape hatch).  Calibrated
#: on RMAT pools across scales 5–11: park/ship/open sit at 0.09–0.16·e_cap,
#: so quarter floors absorb the per-graph variance that fragments
#: independent pow2 rounding.  Touch floors at ``e_cap`` itself — its true
#: worst case (every stub can contribute a touch pair), observed at
#: 0.33–0.6·e_cap — so touch never escapes and never splits a bucket.
LADDER_DIVISORS = {
    "park_cap": 4,
    "ship_cap": 4,
    "open_cap": 4,
    "open_ship_cap": 4,
    "touch_cap": 1,
    "touch_ship_cap": 1,
    # sharded Phase 3 vertex-record shard (DESIGN.md §11): owned degree
    # sums average 2·e_cap/n per device but their *max* over owners swings
    # with partition luck (0.3–0.8·e_cap observed on scale-5 RMAT pools at
    # n=8), so like touch it floors at e_cap itself — the table is
    # 4 int32 lanes, so the full-scale floor costs ~16·e_cap bytes and
    # never splits a bucket
    "p3v_cap": 1,
}

#: Tight-profile divisors: the autotuner's feedback rung (DESIGN.md §12).
#: Buckets whose *measured* per-field needs sit comfortably under half the
#: default floors get re-keyed onto this profile — halved floors across the
#: board — cutting the padded table area roughly in half for pools whose
#: shapes cluster well below the calibrated worst case.  Correctness never
#: depends on the profile: a field exceeding its floor still pow2-escapes.
TIGHT_DIVISORS = {
    "park_cap": 8,
    "ship_cap": 8,
    "open_cap": 8,
    "open_ship_cap": 8,
    "touch_cap": 2,
    "touch_ship_cap": 2,
    "p3v_cap": 2,
}

#: Cap fields the ladder sizes (and the autotuner observes per solve).
LADDER_FIELDS = ("edge_cap", "park_cap", "ship_cap", "new_cap", "open_cap",
                 "touch_cap", "open_ship_cap", "touch_ship_cap", "p3v_cap")


def _edge_floor(e_cap: int, n_parts: int, slack: float) -> int:
    """Worst-case padded local-edge table width over a bucket, rounded up
    to an ``e_cap/8`` rung.  The dummy pad cycle lands entirely in the
    anchor's partition, so the heaviest partition holds up to
    ``e_cap/(2·n) + e_cap/2`` edges (pow2 bucketing keeps the pad under
    ``e_cap/2`` except in the ``min_bucket_edges`` floor regime, where the
    pad can approach ``e_cap`` — hence the clamp to ``e_cap``)."""
    rung = max(1, e_cap // 8)
    need = math.ceil((e_cap / (2 * n_parts) + e_cap / 2) * slack)
    return min(e_cap, rung * math.ceil(need / rung))


def ladder_floors(e_cap: int, n_parts: int, slack: float = 1.3,
                  lo: int = 16, tight: bool = False) -> dict:
    """Per-field cap floors for one bucket scale — the rungs
    :func:`ladder_caps` quantizes onto, exposed so the autotuner can test
    whether a bucket's *observed* needs fit the ``tight`` profile before
    re-keying it (DESIGN.md §12).  edge/new share the worst-case
    padded-partition rung (profile-independent); the divisor fields use
    :data:`LADDER_DIVISORS` or :data:`TIGHT_DIVISORS`.

    >>> f = ladder_floors(128, 8)
    >>> f["park_cap"], f["touch_cap"]
    (32, 128)
    >>> t = ladder_floors(128, 8, tight=True)
    >>> t["park_cap"], t["touch_cap"]
    (16, 64)
    """
    div = TIGHT_DIVISORS if tight else LADDER_DIVISORS
    ef = max(_edge_floor(e_cap, n_parts, slack), lo)
    floors = {"edge_cap": ef, "new_cap": ef}
    for f, d in div.items():
        floors[f] = max(e_cap // d, lo)
    return floors


def ladder_caps(caps: EngineCaps, e_cap: int, n_parts: int,
                slack: float = 1.3, lo: int = 16,
                tight: bool = False) -> EngineCaps:
    """Quantize every table capacity onto the bucket's shared cap ladder.

    Unlike :func:`round_caps` (independent pow2 per field), all fields are
    floored at fixed fractions of the *shared* bucket scale ``e_cap``:
    edge/new at the worst-case padded-partition rung, park/ship/open at
    ``e_cap/4``, touch at its ``e_cap`` worst case.  A field exceeding its
    floor (a shape outlier) still rounds up pow2, so correctness never
    depends on the profile — but same-scale pools collapse onto one cap
    tuple instead of fragmenting at every field's pow2 boundary.
    Padded-table waste is bounded by the floor profile itself: the
    quantized per-device area is at most ``max(profile_area, 2 × exact
    area)``, where ``profile_area ≈ 4.5 · e_cap`` longs against an exact
    area that is itself ``≥ 1.5 · e_cap`` for any padded bucket member
    (edge + new tables alone) — measured per solve by
    :func:`ladder_waste`.

    >>> from repro.core.engine import EngineCaps
    >>> a = EngineCaps(edge_cap=80, park_cap=15, ship_cap=13, new_cap=80,
    ...                open_cap=20, touch_cap=57, open_ship_cap=20,
    ...                touch_ship_cap=57)
    >>> b = EngineCaps(edge_cap=72, park_cap=20, ship_cap=20, new_cap=72,
    ...                open_cap=16, touch_cap=52, open_ship_cap=16,
    ...                touch_ship_cap=52)
    >>> ladder_caps(a, 128, 8) == ladder_caps(b, 128, 8)   # one bucket
    True
    >>> ladder_caps(a, 128, 8).park_cap                    # e_cap/4 floor
    32
    >>> ladder_caps(a, 128, 8, tight=True).park_cap        # tight: e_cap/8
    16
    >>> ladder_caps(a, 128, 8, tight=True).touch_cap       # tight: e_cap/2
    64
    """
    floors = ladder_floors(e_cap, n_parts, slack=slack, lo=lo, tight=tight)

    def q(v: int, floor: int) -> int:
        if not v:
            return 0
        return floor if v <= floor else ceil_pow2(v, lo)

    return dataclasses.replace(
        caps, **{f: q(getattr(caps, f), fl) for f, fl in floors.items()})


def ladder_rounds(caps: EngineCaps, e_cap: int) -> EngineCaps:
    """Schedule-derived straggler budgets for the splice loops (ROADMAP:
    "batch stragglers under vmap").

    Phase 1's splice and Phase 3's pivot splice are ``while_loop``s that
    run a vmapped batch to its *slowest* member; their round budgets
    bound that tail.  Phase 1 splices its cycles along a spanning forest
    first: Borůvka rounds at least halve the trees, so ``ceil(log2 PC)``
    rounds always suffice for a pair table of PC rows, and Phase 1
    derives that budget from the stub pool itself
    (:func:`repro.core.phase1.forest_round_budget`; whatever the degrees
    or the edge order).  Its vote rounds then merge only open paths (at
    P > 1 merge levels; at P=1 there is none), where a path meets a
    cycle: ``splice_rounds`` is their budget, 10-16 from the pool as
    before.  Phase 3's budget derives from the bucket's stub space
    ``2·e_cap`` (doubled, plus slack, because only the globally-min pivot
    is *guaranteed* to fire each round).  Computed from bucket-level
    quantities only, so same-bucket graphs share one budget and the key
    never re-fragments.

    >>> from repro.core.engine import EngineCaps
    >>> from repro.core.phase1 import forest_round_budget
    >>> c = EngineCaps(edge_cap=96, park_cap=32, ship_cap=32, new_cap=96,
    ...                open_cap=32, touch_cap=64)
    >>> r = ladder_rounds(c, 128)
    >>> r.splice_rounds, r.phase3_rounds
    (11, 24)
    >>> forest_round_budget(2 * c.new_cap + c.open_cap, c.touch_cap)
    9
    """
    pool = 2 * caps.new_cap + caps.open_cap + caps.touch_cap
    splice = min(16, max(10, math.ceil(math.log2(max(2, pool))) + 2))
    p3 = min(64, max(24, 2 * math.ceil(math.log2(max(2, 2 * e_cap))) + 8))
    return dataclasses.replace(caps, splice_rounds=splice, phase3_rounds=p3)


def ladder_levels(n_levels: int) -> int:
    """Quantize the scan length onto the pow2 ladder.

    Merge-tree heights vary per graph even at one scale (BFS partition
    luck), and ``n_levels`` is part of the compiled shape — without this,
    same-scale pools split across 3–4 level classes.  Supersteps past the
    real height are no-ops (after the final real level every table is
    empty: all stubs are paired at the root, ``la ≤ height`` retains no
    touch pairs, no parked edge has a later activation), so padding up is
    byte-transparent; it costs at most 2× scan compute in exchange for
    collapsing the level classes.

    >>> [ladder_levels(x) for x in (1, 4, 5, 7, 9)]
    [1, 4, 8, 8, 16]
    """
    return ceil_pow2(n_levels)


def ladder_waste(exact: EngineCaps, quantized: EngineCaps) -> float:
    """Padded-compute waste of the quantized caps: quantized / exact
    per-device table area (longs), over the sizing fields.  1.0 = no
    waste; the ladder's floor profile bounds this at ~2.3× for any
    padded bucket member (DESIGN.md §9).

    >>> from repro.core.engine import EngineCaps
    >>> c = EngineCaps(edge_cap=100, park_cap=10, ship_cap=10, new_cap=100,
    ...                open_cap=10, touch_cap=50)
    >>> ladder_waste(c, c)
    1.0
    """
    fields = ("edge_cap", "park_cap", "ship_cap", "new_cap", "open_cap",
              "touch_cap", "open_ship_cap", "touch_ship_cap", "p3v_cap")
    num = sum(getattr(quantized, f) for f in fields)
    den = max(1, sum(getattr(exact, f) for f in fields))
    return num / den


def pad_graph(graph: Graph, part_of_vertex: np.ndarray,
              e_cap: int) -> Tuple[Graph, np.ndarray]:
    """Pad ``graph`` to exactly ``e_cap`` edges with a dummy cycle.

    The ``k = e_cap - E`` dummy edges form a closed cycle through ``k-1``
    fresh vertices anchored at one real vertex (a self-loop when k == 1),
    all assigned to the anchor's partition — so no cut edges are added and
    the merge tree is untouched.  Returns the padded graph and the padded
    partition assignment.

    >>> import numpy as np
    >>> from repro.core.graph import Graph
    >>> tri = Graph(3, np.array([0, 1, 2]), np.array([1, 2, 0]))
    >>> g2, part2 = pad_graph(tri, np.zeros(3, dtype=np.int64), 8)
    >>> g2.num_edges, g2.is_eulerian(), len(part2)
    (8, True, 7)
    """
    E = graph.num_edges
    k = int(e_cap) - E
    if k < 0:
        raise ValueError(f"e_cap {e_cap} smaller than the graph's {E} edges")
    if k == 0:
        return graph, part_of_vertex
    if E == 0:
        raise ValueError("cannot pad an empty graph")
    anchor = int(graph.edge_u[0])
    V = graph.num_vertices
    if k == 1:
        eu = np.array([anchor], dtype=np.int64)
        ev = np.array([anchor], dtype=np.int64)
        n_new = 0
    else:
        dummies = V + np.arange(k - 1, dtype=np.int64)
        walk = np.concatenate([[anchor], dummies, [anchor]])
        eu, ev = walk[:-1], walk[1:]
        n_new = k - 1
    g2 = Graph(
        num_vertices=V + n_new,
        edge_u=np.concatenate([graph.edge_u, eu]).astype(np.int64),
        edge_v=np.concatenate([graph.edge_v, ev]).astype(np.int64),
    )
    part2 = np.concatenate([
        np.asarray(part_of_vertex, dtype=np.int64),
        np.full(n_new, int(part_of_vertex[anchor]), dtype=np.int64),
    ])
    return g2, part2


def modal_bucket_pool(solver, graphs, n: int) -> list:
    """The ≤ ``n`` graphs sharing the most common shape bucket.

    Batched solving (DESIGN.md §8) needs same-bucket graphs; this groups
    candidates by ``solver.bucket_of`` — skipping graphs too small or
    sparse for the solver's partition count — and returns the modal
    bucket's members in input order (may hold fewer than ``n``; empty if
    no candidate partitions cleanly).  Shared by the serving driver's
    ``--same-bucket`` pool and the batched benchmark series.
    """
    buckets: dict = {}
    for g in graphs:
        try:
            buckets.setdefault(solver.bucket_of(g), []).append(g)
        except ValueError:
            continue  # partitioner can't fill n_parts for this graph
    if not buckets:
        return []
    return max(buckets.values(), key=len)[:n]


def strip_circuit(circuit: np.ndarray, num_edges: int) -> np.ndarray:
    """Drop the dummy-edge arrivals from a padded-graph circuit.

    The dummy cycle touches the real graph at a single anchor vertex and
    its interior vertices have degree 2, so its traversal is one
    contiguous closed sub-walk through the anchor — removing those
    arrivals leaves a valid Euler circuit of the original graph.

    >>> import numpy as np
    >>> strip_circuit(np.array([0, 2, 4, 7, 9, 5]), 3)  # edges ≥ 3 dummy
    array([0, 2, 4, 5])
    """
    c = np.asarray(circuit, dtype=np.int64)
    return c[(c >> 1) < num_edges]
