"""Audit the fused Euler programs' jaxprs against the engine's schedule.

The engine publishes its collective schedule statically
(:func:`repro.core.engine.fused_collective_budget`): per scan level, one
``all_to_all`` per shipped field per table group; after the scan, either
exactly one ``all_gather`` for the replicated device Phase 3, or — under
``sharded_phase3`` (DESIGN.md §11) — the ring schedule of
:func:`repro.core.phase3.sharded_phase3_schedule` (9 ``ppermute``
eqns, two of them in R-round doubling loops, 3 ``psum``, and at most one emission ``all_gather``, elided when
``gather_circuit=False``); nothing else.  This module traces each
``(bucket, batch-width)`` program the solver would cache, walks the
closed jaxpr, and fails if the compiled program communicates — or syncs
with the host — anywhere the schedule says it must not:

  * collective census == budget, with every ``all_to_all`` inside exactly
    ONE ``lax.scan`` whose static length equals the bucket's ``n_levels``
    (the sharded rings lower to ppermute-only scans and gather nothing);
  * zero host callbacks / infeed / outfeed in the fused body (a stray
    ``debug_print`` or ``pure_callback`` re-introduces per-level host
    syncs and silently serializes the BSP pipeline);
  * the one-shot program donates its state buffers
    (``jax.buffer_donor`` present in the lowering) and the cached /
    batched programs do NOT (their uploaded state must survive reuse).

Byte/FLOP costs are *measured from the jaxpr* (operand avals of the
collective eqns), with the caps-derived closed-form alongside, so the
report shows both what the schedule promises and what the trace contains.

Entry points: :func:`audit_program` (one traced program),
:func:`audit_graph` (every width of a graph's bucket — what
``EulerSolver.prewarm`` would compile), and the CLI wrapper
``python -m repro.analysis.audit``.
"""
from __future__ import annotations

import dataclasses
import math
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

COLLECTIVES = ("all_to_all", "all_gather", "psum", "ppermute")

#: Primitives that synchronize with, or call back into, the host.  None
#: may appear in a fused program: each one would stall the device
#: pipeline once per occurrence (per *level* if inside the scan).
HOST_SYNC_PRIMS = frozenset({
    "pure_callback", "io_callback", "callback", "debug_callback",
    "debug_print", "infeed", "outfeed", "host_local_array_to_global_array",
    "global_array_to_host_local_array",
})

DONOR_MARK = "jax.buffer_donor"


def _sub_jaxprs(eqn) -> List[Any]:
    """Nested jaxprs of one eqn (scan/while/cond bodies, pjit calls...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    out: List[Any] = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if isinstance(x, ClosedJaxpr):
                out.append(x.jaxpr)
            elif isinstance(x, Jaxpr):
                out.append(x)
    return out


def _iter_eqns(jaxpr):
    """All eqns of a (closed) jaxpr, recursively, in traversal order."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            stack.extend(_sub_jaxprs(eqn))


def census(jaxpr) -> Dict[str, int]:
    """Primitive-name → eqn count over the whole (nested) jaxpr."""
    return dict(Counter(e.primitive.name for e in _iter_eqns(jaxpr)))


def _scan_bodies(jaxpr) -> List[Tuple[int, Dict[str, int]]]:
    """(static length, body census) of every scan eqn in the jaxpr."""
    out = []
    for eqn in _iter_eqns(getattr(jaxpr, "jaxpr", jaxpr)):
        if eqn.primitive.name == "scan":
            body = eqn.params["jaxpr"]
            out.append((int(eqn.params["length"]),
                        dict(Counter(e.primitive.name
                                     for e in _iter_eqns(body)))))
    return out


def _aval_bytes(avals) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in avals if hasattr(a, "shape"))


def _collective_bytes(jaxpr) -> Dict[str, int]:
    """Measured operand bytes of each collective, one traversal of the
    (per-shard) jaxpr.  Eqns inside a scan body are counted once — the
    per-run total multiplies by the scan length downstream."""
    out: Dict[str, int] = {c: 0 for c in COLLECTIVES}
    for eqn in _iter_eqns(getattr(jaxpr, "jaxpr", jaxpr)):
        if eqn.primitive.name in out:
            out[eqn.primitive.name] += _aval_bytes(
                v.aval for v in eqn.invars)
    return out


# ----------------------------------------------------------------------
# static Phase 3 cost model (mirrors repro.core.phase3 without running it)
# ----------------------------------------------------------------------
def _doubling_rounds(n: int) -> int:
    """Pointer-doubling rounds Phase 3 runs over an n-entry stub space."""
    return int(math.ceil(math.log2(max(2, n)))) + 1


def phase3_cost_model(e_cap: int, batch: Optional[int],
                      n_parts: Optional[int] = None,
                      sharded: bool = False,
                      p3v_cap: int = 0) -> Dict[str, Any]:
    """Static Phase 3 table arithmetic of one fused run: the per-device
    jump-table width, each doubling loop's round count, table bytes and
    gathered elements, and the persistent working set.  The CC loop
    gathers 2 tables per round, list-rank 3.

    With ``sharded=True`` (needs ``n_parts``) the model follows the
    sharded Phase 3 (DESIGN.md §11): tables are the per-device shard
    (width ``S = shard_width(e_cap, n_parts)``), the round count covers
    the full ``n_parts*S`` stub space, and ``phase3_state_bytes`` is the
    per-device persistent working set — the O(2E/n) quantity the memory
    regression test pins (vs the replicated model's O(2E))."""
    b = int(batch or 1)
    n_stubs = 2 * e_cap
    if sharded:
        if not n_parts:
            raise ValueError("sharded cost model needs n_parts")
        from ..core.phase3 import shard_width

        width = shard_width(e_cap, n_parts)
        rounds = _doubling_rounds(n_parts * width)
    else:
        width = n_stubs
        rounds = _doubling_rounds(n_stubs)

    loops = {}
    for name, n_tables in (("cc", 2), ("rank", 3)):
        loops[name] = {
            "n_tables": n_tables,
            "rounds": rounds,
            "table_bytes": int(width * n_tables * 4 * b),
            "gather_elems": int(rounds * width * n_tables * b),
        }
    # per-device persistent Phase 3 working set, int32 throughout: the
    # six live arrays of CC + rank (mate, nxt/ptr, lab/dist, reach and
    # the two ring answer buffers), plus — sharded only — the splice
    # vertex-record table [4, p3v_cap+1] at each vertex owner
    state_bytes = 6 * width * 4 * b
    if sharded:
        state_bytes += 4 * (int(p3v_cap) + 1) * 4 * b
    return {
        "n_stubs": n_stubs,
        "sharded": bool(sharded),
        "n_parts": int(n_parts) if n_parts else None,
        "phase3_table_width": int(width),
        "phase3_state_bytes": int(state_bytes),
        "loops": loops,
    }


# ----------------------------------------------------------------------
# static per-program byte cost (the solver's program_cache_bytes unit)
# ----------------------------------------------------------------------

#: int32 lanes per EngineState table group (see ``EngineState``: parked
#: edges pk_* [7 + mask], open paths op_* [5 + mask], touch pairs tc_*
#: [6 + mask], level-0 local edges le_* [5 + mask]); each group also
#: carries one bool mask lane.
ENGINE_STATE_LANES = {
    "park_cap": 7,
    "open_cap": 5,
    "touch_cap": 6,
    "edge_cap": 5,
}


def engine_state_bytes(caps) -> int:
    """Per-device ``EngineState`` bytes for one bucket's caps: the int32
    table lanes plus one bool mask lane per table group.

    >>> from repro.core.engine import EngineCaps
    >>> engine_state_bytes(EngineCaps(edge_cap=0, park_cap=1, ship_cap=0,
    ...     new_cap=0, open_cap=0, touch_cap=0))      # 7 int32 + 1 bool
    29
    """
    total = 0
    for field, lanes in ENGINE_STATE_LANES.items():
        width = int(getattr(caps, field))
        total += (4 * lanes + 1) * width
    return total


def program_cost_bytes(key, batch: Optional[int] = None,
                       sharded: bool = False) -> int:
    """Modeled whole-mesh device footprint of one cached ``(bucket, B)``
    program — the byte unit of ``EulerSolver(program_cache_bytes=...)``
    and of the audit's cache-budget report: per-device BSP state tables
    times the batch width, plus the Phase 3 persistent working set, times
    ``n_parts`` devices.  ``key`` is a solver bucket key
    ``(e_cap, n_parts, n_levels, caps)``.
    """
    e_cap, n_parts, _n_levels, caps = key[0], key[1], key[2], key[3]
    b = int(batch or 1)
    cost = phase3_cost_model(
        int(e_cap), b, n_parts=int(n_parts), sharded=bool(sharded),
        p3v_cap=(getattr(caps, "p3v_cap", 0) or int(e_cap)))
    per_device = engine_state_bytes(caps) * b + cost["phase3_state_bytes"]
    return int(per_device) * int(n_parts)


# ----------------------------------------------------------------------
# per-program audit
# ----------------------------------------------------------------------
@dataclasses.dataclass
class ProgramAudit:
    """Audit verdict for one traced ``(bucket, width)`` fused program."""

    e_cap: int
    n_levels: int
    n_parts: int
    batch: Optional[int]
    census: Dict[str, int]
    budget: Dict[str, int]
    scans: List[Tuple[int, Dict[str, int]]]
    cost: Dict[str, Any]
    violations: List[str]
    donated_marker: Optional[bool] = None   # one-shot lowering donates
    resident_marker: Optional[bool] = None  # cached lowering must NOT

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["ok"] = self.ok
        return d


def _example_args(eng, pg, batch: Optional[int]):
    """Host-side example inputs shaped exactly like the serving path's
    (state [n,·] / anc [H,n] / sv [2E], batched: state [n,B,·],
    anc [B,H,n], sv [B,2E])."""
    import jax

    state, anc = eng.load(pg, device=False)
    # _pad_sv widens [2E] to [n*S] for the sharded Phase 3 (identity when
    # replicated) — exactly what the solver's upload sites do
    sv = eng._pad_sv(eng._stub_vertex(pg))
    if batch is None:
        return anc, state, sv
    b = int(batch)
    state_b = jax.tree.map(lambda x: np.stack([x] * b, axis=1), state)
    return np.stack([anc] * b), state_b, np.stack([sv] * b)


def audit_program(eng, pg, e_cap: int, batch: Optional[int] = None,
                  check_donation: bool = False) -> ProgramAudit:
    """Trace one fused program and audit it against the static schedule.

    ``eng`` must be a bare :class:`DistributedEngine` for the bucket (its
    trace probes fire during ``make_jaxpr``, so pass one without solver
    accounting hooks).  ``check_donation`` additionally lowers the
    donated one-shot variant (single-width only) and checks the
    ``jax.buffer_donor`` markers both ways.
    """
    import jax

    from ..core.engine import fused_collective_budget
    from ..core.phase1 import forest_round_budget

    sharded = bool(getattr(eng, "sharded_phase3", False))
    if sharded:
        budget = fused_collective_budget(
            eng.n_levels, num_edges=e_cap, n_parts=eng.n,
            sharded_phase3=True, gather_circuit=eng.gather_circuit)
    else:
        # keep the bare positional call for replicated engines — the
        # published-schedule contract (and its live gate) is keyed on it
        budget = fused_collective_budget(eng.n_levels)
    args = _example_args(eng, pg, batch)
    fn = eng.make_fused(e_cap, batch=batch)
    closed = jax.make_jaxpr(fn)(*args)

    cen = census(closed)
    scans = _scan_bodies(closed)
    cost = phase3_cost_model(e_cap, batch, n_parts=eng.n, sharded=sharded,
                             p3v_cap=(eng.caps.p3v_cap or e_cap))
    v: List[str] = []

    def want(prim: str, n: int) -> None:
        got = cen.get(prim, 0)
        if got != n:
            v.append(f"{prim}: traced {got} eqn(s), schedule budgets {n}")

    for prim in COLLECTIVES:
        want(prim, budget.get(prim, 0))

    # every all_to_all must sit inside exactly one scan of length
    # n_levels.  Filter on all_to_all specifically: the sharded Phase 3's
    # ring fori_loops also lower to scans, but they may carry only
    # ppermute (DESIGN.md §11) — never a ship or a gather.
    level_scans = [(ln, body) for ln, body in scans
                   if body.get("all_to_all", 0)]
    if len(level_scans) != 1:
        v.append(f"expected exactly 1 all_to_all-bearing scan (the level "
                 f"scan), found {len(level_scans)}")
    else:
        length, body = level_scans[0]
        if length != eng.n_levels:
            v.append(f"level scan length {length} != bucket n_levels "
                     f"{eng.n_levels}")
        if body.get("all_to_all", 0) != budget["all_to_all"]:
            v.append(f"level-scan body has {body.get('all_to_all', 0)} "
                     f"all_to_all, budget {budget['all_to_all']}")
    if any(body.get("all_gather", 0) for _, body in scans):
        v.append("all_gather inside a scan body (emission gathers at most "
                 "once, after the level scan)")

    host_hits = sorted(p for p in cen if p in HOST_SYNC_PRIMS
                       or "callback" in p)
    if host_hits:
        v.append(f"host-sync primitives in fused body: {host_hits}")

    # measured bytes moved (per shard, per scan iteration for scanned
    # collectives) + caps-derived closed form for the report
    measured = _collective_bytes(closed)
    b = int(batch or 1)
    caps, n = eng.caps, eng.n
    lanes = {
        "park": (8, caps.ship_cap),
        "open": (6, caps.open_ship_cap or caps.open_cap),
        "touch": (7, caps.touch_ship_cap or caps.touch_cap),
        "mate": (3, caps.mate_ship_cap or 2 * caps.pair_cap()),
    }
    modeled = {g: fields * n * lane * 4 * b
               for g, (fields, lane) in lanes.items()}
    cost["bytes"] = {
        "measured_per_shard": measured,
        "a2a_per_level_modeled": modeled,
        "a2a_run_total_modeled": sum(modeled.values()) * eng.n_levels * n,
    }
    # the budgets bounding the straggler while-loops of the traced body:
    # Phase 1's splice forest (derived from the stub pool) and vote
    # rotations, Phase 3's pivot splice (ladder_rounds)
    cost["round_budgets"] = {
        "forest_rounds": forest_round_budget(
            2 * caps.new_cap + caps.open_cap, caps.touch_cap),
        "splice_rounds": caps.splice_rounds,
        "phase3_rounds": caps.phase3_rounds,
        "while_eqns_traced": cen.get("while", 0),
    }

    donated = resident = None
    if check_donation and batch is None:
        resident = DONOR_MARK in fn.lower(*args).as_text()
        if resident:
            v.append("cached program lowers with donated buffers — reused "
                     "uploads would be invalidated")
        one_shot = eng.make_fused(e_cap, donate=True)
        donated = DONOR_MARK in one_shot.lower(*args).as_text()
        if not donated:
            v.append("one-shot program lowers without buffer donation "
                     "(donate_argnums not applied)")

    return ProgramAudit(
        e_cap=e_cap, n_levels=eng.n_levels, n_parts=eng.n, batch=batch,
        census=cen, budget=budget, scans=scans, cost=cost, violations=v,
        donated_marker=donated, resident_marker=resident,
    )


# ----------------------------------------------------------------------
# whole-bucket audit (what prewarm would compile)
# ----------------------------------------------------------------------
def audit_graph(solver, graph, widths=None,
                check_donation: bool = True) -> Dict[str, Any]:
    """Audit every ``(bucket, width)`` program of ``graph``'s bucket.

    ``widths`` defaults to the solver's ``width_ladder`` — the same set
    :meth:`EulerSolver.prewarm` compiles.  Pass the string ``"warmed"``
    to audit the *adaptive* program set instead: exactly the widths the
    autotuner's compile service has landed so far
    (``solver.warmed_widths``; falls back to width 1 when the bucket has
    no live programs yet).  Builds a bare engine for the bucket (same
    caps/levels/flags as the solver's, minus the accounting probes) so
    auditing never perturbs ``cache_stats``.

    The report's ``cache_budget`` section prices each audited program
    with :func:`program_cost_bytes` and totals them against the solver's
    ``program_cache_bytes`` budget (``within_budget`` is None when no
    budget is set).
    """
    import jax

    from .. import obs
    from ..core.engine import DistributedEngine

    pg, tree, key = solver._prepare(graph, None)
    e_cap, n_parts, n_levels, caps = key
    sharded = bool(getattr(solver, "sharded_phase3", False))
    eng = DistributedEngine(
        solver.mesh, tuple(solver.mesh.axis_names), caps, n_levels,
        remote_dedup=solver.remote_dedup,
        deferred_transfer=solver.deferred_transfer,
        sharded_phase3=sharded,
        gather_circuit=getattr(solver, "gather_circuit", True),
        trace=obs.NullTraceLog(),   # audits must not perturb the session
    )
    if widths is None:
        widths = solver.width_ladder
    elif isinstance(widths, str):
        if widths != "warmed":
            raise ValueError(f"widths must be a sequence or 'warmed': "
                             f"{widths!r}")
        widths = solver.warmed_widths(key) or [1]
    programs = []
    per_program_bytes: Dict[str, int] = {}
    total_bytes = 0
    for w in sorted({int(w) for w in widths}):
        batch = None if w == 1 else w
        p = audit_program(
            eng, pg, e_cap, batch=batch,
            check_donation=check_donation and batch is None)
        cost = program_cost_bytes(key, batch, sharded=sharded)
        p.cost["program_bytes"] = cost
        per_program_bytes[f"B{w}"] = cost
        total_bytes += cost
        programs.append(p)
    budget = getattr(solver, "program_cache_bytes", None)
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "bucket": {
            "e_cap": e_cap, "n_parts": n_parts, "n_levels": n_levels,
            "caps": dataclasses.asdict(caps),
            "tree_height": tree.height,
            "sharded_phase3": bool(getattr(solver, "sharded_phase3",
                                           False)),
            "gather_circuit": bool(getattr(solver, "gather_circuit",
                                           True)),
        },
        "programs": [p.to_dict() for p in programs],
        "cache_budget": {
            "per_program_bytes": per_program_bytes,
            "total_bytes": total_bytes,
            "budget_bytes": budget,
            "program_cache_max": getattr(solver, "program_cache_max", None),
            "within_budget": (None if budget is None
                              else total_bytes <= budget),
        },
        "ok": all(p.ok for p in programs),
        # point-in-time cut of the solver's metrics registry (per-session
        # labels separate this solver from others sharing the registry)
        "metrics": (solver.registry.snapshot()
                    if getattr(solver, "registry", None) is not None
                    else {}),
    }
