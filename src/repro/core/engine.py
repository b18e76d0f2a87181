"""Distributed BSP engine: partitions ↔ devices, supersteps ↔ jitted
collective programs.

The paper's Phase-2 execution maps 1:1 onto a TPU pod:

  · each mesh device hosts one partition (512 partitions on the 2×16×16
    production mesh, flattened over ("pod","data","model"));
  · one *superstep* = one shard_map program body: ship pathMap entries
    (activated remote edges, open path endpoints, boundary touch pairs) via
    a single fused ``all_to_all``, then run the vectorized Phase 1 locally;
  · the merge tree is host-side static data (paper builds it offline too),
    baked into an ``anc_table[level, part0] → active partition`` array so
    *one* compiled program serves every level;
  · §5's heuristics are structural here, not just accounting:
    ``deferred_transfer`` keeps parked remote edges on their leaf device
    until their activation level (bounding the static table capacities),
    and ``remote_dedup`` parks each cut edge on exactly one side.  Both
    default ON in the distributed engine; the host engine measures the
    paper's baseline without them.

Execution modes (DESIGN.md §4):

  **fused** (default) — the whole run is ONE compiled device program plus
  one host sync: a ``jax.lax.scan`` over levels inside a single shard_map
  drives every superstep, each level's mate log is scattered on-device
  into a stub-sharded ``mate[2E]`` accumulator (later-level writes win,
  matching the paper's disk-replay order), and Phase 3 (pivot splice +
  list-rank emission) finishes on-device via ``phase3_device``.  Logs
  never leave the devices; the circuit/flags/metrics are fetched once.

  **eager** (``fused=False``) — the original per-level Python loop, one
  jitted superstep per level with the mate logs synced to host and
  replayed there.  It is the debugging/metrics oracle: byte-identical
  circuits to the fused path (both finish with the same ``phase3_device``
  program), with per-level host visibility.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from functools import partial
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.compat import shard_map
from . import bounded
from .graph import PartitionedGraph
from .phase1 import (
    BIG,
    I32,
    NewEdges,
    OpenTable,
    Phase1Caps,
    TouchTable,
    pair_table_cap,
    phase1_local,
)
from .phase2 import MergeTree, generate_merge_tree
from .phase3 import (emit_circuit_np, phase3_device, phase3_sharded,
                     shard_width, sharded_phase3_schedule)


@dataclasses.dataclass(frozen=True)
class EngineCaps:
    """Static capacities of the per-device tables (see loader sizing)."""

    edge_cap: int        # level-0 local edges per partition
    park_cap: int        # parked remote edges per device
    ship_cap: int        # per (src,dst) all_to_all lane width, edges
    new_cap: int         # activated edges entering one Phase 1
    open_cap: int
    touch_cap: int
    open_ship_cap: int = 0    # per (src,dst) lane for opens (0 → open_cap)
    touch_ship_cap: int = 0   # per (src,dst) lane for touch (0 → touch_cap)
    mate_ship_cap: int = 0    # per (src,dst) lane for mate writes on the
                              # fused path (0 → 2·pair-table width, safe)
    hook_rounds: int = 0
    splice_rounds: int = 12   # Phase 1 splice vote rounds (open paths); the
                              # splice forest's budget derives from the pool
    phase3_rounds: int = 64   # pivot-splice round budget of device Phase 3
    static_splice: bool = False
    p3v_cap: int = 0          # sharded Phase 3 per-device vertex-record
                              # table width (0 → e_cap, the safe bound)

    def phase1(self, stub_space: int = 0) -> Phase1Caps:
        """Phase 1's caps; ``stub_space`` (2·num_edges, known where the
        program is built for one edge count) bounds every id it sees."""
        return Phase1Caps(
            open_cap=self.open_cap,
            touch_cap=self.touch_cap,
            hook_rounds=self.hook_rounds,
            splice_rounds=self.splice_rounds,
            static_splice=self.static_splice,
            stub_space=stub_space,
        )

    def pair_cap(self) -> int:
        """Width of Phase 1's compacted pair table (its mate-log width)."""
        return pair_table_cap(2 * self.new_cap + self.open_cap,
                              self.touch_cap)


class EngineState(NamedTuple):
    """Sharded BSP state; leading axis = partition (= device)."""

    # parked remote edges (on the leaf device that owns them)
    pk_eid: jnp.ndarray   # [n, PK]
    pk_u: jnp.ndarray
    pk_v: jnp.ndarray
    pk_lau: jnp.ndarray
    pk_lav: jnp.ndarray
    pk_act: jnp.ndarray   # activation level
    pk_own0: jnp.ndarray  # level-0 partition of endpoint u (dest key)
    pk_mask: jnp.ndarray
    # open path endpoints
    op_stub: jnp.ndarray  # [n, OC]
    op_vert: jnp.ndarray
    op_la: jnp.ndarray
    op_comp: jnp.ndarray
    op_own0: jnp.ndarray
    op_mask: jnp.ndarray
    # boundary touch pairs
    tc_s1: jnp.ndarray    # [n, TC]
    tc_s2: jnp.ndarray
    tc_vert: jnp.ndarray
    tc_la: jnp.ndarray
    tc_comp: jnp.ndarray
    tc_own0: jnp.ndarray
    tc_mask: jnp.ndarray
    # level-0 local edges (consumed at superstep 0)
    le_eid: jnp.ndarray   # [n, EC]
    le_u: jnp.ndarray
    le_v: jnp.ndarray
    le_lau: jnp.ndarray
    le_lav: jnp.ndarray
    le_mask: jnp.ndarray


class StepOut(NamedTuple):
    state: EngineState
    log_s1: jnp.ndarray    # [n, PC] mate log for this level
    log_s2: jnp.ndarray
    log_mask: jnp.ndarray
    flags: jnp.ndarray     # [n, 4] cc, splice, p1-overflow, ship-overflow
    metrics: jnp.ndarray   # [n, 7] longs: remote, opens, touch, comps;
                           #        Phase 1 hook, splice vote and splice
                           #        forest rounds run


class FusedOut(NamedTuple):
    """Everything the fused program returns — fetched in ONE host sync.

    Under ``gather_circuit=False`` (sharded Phase 3 without the final
    ``all_gather``) the program never materializes a replicated circuit:
    ``circuit`` instead carries the sharded post-rank ``(mate, dist,
    reach)`` triple ``[n·S, 3]`` and ``mate`` its ``[n·S]`` first column,
    both assembled host-side by :meth:`PendingRun.wait` (which emits the
    circuit with the same ordering the device path uses).
    """

    circuit: jnp.ndarray   # [E] arrival stubs in walk order (replicated)
    mate: jnp.ndarray      # [2E] post-splice mate permutation (replicated)
    flags: jnp.ndarray     # [n, L, 4]
    metrics: jnp.ndarray   # [n, L, 7] (``StepOut.metrics`` per level)
    phase3_ok: jnp.ndarray  # [] bool: pivot splice converged
    phase3_rounds: jnp.ndarray  # [] int32: pivot-splice rounds run


class PendingRun:
    """An in-flight fused run: the device program has been dispatched
    asynchronously, nothing has been fetched yet.

    ``ready()`` is a non-blocking completion probe (the circuit buffer is
    only materialized once the whole program finishes); ``sync()``
    performs the run's ONE device→host sync (the ``wait`` span) and
    ``wait()`` builds the per-graph results from what it fetched.  The
    serving pipeline holds these to overlap host-side prep of the next
    flush with device execution of the current one (DESIGN.md §9);
    ``_run``/``_run_batch`` are dispatch→wait with no overlap.
    """

    def __init__(self, engine: "DistributedEngine", out: FusedOut,
                 pgs: List[PartitionedGraph], trees, t0: float,
                 batch: Optional[int]):
        self.engine = engine
        self.out: Optional[FusedOut] = out
        self.pgs = pgs
        self.trees = trees
        self.t0 = t0
        self.batch = batch              # None → single-graph program
        self._host = None               # the fetched outputs (sync())
        self._run_s = 0.0
        self._results = None

    def ready(self) -> bool:
        if self._results is not None or self._host is not None:
            return True
        probe = getattr(self.out.circuit, "is_ready", None)
        return bool(probe()) if probe is not None else True

    def sync(self) -> None:
        """Block until the device run completes and fetch every output
        with ONE ``device_get`` (the run's single device→host sync)."""
        if self._host is not None or self._results is not None:
            return
        with self.engine.trace.span("wait", width=self.batch or 1):
            self._host = jax.device_get(tuple(self.out))
        self.out = None                 # free the device buffers
        self._run_s = time.perf_counter() - self.t0

    def wait(self):
        """Block until the device run completes; returns one
        :class:`repro.euler.result.EulerResult` per graph, built from
        :meth:`sync`'s one fetch."""
        if self._results is not None:
            return self._results
        from ..euler.result import EulerResult

        self.sync()
        circuit, mate, flags, metrics, ok3, rounds3 = self._host
        self._host = None
        run_s = self._run_s
        if self.batch is None:          # unify to batched layouts
            circuit, mate, ok3 = circuit[None], mate[None], ok3[None]
            rounds3 = rounds3[None]
            flags, metrics = flags[:, None], metrics[:, None]
        if self.engine.sharded_phase3 and not self.engine.gather_circuit:
            # gather_circuit=False: the program returned the rank triple
            # still sharded ([B, n·S, 3]); emit host-side with the exact
            # ordering the on-device emit_circuit uses (stable argsort on
            # int32 keys), so circuits stay byte-identical (DESIGN.md §11)
            n_stubs = 2 * self.pgs[0].graph.num_edges
            packed = circuit[:, :n_stubs]
            mate = mate[:, :n_stubs]
            circuit = np.stack([
                emit_circuit_np(mate[b] >= 0, packed[b, :, 1],
                                packed[b, :, 2])
                for b in range(mate.shape[0])
            ])
        # circuit [B, E], mate [B, 2E], flags [n, B, L, 4],
        # metrics [n, B, L, 7], ok3 / rounds3 [B]
        if not flags.all():
            raise RuntimeError(
                f"convergence/capacity flags failed: {flags.all((0, 2, 3))}"
            )
        if not ok3.all():
            raise RuntimeError("Phase 3 pivot splice failed to converge")
        if not (mate >= 0).all():
            raise RuntimeError(f"{(mate < 0).sum()} stubs unmated")
        circuit = circuit.astype(np.int64)
        if not (circuit >= 0).all():
            raise RuntimeError("circuit emission left gaps")
        n_levels = self.engine.n_levels
        results = []
        for b, pg in enumerate(self.pgs):
            metrics_list = [metrics[:, b, lvl] for lvl in range(n_levels)]
            timings = {"run_s": run_s}
            if self.batch is not None:
                timings["batch"] = float(self.batch)
            results.append(EulerResult(
                circuit=circuit[b], mate=mate[b].astype(np.int64),
                tree=self.trees[b],
                levels=EulerResult.levels_from_metrics(metrics_list),
                supersteps=n_levels, backend="device", fused=True,
                graph=pg.graph, phase3_converged=bool(ok3[b]),
                phase3_rounds=int(rounds3[b]), timings=timings,
            ))
        self._results = results
        return results


#: Field counts behind the fused program's collective schedule: each table
#: group ships every field (plus its lane mask) through its own
#: ``all_to_all`` per superstep, and the mate route adds (s, v, mask).
#: Derived from ``EngineState`` so the budget tracks the state layout.
_SHIP_GROUPS = {
    "park": sum(f.startswith("pk_") for f in EngineState._fields),   # 8
    "open": sum(f.startswith("op_") for f in EngineState._fields),   # 6
    "touch": sum(f.startswith("tc_") for f in EngineState._fields),  # 7
    "mate": 3,                                                       # s, v, m
}


def fused_collective_budget(n_levels: int, num_edges: Optional[int] = None,
                            n_parts: Optional[int] = None,
                            sharded_phase3: bool = False,
                            gather_circuit: bool = True) -> dict:
    """The fused program's static collective schedule (DESIGN.md §4/§10/§11).

    Per level-scan body: one ``all_to_all`` per shipped field per table
    group (``_SHIP_GROUPS``).  After the scan, the replicated Phase 3
    (default) performs ONE ``all_gather`` and nothing else; the *sharded*
    Phase 3 (``sharded_phase3=True``, needs ``num_edges``/``n_parts``)
    instead runs the ring schedule of
    :func:`repro.core.phase3.sharded_phase3_schedule` — 9
    ``ppermute`` ring loops (R+1 rings at run time where the mate is one
    circuit, 2R+1 more and 6 a splice round where it is not) and 3
    ``psum`` eqns, with the single
    ``all_gather`` deferred to circuit emission (and elided entirely
    under ``gather_circuit=False``).  Nothing else may communicate —
    ``repro.analysis.jaxpr_audit`` walks the compiled jaxpr and fails the
    audit gate on any deviation, so an accidental collective (or a host
    callback standing in for one) is caught before it runs.

    Returns static eqn counts plus the dynamic per-run totals implied by
    the ``n_levels``-length scan.
    """
    per_level = sum(_SHIP_GROUPS.values())
    out = {
        "all_to_all": per_level,          # eqns inside the level-scan body
        "all_gather": 1,                  # eqns outside the scan
        "psum": 0,
        "ppermute": 0,
        "scan_length": n_levels,
        "dynamic_all_to_all": per_level * n_levels,
    }
    if sharded_phase3:
        if num_edges is None or n_parts is None:
            raise ValueError(
                "sharded_phase3 budget needs num_edges and n_parts")
        sched = sharded_phase3_schedule(num_edges, n_parts,
                                        gather_circuit=gather_circuit)
        out["all_gather"] = sched["all_gather"]
        out["ppermute"] = sched["ppermute"]
        out["psum"] = sched["psum"]
        out["phase3"] = sched
    return out


def build_anc_table(tree: MergeTree, n: int) -> np.ndarray:
    """``anc[level, part0] → active partition after that level's merges``
    for every level at once (vectorized ``ancestor_at_level``)."""
    anc = np.empty((max(1, tree.height), n), dtype=np.int32)
    cur = np.arange(n)
    for lv in tree.levels:
        pmap = np.arange(n)
        for child, parent in lv.pairs:
            pmap[child] = parent
        cur = pmap[cur]
        anc[lv.level] = cur
    if tree.height == 0:
        anc[0] = cur
    return anc


def _route(dest: jnp.ndarray, mask: jnp.ndarray, fields, n: int, lane: int):
    """Scatter entries into an [n, lane] send buffer keyed by dest device.
    Returns (buffers..., buf_mask, overflow)."""
    key = jnp.where(mask, dest, n)  # pads route to virtual slot n
    order = bounded.argsort(key)
    kd = key[order]
    idx = jnp.arange(kd.shape[0], dtype=I32)
    newseg = jnp.concatenate([jnp.ones((1,), bool), kd[1:] != kd[:-1]])
    seg_start = bounded.cummax(jnp.where(newseg, idx, 0))
    lane_pos = idx - seg_start
    ok = (kd < n) & (lane_pos < lane)
    overflow = jnp.any((kd < n) & (lane_pos >= lane))
    flat = jnp.where(ok, kd * lane + lane_pos, n * lane)
    outs = []
    for f in fields:
        buf = jnp.full((n * lane + 1,), BIG, dtype=f.dtype)
        buf = buf.at[flat].set(jnp.where(ok, f[order], BIG))
        outs.append(buf[:-1].reshape(n, lane))
    # the mask scatters into int32: a TPU scatter into bool compiles to
    # about 1.8 MB more program code
    bm = jnp.zeros((n * lane + 1,), I32).at[flat].set(ok.astype(I32)) > 0
    return outs, bm[:-1].reshape(n, lane), overflow


def _compact_rows(fields, mask, cap: int):
    """Compact a flat masked table to ``cap`` rows (valid-first)."""
    order = bounded.argsort(~mask)
    overflow = jnp.sum(mask) > cap
    outs = [f[order][:cap] for f in fields]
    return outs, mask[order][:cap], overflow


class DistributedEngine:
    """Drives supersteps over a device mesh; also exposes the compiled
    superstep (eager) and the fully fused run program."""

    def __init__(
        self,
        mesh: Mesh,
        axis_names: Tuple[str, ...],
        caps: EngineCaps,
        n_levels: int,
        remote_dedup: bool = True,
        deferred_transfer: bool = True,
        on_trace: Optional[Callable[[], None]] = None,
        on_upload: Optional[Callable[[], None]] = None,
        sharded_phase3: bool = False,
        gather_circuit: bool = True,
        trace=None,
        timed_probe: bool = False,
    ):
        self.mesh = mesh
        self.axes = axis_names
        self.caps = caps
        self.n_levels = n_levels  # supersteps ≥ tree height + 1 (§9 ladder)
        self.n = int(np.prod([mesh.shape[a] for a in axis_names]))
        self.remote_dedup = remote_dedup
        self.deferred_transfer = deferred_transfer
        # DESIGN.md §11: run Phase 3 distributed over the stub shards
        # (ring-rotation doubling + vertex-owner splice) instead of
        # gathering mate[2E] to every device.  Byte-identical results;
        # per-device Phase 3 state drops from O(2E) to O(2E/n).
        self.sharded_phase3 = sharded_phase3
        # gather_circuit=False additionally elides the emission all_gather:
        # the rank triple comes back sharded and PendingRun.wait emits the
        # circuit host-side (only meaningful with sharded_phase3).
        self.gather_circuit = gather_circuit
        # trace probe: called once each time a whole-run/superstep program
        # is (re)traced by jit — the solver's compile-cache accounting
        self.on_trace = on_trace
        # transfer probe: called once per host→device initial-state upload
        # (single or stacked batch) — backs the §9 device-residency
        # acceptance ("warm repeat solves upload nothing")
        self.on_upload = on_upload
        # span trace log (repro.obs, DESIGN.md §13); default is the
        # process-wide log so standalone engines (the audit) trace too.
        # timed_probe opts the eager per-level oracle into one span per
        # level with a device sync — per-level timing the fused scan
        # cannot expose (host callbacks are banned in its body, §10).
        if trace is None:
            from .. import obs

            trace = obs.default_tracelog()
        self.trace = trace
        self.timed_probe = bool(timed_probe)
        self._step = None
        # (num_edges, batch-or-None, donated) → compiled fused program
        self._fused: Dict[Tuple[int, Optional[int], bool], object] = {}
        self._p3 = None                        # eager-path Phase 3 program
        # id(pg) → loaded inputs; serving pools re-solve the same
        # PartitionedGraph objects, so skip the host-side table build
        # (and, for single solves, the device upload) on repeats.
        # Identity-keyed with the pg kept alive by the entry; bounded FIFO.
        self._load_cache: Dict[int, tuple] = {}
        self._load_cache_max = 32
        # tuple(id(pg)…) → stacked device-resident batch inputs, same
        # hot-pool rationale (a steady micro-batch re-solves one pool).
        # LRU so the compositions a width-ladder flush cycles through all
        # stay resident.
        self._batch_cache: Dict[tuple, dict] = {}
        self._batch_cache_max = 8

    # ------------------------------------------------------------------
    # loading
    # ------------------------------------------------------------------
    @staticmethod
    def plan(pg: PartitionedGraph) -> Tuple[
        MergeTree, np.ndarray, np.ndarray, np.ndarray, np.ndarray
    ]:
        """Merge tree + per-edge activation schedule + per-vertex last
        activation level + the full ancestor table.  Host-side and fully
        vectorized: O(E + n·height) NumPy, no per-edge Python."""
        tree = generate_merge_tree(pg.meta)
        n = pg.num_parts
        anc = build_anc_table(tree, n)
        E = pg.graph.num_edges
        act = np.full(E, -1, dtype=np.int64)
        is_cut = pg.edge_part_u != pg.edge_part_v
        cut_ids = np.nonzero(is_cut)[0]
        if len(cut_ids):
            cu = pg.edge_part_u[cut_ids].astype(np.int64)
            cv = pg.edge_part_v[cut_ids].astype(np.int64)
            # merge_level_of, batched: first level where ancestors agree
            eq = anc[:, cu] == anc[:, cv]          # [height', K]
            hit = eq.any(axis=0)
            act[cut_ids] = np.where(hit, np.argmax(eq, axis=0),
                                    tree.height - 1)
        # last activation level per vertex (for touch-retention)
        V = pg.graph.num_vertices
        la = np.zeros(V, dtype=np.int64)
        np.maximum.at(la, pg.graph.edge_u[cut_ids], act[cut_ids] + 1)
        np.maximum.at(la, pg.graph.edge_v[cut_ids], act[cut_ids] + 1)
        return tree, act, la, cut_ids, anc

    @staticmethod
    def _keepers(pg: PartitionedGraph, cu: np.ndarray,
                 cv: np.ndarray) -> np.ndarray:
        """§5a, batched: the lighter partition keeps (parks) each cut edge
        (ties to the smaller pid)."""
        loads = np.array([len(p.remote_eids) for p in pg.parts],
                         dtype=np.int64)
        keep_u = (loads[cu] < loads[cv]) | (
            (loads[cu] == loads[cv]) & (cu <= cv)
        )
        return np.where(keep_u, cu, cv)

    @classmethod
    def size_caps(cls, pg: PartitionedGraph, slack: float = 1.3,
                  open_cap: Optional[int] = None,
                  touch_cap: Optional[int] = None) -> "EngineCaps":
        """Exact capacity sizing from the activation schedule (segment ops,
        no per-edge Python loops)."""
        tree, act, la, cut_ids, anc = cls.plan(pg)
        n = pg.num_parts
        edge_cap = max(len(p.local_eids) for p in pg.parts)
        if len(cut_ids):
            cu = pg.edge_part_u[cut_ids].astype(np.int64)
            cv = pg.edge_part_v[cut_ids].astype(np.int64)
            keeper = cls._keepers(pg, cu, cv)
            park_max = int(np.bincount(keeper, minlength=n).max())
            lvl = act[cut_ids]
            dest = anc[lvl, cu].astype(np.int64)
            hh = max(1, tree.height)
            new_cap_v = int(np.bincount(dest * hh + lvl).max())
            _, ship_cnt = np.unique((keeper * n + dest) * hh + lvl,
                                    return_counts=True)
            ship_cap_v = int(ship_cnt.max())
        else:
            park_max, new_cap_v, ship_cap_v = 0, 1, 1
        # opens bounded by odd-degree vertex counts; touch by boundary counts
        deg = pg.graph.degrees()
        V = pg.graph.num_vertices
        ob = 0
        bmax = 0
        for lvl in range(tree.height + 1):
            live = cut_ids[act[cut_ids] >= lvl]
            future = np.zeros(V, dtype=np.int64)
            np.add.at(future, pg.graph.edge_u[live], 1)
            np.add.at(future, pg.graph.edge_v[live], 1)
            odd = (deg - future) % 2 == 1
            anc_row = anc[lvl - 1] if lvl > 0 else np.arange(n)
            owner = anc_row[pg.part_of_vertex]
            if odd.any():
                ob = max(ob, int(np.bincount(owner[odd]).max()))
            busy = future > 0
            if busy.any():
                bmax = max(bmax, int(np.bincount(owner[busy]).max()))
        oc = open_cap or max(16, int(2 * ob * slack))
        tc = touch_cap or max(16, int(bmax * 4 * slack))
        # sharded Phase 3 vertex-record table (DESIGN.md §11): device d
        # owns every vertex v ≡ d (mod n) and receives at most one
        # canonical record per mate-pair whose canonical stub sits at an
        # owned vertex — bounded by the owned degree sum.
        owner_v = np.arange(V) % n
        p3v = int(np.bincount(owner_v, weights=deg, minlength=n).max())
        return EngineCaps(
            edge_cap=int(edge_cap * slack),
            park_cap=max(8, int(park_max * slack)),
            ship_cap=max(8, int(ship_cap_v * slack)),
            # the level-0 pool holds the initial local edges too
            new_cap=max(8, int(new_cap_v * slack), int(edge_cap * slack)),
            open_cap=oc,
            touch_cap=tc,
            open_ship_cap=oc,
            touch_ship_cap=tc,
            p3v_cap=max(16, int(p3v * slack)),
        )

    def load(self, pg: PartitionedGraph,
             device: bool = True) -> Tuple[EngineState, np.ndarray]:
        """Build the initial sharded state.  Returns (state, anc_table).

        ``device=False`` keeps the state as host numpy arrays — the
        batched path stacks B of them host-side first and ships each
        field with ONE transfer, instead of stacking device arrays
        (which would dispatch hundreds of tiny device ops per batch)."""
        if pg.num_parts != self.n:
            raise ValueError(
                f"graph partitioned into {pg.num_parts} parts, but this "
                f"engine's mesh has {self.n} devices"
            )
        tree, act, la, cut_ids, anc_table = self.plan(pg)
        self.tree = tree
        # §9 level ladder: the engine may run more supersteps than the
        # graph's real merge tree has levels.  Pad the ancestor table by
        # repeating its last (fully merged) row — the extra levels route
        # everything to the root partition, ship nothing, and pair
        # nothing, so they are byte-transparent no-ops.
        rows = max(1, self.n_levels - 1)
        if self.n_levels < tree.height + 1:
            raise ValueError(
                f"engine compiled for {self.n_levels} supersteps but the "
                f"merge tree needs {tree.height + 1}"
            )
        if anc_table.shape[0] < rows:
            anc_table = np.concatenate([
                anc_table,
                np.repeat(anc_table[-1:], rows - anc_table.shape[0], axis=0),
            ])
        n, c = self.n, self.caps
        g = pg.graph

        def full(shape, fill=BIG):
            return np.full(shape, fill, dtype=np.int32)

        pk = {k: full((n, c.park_cap)) for k in
              ("eid", "u", "v", "lau", "lav", "act", "own0")}
        pk_mask = np.zeros((n, c.park_cap), dtype=bool)
        le = {k: full((n, c.edge_cap)) for k in ("eid", "u", "v", "lau", "lav")}
        le_mask = np.zeros((n, c.edge_cap), dtype=bool)

        for p in pg.parts:
            eids = p.local_eids
            k = len(eids)
            if k > c.edge_cap:
                raise ValueError(
                    f"partition {p.pid} holds {k} local edges, over the "
                    f"edge_cap of {c.edge_cap}; resize the caps"
                )
            le["eid"][p.pid, :k] = eids
            le["u"][p.pid, :k] = g.edge_u[eids]
            le["v"][p.pid, :k] = g.edge_v[eids]
            le["lau"][p.pid, :k] = la[g.edge_u[eids]]
            le["lav"][p.pid, :k] = la[g.edge_v[eids]]
            le_mask[p.pid, :k] = True

        if len(cut_ids):
            cu = pg.edge_part_u[cut_ids].astype(np.int64)
            cv = pg.edge_part_v[cut_ids].astype(np.int64)
            keeper = self._keepers(pg, cu, cv)
            order = np.argsort(keeper, kind="stable")
            ks, es = keeper[order], cut_ids[order]
            idx = np.arange(len(ks))
            seg0 = np.where(np.r_[True, ks[1:] != ks[:-1]], idx, 0)
            pos = idx - np.maximum.accumulate(seg0)
            if int(pos.max(initial=0)) >= c.park_cap:
                raise ValueError("park_cap overflow at load")
            pk["eid"][ks, pos] = es
            pk["u"][ks, pos] = g.edge_u[es]
            pk["v"][ks, pos] = g.edge_v[es]
            pk["lau"][ks, pos] = la[g.edge_u[es]]
            pk["lav"][ks, pos] = la[g.edge_v[es]]
            pk["act"][ks, pos] = act[es]
            pk["own0"][ks, pos] = pg.edge_part_u[es]
            pk_mask[ks, pos] = True

        oc, tc = c.open_cap, c.touch_cap
        z_o = np.full((n, oc), BIG, dtype=np.int32)
        z_t = np.full((n, tc), BIG, dtype=np.int32)
        state = EngineState(
            pk_eid=pk["eid"], pk_u=pk["u"], pk_v=pk["v"], pk_lau=pk["lau"],
            pk_lav=pk["lav"], pk_act=pk["act"], pk_own0=pk["own0"],
            pk_mask=pk_mask,
            op_stub=z_o, op_vert=z_o.copy(), op_la=z_o.copy(),
            op_comp=z_o.copy(), op_own0=z_o.copy(),
            op_mask=np.zeros((n, oc), dtype=bool),
            tc_s1=z_t, tc_s2=z_t.copy(), tc_vert=z_t.copy(),
            tc_la=z_t.copy(), tc_comp=z_t.copy(), tc_own0=z_t.copy(),
            tc_mask=np.zeros((n, tc), dtype=bool),
            le_eid=le["eid"], le_u=le["u"], le_v=le["v"],
            le_lau=le["lau"], le_lav=le["lav"], le_mask=le_mask,
        )
        if device:
            state = jax.tree.map(jnp.asarray, state)
        return state, anc_table

    # ------------------------------------------------------------------
    # the superstep program
    # ------------------------------------------------------------------
    def _make_superstep_core(self, stub_space: int = 0):
        """The per-device superstep body (unsharded view): ship + Phase 1
        + table refresh.  Shared verbatim by the eager per-level program
        and the fused level scan, so both execute identical supersteps;
        the fused scan, built for one edge count, also passes its stub
        space, which lets Phase 1 look ids up in tables (DESIGN.md §2)."""
        n, c = self.n, self.caps
        axes = self.axes
        osc = c.open_ship_cap or c.open_cap
        tsc = c.touch_ship_cap or c.touch_cap
        p1caps = c.phase1(stub_space)
        deferred = self.deferred_transfer

        def core(lvl, anc, state: EngineState):
            me = jax.lax.axis_index(axes).astype(I32)
            lvl = lvl.astype(I32)
            dest_row = anc[jnp.maximum(lvl - 1, 0)]  # [n] part0 → active pid

            # ---- 1. ship activated parked edges ----
            if deferred:
                send = state.pk_mask & (state.pk_act == lvl - 1)
            else:
                # baseline: everything hops to the current ancestor each level
                send = state.pk_mask
            e_dest = dest_row[jnp.clip(state.pk_own0, 0, n - 1)]
            e_dest = jnp.where(send, e_dest, n)
            bufs, bmask, of1 = _route(
                e_dest, send,
                (state.pk_eid, state.pk_u, state.pk_v, state.pk_lau,
                 state.pk_lav, state.pk_act, state.pk_own0),
                n, c.ship_cap,
            )
            keep = state.pk_mask & ~send
            r_eid, r_u, r_v, r_lau, r_lav, r_act, r_own0 = [
                jax.lax.all_to_all(b, axes, 0, 0, tiled=True).reshape(-1)
                for b in bufs
            ]
            r_mask = jax.lax.all_to_all(bmask, axes, 0, 0, tiled=True).reshape(-1)

            if deferred:
                arrived_now = r_mask & (r_act == lvl - 1)
                park_back = jnp.zeros_like(r_mask)
            else:
                arrived_now = r_mask & (r_act == lvl - 1)
                park_back = r_mask & (r_act > lvl - 1)

            # level 0: consume the initial local edges instead
            use_local = lvl == 0
            ne = NewEdges(
                eid=jnp.where(use_local,
                              _fit(state.le_eid, c.new_cap),
                              _fit_masked(r_eid, arrived_now, c.new_cap)),
                u=jnp.where(use_local, _fit(state.le_u, c.new_cap),
                            _fit_masked(r_u, arrived_now, c.new_cap)),
                v=jnp.where(use_local, _fit(state.le_v, c.new_cap),
                            _fit_masked(r_v, arrived_now, c.new_cap)),
                lau=jnp.where(use_local, _fit(state.le_lau, c.new_cap),
                              _fit_masked(r_lau, arrived_now, c.new_cap)),
                lav=jnp.where(use_local, _fit(state.le_lav, c.new_cap),
                              _fit_masked(r_lav, arrived_now, c.new_cap)),
                mask=jnp.where(use_local,
                               _fit(state.le_mask, c.new_cap, fill=False),
                               _fit_mask(arrived_now, c.new_cap)),
            )
            of_new = jnp.where(
                use_local,
                jnp.sum(state.le_mask) > c.new_cap,
                jnp.sum(arrived_now) > c.new_cap,
            )

            # ---- 2. ship opens + touch to their active partition ----
            o_dest = dest_row[jnp.clip(state.op_own0, 0, n - 1)]
            o_dest = jnp.where(lvl > 0, o_dest, me)
            obufs, obm, of2 = _route(
                jnp.where(state.op_mask, o_dest, n), state.op_mask,
                (state.op_stub, state.op_vert, state.op_la, state.op_comp,
                 state.op_own0),
                n, osc,
            )
            a_stub, a_vert, a_la, a_comp, a_own0 = [
                jax.lax.all_to_all(b, axes, 0, 0, tiled=True).reshape(-1)
                for b in obufs
            ]
            a_om = jax.lax.all_to_all(obm, axes, 0, 0, tiled=True).reshape(-1)
            (os_, ov_, ol_, oc_, oo_), om_, of3 = _compact_rows(
                (a_stub, a_vert, a_la, a_comp, a_own0), a_om, c.open_cap
            )
            opens = OpenTable(os_, ov_, ol_, oc_, om_)

            t_dest = dest_row[jnp.clip(state.tc_own0, 0, n - 1)]
            t_dest = jnp.where(lvl > 0, t_dest, me)
            tbufs, tbm, of4 = _route(
                jnp.where(state.tc_mask, t_dest, n), state.tc_mask,
                (state.tc_s1, state.tc_s2, state.tc_vert, state.tc_la,
                 state.tc_comp, state.tc_own0),
                n, tsc,
            )
            b_s1, b_s2, b_v, b_la, b_c, b_o0 = [
                jax.lax.all_to_all(b, axes, 0, 0, tiled=True).reshape(-1)
                for b in tbufs
            ]
            b_tm = jax.lax.all_to_all(tbm, axes, 0, 0, tiled=True).reshape(-1)
            (ts1, ts2, tv_, tl_, tc_, to0), tm_, of5 = _compact_rows(
                (b_s1, b_s2, b_v, b_la, b_c, b_o0), b_tm, c.touch_cap
            )
            touch = TouchTable(ts1, ts2, tv_, tl_, tc_, tm_)

            # ---- 3. Phase 1 ----
            out = phase1_local(ne, opens, touch, lvl, p1caps)

            # ---- 4. refresh parked table ----
            if deferred:
                pk_fields = (state.pk_eid, state.pk_u, state.pk_v,
                             state.pk_lau, state.pk_lav, state.pk_act,
                             state.pk_own0)
                (pe, pu, pv, plau, plav, pact, pown), pm, of6 = _compact_rows(
                    pk_fields, keep, c.park_cap
                )
            else:
                (pe, pu, pv, plau, plav, pact, pown), pm, of6 = _compact_rows(
                    (r_eid, r_u, r_v, r_lau, r_lav, r_act, r_own0),
                    park_back, c.park_cap,
                )

            # own0 for new opens/touch: level-0 partition of the vertex —
            # recover from the shipping key: it is only needed to route to
            # *future* ancestors, and anc_table rows are constant per
            # partition subtree, so the current active pid (me) works as the
            # routing key for everything created here.
            new_oo = jnp.where(out.opens.mask, me, BIG)
            new_to = jnp.where(out.touch.mask, me, BIG)

            nstate = EngineState(
                pk_eid=pe, pk_u=pu, pk_v=pv, pk_lau=plau, pk_lav=plav,
                pk_act=pact, pk_own0=pown, pk_mask=pm,
                op_stub=out.opens.stub, op_vert=out.opens.vert,
                op_la=out.opens.la, op_comp=out.opens.comp,
                op_own0=new_oo, op_mask=out.opens.mask,
                tc_s1=out.touch.s1, tc_s2=out.touch.s2,
                tc_vert=out.touch.vert, tc_la=out.touch.la,
                tc_comp=out.touch.comp, tc_own0=new_to,
                tc_mask=out.touch.mask,
                le_eid=state.le_eid, le_u=state.le_u, le_v=state.le_v,
                le_lau=state.le_lau, le_lav=state.le_lav,
                le_mask=jnp.zeros_like(state.le_mask),
            )
            ship_of = of1 | of2 | of3 | of4 | of5 | of6 | of_new
            flags = jnp.concatenate(
                [out.flags, jnp.stack([~ship_of])]
            )
            metrics = jnp.stack(
                [2 * jnp.sum(pm).astype(I32),
                 3 * jnp.sum(out.opens.mask).astype(I32),
                 4 * jnp.sum(out.touch.mask).astype(I32),
                 4 * out.n_components,
                 out.hook_rounds, out.splice_rounds, out.forest_rounds]
            )
            return nstate, out.log_s1, out.log_s2, out.log_mask, flags, metrics

        return core

    def _state_specs(self):
        return EngineState(*([P(self.axes, None)] * len(EngineState._fields)))

    def make_superstep(self):
        """The eager per-level program: one jitted shard_map serving every
        level, logs/flags/metrics synced to host after each call."""
        core = self._make_superstep_core()

        def device_fn(level, anc, state: EngineState) -> StepOut:
            state = jax.tree.map(lambda x: x[0], state)  # [1,·] → [·]
            nstate, s1, s2, lm, flags, metrics = core(level, anc, state)
            nstate = jax.tree.map(lambda x: x[None], nstate)
            return StepOut(
                state=nstate,
                log_s1=s1[None], log_s2=s2[None], log_mask=lm[None],
                flags=flags[None], metrics=metrics[None],
            )

        state_specs = self._state_specs()
        out_specs = StepOut(
            state=state_specs,
            log_s1=P(self.axes, None), log_s2=P(self.axes, None),
            log_mask=P(self.axes, None),
            flags=P(self.axes, None), metrics=P(self.axes, None),
        )
        fn = shard_map(
            device_fn,
            mesh=self.mesh,
            in_specs=(P(), P(None, None), state_specs),
            out_specs=out_specs,
        )

        def traced(level, anc, state):
            self.trace.event("retrace", program="superstep")
            if self.on_trace is not None:
                self.on_trace()
            return fn(level, anc, state)

        return jax.jit(traced)

    # ------------------------------------------------------------------
    # the fused whole-run program
    # ------------------------------------------------------------------
    def make_fused(self, num_edges: int, batch: Optional[int] = None,
                   donate: bool = False):
        """One compiled program for the entire run (DESIGN.md §4):

          · ``lax.scan`` over all ``n_levels`` supersteps inside a single
            shard_map (``anc_table`` is static per-level data; flags and
            metrics are scan-stacked outputs);
          · per-level on-device mate accumulation: each level's
            ``(log_s1, log_s2)`` pairs are routed with the same
            ``_route`` + ``all_to_all`` machinery to the device owning the
            stub's shard of ``mate[2E]`` (stub s lives on device s // S)
            and scattered in.  Later-level writes overwrite earlier ones —
            exactly the host replay order — and within a level the pairs
            are device-disjoint, so the scatter is conflict-free;
          · Phase 3 on-device: all_gather the mate shards, then the
            list-rank emission, with the pivot splice and a second rank
            only where the rank's orbit misses edges (``phase3_device``),
            replicated per device, XLA gather rounds as the doubling
            backend.

        The program's outputs (circuit, mate, flags, metrics with the
        per-level loop rounds, Phase 3's convergence flag and rounds) are
        fetched with ONE host transfer (:meth:`PendingRun.sync`).  Its
        phases carry ``jax.named_scope``s, which reach the HLO metadata
        (``op_name``) and so a profile: ``phase1`` (the level scan),
        ``merge_exchange`` (the mate writes' ``_route`` + ``all_to_all``,
        inside the scan) and ``phase3`` (with ``cc``, ``splice``, ``rank``
        and ``emit`` inside it); an op belongs to its innermost scope.

        ``batch=B`` builds the *batched* program (DESIGN.md §8): every
        per-graph input grows a leading batch axis *after* the partition
        axis (state ``[n, B, ·]``, ``anc [B, H, n]``, ``sv [B, 2E]``) and
        the whole per-device body — level scan, mate accumulation,
        Phase 3 — runs under one ``jax.vmap``.  B same-bucket graphs cost
        ONE program dispatch and ONE host sync; collectives batch into
        single wider ``all_to_all``/``all_gather`` calls.  ``batch=None``
        (default) keeps the original single-graph program — its cache key
        and jaxpr are unchanged, so existing single-solve callers never
        retrace.

        ``donate=True`` donates the initial-state buffers to the program
        (the §9 state-donation entry point): a one-shot caller that keeps
        no device-resident copy lets XLA reuse the state's device memory
        for the run, instead of holding both the inputs and the working
        set live.  Never combine with cached device-resident state — a
        donated buffer is dead after the call.
        """
        n, c = self.n, self.caps
        axes = self.axes
        L = self.n_levels
        n_stubs = 2 * num_edges
        # mate shard width per device: even (sibling s^1 stays shard-local)
        # so the sharded Phase 3 can run on the accumulator shards as-is
        S = shard_width(num_edges, n)
        sharded = self.sharded_phase3
        gather = self.gather_circuit
        p3v = c.p3v_cap or num_edges           # vertex-record table width
        wcap = c.mate_ship_cap or 2 * c.pair_cap()
        core = self._make_superstep_core(stub_space=n_stubs)

        def one_graph(anc, state: EngineState, sv):
            """Whole-run body for ONE graph on one device (unsharded
            view).  The batched program is exactly ``vmap(one_graph)``."""
            me = jax.lax.axis_index(axes).astype(I32)

            def body(carry, lvl):
                st, mate_sh = carry
                nstate, s1, s2, lm, flags, metrics = core(lvl, anc, st)
                # mate writes: both directions of every logged pair, routed
                # to the stub's owning shard
                with jax.named_scope("merge_exchange"):
                    ws = jnp.concatenate([s1, s2])
                    wv = jnp.concatenate([s2, s1])
                    wm = jnp.concatenate([lm, lm])
                    dest = jnp.where(wm, ws // S, n)
                    (bs, bv), bm, of_m = _route(dest, wm, (ws, wv), n, wcap)
                    r_s, r_v, r_m = [
                        jax.lax.all_to_all(b, axes, 0, 0,
                                           tiled=True).reshape(-1)
                        for b in (bs, bv, bm)]
                    off = jnp.where(r_m, r_s - me * S, S)  # masked → pad
                    mate_sh = mate_sh.at[off].set(jnp.where(r_m, r_v, -1))
                    flags = flags.at[3].set(flags[3] & ~of_m)
                return (nstate, mate_sh), (flags, metrics)

            with jax.named_scope("phase1"):
                mate0 = jnp.full((S + 1,), -1, dtype=I32)
                (state, mate_sh), (flags, metrics) = jax.lax.scan(
                    body, (state, mate0), jnp.arange(L, dtype=I32)
                )
            with jax.named_scope("phase3"):
                if not sharded:
                    mate = jax.lax.all_gather(mate_sh[:S], axes,
                                              tiled=True)[:n_stubs]
                    circuit, mate2, ok3, r3 = phase3_device(
                        mate, sv, splice_rounds=c.phase3_rounds)
                    return circuit, mate2, flags, metrics, ok3, r3
                # DESIGN.md §11: Phase 3 runs on the accumulator shards
                # directly — no mate all_gather; sv arrives sharded too.
                res3 = phase3_sharded(
                    mate_sh[:S], sv, axes, n, n_stubs, p3v,
                    splice_rounds=c.phase3_rounds, gather_circuit=gather)
                if gather:
                    circuit, mate2, ok3, r3 = res3
                    return circuit, mate2, flags, metrics, ok3, r3
                m2_sh, dist_sh, reach_sh, ok3, r3 = res3
                packed = jnp.stack([m2_sh, dist_sh, reach_sh], axis=1)
                return packed, m2_sh, flags, metrics, ok3, r3  # [S,3]

        def device_fn(anc, state: EngineState, sv) -> FusedOut:
            state = jax.tree.map(lambda x: x[0], state)  # [1,·] → [·]
            run = one_graph if batch is None else jax.vmap(one_graph)
            circuit, mate2, flags, metrics, ok3, r3 = run(anc, state, sv)
            with jax.named_scope("phase1"):     # the level scan's outputs
                flags, metrics = flags[None], metrics[None]
            return FusedOut(
                circuit=circuit, mate=mate2, flags=flags, metrics=metrics,
                phase3_ok=ok3, phase3_rounds=r3,
            )

        state_specs = self._state_specs()
        if sharded and not gather:
            # sharded outputs: packed rank triple [S, 3] / mate [S] per
            # device (leading batch axis first under vmap)
            circuit_spec = P(axes, None) if batch is None \
                else P(None, axes, None)
            mate_spec = P(axes) if batch is None else P(None, axes)
        else:
            circuit_spec, mate_spec = P(None), P(None)
        # sharded Phase 3 consumes sv as stub shards (padded to n·S by the
        # dispatch paths); the replicated oracle wants it whole per device
        sv_spec = (P(axes) if batch is None else P(None, axes)) \
            if sharded else P(None)
        out_specs = FusedOut(
            circuit=circuit_spec, mate=mate_spec,
            flags=P(axes, None, None), metrics=P(axes, None, None),
            phase3_ok=P(), phase3_rounds=P(),
        )
        fn = shard_map(
            device_fn,
            mesh=self.mesh,
            in_specs=(P(None, None), state_specs, sv_spec),
            out_specs=out_specs,
        )

        def traced(anc, state, sv):
            self.trace.event("retrace", program="fused",
                             edges=num_edges, batch=batch)
            if self.on_trace is not None:
                self.on_trace()
            return fn(anc, state, sv)

        if donate:
            return jax.jit(traced, donate_argnums=(1,))
        return jax.jit(traced)

    # ------------------------------------------------------------------
    def _load_cached(self, pg: PartitionedGraph):
        """Memoized ``load(pg, device=False)`` + stub-vertex map + tree.
        Returns a dict entry ``{"state", "anc", "sv", "tree", "dev"}``
        where ``dev`` lazily caches the device-resident state for the
        single-graph path."""
        ent = self._load_cache.get(id(pg))
        if ent is not None and ent["pg"] is pg:
            self.tree = ent["tree"]
            return ent
        state, anc = self.load(pg, device=False)
        ent = {"pg": pg, "state": state, "anc": anc,
               "sv": self._stub_vertex(pg), "tree": self.tree, "dev": None}
        if len(self._load_cache) >= self._load_cache_max:
            self._load_cache.pop(next(iter(self._load_cache)))
        self._load_cache[id(pg)] = ent
        return ent

    def _stub_vertex(self, pg: PartitionedGraph) -> np.ndarray:
        E = pg.graph.num_edges
        sv = np.empty(2 * E, dtype=np.int64)
        sv[0::2] = pg.graph.edge_u
        sv[1::2] = pg.graph.edge_v
        return sv

    def _pad_sv(self, sv: np.ndarray) -> np.ndarray:
        """Pad a ``[2E]`` stub-vertex map to the ``n·S`` sharded stub
        space (identity under the replicated Phase 3).  Pad slots carry
        vertex 0 — their stubs are unmated, so Phase 3 never reads them."""
        if not self.sharded_phase3:
            return sv
        total = self.n * shard_width(len(sv) // 2, self.n)
        out = np.zeros(total, dtype=sv.dtype)
        out[:len(sv)] = sv
        return out

    def _phase3_prog(self):
        """Eager-path Phase 3: the same device program the fused path runs,
        jitted standalone so the oracle produces byte-identical circuits."""
        if self._p3 is None:
            self._p3 = jax.jit(
                partial(phase3_device, splice_rounds=self.caps.phase3_rounds)
            )
        return self._p3

    def fused_program(self, num_edges: int, batch: Optional[int] = None,
                      donate: bool = False):
        """Get-or-create the fused jit program for ``(num_edges, batch,
        donate)`` *without calling it* — the compile itself (XLA lowering
        on first call) belongs to whoever invokes the returned program."""
        key = (num_edges, batch, donate)
        prog = self._fused.get(key)
        if prog is None:
            prog = self._fused[key] = self.make_fused(
                num_edges, batch=batch, donate=donate)
        return prog

    def _stage(self, pg: PartitionedGraph, resident: bool = True) -> tuple:
        """Host-side half of a single-graph dispatch: input prep, upload /
        device-state caching, program lookup.  Touches the engine caches,
        so the solver calls it under its session lock; the returned staged
        tuple is then executed by :meth:`_launch` *outside* the lock (the
        program call is where a cold program compiles, and a background
        prewarm compile must not block serving dispatches — DESIGN.md §12).

        ``resident=True`` (default) caches the uploaded device state on
        the ``_load_cached`` entry so repeat solves of the same graph
        skip the host→device transfer entirely.  ``resident=False`` is
        the one-shot path: a fresh upload donated to the program
        (``donate_argnums``), so XLA may reuse the state buffers for the
        run's scratch space instead of holding two copies.
        """
        with self.trace.span("stage", resident=resident) as sp:
            ent = self._load_cached(pg)
            E = pg.graph.num_edges
            sp.set(edges=E)
            if resident:
                if ent["dev"] is None:
                    with self.trace.span("upload", edges=E):
                        ent["dev"] = (
                            jax.tree.map(jnp.asarray, ent["state"]),
                            jnp.asarray(ent["anc"]),
                            jnp.asarray(self._pad_sv(ent["sv"]), dtype=I32),
                        )
                    if self.on_upload is not None:
                        self.on_upload()
                state, anc, sv_dev = ent["dev"]
                donate = False
            else:
                with self.trace.span("upload", edges=E, donated=True):
                    state = jax.tree.map(jnp.asarray, ent["state"])
                    anc = jnp.asarray(ent["anc"])
                    sv_dev = jnp.asarray(self._pad_sv(ent["sv"]), dtype=I32)
                if self.on_upload is not None:
                    self.on_upload()
                donate = True
            prog = self.fused_program(E, batch=None, donate=donate)
        return (prog, (anc, state, sv_dev), donate, [pg], [ent["tree"]], None)

    def _launch(self, staged: tuple,
                t0: Optional[float] = None) -> PendingRun:
        """Device half of a dispatch: call the staged program (compiling
        it on first use) and wrap the in-flight output.  Safe to run
        outside the solver lock — jit programs are thread-safe to call."""
        prog, args, donate, pgs, trees, batch = staged
        if t0 is None:
            t0 = time.perf_counter()   # lint: ok — dispatch epoch; the
            #                            delta lands in wait()'s run_s
        if donate:
            with warnings.catch_warnings():
                # CPU backends can't always honor donation; harmless
                warnings.filterwarnings(
                    "ignore", message=".*donated buffer.*")
                out = prog(*args)
        else:
            out = prog(*args)
        return PendingRun(self, out, pgs, trees, t0, batch=batch)

    def _dispatch(self, pg: PartitionedGraph,
                  resident: bool = True) -> PendingRun:
        """Dispatch ONE fused run asynchronously (stage + launch); no
        host sync happens until :meth:`PendingRun.wait`."""
        return self._launch(self._stage(pg, resident=resident))

    def evict_program(self, num_edges: int, batch: Optional[int]) -> int:
        """Drop the compiled fused program(s) for ``(num_edges, batch)``
        so the solver's width-LRU frees the executable, not just its
        accounting entry.  Returns how many jit entries were dropped."""
        n = 0
        for donate in (False, True):
            if self._fused.pop((num_edges, batch, donate), None) is not None:
                n += 1
        return n

    def live_programs(self) -> list:
        """Sorted ``(num_edges, batch)`` pairs with a live fused program
        (donate variants collapsed) — the audit's adaptive program set."""
        return sorted({(E, b) for (E, b, _d) in self._fused})

    def _run(self, pg: PartitionedGraph, fused: bool = True):
        """Execute the full BSP run on the mesh; returns the unified
        :class:`repro.euler.result.EulerResult` (internal — call sites go
        through :class:`repro.euler.EulerSolver`).

        ``fused=True`` (default): one compiled device program + one host
        sync.  ``fused=False``: the per-level eager oracle with host log
        replay (per-level metrics visibility, same final circuit).
        """
        from ..euler.result import EulerResult

        if fused:
            return self._dispatch(pg).wait()[0]

        t0 = time.perf_counter()
        ent = self._load_cached(pg)
        if ent["dev"] is None:
            with self.trace.span("upload", edges=pg.graph.num_edges):
                ent["dev"] = (
                    jax.tree.map(jnp.asarray, ent["state"]),
                    jnp.asarray(ent["anc"]),
                    jnp.asarray(self._pad_sv(ent["sv"]), dtype=I32),
                )
            if self.on_upload is not None:
                self.on_upload()
        state, anc, sv_dev = ent["dev"]
        E = pg.graph.num_edges
        sv = ent["sv"]

        # ---- eager oracle: per-level programs, host log replay ----
        step = self._step or self.make_superstep()
        self._step = step
        logs: List[Tuple[np.ndarray, np.ndarray]] = []
        all_flags = []
        metrics = []
        for lvl in range(self.n_levels):
            if self.timed_probe:
                # opt-in per-level timing (DESIGN.md §13): one span per
                # merge level with a device sync, the per-level view the
                # fused scan cannot expose (no host callbacks in its
                # body, §10).  Off the warm path unless requested.
                with self.trace.span("level", level=lvl, edges=E):
                    out = step(jnp.int32(lvl), anc, state)
                    jax.block_until_ready(out.log_mask)
            else:
                out = step(jnp.int32(lvl), anc, state)
            state = out.state
            m = np.asarray(out.log_mask)
            s1 = np.asarray(out.log_s1)[m]
            s2 = np.asarray(out.log_s2)[m]
            logs.append((s1, s2))
            all_flags.append(np.asarray(out.flags))
            metrics.append(np.asarray(out.metrics))
        flags = np.concatenate(all_flags, 0)
        if not flags.all():
            raise RuntimeError(
                f"convergence/capacity flags failed: {flags.all(0)}")

        # Phase 3: replay logs (level order; later writes win), then the
        # same device Phase 3 program the fused path uses.
        mate = np.full(2 * E, -1, dtype=np.int64)
        for s1, s2 in logs:
            keep = (s1 < 2 * E) & (s2 < 2 * E)
            mate[s1[keep]] = s2[keep]
            mate[s2[keep]] = s1[keep]
        if not (mate >= 0).all():
            raise RuntimeError(f"{(mate < 0).sum()} stubs unmated")
        circuit_j, mate2_j, ok3, rounds3 = self._phase3_prog()(
            jnp.asarray(mate, dtype=I32), jnp.asarray(sv, dtype=I32)
        )
        if not bool(ok3):
            raise RuntimeError("Phase 3 pivot splice failed to converge")
        circuit = np.asarray(circuit_j).astype(np.int64)
        if not (circuit >= 0).all():
            raise RuntimeError("circuit emission left gaps")
        return EulerResult(
            circuit=circuit, mate=np.asarray(mate2_j).astype(np.int64),
            tree=self.tree, levels=EulerResult.levels_from_metrics(metrics),
            supersteps=self.n_levels, backend="device", fused=False,
            graph=pg.graph, phase3_converged=bool(ok3),
            phase3_rounds=int(rounds3),
            timings={"run_s": time.perf_counter() - t0},
        )

    def _stage_batch(self, pgs: List[PartitionedGraph]) -> tuple:
        """Host-side half of a batched dispatch (stack + ship + program
        lookup); like :meth:`_stage`, runs under the solver lock with the
        program call deferred to :meth:`_launch`.

        Every graph must lower to the same static shapes: equal edge
        count, equal merge-tree height, and the engine's (shared) caps —
        the solver guarantees this by batching within one shape bucket.
        Batched execution is fused-only; the eager oracle stays per-graph.
        """
        if not pgs:
            raise ValueError("empty batch")
        with self.trace.span("stage", width=len(pgs)):
            E = pgs[0].graph.num_edges
            B = len(pgs)
            bkey = tuple(id(pg) for pg in pgs)
            bent = self._batch_cache.get(bkey)
            if bent is not None and all(a is b for a, b in zip(bent["pgs"], pgs)):
                anc, state, sv = bent["dev"]
                trees = bent["trees"]
                self._batch_cache[bkey] = self._batch_cache.pop(bkey)  # LRU touch
            else:
                states, ancs, svs, trees = [], [], [], []
                for pg in pgs:
                    if pg.graph.num_edges != E:
                        raise ValueError(
                            f"mixed edge counts in batch: "
                            f"{pg.graph.num_edges} != {E}")
                    ent = self._load_cached(pg)
                    states.append(ent["state"])
                    ancs.append(ent["anc"])
                    svs.append(ent["sv"])
                    trees.append(ent["tree"])
                # stack along a batch axis AFTER the partition axis ([n, B, ·])
                # on the host, then ship each field once — stacking device
                # arrays instead would dispatch ~#fields × B tiny device ops
                with self.trace.span("upload", edges=E, width=B):
                    state = jax.tree.map(
                        lambda *xs: jnp.asarray(np.stack(xs, axis=1)), *states)
                    anc = jnp.asarray(np.stack(ancs))              # [B, H, n]
                    sv = jnp.asarray(
                        np.stack([self._pad_sv(s) for s in svs]),
                        dtype=I32)                     # [B, 2E]
                if len(self._batch_cache) >= self._batch_cache_max:
                    self._batch_cache.pop(next(iter(self._batch_cache)))
                self._batch_cache[bkey] = {
                    "pgs": list(pgs), "dev": (anc, state, sv), "trees": trees,
                }
                if self.on_upload is not None:
                    self.on_upload()

            prog = self.fused_program(E, batch=B)
            return (prog, (anc, state, sv), False, list(pgs), trees, B)

    def _dispatch_batch(self, pgs: List[PartitionedGraph]) -> PendingRun:
        """Dispatch B same-shape runs as ONE batched fused program
        (DESIGN.md §8) asynchronously (stage + launch);
        :meth:`PendingRun.wait` performs the single host sync and yields
        one :class:`repro.euler.result.EulerResult` per graph,
        byte-identical to B sequential :meth:`_run` calls."""
        return self._launch(self._stage_batch(pgs))

    def _run_batch(self, pgs: List[PartitionedGraph]):
        """Synchronous wrapper: dispatch one batched fused run, then
        immediately perform its single host sync."""
        return self._dispatch_batch(pgs).wait()

    def run(self, pg: PartitionedGraph, validate: bool = True,
            fused: bool = True):
        """Deprecated: use ``repro.euler.EulerSolver`` / ``solve``.

        Thin back-compat shim preserving the old ``(circuit, metrics)``
        return shape; new code gets a typed :class:`EulerResult` from the
        facade instead.
        """
        warnings.warn(
            "DistributedEngine.run is deprecated; use repro.euler.solve / "
            "EulerSolver (returns a typed EulerResult)",
            DeprecationWarning, stacklevel=2,
        )
        res = self._run(pg, fused=fused)
        if validate:
            res.validate()
        return res.circuit, res.metrics_arrays()


def _fit(x: jnp.ndarray, cap: int, fill=None):
    """Pad/trim a 1-D array to ``cap`` (static)."""
    if fill is None:
        fill = BIG if x.dtype != jnp.bool_ else False
    if x.shape[0] == cap:
        return x
    if x.shape[0] > cap:
        return x[:cap]
    pad = jnp.full((cap - x.shape[0],), fill, dtype=x.dtype)
    return jnp.concatenate([x, pad])


def _fit_masked(x: jnp.ndarray, mask: jnp.ndarray, cap: int):
    order = bounded.argsort(~mask)
    return _fit(x[order], cap)


def _fit_mask(mask: jnp.ndarray, cap: int):
    order = bounded.argsort(~mask)
    return _fit(mask[order], cap, fill=False)
