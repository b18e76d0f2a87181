"""`EulerSolver` — the one public entry point to the paper's pipeline.

The solver owns everything call sites used to assemble by hand: vertex
partitioning, merge-tree planning, ``size_caps`` table sizing, mesh
selection, backend choice (``device`` — the shard_map BSP engine — or
``host`` — the exact reference engine), and the device execution mode
(scan-``fused`` whole-run program vs the ``eager`` per-level oracle).

A solver instance is a *persistent serving session*: device solves pad
each request graph into a geometric shape bucket (``bucket.py``) keyed
into a compiled-program cache, so the second and every later graph in a
bucket reuses the lowered fused scan with zero retrace.  Same-bucket
graphs can additionally be *batched*: ``solve_batch`` stacks B of them
along a leading batch axis and runs ONE fused device program — one
dispatch, one host sync — byte-identical to B sequential solves
(DESIGN.md §8).  Cache accounting (hits / misses / traces / evictions,
per ``(bucket, B)`` program) is reported in every result's ``cache``
stats.

The serving warm path (DESIGN.md §9) builds on four solver features:
bucket keys quantized onto a shared cap/level ladder (``bucket.py``) so
same-scale pools share programs; a per-bucket *width ladder* of batched
programs compiled ahead of arrivals (:meth:`EulerSolver.prewarm` /
:meth:`EulerSolver.warmed_widths`); device-resident initial state for
repeat solves of pooled graphs (zero host→device upload, counted in
``cache.state_uploads``); and asynchronous dispatch
(:meth:`EulerSolver.solve_async` / :meth:`EulerSolver.solve_batch_async`
returning :class:`PendingSolve`) so host prep overlaps device execution.

    from repro.euler import solve, EulerSolver

    res = solve(graph, n_parts=8).validate()          # one-shot
    solver = EulerSolver(n_parts=8)                   # serving session
    for res in solver.solve_many(request_graphs, batch=8):
        ...

See DESIGN.md §7 for the API surface and deprecation policy, §8 for the
batched execution model.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import OrderedDict
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..core.engine import DistributedEngine, EngineCaps, PendingRun
from ..core.graph import Graph, partition_graph
from ..core.host_engine import HostEngine
from ..core.phase2 import generate_merge_tree
from ..graphgen.partition import partition_vertices
from .bucket import (LADDER_FIELDS, ceil_pow2, ladder_caps, ladder_levels,
                     ladder_rounds, ladder_waste, pad_graph, round_caps,
                     strip_circuit)
from .result import CacheStats, EulerResult

BucketKey = Tuple[int, int, int, EngineCaps]   # (e_cap, n_parts, n_levels, caps)

# Sessions label their metric-family children in the (shared) registry,
# so per-solver counters stay isolated while one scrape sees them all.
_SESSION_SEQ = itertools.count()


class PendingSolve:
    """An in-flight fused solve or batch: dispatched to the device,
    result not yet fetched.

    ``ready()`` polls completion without blocking; ``results()`` (or
    ``result()`` for a single-graph solve) performs the run's one
    device→host sync, strips bucket padding, and stamps cache stats —
    byte-identical to what the synchronous :meth:`EulerSolver.solve` /
    :meth:`EulerSolver.solve_batch` path returns.  The serving pipeline
    holds one of these per in-flight flush so host prep of the next
    flush overlaps device execution of this one (DESIGN.md §9).

    It also holds the solve's open root span (``solve`` or
    ``solve_batch``, DESIGN.md §13): ``results()`` records its ``wait``
    and ``strip`` children, sets the device loop counters on it, and
    closes it.
    """

    def __init__(self, solver: "EulerSolver", run: PendingRun,
                 graphs: List[Graph], key: BucketKey, hit: bool,
                 t0: float, t_prep: float, batch: int, root: obs.Span):
        self._solver = solver
        self._run = run
        self._graphs = graphs
        self._key = key
        self._hit = hit
        self._t0 = t0
        self._t_prep = t_prep
        self._batch = batch          # reported width (1 = single program)
        self._root = root
        self._out: Optional[List[EulerResult]] = None

    @property
    def bucket(self) -> BucketKey:
        return self._key

    def __len__(self) -> int:
        return len(self._graphs)

    def ready(self) -> bool:
        """Non-blocking: has the device run finished?"""
        return self._out is not None or self._run.ready()

    def results(self) -> List[EulerResult]:
        """Block for the device run; one result per graph, input order."""
        if self._out is not None:
            return self._out
        with self._root.scope():
            self._run.sync()
            with self._solver.trace.span("strip"):
                results = self._run.wait()
                total_s = time.perf_counter() - self._t0
                for g, res in zip(self._graphs, results):
                    res.graph = g
                    res.padded_edges = self._key[0] - g.num_edges
                    res.circuit = strip_circuit(res.circuit, g.num_edges)
                    res.cache = dataclasses.replace(
                        self._solver.cache_stats, bucket=self._key,
                        hit=self._hit, batch=self._batch)
                    res.timings["prepare_s"] = self._t_prep
                    res.timings["total_s"] = total_s
        counters = [_loop_counters(res) for res in results]
        self._root.set(**(counters[0] if len(counters) == 1 else
                          {k: [c[k] for c in counters] for k in counters[0]}))
        self._root.end()
        self._out = results
        return results

    def result(self) -> EulerResult:
        """Single-solve convenience accessor."""
        if len(self._graphs) != 1:
            raise ValueError("batched solve: use results()")
        return self.results()[0]


def _loop_counters(res: EulerResult) -> dict:
    """A fused solve's device loop counters, as its root span records
    them: per-level lists of per-partition Phase 1 rounds, Phase 3's
    splice rounds, and whether Phase 3 ran its CC and splice (1) or
    found the mate one circuit already (0; the splice loop runs at least
    one round whenever it is entered)."""
    return {"hook_rounds": [ls.hook_rounds for ls in res.levels],
            "splice_rounds": [ls.splice_rounds for ls in res.levels],
            "forest_rounds": [ls.forest_rounds for ls in res.levels],
            "phase3_rounds": res.phase3_rounds,
            "phase3_cc_runs": int(res.phase3_rounds > 0)}


class EulerSolver:
    """Stable facade over the partition-centric Euler pipeline.

    A small end-to-end session on the exact host reference engine (the
    device backend is identical API-wise; it pads graphs into compiled
    shape buckets first):

    >>> import numpy as np
    >>> from repro.core.graph import Graph
    >>> from repro.euler import EulerSolver
    >>> bowtie = Graph(5, np.array([0, 1, 2, 0, 3, 4]),
    ...                   np.array([1, 2, 0, 3, 4, 0]))
    >>> solver = EulerSolver(n_parts=1, backend="host")
    >>> res = solver.solve(bowtie).validate()
    >>> res.valid, len(res.circuit)
    (True, 6)

    Parameters
    ----------
    n_parts:            partitions (device backend: one per mesh device;
                        defaults to the mesh size, else ``len(jax.devices())``;
                        host backend defaults to 4).
    backend:            ``"device"`` (shard_map BSP engine, default) or
                        ``"host"`` (exact reference engine).
    fused:              device execution mode — one scan-fused compiled
                        program + one host sync (default) vs the eager
                        per-level oracle.  Overridable per solve call.
    mesh:               a prebuilt 1-D partition mesh; built lazily from
                        ``launch.mesh.make_part_mesh(n_parts)`` otherwise.
    remote_dedup /
    deferred_transfer:  the paper's §5 heuristics (default on).
    slack:              capacity sizing headroom passed to ``size_caps``.
    partition_seed:     seed for the built-in BFS partitioner.
    min_bucket_edges:   smallest edge bucket (keeps tiny graphs from
                        fragmenting the cache).
    cap_ladder:         quantize table caps onto the shared bucket ladder
                        (``ladder_caps``) instead of independent pow2 per
                        field, collapsing same-scale pools into 1–2
                        buckets (default on; off restores PR 3 keying).
    level_ladder:       quantize merge-tree height onto the pow2 ladder
                        (``ladder_levels``) so partition luck can't split
                        a scale across level classes (default on).
    straggler_cap:      derive the Phase 1/Phase 3 ``while_loop`` round
                        budgets from the bucket schedule
                        (``ladder_rounds``) instead of fixed 12/64,
                        bounding vmapped-batch straggler tails.
    ladder_waste_cap:   buckets whose quantized/exact table-area ratio
                        exceeds this fall back to plain ``round_caps``
                        keying, bounding padded-compute waste by
                        construction.
    width_ladder:       partial-flush batch widths :meth:`prewarm`
                        compiles by default (``max_batch`` is appended by
                        the serving tier).
    program_cache_max:  LRU cap on compiled ``(bucket, B)`` programs;
                        evictions drop the executable and are counted in
                        cache stats.
    program_cache_bytes: optional byte budget for the program LRU, using
                        the audit's static per-program cost model
                        (``repro.analysis.jaxpr_audit.program_cost_bytes``)
                        — exceeding it evicts least-recently-used
                        programs just like the count cap.  Programs pinned
                        by the autotuner (:meth:`pin_program`) survive
                        both caps.  ``None`` (default) = count cap only.
    device_resident:    keep each prepared graph's initial device state
                        cached on device (repeat solves skip the
                        host→device upload); off = donate a fresh upload
                        per solve.
    sharded_phase3:     run Phase 3 distributed over the stub shards
                        (DESIGN.md §11) — per-device Phase 3 state
                        O(2E/n) instead of O(2E), byte-identical
                        circuits.  Default ``None`` = on for
                        ``n_parts > 1``, off for a single partition;
                        ``False`` pins the replicated oracle path.
    gather_circuit:     ``False`` elides the sharded path's emission
                        ``all_gather``: the post-rank shards are fetched
                        raw and the circuit is emitted host-side
                        (byte-identical; requires ``sharded_phase3``).
    registry / trace:   the :class:`repro.obs.Registry` and
                        :class:`repro.obs.TraceLog` this session reports
                        into; default: the process-wide ``repro.obs``
                        defaults.  Cache counters are registered as
                        per-session labeled children
                        (``{session="sN"}``), so ``cache_stats`` stays
                        solver-scoped while one scrape sees every
                        session (DESIGN.md §13).
    timed_probe:        emit one ``level`` span per merge level on the
                        eager oracle path (``fused=False``), each with a
                        device sync — the per-level timing view the
                        fused scan cannot expose (host callbacks are
                        banned in its body, DESIGN.md §10/§13).
    """

    def __init__(
        self,
        n_parts: Optional[int] = None,
        backend: str = "device",
        fused: bool = True,
        mesh=None,
        remote_dedup: bool = True,
        deferred_transfer: bool = True,
        slack: float = 1.3,
        partition_seed: int = 0,
        min_bucket_edges: int = 64,
        cap_ladder: bool = True,
        level_ladder: bool = True,
        straggler_cap: bool = True,
        ladder_waste_cap: float = 4.0,
        width_ladder: Sequence[int] = (1, 2, 4),
        program_cache_max: int = 32,
        program_cache_bytes: Optional[int] = None,
        device_resident: bool = True,
        sharded_phase3: Optional[bool] = None,
        gather_circuit: bool = True,
        registry: Optional[obs.Registry] = None,
        trace: Optional[obs.TraceLog] = None,
        timed_probe: bool = False,
    ):
        if backend not in ("device", "host"):
            raise ValueError(f"backend must be 'device' or 'host': {backend}")
        self.backend = backend
        self.fused = fused
        self.remote_dedup = remote_dedup
        self.deferred_transfer = deferred_transfer
        self.slack = slack
        self.partition_seed = partition_seed
        self.min_bucket_edges = min_bucket_edges
        self.cap_ladder = cap_ladder
        self.level_ladder = level_ladder
        self.straggler_cap = straggler_cap
        self.ladder_waste_cap = float(ladder_waste_cap)
        self.width_ladder = tuple(sorted({int(w) for w in width_ladder}))
        self.program_cache_max = int(program_cache_max)
        self.program_cache_bytes = (None if program_cache_bytes is None
                                    else int(program_cache_bytes))
        self.device_resident = device_resident
        self._mesh = mesh
        if n_parts is None:
            if mesh is not None:
                n_parts = int(np.prod(list(mesh.shape.values())))
            elif backend == "device":
                import jax

                n_parts = len(jax.devices())
            else:
                n_parts = 4
        self.n_parts = int(n_parts)
        # DESIGN.md §11: distributed Phase 3 over the stub shards.  On by
        # default whenever there is real parallelism to shard over; P=1
        # defaults to the replicated oracle path (identical results, no
        # ring machinery).  Explicit True/False overrides either way.
        if sharded_phase3 is None:
            sharded_phase3 = self.n_parts > 1
        self.sharded_phase3 = bool(sharded_phase3)
        # gather_circuit=False elides the emission all_gather: the rank
        # shards are fetched raw and the circuit is emitted host-side
        # (byte-identical; sharded mode only).
        self.gather_circuit = bool(gather_circuit)
        if not self.gather_circuit and not self.sharded_phase3:
            raise ValueError(
                "gather_circuit=False requires sharded_phase3 (the "
                "replicated Phase 3 always materializes the circuit)")
        # bucket → engine (+ its compiled programs).  Bounded FIFO so a
        # long-running session over heterogeneous request shapes cannot
        # grow host memory without bound; evicting a bucket just costs a
        # recompile if that shape comes back.
        self._engines: dict = {}
        self._engines_max = 16
        # (bucket, B-or-None) → True for every program compiled and still
        # live this session; an LRU bounded by ``program_cache_max``.
        # Backs the per-solve hit/miss accounting, the batcher's
        # ``warmed_widths`` query, AND eviction: dropping an entry also
        # drops the engine's compiled executable (``evict_program``), not
        # just the bookkeeping.  Bucket eviction purges its widths too.
        self._programs: OrderedDict = OrderedDict()
        # per-graph prep memo (partition/pad/plan/caps): repeat solves of
        # the same Graph object — the serving pool pattern — skip straight
        # to the compiled program.  Bounded FIFO; identity-keyed with the
        # graph kept alive by the entry so ids can't be recycled.
        self._prep_cache: dict = {}
        self._prep_cache_max = 64
        # measured quantized/exact table-area ratio per bucket key
        self.bucket_waste: dict = {}
        # byte-aware budget bookkeeping for the program LRU: modeled bytes
        # per live (bucket, B) program + running total, and the pin set
        # the autotuner protects from eviction (DESIGN.md §12)
        self._program_bytes: dict = {}
        self._bytes_total = 0
        self._pinned: set = set()
        # autotuner feedback rung: bucket scales re-keyed onto the tight
        # cap profile, and the max *raw* (pre-quantization) cap needs
        # observed per scale that justify doing so
        self._tight_scales: set = set()
        self._field_max: dict = {}
        # lazily-created background compile service (prewarm_async)
        self._compile_service = None
        # observability (DESIGN.md §13): cache accounting lives in the
        # metrics registry as per-session labeled children; cache_stats
        # (below) is a read-through view for the existing result API.
        # All instruments share the registry's lock, not the session's.
        reg = registry if registry is not None else obs.default_registry()
        self.registry = reg
        # timed_probe forces the eager per-level oracle path to emit one
        # "level" span per merge-tree level (engine-side; fused programs
        # cannot host-callback, DESIGN.md §13).
        self.timed_probe = bool(timed_probe)
        self.trace = trace if trace is not None else obs.default_tracelog()
        self.session = f"s{next(_SESSION_SEQ)}"
        lab = {"session": self.session}
        self._c_hits = reg.counter(
            "euler_cache_hits", "program-cache hits").labels(**lab)
        self._c_misses = reg.counter(
            "euler_cache_misses", "program-cache misses").labels(**lab)
        self._c_traces = reg.counter(
            "euler_traces", "whole-run program traces (= compiles)"
        ).labels(**lab)
        self._c_evictions = reg.counter(
            "euler_cache_evictions", "programs dropped by LRU/budget"
        ).labels(**lab)
        self._c_prewarms = reg.counter(
            "euler_cache_prewarms", "widths compiled by prewarm"
        ).labels(**lab)
        self._c_uploads = reg.counter(
            "euler_state_uploads", "host->device initial-state transfers"
        ).labels(**lab)
        self._g_bytes = reg.gauge(
            "euler_cache_bytes", "modeled bytes of live cached programs"
        ).labels(**lab)
        self._h_compile = reg.histogram(
            "euler_compile_seconds",
            "cold (bucket, B) program compile+dispatch seconds",
            lo_exp=-10, hi_exp=10).labels(**lab)
        # one solver may be driven from a serving thread and a background
        # compile thread at once: the lock serializes host-side mutation
        # (prep memo, program accounting, dispatch staging); program
        # *calls* — where cold programs compile — and device waits happen
        # outside it, so background compiles never block a serving
        # dispatch (DESIGN.md §12).
        self._lock = threading.RLock()

    # ------------------------------------------------------------------
    @property
    def cache_stats(self) -> CacheStats:
        """Cumulative cache accounting, read through the metrics
        registry (one consistent source for results, serve stats, the
        audit's ``metrics`` section, and the ``--metrics-port``
        endpoint).  Returns a fresh :class:`CacheStats` snapshot —
        callers ``dataclasses.replace`` it per solve as before."""
        return CacheStats(
            hits=self._c_hits.value, misses=self._c_misses.value,
            traces=self._c_traces.value, evictions=self._c_evictions.value,
            prewarms=self._c_prewarms.value,
            state_uploads=self._c_uploads.value)

    @property
    def mesh(self):
        if self._mesh is None:
            from ..launch.mesh import make_part_mesh

            self._mesh = make_part_mesh(self.n_parts)
        return self._mesh

    def _partition(self, graph: Graph,
                   part_of_vertex: Optional[np.ndarray]) -> np.ndarray:
        if part_of_vertex is not None:
            return np.asarray(part_of_vertex, dtype=np.int64)
        if graph.num_vertices < self.n_parts:
            raise ValueError(
                f"graph has {graph.num_vertices} vertices, fewer than "
                f"n_parts={self.n_parts}; construct the solver with fewer "
                f"partitions (n_parts ≤ |V|)"
            )
        if self.n_parts == 1:
            return np.zeros(graph.num_vertices, dtype=np.int64)
        return partition_vertices(graph, self.n_parts,
                                  seed=self.partition_seed)

    def _prepare(self, graph: Graph, part_of_vertex: Optional[np.ndarray]):
        """Partition, pad into the bucket, plan the merge tree, size caps.
        Returns (padded pg, tree, bucket key).  Memoized per Graph object
        (default partitioning only) so repeat solves of a pooled request
        graph skip the host-side prep entirely.

        Bucket keying quantizes every shape dimension onto the shared
        ladder (DESIGN.md §9): caps via ``ladder_caps`` (falling back to
        plain ``round_caps`` when the measured waste would exceed
        ``ladder_waste_cap``), scan length via ``ladder_levels``, and the
        straggler round budgets via ``ladder_rounds``.
        """
        memo = part_of_vertex is None
        with self._lock:
            if memo:
                hit = self._prep_cache.get(id(graph))
                if hit is not None and hit[0] is graph:
                    return hit[1]
            with self.trace.span("partition", parts=self.n_parts):
                part = self._partition(graph, part_of_vertex)
            e_cap = ceil_pow2(graph.num_edges, self.min_bucket_edges)
            g_pad, part_pad = pad_graph(graph, part, e_cap)
            pg = partition_graph(g_pad, part_pad)
            if pg.num_parts != self.n_parts:
                raise ValueError(
                    f"partitioner produced {pg.num_parts} non-empty parts "
                    f"for n_parts={self.n_parts}; the graph is too small or "
                    f"sparse for this partition count"
                )
            tree = generate_merge_tree(pg.meta)
            n_levels = tree.height + 1
            if self.level_ladder:
                n_levels = ladder_levels(n_levels)
            raw = DistributedEngine.size_caps(pg, slack=self.slack)
            rounded = round_caps(raw)
            # record the max raw (pre-quantization, slack-inclusive) need
            # per cap field at this scale — the autotuner's evidence that
            # a bucket's members all fit the tight floor profile
            obs = self._field_max.setdefault(e_cap, {})
            for f in LADDER_FIELDS:
                v = int(getattr(raw, f))
                if v > obs.get(f, 0):
                    obs[f] = v
            caps = rounded
            waste = 1.0
            if self.cap_ladder:
                quant = ladder_caps(raw, e_cap, self.n_parts,
                                    slack=self.slack,
                                    tight=e_cap in self._tight_scales)
                waste = ladder_waste(rounded, quant)
                if waste <= self.ladder_waste_cap:
                    caps = quant        # outlier shapes keep pow2 keying
                else:
                    waste = 1.0
            if self.straggler_cap:
                caps = ladder_rounds(caps, e_cap)
            key: BucketKey = (e_cap, self.n_parts, n_levels, caps)
            self.bucket_waste[key] = max(self.bucket_waste.get(key, 0.0),
                                         waste)
            out = (pg, tree, key)
            if memo:
                if len(self._prep_cache) >= self._prep_cache_max:
                    self._prep_cache.pop(next(iter(self._prep_cache)))
                self._prep_cache[id(graph)] = (graph, out)
            return out

    def bucket_of(self, graph: Graph,
                  part_of_vertex: Optional[np.ndarray] = None) -> BucketKey:
        """The shape-bucket key ``(e_cap, n_parts, n_levels, caps)`` this
        graph would solve under — graphs sharing a key share one compiled
        program."""
        _, _, key = self._prepare(graph, part_of_vertex)
        return key

    def _on_trace(self):
        # fires from inside jit tracing on whichever thread dispatched
        # the program; the registry counter carries its own lock
        self._c_traces.inc()

    def _on_upload(self):
        self._c_uploads.inc()

    def _engine_for(self, key: BucketKey) -> DistributedEngine:
        """The (cached) engine owning this bucket's compiled programs."""
        with self._lock:
            eng = self._engines.get(key)
            if eng is None:
                e_cap, n_parts, n_levels, caps = key
                eng = DistributedEngine(
                    self.mesh, tuple(self.mesh.axis_names), caps, n_levels,
                    remote_dedup=self.remote_dedup,
                    deferred_transfer=self.deferred_transfer,
                    on_trace=self._on_trace,
                    on_upload=self._on_upload,
                    sharded_phase3=self.sharded_phase3,
                    gather_circuit=self.gather_circuit,
                    trace=self.trace,
                    timed_probe=self.timed_probe,
                )
                if len(self._engines) >= self._engines_max:
                    evicted = next(iter(self._engines))
                    self._engines.pop(evicted)
                    for p in [p for p in self._programs if p[0] == evicted]:
                        self._evict_entry(p)   # engine gone: pins included
                self._engines[key] = eng
            return eng

    def _program_cost(self, key: BucketKey, batch: Optional[int]) -> int:
        """Modeled device bytes of one cached program (the audit's static
        cost model); 0 when the key is not a real 4-field bucket key (the
        unit tests' stand-in keys)."""
        if not (isinstance(key, tuple) and len(key) == 4):
            return 0
        from ..analysis.jaxpr_audit import program_cost_bytes

        return int(program_cost_bytes(key, batch,
                                      sharded=self.sharded_phase3))

    def _evict_entry(self, pkey) -> None:
        """Drop one (bucket, B) program — LRU entry, modeled bytes, pin
        mark, and the engine's compiled executable."""
        with self._lock:
            self._programs.pop(pkey, None)
            self._bytes_total -= self._program_bytes.pop(pkey, 0)
            self._pinned.discard(pkey)
            k_old, b_old = pkey
            old_eng = self._engines.get(k_old)
            if old_eng is not None:
                old_eng.evict_program(k_old[0], b_old)
            self._c_evictions.inc()
            self._g_bytes.set(self._bytes_total)

    def _evict_to_budget(self, keep=None) -> None:
        """Evict LRU-first until both the count cap and (when set) the
        byte budget hold; pinned programs and ``keep`` are exempt."""
        with self._lock:
            def victims():
                return [p for p in self._programs
                        if p != keep and p not in self._pinned]

            while len(self._programs) > self.program_cache_max:
                vs = victims()
                if not vs:
                    break
                self._evict_entry(vs[0])
            if self.program_cache_bytes is not None:
                while self._bytes_total > self.program_cache_bytes:
                    vs = victims()
                    if not vs:
                        break
                    self._evict_entry(vs[0])

    def _account(self, key: BucketKey, batch: Optional[int]) -> bool:
        """Record a solve against the ``(bucket, B)`` program LRU;
        returns whether that program already existed (a cache hit).  A
        miss that overflows ``program_cache_max`` — or, when
        ``program_cache_bytes`` is set, the modeled byte budget — evicts
        least-recently-used unpinned programs, executable included,
        counted in ``cache_stats.evictions``."""
        with self._lock:
            pkey = (key, batch)
            hit = pkey in self._programs
            if hit:
                self._c_hits.inc()
                self._programs.move_to_end(pkey)
            else:
                self._c_misses.inc()
                self._programs[pkey] = True
                cost = self._program_cost(key, batch)
                self._program_bytes[pkey] = cost
                self._bytes_total += cost
                self._g_bytes.set(self._bytes_total)
                self._evict_to_budget(keep=pkey)
            return hit

    # ------------------------------------------------------------------
    # width ladder: pre-warmed batch programs per hot bucket
    # ------------------------------------------------------------------
    def warmed_widths(self, key: BucketKey) -> List[int]:
        """Batch widths with a live compiled program for this bucket
        (1 = the single-graph program).  The micro-batcher decomposes
        partial flushes over exactly this set, so it never triggers an
        inline compile mid-stream."""
        with self._lock:
            return sorted({1 if b is None else b
                           for (k, b) in self._programs if k == key})

    def prewarm(self, graph: Graph,
                widths: Optional[Sequence[int]] = None) -> List[int]:
        """Compile the bucket's fused programs for ``widths`` (default:
        the session ``width_ladder``) ahead of arrivals, by solving
        ``graph`` — replicated to each width — through the normal path.

        Designed to run on a background thread while the serving loop
        drains traffic: each width is compiled under the session lock but
        in-flight device runs are not blocked.  Returns the widths newly
        compiled here (already-warm widths are skipped); each one counts
        in ``cache_stats.prewarms``.
        """
        widths = self.width_ladder if widths is None else widths
        key = self.bucket_of(graph)
        compiled: List[int] = []
        for w in sorted({max(1, int(w)) for w in widths}):
            with self._lock:
                if (key, None if w == 1 else w) in self._programs:
                    continue
            with self.trace.span("prewarm", bucket=key[0], width=w):
                if w == 1:
                    self.solve(graph)
                else:
                    self.solve_batch([graph] * w)
            self._c_prewarms.inc()
            compiled.append(w)
        return compiled

    def prewarm_async(self, graph: Graph,
                      widths: Optional[Sequence[int]] = None,
                      priority: float = 0.0) -> list:
        """Enqueue :meth:`prewarm` compiles on the session's background
        compile service (:class:`repro.euler.autotune.CompileService`),
        one job per width, and return a ``CompileTicket`` per width.

        The compiles run on the dedicated compile thread *behind* live
        traffic — staged dispatch keeps program calls outside the session
        lock, so a background compile never blocks a serving dispatch —
        and each width lands in :meth:`warmed_widths` as it completes, so
        the micro-batcher upgrades partial flushes mid-session.
        Already-warm widths return completed tickets immediately.
        """
        svc = self._ensure_compile_service()
        widths = self.width_ladder if widths is None else widths
        return [svc.submit(graph, w, priority=priority)
                for w in sorted({max(1, int(w)) for w in widths})]

    def _ensure_compile_service(self):
        """The session's lazily-created background compile service."""
        from .autotune import CompileService

        with self._lock:
            if self._compile_service is None:
                self._compile_service = CompileService(self)
            return self._compile_service

    @property
    def compile_service(self):
        """The background compile service, or None if never used."""
        with self._lock:
            return self._compile_service

    # ------------------------------------------------------------------
    # byte-aware program budget: pins, explicit drops, usage (DESIGN §12)
    # ------------------------------------------------------------------
    def cache_bytes_used(self) -> int:
        """Modeled device bytes of all live cached programs."""
        with self._lock:
            return self._bytes_total

    def pin_program(self, key: BucketKey, width: int) -> bool:
        """Protect a live ``(bucket, width)`` program from LRU/byte
        eviction (autotuner policy); False if no such program is live."""
        b = None if int(width) <= 1 else int(width)
        with self._lock:
            pkey = (key, b)
            if pkey not in self._programs:
                return False
            self._pinned.add(pkey)
            return True

    def unpin_program(self, key: BucketKey, width: int) -> bool:
        """Release a pin; returns whether it was pinned."""
        b = None if int(width) <= 1 else int(width)
        with self._lock:
            pkey = (key, b)
            was = pkey in self._pinned
            self._pinned.discard(pkey)
            return was

    def pinned_programs(self) -> List[Tuple[BucketKey, int]]:
        """Live pinned programs as ``(bucket, width)`` pairs."""
        with self._lock:
            return sorted(((k, 1 if b is None else b)
                           for (k, b) in self._pinned), key=str)

    def drop_program(self, key: BucketKey, width: int) -> bool:
        """Explicitly evict one ``(bucket, width)`` program (autotuner
        policy for cold entries); pinned or absent programs are left
        alone (returns False)."""
        b = None if int(width) <= 1 else int(width)
        with self._lock:
            pkey = (key, b)
            if pkey not in self._programs or pkey in self._pinned:
                return False
            self._evict_entry(pkey)
            return True

    # ------------------------------------------------------------------
    # the ladder's feedback rung: tighten well-fitting buckets (DESIGN §12)
    # ------------------------------------------------------------------
    def cap_observations(self, e_cap: int) -> dict:
        """Max observed *raw* (pre-quantization, slack-inclusive) cap need
        per ladder field at this bucket scale — evidence for the
        autotuner's tighten decision."""
        with self._lock:
            return dict(self._field_max.get(int(e_cap), {}))

    def tighten(self, e_cap: int) -> bool:
        """Switch a bucket scale to the tight cap profile
        (:data:`repro.euler.bucket.TIGHT_DIVISORS`) for future preps.
        Graphs already memoized keep their old bucket until
        :meth:`rekey` purges the scale — the two-step split lets the
        tight bucket's programs compile (on the compile thread) before
        any serving flush re-keys onto them.  Returns False if already
        tight."""
        with self._lock:
            e = int(e_cap)
            if e in self._tight_scales:
                return False
            self._tight_scales.add(e)
            return True

    def tightened_scales(self) -> List[int]:
        with self._lock:
            return sorted(self._tight_scales)

    def rekey(self, e_cap: int) -> int:
        """Purge the prep memos of every pooled graph at this scale so
        their next solve re-buckets under the current (tight) profile;
        returns how many memo entries were purged."""
        with self._lock:
            e = int(e_cap)
            stale = [gid for gid, (_g, out) in self._prep_cache.items()
                     if out[2][0] == e]
            for gid in stale:
                self._prep_cache.pop(gid)
            return len(stale)

    # ------------------------------------------------------------------
    def solve(self, graph: Graph,
              part_of_vertex: Optional[np.ndarray] = None,
              fused: Optional[bool] = None) -> EulerResult:
        """Find an Euler circuit of ``graph``; returns :class:`EulerResult`.

        ``part_of_vertex`` overrides the built-in partitioner (e.g. for
        external partitioners or benchmark sweeps); ``fused`` overrides
        the session's device execution mode for this call.

        >>> import numpy as np
        >>> from repro.core.graph import Graph
        >>> from repro.euler import solve
        >>> square = Graph(4, np.array([0, 1, 2, 3]),
        ...                   np.array([1, 2, 3, 0]))
        >>> res = solve(square, backend="host", n_parts=1).validate()
        >>> sorted((res.circuit >> 1).tolist())   # each edge exactly once
        [0, 1, 2, 3]
        """
        t0 = time.perf_counter()
        if self.backend == "host":
            if fused is not None:
                raise ValueError(
                    "fused= is a device-backend execution mode; the host "
                    "backend has no fused/eager distinction"
                )
            return self._solve_host(graph, part_of_vertex, t0)
        fused = self.fused if fused is None else fused
        if fused:
            # dispatch + immediate wait: same one-sync semantics as ever
            return self.solve_async(graph, part_of_vertex).result()

        # ---- eager per-level oracle (synchronous by design) ----
        with self.trace.span("prepare") as prep:
            pg, tree, key = self._prepare(graph, part_of_vertex)
        eng = self._engine_for(key)
        hit = self._account(key, None)
        with self.trace.span("solve_eager", bucket=key[0], hit=hit):
            res = eng._run(pg, fused=False)
        res.graph = graph
        res.padded_edges = key[0] - graph.num_edges
        res.circuit = strip_circuit(res.circuit, graph.num_edges)
        res.cache = dataclasses.replace(self.cache_stats, bucket=key,
                                        hit=hit, batch=1)
        res.timings["prepare_s"] = prep.dur_s
        res.timings["total_s"] = time.perf_counter() - t0
        return res

    def solve_async(self, graph: Graph,
                    part_of_vertex: Optional[np.ndarray] = None,
                    ) -> PendingSolve:
        """Dispatch a fused device solve without blocking; returns a
        :class:`PendingSolve` whose ``result()`` performs the run's one
        host sync.  Device backend + fused mode only (jax dispatches the
        compiled program asynchronously, so host code — prep of the next
        request, batching decisions — overlaps device execution).

        The solve is one span tree (DESIGN.md §13): a root ``solve`` span
        with a fresh ``req`` id, and beneath it ``prepare`` (holding
        ``partition``), ``stage`` (holding ``upload``), ``launch``, then
        at ``result()`` ``wait`` and ``strip``."""
        if self.backend != "device":
            raise ValueError("solve_async is a device-backend path; the "
                             "host engine runs synchronously via solve()")
        t0 = time.perf_counter()
        root = self.trace.request("solve", width=1).start()
        with root.scope():
            with self._lock:
                with self.trace.span("prepare") as prep:
                    pg, tree, key = self._prepare(graph, part_of_vertex)
                eng = self._engine_for(key)
                hit = self._account(key, None)
                staged = eng._stage(pg, resident=self.device_resident)
            # program call OUTSIDE the session lock: a cold program
            # compiles here, so background prewarm compiles (the compile
            # service) never block a concurrent serving dispatch (DESIGN.md
            # §12).  A miss's launch time ≈ compile time (the span feeds
            # euler_compile_seconds).
            with self.trace.span("launch",
                                 metric=None if hit else self._h_compile,
                                 bucket=key[0], width=1, hit=hit):
                run = eng._launch(staged, t0)
        root.set(bucket=key[0], hit=hit)
        return PendingSolve(self, run, [graph], key, hit, t0, prep.dur_s,
                            1, root)

    def solve_batch(self, graphs: Iterable[Graph],
                    fused: Optional[bool] = None) -> List[EulerResult]:
        """Solve B same-bucket graphs as ONE batched fused device program.

        All graphs must map to the same shape bucket
        (:meth:`bucket_of`) — same padded edge count, merge-tree height,
        and rounded caps — so the batch stacks into one static-shape
        program; mixed buckets raise ``ValueError`` rather than padding
        everything up to the largest member (DESIGN.md §8 explains the
        trade).  Results are byte-identical to per-graph :meth:`solve`
        calls and are returned in input order.

        The batched program is compiled once per ``(bucket, B)`` and
        cached; a single-element batch delegates to :meth:`solve` (no
        separate program).  Device backend + fused mode only.
        """
        graphs = list(graphs)
        if not graphs:
            return []
        if self.backend != "device":
            raise ValueError(
                "solve_batch is a device-backend path (the host reference "
                "engine solves one graph at a time); use solve_many"
            )
        fused = self.fused if fused is None else fused
        if not fused:
            raise ValueError(
                "solve_batch requires the fused execution mode; the eager "
                "per-level oracle is single-graph by design"
            )
        if len(graphs) == 1:
            return [self.solve(graphs[0], fused=True)]
        return self.solve_batch_async(graphs).results()

    def solve_batch_async(self, graphs: Iterable[Graph]) -> PendingSolve:
        """Dispatch B same-bucket graphs as ONE batched fused program
        without blocking (the async form of :meth:`solve_batch`; same
        same-bucket requirement, same byte-identical results from
        ``results()``)."""
        graphs = list(graphs)
        if not graphs:
            raise ValueError("empty batch")
        if self.backend != "device":
            raise ValueError("solve_batch_async is a device-backend path")
        if len(graphs) == 1:
            return self.solve_async(graphs[0])
        t0 = time.perf_counter()
        B = len(graphs)
        root = self.trace.request("solve_batch", width=B).start()
        with root.scope():
            with self._lock:
                with self.trace.span("prepare", width=B) as prep:
                    preps = [self._prepare(g, None) for g in graphs]
                keys = {p[2] for p in preps}
                if len(keys) > 1:
                    raise ValueError(
                        f"solve_batch needs same-bucket graphs, got "
                        f"{len(keys)} distinct buckets; group with "
                        f"bucket_of() or use solve_many(batch=...)"
                    )
                key = preps[0][2]
                eng = self._engine_for(key)
                hit = self._account(key, B)
                staged = eng._stage_batch([p[0] for p in preps])
            # see solve_async: compile/dispatch happens outside the lock
            with self.trace.span("launch",
                                 metric=None if hit else self._h_compile,
                                 bucket=key[0], width=B, hit=hit):
                run = eng._launch(staged, t0)
        root.set(bucket=key[0], hit=hit)
        return PendingSolve(self, run, graphs, key, hit, t0, prep.dur_s,
                            B, root)

    def solve_many(self, graphs: Iterable[Graph],
                   fused: Optional[bool] = None,
                   batch: Optional[int] = None) -> List[EulerResult]:
        """Solve a stream of graphs through the persistent session; every
        same-bucket graph after the first reuses the compiled program.

        With ``batch=B > 1`` (device backend, fused mode), graphs are
        grouped by shape bucket and each group runs through
        :meth:`solve_batch` in full chunks of B — one program dispatch
        per chunk instead of one per graph — with results returned in
        input order, byte-identical to the sequential path.  Leftover
        chunks smaller than B run per-graph on the warmed single-graph
        program rather than compiling a one-off ``(bucket, B′)``
        program (the same policy as the serving micro-batcher,
        DESIGN.md §8).  The host backend ignores ``batch`` (it has no
        compiled programs to amortize).
        """
        graphs = list(graphs)
        if batch is None or batch <= 1 or self.backend == "host":
            return [self.solve(g, fused=fused) for g in graphs]
        by_bucket: dict = {}
        for i, g in enumerate(graphs):
            by_bucket.setdefault(self.bucket_of(g), []).append(i)
        out: List[Optional[EulerResult]] = [None] * len(graphs)
        for idxs in by_bucket.values():
            for j in range(0, len(idxs), batch):
                chunk = idxs[j:j + batch]
                if len(chunk) == batch:
                    solved = self.solve_batch([graphs[i] for i in chunk],
                                              fused=fused)
                else:
                    solved = [self.solve(graphs[i], fused=fused)
                              for i in chunk]
                for i, res in zip(chunk, solved):
                    out[i] = res
        return out

    # ------------------------------------------------------------------
    def _solve_host(self, graph: Graph,
                    part_of_vertex: Optional[np.ndarray],
                    t0: float) -> EulerResult:
        part = self._partition(graph, part_of_vertex)
        pg = partition_graph(graph, part)
        eng = HostEngine(pg, remote_dedup=self.remote_dedup,
                         deferred_transfer=self.deferred_transfer)
        with self.trace.span("solve_host", edges=graph.num_edges):
            res = eng._run()
        res.timings["total_s"] = time.perf_counter() - t0
        return res


# ---------------------------------------------------------------------------
# module-level one-shot entry points
# ---------------------------------------------------------------------------

def solve(graph: Graph, part_of_vertex: Optional[np.ndarray] = None,
          **opts) -> EulerResult:
    """One-shot ``EulerSolver(**opts).solve(graph)``.

    >>> import numpy as np
    >>> from repro.core.graph import Graph
    >>> g = Graph(3, np.array([0, 1, 2]), np.array([1, 2, 0]))
    >>> solve(g, backend="host", n_parts=1).validate().valid
    True
    """
    return EulerSolver(**opts).solve(graph, part_of_vertex=part_of_vertex)


def solve_many(graphs: Iterable[Graph], batch: Optional[int] = None,
               **opts) -> List[EulerResult]:
    """One-shot session over a stream of graphs (shared program cache);
    ``batch=B`` micro-batches same-bucket graphs through one fused
    program per chunk (see :meth:`EulerSolver.solve_many`)."""
    return EulerSolver(**opts).solve_many(graphs, batch=batch)


def solve_batch(graphs: Iterable[Graph], **opts) -> List[EulerResult]:
    """One-shot ``EulerSolver(**opts).solve_batch(graphs)`` — B
    same-bucket graphs in ONE batched fused device program (DESIGN.md
    §8)."""
    return EulerSolver(**opts).solve_batch(graphs)
