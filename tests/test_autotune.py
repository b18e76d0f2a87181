"""Adaptive serving autotuner (DESIGN.md §12): bounded flush accounting,
compile-service drain ordering / dedupe / error isolation, the pure
ladder policy (`plan`) on deterministic histogram fixtures, byte-aware
program-cache budgeting with pins, the micro-batcher's mid-session
width upgrade, and an end-to-end device session (async prewarm lands →
flushes upgrade → results byte-equal → audit accepts the warmed set →
tighten/rekey byte-equal)."""
import threading

import numpy as np
import pytest

from conftest import run_with_devices

from repro.euler import EulerSolver
from repro.euler.autotune import (AutoTuner, BucketStats, CompileService,
                                  FlushLog, TunerParams, TunerSnapshot,
                                  ladder_decompose, plan)
from repro.launch.serve import MicroBatcher


# ---------------------------------------------------------------------------
# FlushLog: bounded accounting
# ---------------------------------------------------------------------------

def test_flush_log_is_bounded_and_tracks_first_wide():
    t = [0.0]
    log = FlushLog(recent_max=4, clock=lambda: t[0])
    for i in range(100):
        t[0] = float(i)
        log.observe(1)
    assert log.first_wide_t is None and log.narrow_before_wide == 100
    t[0] = 100.0
    log.observe(8)
    t[0] = 101.0
    log.observe(8)
    for i in range(100):
        log.observe(1)
    # histogram + rolling window stay O(#widths + recent_max) forever
    assert log.hist == {1: 200, 8: 2}
    assert list(log.recent) == [1, 1, 1, 1]
    assert log.total == len(log) == 202 and log.requests == 216
    # first-wide marker is sticky: set once, at the 8-wide dispatch
    assert log.first_wide_t == 100.0 and log.narrow_before_wide == 100
    assert log.widths() == [1, 8]
    assert log.mean_width() == pytest.approx(216 / 202)


# ---------------------------------------------------------------------------
# CompileService: ordering, dedupe, error isolation (no jax, no devices)
# ---------------------------------------------------------------------------

class _SvcSolver:
    """Minimal compile-service target: buckets by graph identity, records
    every prewarm/rekey in arrival order."""

    def __init__(self):
        self.warm: dict = {}
        self.log: list = []
        self._lk = threading.Lock()

    def bucket_of(self, graph):
        return graph

    def warmed_widths(self, key):
        with self._lk:
            return sorted(self.warm.get(key, set()))

    def prewarm(self, graph, widths):
        if graph == "boom":
            raise RuntimeError("compile exploded")
        out = []
        with self._lk:
            ws = self.warm.setdefault(self.bucket_of(graph), set())
            for w in widths:
                if w not in ws:
                    ws.add(w)
                    out.append(w)
            self.log.append(("prewarm", graph, tuple(widths)))
        return out

    def rekey(self, e_cap):
        with self._lk:
            self.log.append(("rekey", e_cap))
        return 1


def test_compile_service_drains_by_priority_then_fifo():
    solver = _SvcSolver()
    svc = CompileService(solver, start=False)   # deterministic: queue first
    svc.submit("a", 2, priority=1.0)
    svc.submit("b", 2, priority=5.0)
    svc.submit("c", 2, priority=1.0)            # ties drain FIFO
    svc.submit_retune("d", 128, [2])            # default 1e9: jumps the queue
    assert svc.pending_jobs() == 4 and not svc.idle()
    svc.start()
    assert svc.join(timeout=30)
    assert solver.log == [
        ("rekey", 128), ("prewarm", "d", (1,)), ("prewarm", "d", (2,)),
        ("prewarm", "b", (2,)),
        ("prewarm", "a", (2,)), ("prewarm", "c", (2,)),
    ]
    assert svc.idle() and svc.pending_jobs() == 0
    assert svc.prewarms == 5                    # d×2 + b + a + c
    svc.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        svc.submit("a", 4)


def test_compile_service_dedupes_and_skips_warm_widths():
    solver = _SvcSolver()
    svc = CompileService(solver, start=False)
    t1 = svc.submit("a", 2)
    t2 = svc.submit("a", 2)                     # still queued → same ticket
    assert t1 is t2 and svc.pending_jobs() == 1
    solver.warm["b"] = {2}
    t3 = svc.submit("b", 2)                     # already warm → done now
    assert t3.done() and t3 is not t1 and svc.pending_jobs() == 1
    svc.start()
    assert t1.wait(timeout=30) and t1.error is None and t1.widths == [2]
    t4 = svc.submit("a", 2)                     # warm after drain → done now
    assert t4.done() and t4 is not t1
    svc.stop()


def test_compile_service_isolates_job_errors():
    solver = _SvcSolver()
    svc = CompileService(solver, start=False)
    bad = svc.submit("boom", 2)
    good = svc.submit("a", 2)
    svc.start()
    assert svc.join(timeout=30)
    assert bad.done() and isinstance(bad.error, RuntimeError)
    assert bad.widths == []
    # the worker survives the failed compile and runs the next job
    assert good.error is None and good.widths == [2]
    assert svc.prewarms == 1
    svc.stop()


def test_failed_compile_job_stops_batcher_and_tuner():
    # a failed background compile is an error of the serving loop, not a
    # silent fall back to B=1
    solver = _SvcSolver()
    svc = CompileService(solver, start=False)
    solver.compile_service = svc
    batcher = MicroBatcher(solver, max_batch=4, deadline_s=60.0)
    tuner = AutoTuner(solver, service=svc, max_batch=4)
    assert batcher.poll() == [] and batcher.drain() == []
    bad = svc.submit("boom", 2)
    svc.start()
    assert svc.join(timeout=30) and bad.error is not None
    assert svc.failed == [bad]
    with pytest.raises(RuntimeError, match="background compile") as ei:
        batcher.submit(1, "a")
    assert isinstance(ei.value.__cause__, RuntimeError)
    with pytest.raises(RuntimeError, match="background compile"):
        batcher.poll()
    with pytest.raises(RuntimeError, match="background compile"):
        batcher.drain()
    with pytest.raises(RuntimeError, match="background compile"):
        tuner.step(force=True)
    svc.stop()


def test_main_euler_raises_when_detached_prewarm_fails(monkeypatch,
                                                       tmp_path):
    from repro.launch import serve

    def boom(self, graph, widths=None):
        raise RuntimeError("prewarm exploded")

    monkeypatch.setattr(EulerSolver, "prewarm", boom)
    # JAX reads the variable only at start-up: the helper places nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(RuntimeError, match="prewarm failed") as ei:
        serve.main_euler(["--scale", "5", "--parts", "1", "--pool", "1",
                          "--requests", "2", "--max-batch", "2",
                          "--widths", "1,2", "--sync-prewarm"])
    assert "prewarm exploded" in str(ei.value.__cause__)


# ---------------------------------------------------------------------------
# the pure policy: deterministic histogram fixtures → expected orders
# ---------------------------------------------------------------------------

K = (512, 8)        # plan() only reads key[0]=e_cap, key[1]=n_parts
K2 = (1024, 8)


def test_plan_prewarms_ladder_widths_by_flush_benefit():
    snap = TunerSnapshot(
        buckets={K: BucketStats(mass=10.0, flushes={4: 5.0, 1: 2.0}),
                 K2: BucketStats(mass=0.1, flushes={4: 9.0})},  # < min_mass
        warmed={K: [1], K2: [1]},
        pinned=[], max_batch=4,
    )
    dec = plan(snap)
    # hot bucket's quota width, priority = 5.0 flush-mass × (4-1)/4
    assert dec.prewarm == [(K, 4, pytest.approx(3.75))]
    # the only warmed program with benefit is the hot B=1 fallback
    assert dec.pin == [(K, 1)]
    assert dec.unpin == [] and dec.evict == [] and dec.tighten == []
    # cold bucket ordered nothing (mass below min_mass)
    assert all(key != K2 for key, _, _ in dec.prewarm)


def test_plan_partial_flush_decomposition_and_prewarm_cap():
    # 7-deep flushes on an 8-quota ladder decompose 7 → [4, 2, 1]:
    # both intermediate widths get prewarm orders, amortization-ranked
    snap = TunerSnapshot(
        buckets={K: BucketStats(mass=4.0, flushes={7: 4.0})},
        warmed={K: [1]}, pinned=[], max_batch=8,
    )
    assert ladder_decompose(7, 8) == [4, 2, 1]
    dec = plan(snap)
    assert [(k, w) for k, w, _ in dec.prewarm] == [(K, 4), (K, 2)]
    pri = {w: p for _, w, p in dec.prewarm}
    assert pri[4] == pytest.approx(4.0 * 3 / 4)
    assert pri[2] == pytest.approx(4.0 * 1 / 2)
    # max_prewarms caps orders per step across many hot buckets
    many = {(64 * (i + 1), 8): BucketStats(mass=2.0, flushes={4: 2.0})
            for i in range(10)}
    dec = plan(TunerSnapshot(buckets=many,
                             warmed={k: [1] for k in many},
                             pinned=[], max_batch=4),
               TunerParams(max_prewarms=3))
    assert len(dec.prewarm) == 3


def test_plan_pins_top_programs_and_unpins_stale_ones():
    snap = TunerSnapshot(
        buckets={K: BucketStats(mass=10.0, flushes={4: 6.0}),
                 K2: BucketStats(mass=0.01)},
        warmed={K: [1, 4], K2: [1]},
        pinned=[(K2, 1)],            # pinned while hot, now cold
        max_batch=4,
    )
    dec = plan(snap)
    assert set(dec.pin) == {(K, 4), (K, 1)}
    assert dec.unpin == [(K2, 1)]


def test_plan_evicts_cold_buckets_only_under_byte_pressure():
    buckets = {K: BucketStats(mass=10.0, flushes={4: 6.0}),
               K2: BucketStats(mass=0.01)}          # below evict_mass
    warmed = {K: [1, 4], K2: [1, 2]}
    cold = TunerSnapshot(buckets=dict(buckets), warmed=dict(warmed),
                         pinned=[], max_batch=4,
                         bytes_used=50, bytes_budget=100)
    assert plan(cold).evict == []                   # under hi_water: keep
    hot = TunerSnapshot(buckets=dict(buckets), warmed=dict(warmed),
                        pinned=[], max_batch=4,
                        bytes_used=95, bytes_budget=100)
    dec = plan(hot)
    assert dec.evict == [(K2, 2), (K2, 1)]          # widest first, cold only
    assert all(key != K for key, _ in dec.evict)
    nb = TunerSnapshot(buckets=dict(buckets), warmed=dict(warmed),
                       pinned=[], max_batch=4, bytes_used=10 ** 9)
    assert plan(nb).evict == []                     # no budget → no pressure


def test_plan_tightens_only_wasteful_buckets_that_fit_tight_floors():
    kt = (128, 8)
    fits = {"park_cap": 10, "touch_cap": 50}        # tight floors: 16 / 64
    base = dict(buckets={kt: BucketStats(mass=5.0, flushes={1: 3.0})},
                warmed={kt: [1]}, pinned=[], max_batch=4)
    dec = plan(TunerSnapshot(waste={kt: 2.0}, field_max={128: fits}, **base))
    assert dec.tighten == [128]
    # measured waste under threshold → caps already fine
    dec = plan(TunerSnapshot(waste={kt: 1.1}, field_max={128: fits}, **base))
    assert dec.tighten == []
    # an observed need above a tight floor → tightening would break members
    toobig = {"park_cap": 20, "touch_cap": 50}
    dec = plan(TunerSnapshot(waste={kt: 2.0}, field_max={128: toobig},
                             **base))
    assert dec.tighten == []
    # already tightened → never re-ordered
    dec = plan(TunerSnapshot(waste={kt: 2.0}, field_max={128: fits},
                             tightened={128}, **base))
    assert dec.tighten == []


# ---------------------------------------------------------------------------
# AutoTuner: observations → decisions → applied orders (fake solver)
# ---------------------------------------------------------------------------

class _TunerSolver(_SvcSolver):
    """Adds the snapshot/apply surface AutoTuner reads and writes.  Every
    graph lands in bucket ``K`` so the tuner's histogram key, the compile
    service's job key, and the warm set all line up like the real
    solver's ``bucket_of``."""

    def __init__(self):
        super().__init__()
        self.program_cache_bytes = None
        self.bucket_waste: dict = {}
        self.slack = 1.3
        self.pins: set = set()

    def bucket_of(self, graph):
        return K

    def pinned_programs(self):
        return sorted(self.pins, key=str)

    def cache_bytes_used(self):
        return 0

    def cap_observations(self, e_cap):
        return {}

    def tightened_scales(self):
        return []

    def pin_program(self, key, w):
        self.pins.add((key, w))
        return True

    def unpin_program(self, key, w):
        self.pins.discard((key, w))
        return True

    def drop_program(self, key, w):
        self.log.append(("drop", key, w))
        return True


def test_autotuner_step_orders_prewarms_from_observations():
    solver = _TunerSolver()
    svc = CompileService(solver, start=False)
    t = [0.0]
    tuner = AutoTuner(solver, service=svc, max_batch=4,
                      clock=lambda: t[0])
    g = "g-rep"
    for i in range(8):
        tuner.observe_arrival(K, g)
    tuner.observe_flush(K, 4)
    tuner.observe_flush(K, 4)
    dec = tuner.step()
    assert dec is not None and [(k, w) for k, w, _ in dec.prewarm] == [(K, 4)]
    # the rep graph was handed to the compile service
    assert svc.pending_jobs() == 1
    # rate limit: an immediate second step is skipped, force overrides
    assert tuner.step() is None
    assert tuner.step(force=True) is not None
    assert tuner.steps == 2
    svc.start()
    assert svc.join(timeout=30)
    assert solver.warmed_widths(K) == [4]
    # with B=4 warm the policy pins it; stats reflect the session
    t[0] = 1.0
    tuner.observe_flush(K, 4)
    dec = tuner.step()
    assert (K, 4) in dec.pin and (K, 4) in solver.pins
    st = tuner.stats()
    assert st["async_prewarms"] == 1 and st["tuner_buckets"] == 1
    assert st["pinned"] == 1 and st["prewarm_queue"] == 0
    tuner.close()


def test_autotuner_decay_forgets_cold_buckets():
    solver = _TunerSolver()
    svc = CompileService(solver, start=False)
    t = [0.0]
    tuner = AutoTuner(solver, service=svc, max_batch=4,
                      params=TunerParams(decay_tau=1.0, min_interval=0.0),
                      clock=lambda: t[0])
    tuner.observe_arrival(K, "g")
    tuner.observe_flush(K, 4)
    tuner.step()
    # still hot: the policy re-orders the prewarm (the service dedupes
    # the still-queued job, not the policy)
    assert tuner.step(force=True).prewarm
    t[0] = 20.0                            # 20 time constants later
    dec = tuner.step()
    assert dec is not None and dec.prewarm == []   # mass decayed below floor
    tuner.close()


# ---------------------------------------------------------------------------
# byte-aware program budget + pinning on the real solver (host-side)
# ---------------------------------------------------------------------------

def test_program_cache_byte_budget_evicts_lru_but_not_pinned():
    solver = EulerSolver(n_parts=1, program_cache_max=10,
                         program_cache_bytes=25)
    solver._program_cost = lambda key, batch: 10    # 10 bytes/program
    k1, k2, k3 = ("b1",), ("b2",), ("b3",)
    solver._account(k1, None)
    assert solver.pin_program(k1, 1)                # live → pinnable
    solver._account(k2, None)
    assert solver.cache_bytes_used() == 20
    solver._account(k3, None)                       # 30 > 25: evict LRU...
    assert solver.cache_bytes_used() == 20
    # ...but the pinned k1 survives; unpinned k2 went instead
    assert solver.warmed_widths(k1) == [1]
    assert solver.warmed_widths(k2) == []
    assert solver.warmed_widths(k3) == [1]
    assert solver.pinned_programs() == [(k1, 1)]
    assert solver.cache_stats.evictions == 1
    # unpin → droppable; drop_program refuses pinned entries
    assert not solver.drop_program(k1, 1)
    assert solver.unpin_program(k1, 1)
    assert solver.drop_program(k1, 1)
    assert solver.warmed_widths(k1) == []
    # pinning a program that isn't live fails cleanly
    assert not solver.pin_program(("nope",), 1)


def test_tighten_is_one_way_and_rekey_purges_scale():
    solver = EulerSolver(n_parts=1)
    assert solver.tightened_scales() == []
    assert solver.tighten(256)
    assert not solver.tighten(256)                  # idempotent
    assert solver.tightened_scales() == [256]
    assert solver.rekey(256) == 0                   # nothing memoized yet


# ---------------------------------------------------------------------------
# MicroBatcher: mid-session width upgrade driven by warmed_widths
# ---------------------------------------------------------------------------

def test_micro_batcher_upgrades_flush_width_when_prewarm_lands():
    from test_batched import _Clock, _FakeSolver

    class _Obs:
        def __init__(self):
            self.arrivals: list = []
            self.flushes: list = []

        def observe_arrival(self, key, graph=None):
            self.arrivals.append(key)

        def observe_flush(self, key, n):
            self.flushes.append((key, n))

    solver = _FakeSolver()          # warmed = [] → only B=1 available
    obs = _Obs()
    clock = _Clock()
    mb = MicroBatcher(solver, max_batch=4, deadline_s=0.010, clock=clock,
                      autotuner=obs)
    from repro.core.graph import Graph
    v = np.arange(4, dtype=np.int64)
    graphs = [Graph(4, v, np.roll(v, -1)) for _ in range(8)]

    for i in range(4):
        mb.submit(i, graphs[i])     # quota flush, nothing warm → 4× B=1
    assert list(mb.flushes.recent) == [1, 1, 1, 1]
    # "async prewarm lands": the warm set grows mid-session…
    solver.warmed = [4]
    for i in range(4, 8):
        mb.submit(i, graphs[i])
    # …and the very next quota flush upgrades to one B=4 dispatch
    assert list(mb.flushes.recent) == [1, 1, 1, 1, 4]
    # the batcher fed the tuner every arrival and both flush sizes
    assert len(obs.arrivals) == 8
    assert obs.flushes == [(4, 4), (4, 4)]


# ---------------------------------------------------------------------------
# end-to-end on the device mesh: async prewarm → upgraded flushes are
# byte-equal, audit accepts the warmed set, tighten/rekey stays byte-equal
# ---------------------------------------------------------------------------

def test_adaptive_session_upgrades_and_stays_byte_equal():
    out = run_with_devices("""
        import numpy as np
        from repro.analysis.jaxpr_audit import audit_graph
        from repro.euler import EulerSolver
        from repro.euler.autotune import AutoTuner, TunerParams
        from repro.graphgen.eulerize import eulerian_rmat
        from repro.launch.serve import MicroBatcher

        solver = EulerSolver(n_parts=8)
        buckets = {}
        for s in range(40):
            g = eulerian_rmat(5, avg_degree=5, seed=s)
            buckets.setdefault(solver.bucket_of(g), []).append(g)
        key, group = max(buckets.items(), key=lambda kv: len(kv[1]))
        assert len(group) >= 4, f"modal bucket holds {len(group)} < 4"
        group = group[:4]

        tuner = AutoTuner(solver, max_batch=2,
                          params=TunerParams(min_interval=0.0))
        mb = MicroBatcher(solver, max_batch=2, deadline_s=0.0,
                          autotuner=tuner)

        # cold session start: nothing warmed, first flushes run at B=1.
        # submit() hands back whatever the pipeline already completed
        done = {}
        for i in (0, 1):
            done.update(mb.submit(i, group[i]))
        done.update(mb.drain())
        assert list(mb.flushes.recent) == [1, 1], mb.flushes.hist
        # the flush histogram drove a B=2 prewarm order onto the
        # background compile service; wait for it to land
        dec = tuner.step(force=True)
        assert [(k, w) for k, w, _ in dec.prewarm] == [(key, 2)], dec
        assert tuner.service.join(timeout=600)
        assert solver.warmed_widths(key) == [1, 2]
        assert tuner.service.prewarms == 1

        # mid-session upgrade: the same bucket's next quota flush now
        # dispatches one B=2 program
        for i in (2, 3):
            done.update(mb.submit(i, group[i]))
        done.update(mb.drain())
        assert list(mb.flushes.recent) == [1, 1, 2], mb.flushes.hist
        assert done[2].cache.batch == 2

        # upgraded flushes are byte-equal to fresh sequential solves
        fresh = EulerSolver(n_parts=8)
        for i, g in enumerate(group):
            ref = fresh.solve(g)
            assert (done[i].circuit == ref.circuit).all(), i
            assert (done[i].mate == ref.mate).all(), i

        # the audit accepts the adaptive program set as-is
        rep = audit_graph(solver, group[0], widths="warmed")
        assert rep["ok"], rep
        assert set(rep["cache_budget"]["per_program_bytes"]) == {"B1", "B2"}
        assert rep["cache_budget"]["total_bytes"] > 0

        # feedback rung: tighten + rekey on the compile thread, then the
        # re-keyed tight bucket still solves byte-identically
        e_cap = key[0]
        tk = tuner.service.submit_retune(group[0], e_cap, [2])
        assert tk.wait(timeout=600) and tk.error is None, tk.error
        assert solver.tighten(e_cap)
        solver.rekey(e_cap)
        tight = solver.solve(group[0])
        tkey = tight.cache.bucket
        assert tkey[3].park_cap <= key[3].park_cap
        ref = fresh.solve(group[0])
        assert (tight.circuit == ref.circuit).all()
        assert (tight.mate == ref.mate).all()
        tuner.close()
        print("ADAPTIVE_SESSION_OK", mb.flushes.hist, tkey[0])
    """, timeout=1800)
    assert "ADAPTIVE_SESSION_OK" in out
