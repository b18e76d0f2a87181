"""E1/E6 — paper Fig. 5 + superstep comparison, plus fused-vs-eager and
warm serving throughput, all through the ``repro.euler`` facade.

Weak-ish scaling series (graph size ∝ partitions, scaled down from the
paper's G20/P2…G50/P8 to CPU-feasible sizes), reporting total engine time,
user (Phase-1) compute time, supersteps, and the Makki-baseline
coordination costs the paper argues against (§2.2).

The device series runs the distributed engine both ways on the same graph
and mesh: the scan-fused whole-run program (one compile, one host sync)
vs the eager per-level loop (one program call + one log sync per level).
Wall-clock excludes compile (each path is warmed once first).

The serving series measures the headline multi-graph path: ``solve_many``
over a pool of same-scale request graphs through one solver session —
the shape-bucket program cache makes every post-warmup solve retrace-free
— reported as warm circuits/s next to the compile counts.

The batched-serving series sweeps the micro-batch width B over one
modal-bucket pool: B same-bucket graphs per ``solve_batch`` call run as
ONE fused device program (DESIGN.md §8), so circuits/s rises with B as
per-program dispatch, collective-rendezvous, and host-sync overheads
amortize — the acceptance target is B=8 ≥ 2× B=1 even on the
simulated CPU mesh.  On a 2-core host the sequential baseline is
dispatch-noise-limited (observed B=8/B=1 ratios 1.9–2.9× across
processes, ≈2.0–2.4× typical); beefier hosts amortize more, since the
batched program's wider ops also gain intra-op parallelism the tiny
sequential ops cannot use.
"""
from __future__ import annotations

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import numpy as np

from repro.core.graph import partition_graph
from repro.core.makki import makki_tour
from repro.euler import EulerSolver, solve
from repro.graphgen.eulerize import eulerian_rmat
from repro.graphgen.partition import partition_vertices

SERIES = [  # (scale, parts) — mirrors G20/P2, G30/P3, G40/P4, G40/P8
    (12, 2), (13, 3), (14, 4), (14, 8),
]

DEVICE_SERIES = [  # (scale, parts) — ≥2 graph scales, fused vs eager
    (9, 8), (11, 8),
]

SERVE_SERIES = [  # (scale, parts, pool size) — warm-solve throughput
    (9, 8, 8), (11, 8, 4),
]

BATCHED_SERIES = [  # (scale, parts, avg degree, widths) — batched serving
    (5, 8, 3, (1, 2, 4, 8)),
]

LADDER_SERIES = [  # (scale, parts, avg degree, pool, max_batch, passes)
    (5, 8, 4, 16, 4, 3),
]

AUTOTUNE_SERIES = [  # (scale, parts, avg degree, pool, max_batch, passes)
    (5, 8, 4, 16, 4, 3),
]

PHASE3_SERIES = [  # (scale, parts) — replicated vs sharded Phase 3
    (9, 8), (11, 8),
]


def run(series=SERIES, seed=0):
    rows = []
    for scale, parts in series:
        g = eulerian_rmat(scale, avg_degree=5, seed=seed + scale)
        part = partition_vertices(g, parts, seed=seed)
        pg = partition_graph(g, part)
        t0 = time.perf_counter()
        # §5 heuristics off: the paper's baseline configuration.  total_s
        # spans the facade solve (partition annotation + engine init +
        # run, ms-scale prep on top of the old engine-only window).
        res = solve(g, part_of_vertex=part, backend="host", n_parts=parts,
                    remote_dedup=False, deferred_transfer=False).validate()
        total = time.perf_counter() - t0
        user = sum(sum(ls.phase1_seconds.values()) for ls in res.levels)
        mk = makki_tour(pg)
        rows.append({
            "graph": f"V{g.num_vertices//1000}k/P{parts}",
            "V": g.num_vertices, "E": g.num_edges,
            "cut%": round(100 * pg.cut_fraction(), 1),
            "imbal%": round(100 * pg.vertex_imbalance(), 1),
            "total_s": round(total, 2),
            "user_s": round(user, 2),
            "supersteps": res.supersteps,
            "makki_vertex_supersteps": mk.supersteps_vertex_centric,
            "makki_partition_supersteps": mk.supersteps_partition_centric,
        })
    return rows


def run_device(series=DEVICE_SERIES, seed=0, repeats=3):
    """Fused vs eager wall-clock on the simulated device mesh."""
    rows = []
    for scale, parts in series:
        g = eulerian_rmat(scale, avg_degree=5, seed=seed + scale)
        solver = EulerSolver(n_parts=parts, partition_seed=seed)

        def timed(fused):
            solver.solve(g, fused=fused)                   # warm/compile
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                solver.solve(g, fused=fused)
                best = min(best, time.perf_counter() - t0)
            return best

        t_fused = timed(True)
        t_eager = timed(False)
        res = solver.solve(g).validate()
        rows.append({
            "graph": f"s{scale}/P{parts}",
            "V": g.num_vertices, "E": g.num_edges,
            # the solved problem is the bucket-padded graph — report it
            "E_cap": res.cache.bucket[0],
            "levels": res.supersteps,
            "fused_s": round(t_fused, 3),
            "eager_s": round(t_eager, 3),
            "speedup": round(t_eager / t_fused, 2),
        })
    return rows


def run_serving(series=SERVE_SERIES, seed=0):
    """Warm-solve throughput of ``solve_many`` over a request-graph pool
    (the shape-bucketed serving path): circuits/s after the session's
    buckets are compiled, plus compile/hit accounting."""
    rows = []
    for scale, parts, pool_n in series:
        pool = [eulerian_rmat(scale, avg_degree=5, seed=seed + 37 * i)
                for i in range(pool_n)]
        solver = EulerSolver(n_parts=parts, partition_seed=seed)
        solver.solve_many(pool)                            # warm every bucket
        t0 = time.perf_counter()
        results = solver.solve_many(pool)
        dt = time.perf_counter() - t0
        results[0].validate()
        cs = solver.cache_stats
        rows.append({
            "graph": f"s{scale}/P{parts}",
            "pool": pool_n,
            "E≈": pool[0].num_edges,
            "warm_s": round(dt, 3),
            "circuits/s": round(pool_n / max(dt, 1e-9), 2),
            "compiles": cs.compiles,
            "hits": cs.hits,
        })
    return rows


def run_batched(series=BATCHED_SERIES, seed=0, reps=5):
    """Micro-batched serving throughput: warm circuits/s of an 8-graph
    modal-bucket pool solved in chunks of B through one ``solve_batch``
    program per chunk, for each batch width B.  One row per (graph
    scale, B); ``x_vs_B1`` is the headline amortization multiple.

    Timing is the *median* over ``reps`` pool passes, with the widths'
    passes interleaved in one measurement window: dispatch-heavy
    sequential (B=1) passes are much noisier than batched passes on an
    oversubscribed CPU host (thread-placement modes can swing them
    2–3×), so interleaving samples every width under the same host
    conditions and the median keeps outlier passes from skewing the
    ratio either way."""
    from repro.euler import modal_bucket_pool

    rows = []
    for scale, parts, deg, widths in series:
        solver = EulerSolver(n_parts=parts, partition_seed=seed)
        pool = modal_bucket_pool(
            solver,
            (eulerian_rmat(scale, avg_degree=deg, seed=seed + s)
             for s in range(80)),
            8,
        )
        if len(pool) < 8:
            continue  # no modal bucket wide enough at this scale
        key = solver.bucket_of(pool[0])
        compiles = {}
        for B in widths:                                   # compile pass
            before = solver.cache_stats.compiles
            solver.solve_many(pool, batch=B)[0].validate()
            compiles[B] = solver.cache_stats.compiles - before
        times = {B: [] for B in widths}
        for _ in range(reps):
            for B in widths:
                t0 = time.perf_counter()
                solver.solve_many(pool, batch=B)
                times[B].append(time.perf_counter() - t0)
        base = None
        for B in widths:
            dt = float(np.median(times[B]))
            cps = len(pool) / max(dt, 1e-9)
            base = base or cps
            rows.append({
                "graph": f"s{scale}/P{parts}",
                "E_cap": key[0],
                "B": B,
                "warm_s": round(dt, 3),
                "circuits/s": round(cps, 2),
                "x_vs_B1": round(cps / base, 2),
                "compiles": compiles[B],
            })
    return rows


def run_ladder(series=LADDER_SERIES, seed=0):
    """Warm-path serving ladder (DESIGN.md §9): a *heterogeneous*
    same-scale pool served by the PR 3 synchronous driver configuration
    (independent pow2-per-field bucket keys, B=1 partial flushes, sync
    dispatch) vs the PR 6 pipeline (quantized cap/level ladder, width-
    laddered partial flushes, depth-2 async dispatch).  One row per
    config; ``x_vs_pr3`` on the ladder row is the headline acceptance
    multiple (target ≥ 1.5×).

    ``circuits/s`` is *session* throughput: the clock spans the cold
    pass, width prewarm, and the serving loop.  Program compiles are
    real serving cost — a fresh tier answers no requests while XLA
    compiles — and they are exactly what the bucket ladder collapses
    (this pool: 10 PR 3 buckets → 3 ladder buckets, at ~12s/compile).
    ``steady_circuits/s`` isolates the post-warmup loop for comparison;
    on this 1-core CI host the 8 simulated devices time-share one core,
    so vmap batching amortizes dispatch but not compute and the steady
    gap is modest — on a multi-core host or real accelerator the steady
    term adds (see ``run_batched``: B=8 ≈ 2× on 2 cores).

    The arrival loop bounds outstanding submissions at the pool size, so
    the PR 3 config serves the way the PR 3 driver really did on this
    pool: its fragmented buckets never fill the batch quota and every
    flush falls back to B=1 loops, while the ladder config's modal
    bucket accumulates quota/ladder-width batches.

    Straggler note: the ladder rows also report the per-bucket splice /
    Phase-3 round budgets.  Phase 1's splice merge is an *unrolled*
    ``splice_rounds`` loop and Phase 3's pivot splice is a vmapped
    ``while_loop`` that runs every batch element to the slowest member's
    convergence, capped by ``phase3_rounds`` — so shrinking the budgets
    from the fixed 12/64 to the schedule-derived values (11/24 at this
    scale, ``ladder_rounds``) removes up to 8% of the unrolled Phase-1
    splice ops and bounds the batched Phase-3 straggler tail at ~1/3 of
    its former worst case, at identical results (the budgets stay upper
    bounds on the convergence need).
    """
    from repro.launch.serve import MicroBatcher

    rows = []
    for scale, parts, deg, pool_n, max_batch, passes in series:
        pool = [eulerian_rmat(scale, avg_degree=deg, seed=seed + i)
                for i in range(pool_n)]
        configs = [
            ("pr3-sync", dict(cap_ladder=False, level_ladder=False,
                              straggler_cap=False), 0, ()),
            ("pr6-ladder-async", {}, 2, (max_batch,)),
        ]
        base = None
        for name, opts, depth, widths in configs:
            solver = EulerSolver(n_parts=parts, partition_seed=seed,
                                 **opts)
            t_session = time.perf_counter()
            t0 = time.perf_counter()
            warm = solver.solve_many(pool)          # cold pass: B=1 compiles
            t_cold = time.perf_counter() - t0
            rep, members = {}, {}
            for g, r in zip(pool, warm):
                rep.setdefault(r.cache.bucket, g)
                members[r.cache.bucket] = members.get(r.cache.bucket, 0) + 1
            t0 = time.perf_counter()
            if widths:
                # width-ladder prewarm for the *modal* bucket only: on a
                # compile-bound host, batch widths only pay for the
                # bucket that actually accumulates quota flushes
                modal = max(members, key=members.get)
                solver.prewarm(rep[modal], widths)
            t_warm = time.perf_counter() - t0

            mb = MicroBatcher(solver, max_batch=max_batch,
                              deadline_s=0.005, pipeline_depth=depth)
            target = pool_n * passes
            seq = served = 0
            up0 = solver.cache_stats.state_uploads
            t0 = time.perf_counter()
            while served < target:
                if seq < target and seq - served < pool_n:
                    done = mb.submit(seq, pool[seq % pool_n])
                    seq += 1
                elif seq < target:
                    done = mb.poll()
                else:
                    done = mb.drain()
                    assert done, "drain lost requests"
                served += len(done)
            dt = time.perf_counter() - t0
            session_s = time.perf_counter() - t_session
            cps = served / max(session_s, 1e-9)
            steady = served / max(dt, 1e-9)
            base = base or cps
            caps = next(iter(rep))[3]
            cs = solver.cache_stats
            widths_used = mb.flushes.widths()
            rows.append({
                "config": name, "pool": pool_n, "buckets": len(rep),
                "cold_s": round(t_cold, 2),
                "prewarm_s": round(t_warm, 2),
                "circuits/s": round(cps, 2),
                "steady_circuits/s": round(steady, 2),
                "x_vs_pr3": round(cps / base, 2),
                "widths_used": widths_used,
                "splice_rounds": caps.splice_rounds,
                "p3_rounds": caps.phase3_rounds,
                "compiles": cs.compiles,
                "steady_uploads": cs.state_uploads - up0,
            })
    return rows


def run_autotune(series=AUTOTUNE_SERIES, seed=0):
    """Static ``--widths`` configuration vs the adaptive autotuner
    (DESIGN.md §12) on the same heterogeneous same-scale pool.

    The *static* config is the PR 6 serving recipe: a blocking cold
    sweep compiles every bucket's B=1 program, then the modal bucket's
    quota width is prewarmed synchronously, and only then does the
    serving loop start — no request is answered until every compile has
    retired.  The *adaptive* config serves from the first arrival: B=1
    programs compile on first flush, and the autotuner's background
    compile service warms ladder widths behind live traffic as the flush
    histograms accrue, so ``first_wide_s`` (seconds from the first
    arrival to the first >1-width dispatch) and ``dispatches_before_wide``
    bound the mid-session upgrade the policy delivers.

    ``session_circuits/s`` spans everything from config construction
    (static pays its cold+prewarm stall inside the window; adaptive pays
    cold compiles inline, overlapped with serving).  ``steady_circuits/s``
    is the best of two post-warmup passes in which no background compile
    landed (windows that absorb one are re-measured — on a CPU host the
    compile thread shares cores with the simulated mesh) — the acceptance
    bound is adaptive steady ≥ static steady within tolerance (same
    warmed ladder, same programs; the autotuner must not tax the warm
    path).
    """
    from repro.euler.autotune import AutoTuner
    from repro.launch.serve import MicroBatcher

    def serve_passes(mb, pool, passes, tuner=None):
        target = len(pool) * passes
        seq = served = 0
        t0 = time.perf_counter()
        while served < target:
            if seq < target and seq - served < len(pool):
                done = mb.submit(seq, pool[seq % len(pool)])
                seq += 1
            elif seq < target:
                done = mb.poll()
            else:
                done = mb.drain()
                assert done, "drain lost requests"
            if tuner is not None:
                tuner.step()
            served += len(done)
        return time.perf_counter() - t0

    rows = []
    for scale, parts, deg, pool_n, max_batch, passes in series:
        pool = [eulerian_rmat(scale, avg_degree=deg, seed=seed + i)
                for i in range(pool_n)]
        for name in ("static-widths", "adaptive"):
            t_session = time.perf_counter()
            solver = EulerSolver(n_parts=parts, partition_seed=seed)
            tuner = None
            if name == "adaptive":
                tuner = AutoTuner(solver, max_batch=max_batch)
            else:
                warm = solver.solve_many(pool)      # blocking cold sweep
                rep, members = {}, {}
                for g, r in zip(pool, warm):
                    rep.setdefault(r.cache.bucket, g)
                    members[r.cache.bucket] = \
                        members.get(r.cache.bucket, 0) + 1
                modal = max(members, key=members.get)
                solver.prewarm(rep[modal], (max_batch,))
            mb = MicroBatcher(solver, max_batch=max_batch,
                              deadline_s=0.005, pipeline_depth=2,
                              autotuner=tuner)
            t_first = time.perf_counter()
            serve_passes(mb, pool, passes, tuner)
            session_s = time.perf_counter() - t_session
            fl = mb.flushes
            first_wide = (round(fl.first_wide_t - t_first, 2)
                          if fl.first_wide_t is not None else None)
            # steady window: a pass only counts as steady if no
            # background compile landed inside it — a bucket's flush
            # mass can cross the prewarm threshold mid-window and the
            # resulting XLA compile steals the serving cores (CPU
            # hosts share them with the simulated mesh).  Re-measure
            # until a window stays quiet, then keep the best of two
            # quiet windows (static has no queue — its compiles all
            # retired before serving began).
            def steady_pass():
                while True:
                    p0 = (tuner.service.prewarms
                          if tuner is not None else 0)
                    s = serve_passes(mb, pool, passes, tuner)
                    if tuner is None or tuner.service.prewarms == p0:
                        return s
                    tuner.service.join(timeout=600)

            if tuner is not None:
                tuner.service.join(timeout=600)
            steady_s = min(steady_pass(), steady_pass())
            cs = solver.cache_stats
            ts = tuner.stats() if tuner is not None else {}
            if tuner is not None:
                tuner.close(timeout=10)
            rows.append({
                "config": name, "pool": pool_n,
                "session_circuits/s":
                    round(pool_n * passes / max(session_s, 1e-9), 2),
                "steady_circuits/s":
                    round(pool_n * passes / max(steady_s, 1e-9), 2),
                "first_wide_s": first_wide,
                "narrow_before_wide": fl.narrow_before_wide,
                "widths_used": fl.widths(),
                "compiles": cs.compiles,
                "async_prewarms": ts.get("async_prewarms", 0),
                "pinned": ts.get("pinned", 0),
            })
    return rows


def run_phase3(series=PHASE3_SERIES, seed=0, repeats=3):
    """Sharded vs replicated Phase 3 (DESIGN.md §11): warm fused
    wall-clock of the same graph and mesh under all three modes —
    replicated oracle, sharded with the emission ``all_gather``, and
    ``gather_circuit=False`` (host-side emission) — next to the audit
    cost model's per-device Phase 3 table width and state bytes, i.e.
    the O(2E) → O(2E/n) memory claim the sharding buys.  Circuits are
    asserted byte-identical across the modes before timing is reported.
    """
    from repro.analysis.jaxpr_audit import phase3_cost_model

    rows = []
    for scale, parts in series:
        g = eulerian_rmat(scale, avg_degree=5, seed=seed + scale)

        def timed(**opts):
            solver = EulerSolver(n_parts=parts, partition_seed=seed,
                                 **opts)
            res = solver.solve(g)                          # warm/compile
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                res = solver.solve(g)
                best = min(best, time.perf_counter() - t0)
            res.validate()
            return best, res

        t_rep, r_rep = timed(sharded_phase3=False)
        t_sh, r_sh = timed()
        t_ng, r_ng = timed(gather_circuit=False)
        assert np.array_equal(r_rep.circuit, r_sh.circuit)
        assert np.array_equal(r_rep.circuit, r_ng.circuit)
        e_cap = r_sh.cache.bucket[0]
        rep_cost = phase3_cost_model(e_cap, None)
        sh_cost = phase3_cost_model(e_cap, None, n_parts=parts,
                                    sharded=True)
        rows.append({
            "graph": f"s{scale}/P{parts}",
            "E_cap": e_cap,
            "replicated_s": round(t_rep, 3),
            "sharded_s": round(t_sh, 3),
            "nogather_s": round(t_ng, 3),
            "p3_width_rep": rep_cost["phase3_table_width"],
            "p3_width_sh": sh_cost["phase3_table_width"],
            "p3_bytes_ratio": round(
                rep_cost["phase3_state_bytes"]
                / max(1, sh_cost["phase3_state_bytes"]), 2),
        })
    return rows


def _print_table(rows):
    if not rows:
        print("  (no rows)")
        return
    cols = list(rows[0].keys())
    print(" | ".join(f"{c:>12s}" for c in cols))
    for r in rows:
        print(" | ".join(f"{str(r[c]):>12s}" for c in cols))


def main():
    rows = run()
    _print_table(rows)
    print("\nfused vs eager (distributed engine, simulated 8-device mesh):")
    dev_rows = run_device()
    _print_table(dev_rows)
    print("\nwarm serving throughput (solve_many, shape-bucket cache):")
    serve_rows = run_serving()
    _print_table(serve_rows)
    print("\nbatched serving throughput (solve_batch, one program per "
          "B-chunk):")
    batched_rows = run_batched()
    _print_table(batched_rows)
    print("\nsharded vs replicated Phase 3 (warm wall-clock + per-device "
          "memory model):")
    p3_rows = run_phase3()
    _print_table(p3_rows)
    return rows + dev_rows + serve_rows + batched_rows + p3_rows


if __name__ == "__main__":
    main()
