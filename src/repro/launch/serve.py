"""Request-serving drivers.

Default workload — the paper's own architecture behind the public facade:
an arrival-driven loop feeding a stream of generated graphs through ONE
persistent :class:`repro.euler.EulerSolver` session, scheduled by a
*micro-batcher* (:class:`MicroBatcher`): requests accumulate per
shape-bucket key and flush when a bucket reaches ``--max-batch`` or its
oldest request has waited ``--deadline-ms``.  Flushes dispatch
*asynchronously* (``solve_batch_async``, DESIGN.md §9) through a
``--pipeline-depth``-deep window, so host-side prep and batching of the
next flush overlap device execution of the current one; partial flushes
decompose onto the largest pre-warmed batch widths (the solver's width
ladder) instead of falling back to per-graph B=1 loops.  Each request
graph is padded into a quantized shape bucket (cap/level ladder,
DESIGN.md §9); after warmup every flush reuses a compiled ``(bucket,
B)`` program with zero retrace and — for pooled graphs — zero
host→device state upload.  Reports circuits/s, p50/p95 latency, and the
session's cache stats; ``--sync --no-ladder`` recovers the PR 3 driver.

    PYTHONPATH=src python -m repro.launch.serve --scale 9 --parts 8 \
        --duration 30 --max-batch 8

The original LM prefill+decode driver is kept behind ``--workload lm``
(:func:`main_lm`):

    PYTHONPATH=src python -m repro.launch.serve --workload lm \
        --arch smollm-360m --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import deque


class MicroBatcher:
    """Bucket-keyed micro-batching scheduler over an ``EulerSolver``.

    ``submit(seq, graph)`` queues one request; ``poll()`` flushes buckets
    whose oldest request passed ``deadline_s``; ``drain()`` flushes and
    completes everything at shutdown.  All three return completed
    ``(seq, EulerResult)`` pairs (each pair exactly once, seq-sorted
    within a call).

    Flushing is asynchronous and width-laddered (DESIGN.md §9):

    - A flush of n requests decomposes greedily onto the *largest
      pre-warmed* batch widths ≤ n (``solver.warmed_widths`` ∪ {1}),
      so a 5-request deadline flush with a warmed {1, 2, 4} ladder runs
      as one B=4 program + one B=1 program instead of five B=1 loops —
      and never dispatches an unwarmed width, whose multi-second XLA
      compile would stall every request behind it (``prewarm`` is the
      one path that adds widths; an unwarmed bucket serves entirely at
      B=1).
    - Each dispatch enters a ``pipeline_depth``-deep in-flight window
      (``solve_batch_async``); the device executes while the host
      preps/batches the next flush.  Overflowing the window blocks on
      the *oldest* dispatch, so results complete in dispatch order.
      ``pipeline_depth=0`` is the synchronous PR 3 driver.

    Mixed buckets never share a flush — each bucket queue is
    independent — so no request is padded up to a foreign shape
    (DESIGN.md §8).

    With ``autotuner=`` set (an :class:`repro.euler.autotune.AutoTuner`),
    the batcher feeds it per-bucket arrival and flush-size observations;
    the tuner's policy then prewarms ladder widths on the background
    compile service, and — because ``_widths_for`` consults
    ``warmed_widths`` on every flush — partial flushes upgrade from B=1
    to ladder widths mid-session as compiles land (DESIGN.md §12).
    """

    def __init__(self, solver, max_batch: int = 8,
                 deadline_s: float = 0.010, clock=time.perf_counter,
                 pipeline_depth: int = 2, autotuner=None):
        from .. import obs
        from ..euler.autotune import FlushLog

        if max_batch < 1 or pipeline_depth < 0:
            raise ValueError(
                f"need max_batch >= 1 and pipeline_depth >= 0, got "
                f"{max_batch}, {pipeline_depth}")
        self.solver = solver
        self.max_batch = max_batch
        self.deadline_s = deadline_s
        self.clock = clock
        self.pipeline_depth = pipeline_depth
        self.autotuner = autotuner
        self.pending: dict = {}     # bucket key → [(seq, graph, t_arrival)]
        self.inflight: deque = deque()   # (PendingSolve, [seq], [t_arrival])
        # observability (DESIGN.md §13): flush widths, request latencies
        # and queue depth live in the metrics registry as per-session
        # labeled children (same session label as the solver's cache
        # counters), so one scrape separates concurrent batchers; flush
        # decomposition is additionally traced as "flush" spans.
        reg = getattr(solver, "registry", None) or obs.default_registry()
        self.trace = getattr(solver, "trace", None) or obs.default_tracelog()
        lab = {"session": getattr(solver, "session", "s?")}
        # bounded per-dispatch width accounting (histogram + rolling
        # window) — a long-lived server no longer grows a per-dispatch
        # list without bound; widths also land in euler_flush_width
        self.flushes = FlushLog(clock=clock, metric=reg.histogram(
            "euler_flush_width", "requests per dispatched program",
            lo_exp=0, hi_exp=8).labels(**lab))
        # per-request arrival→delivery seconds (bounded log2 histogram —
        # replaces the PR 6 rolling deque + sort-based percentiles)
        self.latencies = reg.histogram(
            "euler_latency_seconds", "request arrival→delivery seconds",
            lo_exp=-14, hi_exp=8).labels(**lab)
        self._g_depth = reg.gauge(
            "euler_queue_depth", "requests queued awaiting a flush"
        ).labels(**lab)

    # -- pipeline ------------------------------------------------------
    def _harvest_one(self):
        """Block on the OLDEST in-flight dispatch and deliver it."""
        pend, seqs, ts = self.inflight.popleft()
        results = pend.results()
        now = self.clock()
        for t in ts:
            self.latencies.observe(now - t)
        return list(zip(seqs, results))

    def _harvest(self, block: bool = False):
        """Deliver completed dispatches, oldest first; ``block=True``
        waits for all of them (drain), else only already-finished heads
        are taken."""
        out = []
        while self.inflight and (block or self.inflight[0][0].ready()):
            out.extend(self._harvest_one())
        return out

    def _widths_for(self, key, n: int):
        """Program widths a flush of ``n`` may dispatch at: every warmed
        width plus B=1 (compiled by the bucket's first solve).  An
        unwarmed width — including the full quota — is never dispatched
        from the serving loop: a fresh batch program is a multi-second
        XLA compile that would stall every in-flight request behind it.
        ``EulerSolver.prewarm`` is the one path that adds widths."""
        ws = {w for w in self.solver.warmed_widths(key)
              if 1 <= w <= self.max_batch}
        ws.add(1)
        return sorted(ws, reverse=True)

    def _flush(self, key):
        reqs = self.pending.pop(key, [])
        if not reqs:
            return []
        if self.autotuner is not None:
            self.autotuner.observe_flush(key, len(reqs))
        out = []
        bucket = key[0] if isinstance(key, tuple) else key
        widths = []
        with self.trace.span("flush", bucket=bucket, n=len(reqs)) as sp:
            i = 0
            while i < len(reqs):
                n = len(reqs) - i
                w = next(x for x in self._widths_for(key, n) if x <= n)
                chunk = reqs[i:i + w]
                i += w
                graphs = [g for _, g, _ in chunk]
                pend = (self.solver.solve_batch_async(graphs) if w > 1
                        else self.solver.solve_async(graphs[0]))
                self.inflight.append((pend, [s for s, _, _ in chunk],
                                      [t for _, _, t in chunk]))
                self.flushes.observe(w)
                widths.append(w)
                while len(self.inflight) > self.pipeline_depth:
                    out.extend(self._harvest_one())
            sp.set(widths=widths)
        self._g_depth.set(sum(len(q) for q in self.pending.values()))
        return out

    def _raise_compile_failures(self):
        """A failed background compile (prewarm or autotuner job) is an
        error of the serving loop, not a silent fall back to B=1."""
        svc = getattr(self.solver, "compile_service", None)
        if svc is not None:
            svc.raise_failures()

    # -- public interface ----------------------------------------------
    def submit(self, seq: int, graph):
        """Queue one request; returns any results completed by the
        pipeline, plus this bucket's flush if the submission filled it.
        Raises if a background compile of this solver has failed."""
        self._raise_compile_failures()
        key = self.solver.bucket_of(graph)
        if self.autotuner is not None:
            self.autotuner.observe_arrival(key, graph)
        q = self.pending.setdefault(key, [])
        q.append((seq, graph, self.clock()))
        self._g_depth.set(sum(len(x) for x in self.pending.values()))
        out = self._flush(key) if len(q) >= self.max_batch else []
        out.extend(self._harvest())
        return sorted(out)

    def poll(self):
        """Flush every bucket whose oldest request passed the deadline;
        deliver whatever the pipeline has completed."""
        self._raise_compile_failures()
        now = self.clock()
        due = [k for k, q in self.pending.items()
               if q and now - q[0][2] >= self.deadline_s]
        out = []
        for k in due:
            out.extend(self._flush(k))
        out.extend(self._harvest())
        return sorted(out)

    def next_deadline(self):
        """Earliest pending-request deadline (None if nothing pending) —
        the arrival loop sleeps until this instead of spinning."""
        ts = [q[0][2] for q in self.pending.values() if q]
        return min(ts) + self.deadline_s if ts else None

    def drain(self):
        """Flush all pending requests and complete the pipeline
        (shutdown); results are seq-sorted — i.e. submit order."""
        out = []
        for k in list(self.pending):
            out.extend(self._flush(k))
        out.extend(self._harvest(block=True))
        self._raise_compile_failures()
        return sorted(out)


def main_euler(argv=None):
    ap = argparse.ArgumentParser(
        description="Euler-circuit serving loop over the solver facade")
    ap.add_argument("--scale", type=int, default=9,
                    help="RMAT scale of the request graphs")
    ap.add_argument("--avg-degree", type=int, default=5)
    ap.add_argument("--parts", type=int, default=0,
                    help="partitions (0 → one per visible device)")
    ap.add_argument("--pool", type=int, default=6,
                    help="distinct graphs cycled through the request stream")
    ap.add_argument("--same-bucket", action="store_true",
                    help="draw the pool from one modal shape bucket so "
                         "every flush can fill the batch quota (small "
                         "graphs otherwise fragment across buckets)")
    ap.add_argument("--requests", type=int, default=0,
                    help="serve exactly N requests (0 → duration-driven)")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="serve for this many seconds after warmup")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="micro-batch flush quota per bucket (1 → "
                         "unbatched request loop)")
    ap.add_argument("--deadline-ms", type=float, default=10.0,
                    help="flush a bucket when its oldest request has "
                         "waited this long")
    ap.add_argument("--eager", action="store_true",
                    help="per-level eager supersteps instead of the fused "
                         "scan (disables micro-batching)")
    ap.add_argument("--sync", action="store_true",
                    help="synchronous dispatch (pipeline depth 0) — the "
                         "PR 3 driver; default is the async pipeline")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="in-flight dispatch window of the async batcher")
    ap.add_argument("--no-ladder", action="store_true",
                    help="disable cap/level/round bucket quantization "
                         "(PR 3 pow2-per-field keying)")
    ap.add_argument("--widths", default="1,2,4",
                    help="comma-separated batch widths to pre-warm per "
                         "hot bucket (max-batch is always added)")
    ap.add_argument("--no-prewarm", action="store_true",
                    help="skip the background width-ladder prewarm "
                         "(partial flushes then run at B=1)")
    ap.add_argument("--adaptive", action="store_true",
                    help="self-tuning warm path (DESIGN.md §12): skip the "
                         "cold sweep and static prewarm, serve from the "
                         "first arrival, and let the autotuner's compile "
                         "service warm ladder widths behind live traffic "
                         "from the observed flush histograms")
    ap.add_argument("--sync-prewarm", action="store_true",
                    help="force joining the static prewarm thread before "
                         "serving on any backend (default: join on CPU "
                         "hosts only, detach on accelerators)")
    ap.add_argument("--cache-bytes", type=int, default=0,
                    help="byte budget for the compiled-program LRU using "
                         "the audit's static cost model (0 → count-capped "
                         "only); autotuner-pinned programs survive it")
    ap.add_argument("--arrival-hz", type=float, default=0.0,
                    help="paced request arrivals per second "
                         "(0 → closed loop: submit as fast as served)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="expose the session's metrics registry over HTTP "
                         "on this port for the run: GET /metrics "
                         "(Prometheus text) and /metrics.json (snapshot); "
                         "0 picks an ephemeral port")
    ap.add_argument("--json", default=None,
                    help="append a JSON line of serving stats to this file")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import threading

    from .compile_cache import setup_compile_cache

    setup_compile_cache()
    import jax

    from ..euler import EulerSolver
    from ..euler.autotune import AutoTuner
    from ..graphgen.eulerize import eulerian_rmat

    n_parts = args.parts or len(jax.devices())
    max_batch = 1 if args.eager else args.max_batch
    ladder = not args.no_ladder
    widths = sorted({int(w) for w in args.widths.split(",") if w}
                    | {max_batch})
    if args.adaptive and (args.eager or max_batch <= 1):
        raise SystemExit("--adaptive needs the fused path and "
                         "--max-batch > 1 (there is no width ladder to "
                         "tune otherwise)")
    solver = EulerSolver(n_parts=n_parts, fused=not args.eager,
                         cap_ladder=ladder, level_ladder=ladder,
                         straggler_cap=ladder,
                         width_ladder=tuple(widths),
                         program_cache_bytes=args.cache_bytes or None)
    metrics_srv = None
    if args.metrics_port is not None:
        from .. import obs

        metrics_srv = obs.MetricsServer(solver.registry,
                                        port=args.metrics_port,
                                        trace=solver.trace)
        print(f"metrics: {metrics_srv.url}/metrics (Prometheus) and "
              f"{metrics_srv.url}/metrics.json")
    if args.same_bucket:
        from ..euler import modal_bucket_pool

        pool = modal_bucket_pool(
            solver,
            (eulerian_rmat(args.scale, avg_degree=args.avg_degree,
                           seed=args.seed + i) for i in range(args.pool * 8)),
            args.pool,
        )
        if not pool:
            raise SystemExit(
                "--same-bucket found no graph that partitions into "
                f"{n_parts} non-empty parts at scale {args.scale}; use a "
                f"larger --scale or fewer --parts"
            )
    else:
        pool = [eulerian_rmat(args.scale, avg_degree=args.avg_degree,
                              seed=args.seed + i) for i in range(args.pool)]
    mode = "eager" if args.eager else "fused"
    depth = 0 if (args.sync or args.eager) else args.pipeline_depth
    print(f"serving {mode} on {n_parts} partitions; request pool: "
          f"{len(pool)} graphs, ~{pool[0].num_edges} edges each; "
          f"micro-batch ≤{max_batch}, deadline {args.deadline_ms}ms, "
          f"pipeline depth {depth}, widths {widths}")

    tuner = None
    rep: dict = {}
    prewarm_errors: list = []     # exceptions of the detached prewarm
    if args.adaptive:
        # Adaptive warm path (DESIGN.md §12): no cold sweep, no static
        # prewarm — requests are served from the first arrival and the
        # autotuner's compile service warms ladder widths behind live
        # traffic, driven by the observed flush-size histograms.  Even
        # B=1 programs compile on first flush (an unavoidable cold-start
        # cost the static path pays in its cold sweep instead).
        t_cold = t_warm = 0.0
        cold_thr = 0.0
        tuner = AutoTuner(solver, max_batch=max_batch)
        print("adaptive: serving from first arrival; ladder widths "
              "compile behind live traffic as flush histograms accrue")
    else:
        # Cold pass: one sequential sweep compiles each bucket's B=1
        # program and measures cold (compile-inclusive) latency for the
        # warm-vs-cold series.  The width ladder then pre-warms on a
        # background thread — the batcher only ever dispatches to
        # already-warm widths, so serving can start immediately and
        # partial flushes upgrade from B=1 to laddered widths as
        # programs come online.
        t0 = time.perf_counter()
        with solver.trace.span("cold_sweep", pool=len(pool)):
            warm = solver.solve_many(pool)
        warm[0].validate()
        t_cold = time.perf_counter() - t0
        cold_thr = len(pool) / max(t_cold, 1e-9)
        for g, r in zip(pool, warm):
            rep.setdefault(r.cache.bucket, g)
        t0 = time.perf_counter()
        if max_batch > 1 and not args.eager and not args.no_prewarm:
            ladder_widths = [w for w in widths if w > 1]
            def prewarm_all():
                try:
                    for g in rep.values():
                        solver.prewarm(g, ladder_widths)
                except BaseException as exc:  # noqa: BLE001 - re-raised
                    prewarm_errors.append(exc)  # by main_euler at exit

            # thread-contract: daemon (never blocks interpreter exit;
            # prewarm holds no external resources and its work is safely
            # abandoned mid-compile).  Joined before the measured loop
            # only on CPU hosts (or --sync-prewarm), where GIL-bound
            # compiles would skew the series; accelerator backends
            # compile in XLA worker threads, so the thread detaches and
            # the ladder warms behind live traffic — the batcher
            # dispatches only to already-warm widths either way.  An
            # exception it records is raised when serving ends.
            pw = threading.Thread(target=prewarm_all, name="prewarm",
                                  daemon=True)
            pw.start()
            if args.sync_prewarm or jax.default_backend() == "cpu":
                pw.join()
        t_warm = time.perf_counter() - t0
        cs = solver.cache_stats
        print(f"cold pass {t_cold:.2f}s ({cold_thr:.2f} circuits/s); "
              f"width prewarm {t_warm:.2f}s — {len(rep)} bucket(s), "
              f"{cs.compiles} program compile(s), "
              f"{cs.prewarms} prewarmed width(s)")

    batcher = MicroBatcher(solver, max_batch=max_batch,
                           deadline_s=args.deadline_ms / 1e3,
                           pipeline_depth=depth, autotuner=tuner)
    served = 0
    edges = 0
    submitted = 0
    last = None
    period = 1.0 / args.arrival_hz if args.arrival_hz > 0 else 0.0
    t0 = time.perf_counter()
    next_arrival = t0
    while True:
        now = time.perf_counter()
        # --requests caps *submissions*; the final drain then delivers
        # exactly N results even when flushes complete out of quota
        if args.requests and submitted >= args.requests:
            break
        if not args.requests and now - t0 >= args.duration:
            break
        done = []
        if now >= next_arrival:
            done.extend(batcher.submit(submitted,
                                       pool[submitted % len(pool)]))
            submitted += 1
            next_arrival = (next_arrival + period) if period else now
        done.extend(batcher.poll())
        if tuner is not None:
            # rate-limited inside step(): decays histograms, snapshots
            # solver state, and feeds the compile service / pin set
            tuner.step()
        if period:
            # arrival-driven idle: sleep to the next arrival or the next
            # bucket deadline, whichever fires first (no spinning)
            dl = batcher.next_deadline()
            wake = min(next_arrival, dl) if dl is not None else next_arrival
            pause = wake - time.perf_counter()
            if pause > 0:
                time.sleep(min(pause, 0.05))
        for _, res in done:
            served += 1
            edges += len(res.circuit)
            last = res
    for _, res in batcher.drain():
        served += 1
        edges += len(res.circuit)
        last = res
    elapsed = time.perf_counter() - t0

    tuner_stats = {}
    if tuner is not None:
        tuner_stats = tuner.stats()
        tuner.close(timeout=5.0)
    if prewarm_errors:
        raise RuntimeError("background width prewarm failed") \
            from prewarm_errors[0]

    cs = solver.cache_stats
    thr = served / max(elapsed, 1e-9)
    fl = batcher.flushes
    first_wide = (fl.first_wide_t - t0 if fl.first_wide_t is not None
                  else None)
    # percentiles come from the registry histogram (log2 buckets with
    # linear interpolation, DESIGN.md §13) — same --json keys as the
    # PR 6 sorted-deque math they replace
    p50 = batcher.latencies.percentile(0.50) * 1e3
    p95 = batcher.latencies.percentile(0.95) * 1e3
    print(f"served {served} circuits ({edges} edges) in {elapsed:.2f}s "
          f"→ {thr:.2f} circuits/s, {edges / max(elapsed, 1e-9):.0f} edges/s "
          f"({fl.total} dispatches, mean width {fl.mean_width():.1f})")
    print(f"latency p50 {p50:.1f}ms / p95 {p95:.1f}ms; cache: {cs.hits} "
          f"hits / {cs.misses} misses / {cs.compiles} compiles / "
          f"{cs.evictions} evictions; {cs.state_uploads} state uploads")
    if tuner is not None:
        fw = f"{first_wide:.2f}s" if first_wide is not None else "never"
        print(f"adaptive: first wide flush at {fw} "
              f"({fl.narrow_before_wide} narrow dispatches before it); "
              f"{tuner_stats.get('async_prewarms', 0)} async prewarm(s), "
              f"{tuner_stats.get('pinned', 0)} pinned program(s), "
              f"{tuner_stats.get('tuner_steps', 0)} tuner step(s)")
    assert served > 0, "serving loop made no progress"
    last.validate()
    if args.json:
        width_hist = {str(w): c for w, c in sorted(fl.hist.items())}
        stats = {
            "workload": "euler-serve", "scale": args.scale,
            "parts": n_parts, "max_batch": max_batch,
            "deadline_ms": args.deadline_ms, "pipeline_depth": depth,
            "ladder": ladder, "adaptive": bool(args.adaptive),
            "served": served,
            "elapsed_s": round(elapsed, 3),
            "circuits_per_s": round(thr, 3),
            "cold_circuits_per_s": round(cold_thr, 3),
            "cold_s": round(t_cold, 3), "prewarm_s": round(t_warm, 3),
            "p50_ms": round(p50, 3), "p95_ms": round(p95, 3),
            "mean_flush": round(fl.mean_width(), 2),
            "width_hist": width_hist,
            "first_wide_flush_s": (round(first_wide, 3)
                                   if first_wide is not None else None),
            "dispatches_before_wide": fl.narrow_before_wide,
            "buckets": len(rep) or tuner_stats.get("tuner_buckets", 0),
            "compiles": cs.compiles, "hits": cs.hits, "misses": cs.misses,
            "evictions": cs.evictions, "prewarms": cs.prewarms,
            "state_uploads": cs.state_uploads,
        }
        stats.update(tuner_stats)
        with open(args.json, "a") as f:
            f.write(json.dumps(stats) + "\n")
    if metrics_srv is not None:
        metrics_srv.close()
    return thr


def main_lm(argv=None):
    """Batched LM serving: prefill + decode with a KV cache (CPU-reduced;
    the full configs serve identically on a pod via the decode cells
    proven by the dry-run)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..configs.registry import get_config
    from ..models.transformer import (decode_step, init_kv_cache,
                                      init_lm_params, prefill_step)

    arch = get_config(args.arch, reduced=True)
    cfg = arch.model
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)

    max_len = args.prompt_len + args.gen
    prefill = jax.jit(lambda p, t: prefill_step(p, cfg, t))
    decode = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t),
                     donate_argnums=(1,))

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts)
    # widen the cache to max_len
    full = init_kv_cache(cfg, args.batch, max_len)
    cache = full._replace(
        k=full.k.at[:, :, :args.prompt_len].set(cache.k),
        v=full.v.at[:, :, :args.prompt_len].set(cache.v),
        length=cache.length,
    )
    t_prefill = time.perf_counter() - t0

    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [toks]
    t0 = time.perf_counter()
    for _ in range(args.gen - 1):
        logits, cache = decode(params, cache, toks)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.perf_counter() - t0

    gen = np.stack([np.asarray(t) for t in out], 1)
    tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"prefill {args.batch}×{args.prompt_len} in {t_prefill:.2f}s; "
          f"decode {args.gen-1} steps at {tps:.1f} tok/s")
    print("generated ids (first seq):", gen[0][:16])
    assert gen.shape == (args.batch, args.gen)
    return gen


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", choices=("euler", "lm"), default="euler",
                    help="request-serving workload (default: euler)")
    args, rest = ap.parse_known_args(argv)
    return main_lm(rest) if args.workload == "lm" else main_euler(rest)


if __name__ == "__main__":
    main()
