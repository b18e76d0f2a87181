#!/usr/bin/env python3
"""Smoke test of the Euler solver's main path on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded Phase 3 only

One chip (the default):
  * builds the paper's §4.2 workload, ``eulerian_rmat(scale=20,
    avg_degree=5, seed=0)``, and solves it with ``EulerSolver`` on one
    partition: cold (the compile counts as set-up), then warm.  Both
    circuits are validated and must be byte-identical;
  * serves ten same-bucket scale-8 graphs through a ``MicroBatcher`` with
    B=4 and B=1 flushes.  Every result is validated and byte-compared
    with a sequential ``solve()`` of the same graph.

``--chips 4`` runs only the multi-chip path: ``EulerSolver(n_parts=4)``
with the sharded Phase 3, byte-compared with the replicated Phase 3
(``sharded_phase3=False``), both validated, and one validated B=2
batched solve.  Its graph is the same RMAT family at scale 18 (687,709
edges): a P=4 program takes about 6 min to compile at scale 20 and about
2 min at scale 18 (described v5e, 8-core host), and the four-chip path
compiles two of them.

The sizes are fixed (the constants below); a CPU rehearsal imports this
module and calls ``big_solve`` / ``four_chip`` with a smaller ``scale``.

Measurements go to earlier lines of standard output.  The last line is
one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every phase passed.  The script exits non-zero, without that line, when
JAX finds no TPU, when fewer chips are visible than asked for, when a
validation or byte comparison fails, or when a background compile failed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

SCALE_1CHIP = 20     # the paper's §4.2 workload
SCALE_4CHIP = 18
SERVE_SCALE = 8      # serving requests (bucket E = 1,024)
REQUESTS = 10
SEED = 0


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileClock:
    """Sums the backend compile seconds JAX reports while active."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def same(a, b) -> bool:
    import numpy as np

    return a.shape == b.shape and bool(np.array_equal(a, b))


def big_solve(clock, dev, scale: int = SCALE_1CHIP) -> dict:
    """Cold then warm solve of the scale-``scale`` graph on one chip."""
    from repro.euler import EulerSolver
    from repro.graphgen.eulerize import eulerian_rmat

    t0 = time.perf_counter()
    g = eulerian_rmat(scale, avg_degree=5, seed=SEED)
    gen_s = time.perf_counter() - t0
    solver = EulerSolver(n_parts=1)
    bucket = solver.bucket_of(g)[0]
    report("solve", scale=scale, V=g.num_vertices, E=g.num_edges,
           bucket_E=bucket, gen_s=f"{gen_s:.3f}")

    c0 = clock.seconds
    t0 = time.perf_counter()
    cold = solver.solve(g)
    cold_s = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    cold.validate()
    check(cold.valid, "cold solve failed validation")

    c0 = clock.seconds
    t0 = time.perf_counter()
    warm = solver.solve(g)
    warm_s = time.perf_counter() - t0
    warm.validate()
    check(warm.valid, "warm solve failed validation")
    check(clock.seconds - c0 == 0.0, "warm solve recompiled")
    check(same(cold.circuit, warm.circuit), "cold and warm circuits differ")
    out = {"scale": scale, "V": g.num_vertices, "E": g.num_edges,
           "bucket_E": bucket, "cold_s": cold_s, "warm_s": warm_s,
           "compile_s": compile_s, "peak_bytes": peak_bytes(dev),
           "edges_per_s_warm": g.num_edges / warm_s}
    report("solve", **out)
    return out


def serving(clock) -> dict:
    """Same-bucket requests through the micro-batcher at B=4 and B=1."""
    from repro.euler import EulerSolver, modal_bucket_pool
    from repro.graphgen.eulerize import eulerian_rmat
    from repro.launch.serve import MicroBatcher

    solver = EulerSolver(n_parts=1, width_ladder=(1, 4))
    pool = modal_bucket_pool(
        solver, (eulerian_rmat(SERVE_SCALE, avg_degree=5, seed=SEED + i)
                 for i in range(80)),
        REQUESTS)
    check(len(pool) == REQUESTS,
          f"found {len(pool)} same-bucket graphs, need {REQUESTS}")
    # sequential reference; also compiles the bucket's B=1 program
    ref = [solver.solve(g) for g in pool]
    for r in ref:
        check(r.validate().valid, "sequential solve failed validation")
    c0 = clock.seconds
    tickets = solver.prewarm_async(pool[0], widths=[4])
    for t in tickets:
        check(t.wait(timeout=1200), f"compile job {t.label} timed out")
        check(t.error is None, f"compile job {t.label} failed: {t.error!r}")
    prewarm_s = clock.seconds - c0
    key = solver.bucket_of(pool[0])
    check(solver.warmed_widths(key) == [1, 4],
          f"warmed widths {solver.warmed_widths(key)}, want [1, 4]")

    batcher = MicroBatcher(solver, max_batch=4, deadline_s=3600.0,
                           pipeline_depth=2)
    c0 = clock.seconds
    t0 = time.perf_counter()
    done = []
    for i, g in enumerate(pool):
        done.extend(batcher.submit(i, g))
    done.extend(batcher.drain())
    serve_s = time.perf_counter() - t0
    check(clock.seconds - c0 == 0.0, "serving compiled inline")
    check([s for s, _ in done] == list(range(len(pool))),
          "batcher lost or duplicated requests")
    for seq, res in done:
        check(res.validate().valid, f"served request {seq} failed validation")
        check(same(res.circuit, ref[seq].circuit),
              f"served request {seq} differs from its sequential solve")
    hist = dict(batcher.flushes.hist)
    check(hist.get(4, 0) >= 1 and hist.get(1, 0) >= 1,
          f"flush widths {hist}: want both B=4 and B=1")
    out = {"scale": SERVE_SCALE, "requests": len(pool),
           "bucket_E": key[0], "flush_widths": hist,
           "prewarm_compile_s": prewarm_s, "serve_s": serve_s,
           "circuits_per_s": len(pool) / serve_s}
    report("serve", **out)
    return out


def four_chip(clock, scale: int = SCALE_4CHIP) -> dict:
    """Sharded vs replicated Phase 3 on four partitions, plus one B=2."""
    from repro.euler import EulerSolver, modal_bucket_pool
    from repro.graphgen.eulerize import eulerian_rmat

    t0 = time.perf_counter()
    g = eulerian_rmat(scale, avg_degree=5, seed=SEED)
    report("p4", scale=scale, V=g.num_vertices, E=g.num_edges,
           gen_s=f"{time.perf_counter() - t0:.3f}")
    out = {"scale": scale, "V": g.num_vertices, "E": g.num_edges}
    circuits = {}
    for name, sharded in (("sharded", True), ("replicated", False)):
        solver = EulerSolver(n_parts=4, sharded_phase3=sharded)
        c0 = clock.seconds
        t0 = time.perf_counter()
        res = solver.solve(g)
        out[f"{name}_cold_s"] = time.perf_counter() - t0
        out[f"{name}_compile_s"] = clock.seconds - c0
        check(res.validate().valid, f"{name} solve failed validation")
        circuits[name] = res.circuit
        report("p4", phase3=name, bucket_E=res.cache.bucket[0],
               **{k: v for k, v in out.items() if k.startswith(name)})
    out["byte_identical"] = same(circuits["sharded"], circuits["replicated"])
    check(out["byte_identical"], "sharded and replicated circuits differ")

    solver = EulerSolver(n_parts=4)
    pool = modal_bucket_pool(
        solver, (eulerian_rmat(SERVE_SCALE, avg_degree=5, seed=SEED + i)
                 for i in range(40)), 2)
    check(len(pool) == 2, "no two same-bucket graphs for the B=2 solve")
    t0 = time.perf_counter()
    batch = solver.solve_batch(pool)
    out["b2_cold_s"] = time.perf_counter() - t0
    for g2, res in zip(pool, batch):
        check(res.cache.batch == 2,
              f"batched solve ran at B={res.cache.batch}")
        check(res.validate().valid, "B=2 solve failed validation")
        check(same(res.circuit, solver.solve(g2).circuit),
              "B=2 circuit differs from its sequential solve")
    report("p4", byte_identical=out["byte_identical"],
           b2_scale=SERVE_SCALE, b2_cold_s=out["b2_cold_s"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--json", default=None,
                    help="also write the measurements to this file")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(SRC))
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = setup_compile_cache()

    import jax

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    report("device", cache_dir=cache_dir, jax=jax.__version__, **device)
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: {args.chips} chips requested, {len(devs)} "
              f"visible", file=sys.stderr)
        return 1

    clock = CompileClock()
    record = {"device": device, "chips": args.chips}
    try:
        if args.chips == 4:
            record["p4"] = four_chip(clock)
        else:
            record["solve"] = big_solve(clock, dev)
            record["serve"] = serving(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    record["compile_s_total"] = clock.seconds
    print(json.dumps({"record": record}, default=str), flush=True)
    if args.json:
        path = Path(args.json)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(record, indent=1, default=str) + "\n")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
