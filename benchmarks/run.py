"""Benchmark runner: one harness per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

E1/E6 scaling+supersteps (Fig 5), E2 splits (Fig 6), E3 Phase-1 complexity
fit (Fig 7), E4/E5 memory state (Fig 8/9).  The dry-run/roofline harnesses
(E7) run separately via repro.launch.dryrun / benchmarks.roofline because
they need the 512-device environment.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller graphs (CI-sized)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    from . import bench_memory, bench_phase1, bench_scaling, bench_splits

    if args.quick:
        scaling_series = [(10, 2), (11, 3), (11, 4), (12, 8)]
        batched_series = [(5, 8, 3, (1, 2, 4, 8))]
        phase3_series = [(9, 8)]
        kw = dict(scale=11, parts=8)
    else:
        scaling_series = bench_scaling.SERIES
        batched_series = bench_scaling.BATCHED_SERIES
        phase3_series = bench_scaling.PHASE3_SERIES
        kw = dict(scale=14, parts=8)

    suites = {
        "scaling": lambda: bench_scaling.run(series=scaling_series),
        "fused": lambda: bench_scaling.run_device(),
        "serving": lambda: bench_scaling.run_serving(),
        "batched": lambda: bench_scaling.run_batched(series=batched_series),
        "ladder": lambda: bench_scaling.run_ladder(),
        "autotune": lambda: bench_scaling.run_autotune(),
        "phase3": lambda: bench_scaling.run_phase3(series=phase3_series),
        "splits": lambda: bench_splits.run(scale=kw["scale"] - 1,
                                           parts=kw["parts"]),
        "phase1": lambda: bench_phase1.run(**kw),
        "memory": lambda: bench_memory.run(**kw),
    }
    from repro import obs

    results = {}
    metrics = {}
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.perf_counter()
        print(f"\n=== E-bench: {name} " + "=" * 50)
        results[name] = fn()
        print(f"=== {name} done in {time.perf_counter() - t0:.1f}s")
        _summarize(name, results[name])
        # per-suite cut of the process metrics registry (cumulative —
        # solver sessions are separated by their session label)
        metrics[name] = obs.default_registry().snapshot()
    if metrics:
        results["metrics"] = metrics
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, default=float)
    print("\nall benchmarks complete")
    return results


def _summarize(name, res):
    if name == "scaling":
        for r in res:
            print(f"  {r['graph']:>10s}: total={r['total_s']}s "
                  f"user={r['user_s']}s supersteps={r['supersteps']} "
                  f"(makki: {r['makki_partition_supersteps']} partition / "
                  f"{r['makki_vertex_supersteps']} vertex supersteps)")
    elif name == "fused":
        for r in res:
            print(f"  {r['graph']:>10s}: fused={r['fused_s']}s "
                  f"eager={r['eager_s']}s over {r['levels']} levels "
                  f"→ {r['speedup']}x")
    elif name == "serving":
        for r in res:
            print(f"  {r['graph']:>10s}: pool={r['pool']} warm "
                  f"{r['circuits/s']} circuits/s "
                  f"({r['compiles']} compiles, {r['hits']} cache hits)")
    elif name == "batched":
        for r in res:
            print(f"  {r['graph']:>10s}: B={r['B']} "
                  f"{r['circuits/s']} circuits/s ({r['x_vs_B1']}x vs B=1)")
    elif name == "ladder":
        for r in res:
            print(f"  {r['config']:>18s}: {r['buckets']} bucket(s), "
                  f"session {r['circuits/s']} circuits/s "
                  f"({r['x_vs_pr3']}x vs pr3-sync; steady "
                  f"{r['steady_circuits/s']}), widths {r['widths_used']}, "
                  f"rounds {r['splice_rounds']}/{r['p3_rounds']}")
    elif name == "autotune":
        for r in res:
            fw = (f"first wide at {r['first_wide_s']}s"
                  if r["first_wide_s"] is not None else "no wide flush")
            print(f"  {r['config']:>14s}: session "
                  f"{r['session_circuits/s']} circuits/s, steady "
                  f"{r['steady_circuits/s']}, widths {r['widths_used']} "
                  f"({fw}, {r['narrow_before_wide']} narrow before; "
                  f"{r['async_prewarms']} async prewarm(s), "
                  f"{r['pinned']} pinned)")
    elif name == "phase3":
        for r in res:
            print(f"  {r['graph']:>10s}: replicated={r['replicated_s']}s "
                  f"sharded={r['sharded_s']}s nogather={r['nogather_s']}s "
                  f"per-device table {r['p3_width_rep']} → "
                  f"{r['p3_width_sh']} ({r['p3_bytes_ratio']}x less state)")
    elif name == "phase1":
        print(f"  fit over {res['points']} points: R2={res['r2']}")
    elif name == "memory":
        print(f"  level-0 drop (dedup): "
              f"{res['claims']['level0_cumulative_drop_dedup']*100:.0f}%  "
              f"mid-level avg drop (proposed): "
              f"{res['claims']['mid_level_average_drop_proposed']*100:.0f}% "
              f"(paper: 43% / 50-75%, pass: {res['claims_pass']})")
    elif name == "splits":
        print(f"  build={res['build_s']}s over {len(res['rows'])} "
              f"(partition, level) cells")


if __name__ == "__main__":
    main()
