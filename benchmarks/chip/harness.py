"""The benchmark harness: one cell, one run, one result line.

A run looks its cell up in ``BENCHMARK.json``, and the cell's
configuration, traffic mix, loop kind and per-layer metric readers up by
name in this directory.  It then

1. checks that JAX sees a TPU with at least the cell's chips, and that
   the chip is in ``peaks.json`` (anything else ends the run, no result);
2. set-up: generates the traffic mix's graph pool with the benchmark's
   own generator, builds ``repro.euler.EulerSolver`` as the
   configuration states, and warms every bucket that the pool maps to;
3. runs the traffic mix's loop for ``--seconds`` in an order drawn from
   ``--seed`` (with ``--trace 1`` under the JAX profiler), counting the
   compiles inside the window;
4. reads the peak device memory, solves one more graph drawn from
   ``--seed`` (outside the timing: the window's pool is the same in
   every run), frees the solver, and checks every circuit against its
   graph with the plain reference (``reference/``);
5. prints the numbers compared beside their limits, and last one JSON
   line: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
   ``breakdown`` (traced runs) and ``checks``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the persistent compile cache: a fixed directory inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: per-layer metric files are named after the metric
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class BenchError(RuntimeError):
    """The run cannot be made: no chip, an unknown name, a bad file."""


# ---------------------------------------------------------------------------
# lookups by name
# ---------------------------------------------------------------------------

def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"{path.relative_to(ROOT)} does not exist") from None


def load_benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def _checked(name: str, what: str) -> str:
    if not NAME_RE.match(name):
        raise BenchError(f"{what} name {name!r} is not a benchmark name")
    return name


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, here: Path = HERE) -> dict:
    return _json(here / "configs" / f"{_checked(name, 'config')}.json")


def load_traffic(name: str, here: Path = HERE) -> dict:
    return _json(here / "traffic" / f"{_checked(name, 'traffic')}.json")


def load_loop(kind: str):
    """The loop ``traffic/<kind>.py`` (a module with ``run_window``)."""
    return importlib.import_module(
        f"{__package__}.traffic.{_checked(kind, 'loop')}")


def load_metric(name: str, here: Path = HERE) -> Callable:
    """The reader ``metrics/<name>.py``: ``read(ctx) -> float | None``."""
    path = here / "metrics" / f"{_checked(name, 'metric')}.py"
    if not path.exists():
        raise BenchError(f"no reader {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, kind: str, workload: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this workload reports:
    those with no ``workloads`` list, and those that list it."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


# ---------------------------------------------------------------------------
# device side
# ---------------------------------------------------------------------------

class CompileClock:
    """Counts the backend compiles JAX reports, and sums their seconds."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` on the fullest of ``devices`` (-1: unknown)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devices)


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def check_chip(chips: int, peaks: dict):
    """The devices JAX found, when they are TPUs, at least ``chips`` of
    them, and of a kind with known peaks; raises :class:`BenchError`."""
    import jax

    devs = jax.devices()
    info = device_info(devs)
    if info["platform"] != "tpu":
        raise BenchError(f"JAX found no TPU: platform {info['platform']!r}, "
                         f"kind {info['kind']!r}, {info['count']} devices")
    if info["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{info['count']} {info['kind']!r}")
    if info["kind"] not in peaks["devices"]:
        raise BenchError(f"no peaks for device kind {info['kind']!r} in "
                         f"peaks.json")
    return devs


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def graph_seeds(seed: int, n: int) -> List[int]:
    """``n`` graph seeds drawn from ``seed`` (any whole number)."""
    ss = np.random.SeedSequence(abs(int(seed)))
    return [int(s) for s in ss.generate_state(n, dtype=np.uint64) >> 2]


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric reader sees."""

    records: list                 # the window's solve records (loop's)
    trace: Optional[object]       # trace_reduce.Reduction, traced runs


def say(*parts, err: bool = False) -> None:
    print(*parts, file=sys.stderr if err else sys.stdout, flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             devices, t_start: float, solver_factory: Optional[Callable] = None,
             config_overrides: Optional[dict] = None,
             trace_dir: Optional[Path] = None) -> dict:
    """One run of one cell on ``devices``; returns the result line's dict.

    ``solver_factory(**solver_options)`` builds the system under test
    (default ``repro.euler.EulerSolver``); ``config_overrides`` replaces
    top-level groups of the configuration.  Both exist for the control
    and the fault tests, which put something else in the program's place.
    """
    from .gen import generator
    from .reference.checker import circuit_fault
    from .reference.hierholzer import hierholzer

    bench = load_benchmark()
    cell = find_workload(bench, workload)
    cfg = dict(load_config(cell["config"]))
    cfg.update(config_overrides or {})
    mix = load_traffic(cell["traffic"])
    loop = load_loop(mix["loop"])
    devices = list(devices)[:cell["chips"]]
    clock = CompileClock()

    from repro.core.graph import Graph

    if solver_factory is None:
        from repro.euler import EulerSolver as solver_factory

    # ---- set-up: graphs, solver, warm buckets ----
    t0 = time.perf_counter()
    gen = generator(cfg["generator"])
    pool = [gen(s, **cfg["graph"])
            for s in graph_seeds(mix["pool_seed"], mix["pool_graphs"])]
    gen_s = time.perf_counter() - t0
    say(f"[setup] generated {len(pool)} graphs in {gen_s:.3f} s: "
        f"E {min(g.num_edges for g in pool)}..{max(g.num_edges for g in pool)}, "
        f"V {min(g.num_vertices for g in pool)}..{max(g.num_vertices for g in pool)}")

    def request(i: int):
        # a new object each time: the solver's per-graph prep memo is keyed
        # by identity, so no request reuses another's host preparation
        g = pool[i]
        return Graph(g.num_vertices, g.edge_u, g.edge_v)

    solver = solver_factory(**cfg["solver"])
    t1 = time.perf_counter()
    buckets: Dict[object, int] = {}
    for i in range(len(pool)):
        buckets.setdefault(solver.bucket_of(request(i)), i)
    c0, t2 = clock.seconds, time.perf_counter()
    warm_faults = []                # a warm solve is checked like the rest
    for i in buckets.values():
        try:
            fault = circuit_fault(pool[i], solver.solve(request(i)).circuit)
        except Exception as e:  # counted below; the run goes on
            fault = f"the warm solve failed: {e!r}"
        if fault:
            warm_faults.append(fault)
            say(f"[check] warm solve of graph {i}: {fault}", err=True)
    warm_s = time.perf_counter() - t2
    warm_compile_s = clock.seconds - c0
    say(f"[setup] {len(buckets)} bucket(s) of the pool: "
        f"E {sorted(k[0] for k in buckets if k)} (prep {t2 - t1:.3f} s); "
        f"warm solves {warm_s:.3f} s, of it compile {warm_compile_s:.3f} s "
        f"in {clock.count} compiles")

    # ---- the window ----
    profile_dir = None
    if trace:
        import jax

        profile_dir = Path(trace_dir or tempfile.mkdtemp(prefix="chipbench-"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only: a small trace
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(profile_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    n_before, s_before = clock.count, clock.seconds
    records = loop.run_window(solver.solve, request, seconds, mix, seed)
    window_compiles = clock.count - n_before
    if trace:
        import jax

        jax.profiler.stop_trace()
    w = loop.window_stats(records)
    say(f"[window] {w['solves']} solves, {w['edges']} edges in "
        f"{w['window_s']:.3f} s; compiles inside the window: "
        f"{window_compiles} ({clock.seconds - s_before:.3f} s)")

    # ---- after the window: memory, a graph of this seed, then check ----
    peak = peak_bytes(devices)
    seed_graph = gen(graph_seeds(seed, 1)[0], **cfg["graph"])
    t3 = time.perf_counter()
    try:
        seed_circuit, seed_error = np.asarray(solver.solve(Graph(
            seed_graph.num_vertices, seed_graph.edge_u,
            seed_graph.edge_v)).circuit), None
    except Exception as e:  # counted below
        seed_circuit, seed_error = None, repr(e)
    seed_s = time.perf_counter() - t3
    del solver
    gc.collect()

    reduction = None
    if trace:
        from . import trace_reduce

        reduction = trace_reduce.reduce_dir(profile_dir)
        say(f"[trace] busy {reduction.busy_s:.6f} s of "
            f"{reduction.window_s:.6f} s; while loops with no body op "
            f"running {reduction.loop_control_s:.6f} s")
        if trace_dir is None:
            shutil.rmtree(profile_dir, ignore_errors=True)

    failed = [r for r in records if r.error is not None]
    invalid = []
    for r in records:
        if r.error is None:
            fault = circuit_fault(pool[r.index], r.circuit)
            if fault:
                invalid.append((r.index, fault))
    for r in failed[:3]:
        say(f"[check] solve {r.index} failed: {r.error}", err=True)
    for i, fault in invalid[:3]:
        say(f"[check] circuit {i} is wrong: {fault}", err=True)
    seed_fault = (f"the solve failed: {seed_error}" if seed_error else
                  circuit_fault(seed_graph, seed_circuit))
    if seed_fault:
        say(f"[check] the graph of seed {seed}: {seed_fault}", err=True)
    ref_fault = circuit_fault(seed_graph, hierholzer(seed_graph))
    checks = {
        "failed_solves": {"value": len(failed), "limit": 0},
        "wrong_circuits": {"value": len(invalid), "limit": 0},
        "wrong_warm_solves": {"value": len(warm_faults), "limit": 0},
        "wrong_seed_graph": {"value": int(seed_fault is not None),
                             "limit": 0},
        "reference_rejected": {"value": int(ref_fault is not None),
                               "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    ctx = Ctx(records=records, trace=reduction)
    metrics: Dict[str, dict] = {}
    if trace:
        for m in metrics_for(bench, "per_layer", workload):
            value = load_metric(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"solve_edges_per_s": w["edges"] / w["window_s"],
                  "peak_hbm_bytes": peak, "setup_s": setup_s}
        for m in metrics_for(bench, "end_to_end", workload):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device = device_info(devices)
    device["memory_peak_bytes"] = peak
    out = {"correct": correct, "attempted": len(records),
           "failed": len(failed) + len(invalid), "metrics": metrics,
           "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        out["breakdown"] = reduction.breakdown()
    out["window_compiles"] = window_compiles
    out["setup"] = {"gen_s": gen_s, "warm_s": warm_s,
                    "warm_compile_s": warm_compile_s, "setup_s": setup_s}
    # each window solve: pool graph, start from the window's opening,
    # seconds, the solver's own host prep
    t_open = records[0].start
    out["solves"] = [[r.index, r.start - t_open, r.end - r.start, r.prepare_s]
                     for r in records]
    out["seed_graph"] = {"edges": seed_graph.num_edges, "solve_s": seed_s}
    out["checks"] = checks
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache lives inside the checkout, at a path that never
    # moves; every program is cached, so only a cell's first run compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    try:
        bench = load_benchmark()
        cell = find_workload(bench, args.workload)
        sys.path.insert(0, str(ROOT / "src"))
        try:
            from repro.launch.compile_cache import setup_compile_cache
        except ImportError as e:
            raise BenchError(f"the system under test is not in this "
                             f"checkout: {e}") from None
        setup_compile_cache()
        devices = check_chip(cell["chips"], _json(HERE / "peaks.json"))
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), devices=devices, t_start=t_start)
    except BenchError as e:
        say(f"chipbench: {e}", err=True)
        return 1
    for name, c in out["checks"].items():
        say(f"[check] {name} = {c['value']} (limit {c['limit']})", err=True)
    print(json.dumps(out), flush=True)
    return 0
