"""The control and the planted faults that ``correct`` must catch.

    python3 -m benchmarks.chip.control --workload <cell> --seeds 11,12,13 \
        --seconds 20 [--fault control|altered_answer|no_exchange]

Each runs the cell's whole harness on the chip, at the cell's own size,
once per seed in one process, with something wrong in the program's
place, and prints each run's checks and ``correct``; every run must
come out not correct.  The benchmark's own runs never use this module.

* ``control`` — the plain reference (``reference/hierholzer.py``) put in
  the program's place with one guarantee of the configuration broken:
  its last step walks the first edge a second time instead of the last
  edge (every edge exactly once).
* ``altered_answer`` — the program itself, with Phase 3's circuit
  emission swapping the first two steps of every circuit it produces.
* ``no_exchange`` — the program itself, with the exchange between chips
  (``all_to_all``) left out: each chip keeps what it would have sent.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time
from typing import Optional

import numpy as np

from .reference.hierholzer import hierholzer


@dataclasses.dataclass
class _Result:
    circuit: np.ndarray
    timings: dict


class ControlSolver:
    """The reference in the solver's place, one guarantee broken."""

    def __init__(self, **_options):
        pass

    def bucket_of(self, graph):
        return ()                  # nothing to warm

    def solve(self, graph):
        t0 = time.perf_counter()
        c = hierholzer(graph).copy()
        if len(c) > 1:
            c[-1] = c[0]           # the first edge twice, the last never
        return _Result(c, {"prepare_s": time.perf_counter() - t0})


@contextlib.contextmanager
def altered_answer():
    """Phase 3 emits every circuit with its first two steps swapped."""
    import jax.numpy as jnp
    from repro.core import phase3

    emit = phase3.emit_circuit

    def swapped(valid, dist, reach):
        out = emit(valid, dist, reach)
        return out.at[jnp.array([0, 1])].set(out[jnp.array([1, 0])])

    phase3.emit_circuit = swapped
    try:
        yield
    finally:
        phase3.emit_circuit = emit


@contextlib.contextmanager
def no_exchange():
    """``all_to_all`` returns its input: nothing crosses between chips."""
    import jax

    a2a = jax.lax.all_to_all

    def kept(x, axis_name, split_axis, concat_axis, *, axis_index_groups=None,
             tiled=False):
        if not tiled or split_axis != concat_axis:
            raise NotImplementedError("only the engine's tiled exchange")
        return x

    jax.lax.all_to_all = kept
    try:
        yield
    finally:
        jax.lax.all_to_all = a2a


FAULTS = {"control": None, "altered_answer": altered_answer,
          "no_exchange": no_exchange}


def run_fault(fault: str, workload: str, seed: int, seconds: float, *,
              devices, config_overrides: Optional[dict] = None,
              fresh: bool = True) -> dict:
    """One harness run of ``workload`` with ``fault`` in place.

    ``fresh`` drops the programs this process compiled before; a run
    after one with the same fault may keep them (``fresh=False``)."""
    import jax

    from . import harness

    # a planted fault changes the program, not its cache key's inputs
    # alone: never read or write compiled programs here
    jax.config.update("jax_enable_compilation_cache", False)
    if fresh:
        jax.clear_caches()
    if fault == "control":
        return harness.run_cell(workload, seed, seconds, False,
                                devices=devices, t_start=time.perf_counter(),
                                solver_factory=ControlSolver,
                                config_overrides=config_overrides)
    with FAULTS[fault]():
        return harness.run_cell(workload, seed, seconds, False,
                                devices=devices, t_start=time.perf_counter(),
                                config_overrides=config_overrides)


def main(argv=None) -> int:
    import argparse

    from . import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), default="control")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(harness.ROOT / "src"))
    bench = harness.load_benchmark()
    cell = harness.find_workload(bench, args.workload)
    try:
        devices = harness.check_chip(cell["chips"],
                                     harness._json(harness.HERE / "peaks.json"))
    except harness.BenchError as e:
        harness.say(f"control: {e}", err=True)
        return 1
    caught = True
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = run_fault(args.fault, args.workload, seed, args.seconds,
                        devices=devices, fresh=k == 0)
        caught &= not out["correct"]
        print(json.dumps({"fault": args.fault, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    print(json.dumps({"fault": args.fault, "all_caught": caught}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
