"""Tests of the Graph500 Kronecker cell (``graph500-kron-s14``), run on
the CPU: the generator's copy against ``repro.graphgen``, and that the
cell at scale 8 comes out correct while the control and a planted fault
come out not correct.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.gen import generator  # noqa: E402

CONFIG = "graph500-kron-s14"


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
@pytest.mark.parametrize("scale", [6, 9])
def test_kronecker_copy_is_byte_identical(seed, scale):
    from repro.graphgen.kronecker import eulerian_kronecker

    ours = generator("kron.eulerian_kronecker")(seed, scale=scale,
                                                 edgefactor=16)
    theirs = eulerian_kronecker(scale, edgefactor=16, seed=seed)
    assert ours.num_vertices == theirs.num_vertices and ours.is_eulerian()
    for a, b in ((ours.edge_u, theirs.edge_u), (ours.edge_v, theirs.edge_v)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_kronecker_cell_is_correct_and_catches_control_and_faults():
    """The Kronecker cell at scale 8, in a child process on one CPU device
    (the chip look is skipped, everything after it runs): its many cycles
    at dense vertices splice into one circuit (sound), and the control
    and a planted fault come out not correct."""
    cfg = harness.load_config(CONFIG)
    overrides = dict(cfg, graph=dict(cfg["graph"], scale=8))
    workload, = [w["name"] for w in harness.load_benchmark()["workloads"]
                 if w["config"] == CONFIG]
    faults = ("sound", "control", "altered_answer")
    code = (
        "import json, sys\n"
        "sys.path[:0] = [%r, %r]\n"
        "import jax\n"
        "from benchmarks.chip import control, harness\n"
        "for fault in %r:\n"
        "    if fault == 'sound':\n"
        "        jax.config.update('jax_enable_compilation_cache', False)\n"
        "        out = harness.run_cell(%r, 2**31 + 11, 1.0, False,\n"
        "            devices=jax.devices(), t_start=0.0,\n"
        "            config_overrides=%r)\n"
        "    else:\n"
        "        out = control.run_fault(fault, %r, 2**31 + 11, 1.0,\n"
        "            devices=jax.devices(), config_overrides=%r)\n"
        "    print('RESULT', json.dumps({'fault': fault, 'correct': out['correct'],\n"
        "          'attempted': out['attempted'], 'checks': out['checks']}))\n"
    ) % (str(ROOT), str(ROOT / "src"), list(faults), workload, overrides,
         workload, overrides)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith("RESULT "):
            r = json.loads(line[len("RESULT "):])
            out[r["fault"]] = r
    assert sorted(out) == sorted(faults), p.stdout[-2000:]
    sound = out.pop("sound")
    assert sound["correct"] and sound["attempted"] >= 1, sound
    assert all(c["value"] == 0 for c in sound["checks"].values()), sound
    for fault, r in out.items():
        assert not r["correct"], (fault, r)
        assert r["checks"]["reference_rejected"]["value"] == 0, (fault, r)
