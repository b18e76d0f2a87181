"""Tests of the on-chip benchmark's harness, run on the CPU.

They cover what a run on the chip rests on and a CPU can show: the names
in ``BENCHMARK.json``, lookups by name (and that a dropped-in file is
found), the generator's copy against ``repro.graphgen``, the reference
checker, the trace reduction on a trace recorded on a v5e, the refusal
to run without a TPU, and that the control and the planted faults come
out not correct while a sound run comes out correct.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.gen import generator  # noqa: E402
from benchmarks.chip.reference.checker import circuit_fault  # noqa: E402
from benchmarks.chip.reference.hierholzer import hierholzer  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SMALL = {"graph": {"scale": 8, "avg_degree": 5}}


# ---------------------------------------------------------------------------
# names and lookups
# ---------------------------------------------------------------------------

def test_benchmark_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for text in ([w["why"] for w in BENCH["workloads"]]
                 + [c["why"] for c in BENCH["configs"]]
                 + [c["source"] for c in BENCH["configs"]]
                 + [m["layer"] for m in BENCH["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_resolves_by_name():
    for w in BENCH["workloads"]:
        cfg = harness.load_config(w["config"])
        assert cfg["name"] == w["config"]
        assert cfg["chips"] == w["chips"]
        entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
        assert (ROOT / entry["file"]).resolve() == (
            CHIP / "configs" / f"{w['config']}.json")
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
        assert callable(generator(cfg["generator"]))
        mix = harness.load_traffic(w["traffic"])
        assert callable(harness.load_loop(mix["loop"]).run_window)
        per_layer = harness.metrics_for(BENCH, "per_layer", w["name"])
        assert per_layer
        for m in per_layer:
            assert callable(harness.load_metric(m["name"]))
        assert {m["name"] for m in harness.metrics_for(
            BENCH, "end_to_end", w["name"])} >= {"setup_s"}


def test_dropped_in_files_are_found(tmp_path):
    """A new configuration, mix and metric are new files: nothing that
    exists is edited to find them."""
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(CHIP / sub, tmp_path / sub)
    (tmp_path / "configs" / "new-cfg.json").write_text(
        json.dumps({"name": "new-cfg", "generator": "eulerize.eulerian_rmat",
                    "graph": {"scale": 6}, "solver": {"n_parts": 1}}))
    (tmp_path / "traffic" / "new_mix.json").write_text(
        json.dumps({"loop": "closed_oneshot", "pool_graphs": 2}))
    (tmp_path / "metrics" / "new_metric.serve.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    assert harness.load_config("new-cfg", here=tmp_path)["graph"] == {"scale": 6}
    assert harness.load_traffic("new_mix", here=tmp_path)["pool_graphs"] == 2
    assert harness.load_metric("new_metric.serve", here=tmp_path)(None) == 7.0
    bench = dict(BENCH, per_layer=BENCH["per_layer"] + [
        {"name": "new_metric.serve", "workloads": ["new-cell"]}])
    assert [m["name"] for m in harness.metrics_for(
        bench, "per_layer", "new-cell")][-1] == "new_metric.serve"
    assert "new_metric.serve" not in [m["name"] for m in harness.metrics_for(
        bench, "per_layer", BENCH["workloads"][0]["name"])]
    with pytest.raises(harness.BenchError):
        harness.load_metric("../harness", here=tmp_path)


# ---------------------------------------------------------------------------
# the yardstick: generator copy and reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
@pytest.mark.parametrize("scale", [6, 10])
def test_generator_copy_is_byte_identical(seed, scale):
    from repro.graphgen.eulerize import eulerian_rmat

    ours = generator("eulerize.eulerian_rmat")(seed, scale=scale,
                                                avg_degree=5)
    theirs = eulerian_rmat(scale, avg_degree=5, seed=seed)
    assert ours.num_vertices == theirs.num_vertices
    for a, b in ((ours.edge_u, theirs.edge_u), (ours.edge_v, theirs.edge_v)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


@pytest.fixture(scope="module")
def small_graph():
    return generator("eulerize.eulerian_rmat")(3, scale=9, avg_degree=5)


def test_checker_accepts_hierholzer(small_graph):
    from repro.core.graph import Graph
    from repro.core.hierholzer import hierholzer_circuit

    assert circuit_fault(small_graph, hierholzer(small_graph)) is None
    g = Graph(small_graph.num_vertices, small_graph.edge_u, small_graph.edge_v)
    assert circuit_fault(small_graph, hierholzer_circuit(g)) is None


@pytest.mark.parametrize("fault", ["swap_stub", "swap_steps", "drop_edge",
                                   "edge_twice", "open_walk"])
def test_checker_rejects_broken_circuit(small_graph, fault):
    c = hierholzer(small_graph)
    if fault == "swap_stub":
        c[5] ^= 1                       # one step walked the other way
    elif fault == "swap_steps":
        c[[3, 4]] = c[[4, 3]]
    elif fault == "drop_edge":
        c = c[:-1]
    elif fault == "edge_twice":
        c[-1] = c[0]
    else:
        c = np.roll(c, 1)[::-1].copy()
    assert circuit_fault(small_graph, c) is not None


def test_hierholzer_refuses_odd_graph():
    from benchmarks.chip.gen import EdgeList

    path = EdgeList(3, np.array([0, 1]), np.array([1, 2]))
    with pytest.raises(ValueError):
        hierholzer(path)


# ---------------------------------------------------------------------------
# no chip, no result
# ---------------------------------------------------------------------------

def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "4294967311", "--seconds",
         "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_run_exits_nonzero_without_tpu(tmp_path):
    # run from a copy, so that nothing is written into the repository
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copytree(ROOT / "src", tmp_path / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and not _has_result(p.stdout), p.stderr
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and not _has_result(p.stdout), p.stderr


def test_no_tpu_library_at_import():
    code = ("import sys; sys.path[:0] = [%r, %r]\n"
            "import benchmarks.chip.harness, benchmarks.chip.control, "
            "benchmarks.chip.trace_reduce\n"
            "print(sorted(m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'libtpu')))" % (str(ROOT), str(ROOT / "src")))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "[]"


# ---------------------------------------------------------------------------
# correct: a sound run passes, the control and every planted fault fail
# ---------------------------------------------------------------------------

def _faults(config: str, faults, devices: int) -> dict:
    """Harness runs of the one-chip cell with ``config`` at scale 8 on
    ``devices`` CPU devices, one per fault ("sound" = nothing planted),
    in a child process; the chip look is skipped, everything after it
    runs."""
    overrides = dict(harness.load_config(config), **SMALL)
    workload, = [w["name"] for w in BENCH["workloads"]
                 if w["config"] == config]
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
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith("RESULT "):
            r = json.loads(line[len("RESULT "):])
            out[r["fault"]] = r
    assert sorted(out) == sorted(faults), p.stdout[-2000:]
    return out


# each cell's configuration at scale 8, the four-chip one on four CPU devices
@pytest.mark.parametrize("config,devices,faults", [
    ("paper-rmat-s16", 1, ("sound", "control", "altered_answer")),
    ("paper-rmat-s16-p4", 4, ("sound", "altered_answer", "no_exchange")),
])
def test_correct_catches_control_and_faults(config, devices, faults):
    out = _faults(config, faults, devices)
    sound = out.pop("sound")
    assert sound["correct"] and sound["attempted"] >= 1, sound
    assert all(c["value"] == 0 for c in sound["checks"].values()), sound
    assert "wrong_seed_graph" in sound["checks"], sound
    for fault, r in out.items():
        assert not r["correct"], (fault, r)
        assert r["checks"]["reference_rejected"]["value"] == 0, (fault, r)


def test_seed_graph_catches_a_fault_off_the_pool():
    """A program that is wrong only on graphs outside the window's fixed
    pool is caught by the graph drawn from ``--seed`` after the window."""
    overrides = dict(harness.load_config("paper-rmat-s16"), **SMALL)
    workload = BENCH["workloads"][0]["name"]
    code = (
        "import json, sys\n"
        "sys.path[:0] = [%r, %r]\n"
        "import jax\n"
        "from benchmarks.chip import harness\n"
        "from benchmarks.chip.gen import generator\n"
        "from repro.euler import EulerSolver\n"
        "mix = harness.load_traffic('closed_oneshot')\n"
        "cfg = %r\n"
        "gen = generator(cfg['generator'])\n"
        "pool = {gen(s, **cfg['graph']).num_edges for s in\n"
        "        harness.graph_seeds(mix['pool_seed'], mix['pool_graphs'])}\n"
        "class OffPool(EulerSolver):\n"
        "    def solve(self, g):\n"
        "        res = super().solve(g)\n"
        "        if g.num_edges not in pool:\n"
        "            c = res.circuit.copy()\n"
        "            c[[0, 1]] = c[[1, 0]]\n"
        "            res.circuit = c\n"
        "        return res\n"
        "jax.config.update('jax_enable_compilation_cache', False)\n"
        "out = harness.run_cell(%r, 2**31 + 11, 1.0, False,\n"
        "    devices=jax.devices(), t_start=0.0, solver_factory=OffPool,\n"
        "    config_overrides=cfg)\n"
        "print('RESULT', json.dumps(out['checks']), out['correct'])\n"
    ) % (str(ROOT), str(ROOT / "src"), overrides, workload)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    line, = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    checks, correct = line[len("RESULT "):].rsplit(" ", 1)
    checks = json.loads(checks)
    assert correct == "False", line
    assert checks["wrong_seed_graph"]["value"] == 1, checks
    assert checks["wrong_circuits"]["value"] == 0, checks
    assert checks["wrong_warm_solves"]["value"] == 0, checks


# ---------------------------------------------------------------------------
# the trace reduction, on a trace recorded on a v5e (a scale-8 window)
# ---------------------------------------------------------------------------

TRACE = HERE / "data" / "rmat8_1chip.xplane.pb.gz"


@pytest.fixture(scope="module")
def space():
    from benchmarks.chip.xspace import read_xspace

    return read_xspace(str(TRACE))


def _events(space, plane_prefix, line=None):
    """(name, start ps, end ps) of the events on matching planes/lines."""
    out = []
    for p in space.planes:
        if not p.name.startswith(plane_prefix):
            continue
        for ln in p.lines:
            if line is not None and ln.name != line:
                continue
            for e in ln.events:
                start = ln.timestamp_ns * 1000 + e.offset_ps
                out.append((p.event_metadata[e.metadata_id].name, start,
                            start + e.duration_ps))
    return out


def _union(intervals):
    total, cur = 0, None
    for s, e in sorted(intervals):
        if cur is None or s > cur[1]:
            total += 0 if cur is None else cur[1] - cur[0]
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    return total + (0 if cur is None else cur[1] - cur[0])


def test_trace_reduction_accounts_for_busy_time(space):
    from benchmarks.chip import trace_reduce

    red = trace_reduce.reduce_space(space)
    assert len(red.devices) == 1 and red.n_solves == 2
    d = red.devices[0]
    assert d.busy_s > 0
    assert sum(d.by_file.values()) == pytest.approx(d.busy_s, rel=1e-9)
    assert sum(d.by_op.values()) == pytest.approx(d.busy_s, rel=1e-9)
    for f in ("repro/core/phase1.py", "repro/core/engine.py",
              "repro/core/bounded.py", "repro/kernels/ref.py"):
        assert d.by_file.get(f, 0) > 0, f
    # busy and the window recomputed straight from the events
    (_, lo, hi), = [e for e in _events(space, "/host") if
                    e[0] == trace_reduce.WINDOW]
    assert red.window_s == pytest.approx((hi - lo) / 1e12)
    ops = [(max(lo, s), min(hi, e))
           for _, s, e in _events(space, "/device", trace_reduce.OPS_LINE)]
    assert d.busy_s == pytest.approx(
        _union([iv for iv in ops if iv[1] > iv[0]]) / 1e12, rel=1e-9)
    idle = sum(s for _, s in red.gaps)
    assert idle == pytest.approx(red.window_s - d.busy_s, rel=1e-6)


def test_trace_reduction_lists_loop_control(space):
    """A ``while`` op's time that none of its body's ops covers is its
    own breakdown entry: the union of all ops less that of the ops that
    are no ``while``."""
    from benchmarks.chip import trace_reduce

    red = trace_reduce.reduce_space(space)
    d = red.devices[0]
    assert d.by_op[trace_reduce.LOOP_CONTROL] == d.loop_control_s > 0
    assert not [k for k in d.by_op if k.endswith(" while")]
    (_, lo, hi), = [e for e in _events(space, "/host") if
                    e[0] == trace_reduce.WINDOW]
    ops = [(n, max(lo, s), min(hi, e))
           for n, s, e in _events(space, "/device", trace_reduce.OPS_LINE)]
    ops = [o for o in ops if o[2] > o[1]]
    body = [(s, e) for n, s, e in ops if trace_reduce._kind(n) != "while"]
    assert d.loop_control_s == pytest.approx(
        (_union([(s, e) for _, s, e in ops]) - _union(body)) / 1e12,
        rel=1e-9)
    names = [k for k, _ in red.breakdown()["device_ops"]]
    assert trace_reduce.LOOP_CONTROL in names


def _synthetic_space(ops):
    """An ``XSpace`` with a 100-ps window and one solve on the host and
    ``ops`` ((HLO instruction name, start ps, end ps)) on one chip."""
    from benchmarks.chip import trace_reduce
    from benchmarks.chip.xspace import XSpace

    space = XSpace()
    host = space.planes.add(name="/host:CPU")
    line = host.lines.add(name="python")
    for k, name in enumerate((trace_reduce.WINDOW, trace_reduce.SOLVE)):
        host.event_metadata[k].name = name
        line.events.add(metadata_id=k, offset_ps=0, duration_ps=100)
    dev = space.planes.add(name="/device:TPU:0")
    line = dev.lines.add(name=trace_reduce.OPS_LINE)
    for k, (name, start, end) in enumerate(ops):
        dev.event_metadata[k].name = name
        line.events.add(metadata_id=k, offset_ps=start, duration_ps=end - start)
    return space


def test_collectives_are_found_by_their_opcode():
    """A TPU trace names an instruction after the JAX primitive it comes
    from, so a ``reshape`` inside ``all_to_all`` is ``all_to_all.N`` too;
    an op is a collective by the opcode in its HLO text."""
    from benchmarks.chip import trace_reduce

    space = _synthetic_space([
        ("%all_to_all.394 = pred[4,4,16]{2,1,0:T(4,128)(4,1)S(1)} "
         "all-to-all(pred[4,4,16]{2,1,0} %reshape.3), channel_id=1", 0, 10),
        ("%all_to_all.393 = pred[4,1,64]{2,1,0:T(4,128)(4,1)S(1)} "
         "reshape(pred[256]{0} %slice.2)", 10, 12),
        ("%psum.2 = s32[] all-reduce(s32[] %x), to_apply=%add", 12, 14),
        ("%ppermute.1 = (s32[4]{0}, u32[]{:S(2)}) "
         "collective-permute-start(s32[4]{0} %y)", 14, 15),
        ("%collective-permute-done.1 = s32[4]{0} "
         "collective-permute-done((s32[4]{0}, u32[]) %ppermute.1)", 15, 16),
        ("%all-gather.7 = s32[16]{0} all-gather(s32[4]{0} %z)", 16, 19),
        ("%fusion.9 = s32[256]{0:T(1024)} fusion(s32[256]{0} %a), "
         "kind=kLoop", 19, 60)])
    red = trace_reduce.reduce_space(space)
    assert red.collective_ops == 5
    assert red.collective_s == pytest.approx(17e-12)
    assert red.busy_s == pytest.approx(60e-12)
    assert sorted(k.split(" ", 1)[1] for k in red.devices[0].by_op) == [
        "all-gather", "all-reduce", "all-to-all", "collective-permute-done",
        "collective-permute-start", "fusion", "reshape"]


def test_idle_share_and_per_solve_readers(space):
    from benchmarks.chip import trace_reduce
    from benchmarks.chip.traffic.closed_oneshot import Record

    red = trace_reduce.reduce_space(space)
    records = [Record(i, 0.0, 1.0, 10, None, 0.5) for i in range(red.n_solves)]
    ctx = harness.Ctx(records=records, trace=red)
    idle = harness.load_metric("device_idle_share.oneshot")(ctx)
    assert idle == pytest.approx(100 * (1 - red.busy_s / red.window_s))
    assert 0 < idle < 100
    assert harness.load_metric("host_prep_s.oneshot")(ctx) == 0.5
    layers = sum(harness.load_metric(m)(ctx) * red.n_solves for m in (
        "levels_device_s.oneshot", "phase3_device_s.oneshot",
        "bounded_device_s.oneshot"))
    other = red.devices[0].by_file.get("other", 0.0)
    assert layers + other == pytest.approx(red.busy_s, rel=1e-9)
    # no collective runs on one chip: the reader finds nothing to read
    assert harness.load_metric("collective_device_s.oneshot")(ctx) is None
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
