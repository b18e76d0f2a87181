"""Placement of JAX's persistent compilation cache by the launchers
(``repro.launch.compile_cache``): the environment's
``JAX_COMPILATION_CACHE_DIR`` wins, else the fixed ``<repo>/.jax_cache``,
and a second process running the same solve reads the first one's
programs back."""
import os
import subprocess
import sys
import textwrap

import jax

from conftest import REPO
from repro.launch.compile_cache import REPO_CACHE_DIR, setup_compile_cache


def test_cache_dir_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = setup_compile_cache()
        assert got == str(REPO_CACHE_DIR) == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_cache_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


_SOLVE_TWICE = """
import jax
from repro.launch.compile_cache import setup_compile_cache

print("DIR=" + setup_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda event, **_: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
from repro.euler import EulerSolver
from repro.graphgen.eulerize import eulerian_rmat

EulerSolver(n_parts=1).solve(eulerian_rmat(5, avg_degree=3, seed=0)).validate()
print("HITS=%d" % len(hits))
"""


def test_second_process_hits_the_cache(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")

    def run():
        r = subprocess.run([sys.executable, "-c",
                            textwrap.dedent(_SOLVE_TWICE)],
                           capture_output=True, text=True, env=env,
                           timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr
        assert f"DIR={tmp_path}" in r.stdout
        return int(r.stdout.split("HITS=")[1].split()[0])

    assert run() == 0                  # cold: compiles and writes entries
    assert any(tmp_path.iterdir())
    assert run() > 0                   # warm: reads them back
