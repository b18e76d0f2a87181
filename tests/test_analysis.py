"""repro.analysis: lint rules (each proven live by a known-bad fixture
that fires exactly once), jaxpr program audit (golden collective census
for the scale-5 / P=2 bucket at widths 1 and 4), and the static Phase 3
table model's O(2E/n) per-device state under the sharded Phase 3."""
import json

import pytest

from conftest import run_with_devices
from repro.analysis import check_paths, check_source
from repro.analysis.jaxpr_audit import census, phase3_cost_model
from repro.analysis.lint import default_target


# ----------------------------------------------------------------------
# lint: one bad fixture per rule, each must fire exactly once
# ----------------------------------------------------------------------
BAD = {
    "R001": """
import jax, numpy as np
def f(x):
    return np.sort(x)
fn = jax.jit(f)
""",
    "R002": """
import jax
@jax.jit
def f(x):
    return float(x) + 1
""",
    "R003": """
from jax import lax
def body(c, x):
    if x > 0:
        c = c + x
    return c, x
def run(xs):
    return lax.scan(body, 0, xs)
""",
    "R004": """
def load(g):
    assert g.num_edges > 0, "empty graph"
""",
    "R005": """
import threading
class Solver:
    def __init__(self):
        self._lock = threading.Lock()
        self._programs = {}
    def put(self, k, v):
        with self._lock:
            self._programs[k] = v
    def evict(self, k):
        self._programs.pop(k)
""",
    "R006": """
import threading
def go():
    t = threading.Thread(target=print)
    t.start()
""",
    "R007": """
import time
def dispatch(prog, args):
    t0 = time.perf_counter()
    out = prog(*args)
    return out, time.perf_counter() - t0  # lint: ok
""",
}


@pytest.mark.parametrize("rule", sorted(BAD))
def test_each_rule_fires_exactly_once(rule):
    path = ("src/repro/core/fx.py" if rule in ("R004", "R007")
            else "fx.py")
    findings = check_source(BAD[rule], path)
    assert [f.rule for f in findings] == [rule], findings


def test_method_coercion_fires():
    findings = check_source(
        "import jax\n@jax.jit\ndef f(x):\n    return x.item()\n", "fx.py")
    assert [f.rule for f in findings] == ["R002"]


def test_suppression_marker():
    src = BAD["R002"].replace("float(x) + 1",
                              "float(x) + 1  # lint: ok")
    assert check_source(src, "fx.py") == []


def test_traced_marker_forces_scope():
    src = """
import numpy as np
# lint: traced
def helper(x):
    return np.sort(x)
"""
    findings = check_source(src, "fx.py")
    assert [f.rule for f in findings] == ["R001"]
    # without the marker nothing marks `helper` traced -> clean
    assert check_source(src.replace("# lint: traced\n", ""), "fx.py") == []


def test_transitive_traced_scope():
    # `inner` is only reached via `outer`, which lax.scan traces
    src = """
import numpy as np
from jax import lax
def inner(x):
    return np.cumsum(x)
def outer(c, x):
    return c, inner(x)
def run(xs):
    return lax.scan(outer, 0, xs)
"""
    findings = check_source(src, "fx.py")
    assert [f.rule for f in findings] == ["R001"]


def test_static_values_do_not_fire():
    # shape-derived statics, config annotations, defaults, identity
    # tests: the exact idioms the engine/kernels rely on
    src = """
import jax, numpy as np
@jax.jit
def f(x, cap: int, fill=None, interpret=None):
    if fill is None:
        fill = 0
    rounds = int(np.ceil(np.log2(max(2, x.shape[0]))))
    if x.shape[0] > cap:
        x = x[:cap]
    if interpret:
        rounds += 1
    return x, rounds
"""
    assert check_source(src, "fx.py") == []


def test_lock_mutation_in_init_exempt():
    src = """
import threading
class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._cache = {}
        self._cache["warm"] = 1
    def put(self, k, v):
        with self._lock:
            self._cache[k] = v
"""
    assert check_source(src, "fx.py") == []


def test_compile_thread_shaped_fixtures():
    """R005/R006 cover the autotuner's compile-service shape: a worker
    thread draining a queue and mutating shared dicts.  The clean variant
    mirrors ``repro.euler.autotune.CompileService``; dropping the lock
    around the worker-side ``pop`` or the thread contract re-fires the
    rules."""
    good = """
import threading
class Svc:
    def __init__(self):
        self._lock = threading.Lock()
        self._pending = {}
    def submit(self, k, t):
        with self._lock:
            self._pending[k] = t
    def _worker(self):
        while True:
            with self._lock:
                self._pending.pop(None, None)
    def start(self):
        # thread-contract: daemon compile worker; stop() joins it after
        # the sentinel drains.
        t = threading.Thread(target=self._worker, daemon=True)
        t.start()
"""
    assert check_source(good, "fx.py") == []
    # worker mutates the guarded dict outside any lock → R005
    racy = good.replace(
        "            with self._lock:\n"
        "                self._pending.pop(None, None)",
        "            self._pending.pop(None, None)")
    assert [f.rule for f in check_source(racy, "fx.py")] == ["R005"]
    # thread creation without the contract comment → R006
    bare = good.replace("        # thread-contract: daemon compile worker; "
                        "stop() joins it after\n"
                        "        # the sentinel drains.\n", "")
    assert [f.rule for f in check_source(bare, "fx.py")] == ["R006"]


def test_timing_rule_scope_and_sinks():
    """R007 is satisfied by routing the measurement through an obs sink,
    by clock *references*, and by being outside the policed trees."""
    sinked = """
import time
def dispatch(trace, prog, args):
    t0 = time.perf_counter()
    with trace.span("dispatch"):
        out = prog(*args)
    return out, time.perf_counter() - t0
"""
    assert check_source(sinked, "src/repro/core/fx.py") == []
    # a clock reference (no call) is how instruments take injectable
    # clocks — never a finding
    ref = """
import time
def make(clock=time.perf_counter):
    return clock
"""
    assert check_source(ref, "src/repro/euler/fx.py") == []
    # identical orphan timing outside repro/{core,euler,launch} is fine
    assert check_source(BAD["R007"], "src/repro/analysis/fx.py") == []


def test_source_tree_is_clean():
    findings = check_paths([default_target()])
    assert findings == [], "\n".join(str(f) for f in findings)


# ----------------------------------------------------------------------
# jaxpr census unit (no mesh needed)
# ----------------------------------------------------------------------
def test_census_counts_nested_scan_eqns():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def body(c, x):
        return c + jnp.sin(x), c

    def run(xs):
        return lax.scan(body, 0.0, xs)

    cen = census(jax.make_jaxpr(run)(jnp.zeros(7)))
    assert cen.get("scan") == 1
    assert cen.get("sin") == 1       # found inside the scan body


# ----------------------------------------------------------------------
# golden audit of the real fused programs (subprocess: needs 2 devices)
# ----------------------------------------------------------------------
def test_audit_golden_scale5():
    out = run_with_devices("""
        import json
        import repro.core.engine as engine_mod
        from repro.analysis import audit_graph
        from repro.euler import EulerSolver
        from repro.graphgen.eulerize import eulerian_rmat

        g = eulerian_rmat(5, avg_degree=3, seed=0)
        # pin the replicated Phase 3 oracle path (sharded defaults on
        # for P>1 and has its own golden below)
        solver = EulerSolver(n_parts=2, width_ladder=(1, 4),
                             sharded_phase3=False)
        report = audit_graph(solver, g)
        print("REPORT=" + json.dumps(report, default=str))

        # the gate is live: an under-budgeted schedule must fail the audit
        real = engine_mod.fused_collective_budget
        def tampered(n_levels):
            b = dict(real(n_levels))
            b["all_to_all"] -= 1
            return b
        engine_mod.fused_collective_budget = tampered
        bad = audit_graph(solver, g, widths=(1,), check_donation=False)
        assert not bad["ok"], "audit passed under a tampered budget"
        viol = bad["programs"][0]["violations"]
        assert any("all_to_all" in v for v in viol), viol
        print("TAMPER_DETECTED")
    """, n=8)
    assert "TAMPER_DETECTED" in out
    report = json.loads(out.split("REPORT=", 1)[1].splitlines()[0])
    assert report["ok"], report
    assert [p["batch"] for p in report["programs"]] == [None, 4]
    n_levels = report["bucket"]["n_levels"]
    for prog in report["programs"]:
        assert prog["violations"] == []
        cen = prog["census"]
        assert cen["all_to_all"] == prog["budget"]["all_to_all"]
        assert cen["all_gather"] == 1
        assert cen.get("psum", 0) == 0
        level_scans = [s for s in prog["scans"] if s[1].get("all_to_all")]
        assert len(level_scans) == 1 and level_scans[0][0] == n_levels
    one = report["programs"][0]
    assert one["donated_marker"] is True       # one-shot path donates
    assert one["resident_marker"] is False     # cached program must not
    # byte-budget accounting: the static cost model prices every audited
    # program and the totals feed the solver's byte-aware LRU
    budget = report["cache_budget"]
    assert set(budget["per_program_bytes"]) == {"B1", "B4"}
    assert all(v > 0 for v in budget["per_program_bytes"].values())
    assert budget["total_bytes"] == sum(budget["per_program_bytes"].values())
    assert budget["budget_bytes"] is None      # solver had no byte budget
    assert budget["within_budget"] is None
    for prog in report["programs"]:
        assert prog["cost"]["program_bytes"] > 0


# ----------------------------------------------------------------------
# golden audit of the SHARDED Phase 3 programs (DESIGN.md §11)
# ----------------------------------------------------------------------
def test_audit_golden_sharded_scale5():
    out = run_with_devices("""
        import json
        import repro.core.engine as engine_mod
        from repro.analysis import audit_graph
        from repro.euler import EulerSolver
        from repro.graphgen.eulerize import eulerian_rmat

        g = eulerian_rmat(5, avg_degree=3, seed=0)
        solver = EulerSolver(n_parts=2, width_ladder=(1, 4))
        assert solver.sharded_phase3          # default ON for P > 1
        report = audit_graph(solver, g)
        print("REPORT=" + json.dumps(report, default=str))

        ng = EulerSolver(n_parts=2, width_ladder=(1,),
                         gather_circuit=False)
        rep_ng = audit_graph(ng, g, widths=(1,), check_donation=False)
        print("REPORT_NG=" + json.dumps(rep_ng, default=str))

        # the live gate covers the ring schedule too: an under-budgeted
        # ppermute count must fail the sharded audit
        real = engine_mod.fused_collective_budget
        def tampered(n_levels, **kw):
            b = dict(real(n_levels, **kw))
            if "ppermute" in b and b["ppermute"]:
                b["ppermute"] -= 1
            return b
        engine_mod.fused_collective_budget = tampered
        bad = audit_graph(solver, g, widths=(1,), check_donation=False)
        assert not bad["ok"], "audit passed under a tampered ring budget"
        viol = bad["programs"][0]["violations"]
        assert any("ppermute" in v for v in viol), viol
        print("TAMPER_DETECTED")
    """, n=8)
    assert "TAMPER_DETECTED" in out
    report = json.loads(out.split("REPORT=", 1)[1].splitlines()[0])
    assert report["ok"], report
    assert report["bucket"]["sharded_phase3"] is True
    n_levels = report["bucket"]["n_levels"]
    for prog in report["programs"]:
        assert prog["violations"] == []
        cen, sched = prog["census"], prog["budget"]["phase3"]
        rounds = sched["doubling_rounds"]
        # ring schedule: 9 ppermute eqns (the two doubling rings each
        # sit in one R-round loop), 3 psum (the halt stub, the edges off
        # the rank's orbit, the splice's `changed`), one emission gather
        assert cen["ppermute"] == 9 == sched["ppermute"]
        assert sum(1 for ln, body in prog["scans"]
                   if ln == rounds and body.get("ppermute")) == 2
        assert cen["psum"] == 3 == sched["psum"]
        assert cen["all_gather"] == 1
        assert cen["all_to_all"] == prog["budget"]["all_to_all"]
        assert prog["cost"]["sharded"] is True
        # exactly one all_to_all-bearing scan (the level scan); the ring
        # fori_loops lower to ppermute-only scans; NO gather in any scan
        level_scans = [s for s in prog["scans"] if s[1].get("all_to_all")]
        assert len(level_scans) == 1 and level_scans[0][0] == n_levels
        assert not any(s[1].get("all_gather") for s in prog["scans"])

    rep_ng = json.loads(out.split("REPORT_NG=", 1)[1].splitlines()[0])
    assert rep_ng["ok"], rep_ng
    ng_prog = rep_ng["programs"][0]
    # gather_circuit=False elides the final all_gather entirely
    assert ng_prog["census"].get("all_gather", 0) == 0
    assert ng_prog["budget"]["phase3"]["all_gather"] == 0


# ----------------------------------------------------------------------
# peak-memory regression: per-device Phase 3 state is O(2E/n), not O(2E)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_parts", [2, 4, 8])
def test_sharded_phase3_memory_is_o_2e_over_n(n_parts):
    e_cap = 1 << 20
    rep = phase3_cost_model(e_cap, None)
    sh = phase3_cost_model(e_cap, None, n_parts=n_parts, sharded=True)
    assert sh["sharded"] and not rep["sharded"]
    # table width shrinks by exactly the partition count (up to the
    # even-width rounding of shard_width)
    assert sh["phase3_table_width"] * n_parts <= \
        rep["phase3_table_width"] + 2 * n_parts
    # the persistent working set follows: n devices hold ~1/n each
    assert sh["phase3_state_bytes"] * n_parts <= \
        rep["phase3_state_bytes"] + 64 * n_parts
    for name in ("cc", "rank"):
        assert sh["loops"][name]["table_bytes"] * n_parts <= \
            rep["loops"][name]["table_bytes"] + 64 * n_parts
