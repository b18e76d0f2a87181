"""repro.obs: metrics registry (thread-safety under concurrent writers),
span tracing (deterministic trees under an injected clock), and the
exporters (Prometheus text, JSON snapshot, HTTP endpoint).

No jax anywhere: the obs layer is stdlib-only by design (DESIGN.md §13)
so instrumentation can never drag device initialization into a tool.
"""
import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.error
import urllib.request

import pytest

from repro.obs import (MetricsServer, NullTraceLog, Registry, TraceLog,
                       default_registry, default_tracelog,
                       render_prometheus, snapshot)


# ----------------------------------------------------------------------
# registry semantics
# ----------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    reg = Registry(clock=lambda: 0.0)
    c = reg.counter("hits", "cache hits")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("bytes", "cache bytes")
    g.set(10.0)
    g.add(-4.0)
    assert g.value == 6.0

    h = reg.histogram("width", "flush width", lo_exp=0, hi_exp=4)
    for w in (1, 1, 2, 4, 16, 100):
        h.observe(w)
    assert h.count == 6
    assert h.sum == 124.0
    # bounds are 1,2,4,8,16 plus +Inf; 100 lands in the overflow bucket
    assert h.percentile(0.0) == 0.0 or h.percentile(0.0) <= 1.0
    assert h.percentile(1.0) == 16.0   # overflow bucket reports lo bound


def test_family_kind_mismatch_rejected():
    reg = Registry()
    reg.counter("x", "first registration wins")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")
    # re-request with the same kind returns the SAME family
    assert reg.counter("x") is reg.counter("x")


def test_labeled_children_are_distinct_and_stable():
    reg = Registry()
    fam = reg.counter("hits", "per-session hits")
    a = fam.labels(session="s0")
    b = fam.labels(session="s1")
    assert a is not b
    assert fam.labels(session="s0") is a     # keyed get-or-create
    a.inc(2)
    b.inc(5)
    assert a.value == 2 and b.value == 5
    # the no-label convenience child is its own point
    fam.inc()
    assert fam.value == 1
    assert {dict(k).get("session") for k, _ in fam.children()} == \
        {None, "s0", "s1"}


def test_percentile_interpolation():
    reg = Registry()
    h = reg.histogram("lat", "latency", lo_exp=-4, hi_exp=4)
    # 100 observations all in the (1, 2] bucket -> percentiles
    # interpolate linearly across that bucket
    for _ in range(100):
        h.observe(1.5)
    p50, p95 = h.percentile(0.50), h.percentile(0.95)
    assert 1.0 < p50 < p95 <= 2.0
    assert h.percentile(0.0) <= p50
    # empty histogram reports 0.0
    assert reg.histogram("empty", "x").percentile(0.5) == 0.0


def test_snapshot_shape():
    reg = Registry()
    reg.counter("hits", "h").labels(session="s0").inc(3)
    reg.histogram("w", "w", lo_exp=0, hi_exp=2).observe(2)
    snap = reg.snapshot()
    assert snap["hits"]["kind"] == "counter"
    assert snap["hits"]["points"] == [
        {"labels": {"session": "s0"}, "value": 3}]
    (pt,) = snap["w"]["points"]
    assert pt["count"] == 1 and pt["sum"] == 2.0
    # only non-empty buckets are materialized
    assert sum(pt["buckets"].values()) == pt["count"]


def test_registry_concurrent_writers_consistent_snapshots():
    """The one-lock design promise: every snapshot is a consistent cut.
    Concurrent writers can never produce a snapshot whose histogram
    bucket counts disagree with its total count, and counters are
    monotone across successive snapshots."""
    reg = Registry()
    c = reg.counter("ops", "total ops")
    h = reg.histogram("val", "values", lo_exp=0, hi_exp=8)
    N_THREADS, N_OPS = 8, 2000
    start = threading.Barrier(N_THREADS + 1)

    def writer(i):
        ch = c.labels(worker=str(i))
        start.wait()
        for k in range(N_OPS):
            ch.inc()
            c.inc()                     # shared no-label child
            h.observe(float(1 + k % 200))

    threads = [threading.Thread(target=writer, args=(i,), daemon=True)
               for i in range(N_THREADS)]
    for t in threads:
        t.start()
    start.wait()

    last_total = 0
    for _ in range(50):                 # reader races the writers
        snap = reg.snapshot()
        for pt in snap["val"]["points"]:
            assert sum(pt["buckets"].values()) == pt["count"], \
                "torn histogram snapshot"
        totals = [p["value"] for p in snap["ops"]["points"]
                  if not p["labels"]]
        if totals:
            assert totals[0] >= last_total, "counter went backwards"
            last_total = totals[0]
    for t in threads:
        t.join(timeout=30)
    assert c.value == N_THREADS * N_OPS
    per_worker = {p["labels"].get("worker"): p["value"]
                  for p in reg.snapshot()["ops"]["points"]}
    assert all(per_worker[str(i)] == N_OPS for i in range(N_THREADS))
    assert h.count == N_THREADS * N_OPS


# ----------------------------------------------------------------------
# span tracing
# ----------------------------------------------------------------------
def test_span_tree_deterministic_under_fake_clock():
    t = [0.0]
    log = TraceLog(capacity=16, clock=lambda: t[0])
    with log.span("flush", bucket=64) as outer:
        t[0] = 1.0
        with log.span("launch", hit=False):
            t[0] = 4.0
        with log.span("fetch"):
            t[0] = 6.0
        outer.set(widths=[2, 1])
    spans = log.spans()
    by_name = {s["name"]: s for s in spans}
    assert [s["name"] for s in spans] == ["launch", "fetch", "flush"]
    assert by_name["flush"]["parent"] is None
    assert by_name["launch"]["parent"] == by_name["flush"]["id"]
    assert by_name["fetch"]["parent"] == by_name["flush"]["id"]
    assert by_name["launch"]["dur_s"] == 3.0
    assert by_name["fetch"]["dur_s"] == 2.0
    assert by_name["flush"]["dur_s"] == 6.0
    assert by_name["flush"]["attrs"] == {"bucket": 64, "widths": [2, 1]}
    assert by_name["launch"]["attrs"] == {"hit": False}


def test_span_error_status_and_metric_feed():
    t = [0.0]
    reg = Registry()
    h = reg.histogram("dur", "span durations", lo_exp=-4, hi_exp=4)
    log = TraceLog(clock=lambda: t[0])
    with pytest.raises(RuntimeError):
        with log.span("compile", metric=h):
            t[0] = 2.0
            raise RuntimeError("boom")
    (s,) = log.spans()
    assert s["status"] == "error"
    assert s["attrs"]["error"] == "RuntimeError"
    # the duration still fed the histogram
    assert h.count == 1 and h.sum == 2.0


def test_span_parentage_never_crosses_threads():
    log = TraceLog(clock=lambda: 0.0)
    done = threading.Event()

    def other():
        with log.span("worker"):
            pass
        done.set()

    with log.span("main"):
        th = threading.Thread(target=other, daemon=True)
        th.start()
        assert done.wait(10)
        th.join(10)
    by_name = {s["name"]: s for s in log.spans()}
    # the worker span opened while "main" was open on another thread,
    # yet has no parent: stacks are thread-local
    assert by_name["worker"]["parent"] is None
    assert by_name["main"]["parent"] is None
    assert by_name["worker"]["thread"] != by_name["main"]["thread"]


def test_trace_ring_is_bounded_and_event_is_instant():
    t = [0.0]
    log = TraceLog(capacity=4, clock=lambda: t[0])
    for i in range(10):
        log.event("e", i=i)
    assert len(log) == 4
    assert [s["attrs"]["i"] for s in log.spans()] == [6, 7, 8, 9]
    assert all(s["dur_s"] == 0.0 for s in log.spans())
    log.clear()
    assert len(log) == 0


def test_jsonl_sink(tmp_path):
    path = tmp_path / "spans.jsonl"
    t = [0.0]
    log = TraceLog(clock=lambda: t[0], sink=str(path))
    with log.span("a"):
        t[0] = 1.0
    log.event("b")
    log.close()
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["name"] for r in recs] == ["a", "b"]
    assert recs[0]["dur_s"] == 1.0


def test_null_tracelog_records_nothing():
    log = NullTraceLog()
    with log.span("x", k=1) as sp:
        sp.set(more=2)      # no-op, chainable surface
    log.event("y")
    assert len(log) == 0 and log.spans() == []


def test_null_tracelog_spans_still_time_themselves():
    t = [0.0]
    log = NullTraceLog()
    log.clock = lambda: t[0]
    with log.span("prepare") as sp:
        t[0] = 2.5
    assert sp.dur_s == 2.5 and sp.id is None
    assert len(log) == 0


def test_request_tree_outlives_its_block_and_shares_req():
    """A root opened in one call (dispatch) and closed in another
    (fetch): children opened under ``scope()`` nest in it, inherit its
    ``req``; a second request draws a new one."""
    t = [0.0]
    log = TraceLog(clock=lambda: t[0])
    with log.span("flush"):
        root = log.request("solve", width=1).start()
        with root.scope():
            with log.span("prepare"):
                with log.span("partition"):
                    t[0] = 1.0
        with log.span("outside"):
            pass
    with root.scope():
        with log.span("wait"):
            t[0] = 3.0
    root.set(phase3_rounds=2)
    root.end()
    other = log.request("solve").start()
    other.end()
    by = {s["name"]: s for s in log.spans()}
    first = [s for s in log.spans() if s["name"] == "solve"][0]
    assert first["parent"] == by["flush"]["id"]
    assert first["dur_s"] == 3.0 and first["attrs"]["phase3_rounds"] == 2
    for name in ("prepare", "wait"):
        assert by[name]["parent"] == first["id"]
        assert by[name]["req"] == first["req"]
    assert by["partition"]["req"] == first["req"]
    assert "req" not in by["outside"] and "req" not in by["flush"]
    assert by["outside"]["parent"] == by["flush"]["id"]
    assert by["solve"]["req"] == first["req"] + 1    # the second request


def test_scope_ends_the_span_on_error():
    log = TraceLog(clock=lambda: 0.0)
    root = log.request("solve").start()
    with pytest.raises(KeyError):
        with root.scope():
            raise KeyError("x")
    (s,) = log.spans()
    assert s["status"] == "error" and s["attrs"]["error"] == "KeyError"
    with log.span("after"):      # the stack was popped
        pass
    assert log.spans()[-1]["parent"] is None


def test_default_clock_is_perf_counter():
    assert TraceLog().clock is time.perf_counter
    assert default_tracelog().clock is time.perf_counter


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    enabled = True
    log = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_spans_annotate_a_profile_when_jax_is_loaded(monkeypatch):
    fake = types.SimpleNamespace(
        profiler=types.SimpleNamespace(TraceAnnotation=_FakeAnnotation))
    monkeypatch.setitem(sys.modules, "jax", fake)
    _FakeAnnotation.log = []
    log = TraceLog(clock=lambda: 0.0)
    with log.span("solve"):
        log.event("retrace")
    assert _FakeAnnotation.log == [
        ("enter", "repro.solve"), ("enter", "repro.retrace"),
        ("exit", "repro.retrace"), ("exit", "repro.solve")]
    # no profiler session: nothing is entered
    _FakeAnnotation.enabled, _FakeAnnotation.log = False, []
    with log.span("solve"):
        pass
    assert _FakeAnnotation.log == []
    _FakeAnnotation.enabled = True
    # tracing off: nothing is annotated
    with NullTraceLog().span("solve"):
        pass
    assert _FakeAnnotation.log == []
    # jax not loaded: nothing to annotate with
    monkeypatch.delitem(sys.modules, "jax")
    with log.span("solve"):
        pass
    assert _FakeAnnotation.log == []


def test_obs_imports_without_jax():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src
    code = ("import sys, repro.obs as o; log = o.TraceLog();\n"
            "with log.span('x'): pass\n"
            "assert 'jax' not in sys.modules, sorted(sys.modules)")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_process_defaults_are_singletons():
    assert default_registry() is default_registry()
    assert default_tracelog() is default_tracelog()


# ----------------------------------------------------------------------
# exporters
# ----------------------------------------------------------------------
def _seeded_registry():
    reg = Registry()
    reg.counter("euler_cache_hits", "hits").labels(session="s0").inc(7)
    reg.gauge("euler_cache_bytes", "bytes").labels(session="s0").set(512)
    h = reg.histogram("euler_flush_width", "widths", lo_exp=0, hi_exp=3)
    for w in (1, 2, 2, 8):
        h.labels(session="s0").observe(w)
    return reg


def test_render_prometheus_format():
    text = render_prometheus(_seeded_registry())
    assert "# TYPE euler_cache_hits counter" in text
    assert 'euler_cache_hits{session="s0"} 7' in text
    assert "# TYPE euler_cache_bytes gauge" in text
    assert 'euler_cache_bytes{session="s0"} 512.0' in text
    assert "# TYPE euler_flush_width histogram" in text
    # cumulative buckets: 1 @ le=1, 3 @ le=2, 3 @ le=4, 4 @ le=8, 4 @ +Inf
    assert 'euler_flush_width_bucket{le="1.0",session="s0"} 1' in text
    assert 'euler_flush_width_bucket{le="2.0",session="s0"} 3' in text
    assert 'euler_flush_width_bucket{le="8.0",session="s0"} 4' in text
    assert 'euler_flush_width_bucket{le="+Inf",session="s0"} 4' in text
    assert 'euler_flush_width_sum{session="s0"} 13.0' in text
    assert 'euler_flush_width_count{session="s0"} 4' in text


def test_snapshot_includes_spans_when_given_a_trace():
    reg = _seeded_registry()
    t = [0.0]
    log = TraceLog(clock=lambda: t[0])
    log.event("retrace", program="fused")
    snap = snapshot(reg, log)
    assert "euler_flush_width" in snap["metrics"]
    assert [s["name"] for s in snap["spans"]] == ["retrace"]
    # json-serializable end to end (the --json / audit contract)
    json.dumps(snap)


def test_metrics_server_endpoints():
    reg = _seeded_registry()
    log = TraceLog(clock=lambda: 0.0)
    log.event("probe")
    srv = MetricsServer(reg, port=0, trace=log)
    try:
        with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert 'euler_cache_hits{session="s0"} 7' in text
        with urllib.request.urlopen(srv.url + "/metrics.json",
                                    timeout=10) as r:
            snap = json.loads(r.read().decode())
        assert snap["metrics"]["euler_cache_hits"]["points"][0]["value"] == 7
        assert [s["name"] for s in snap["spans"]] == ["probe"]
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url + "/nope", timeout=10)
    finally:
        srv.close()
