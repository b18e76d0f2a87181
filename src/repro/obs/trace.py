"""Span tracing: context-manager spans into a bounded in-process ring.

A ``TraceLog`` is the collector: spans open with ``log.span(name)``,
nest via a thread-local parent stack (so concurrent serving / compile
threads interleave without cross-linking), and close into a bounded
ring (``collections.deque``) plus an optional JSONL sink.  Span ids
are sequential ints assigned under the log's lock — with an injected
clock the whole span tree is deterministic, which is what the tests
pin down.

A span can also feed a histogram: ``log.span("launch", metric=h)``
observes the span's duration into ``h`` on exit, so one seam yields
both the trace tree and the latency distribution.

>>> t = [0.0]
>>> log = TraceLog(capacity=8, clock=lambda: t[0])
>>> with log.span("flush", bucket="(16,2,4)") as outer:
...     t[0] = 1.0
...     with log.span("launch"):
...         t[0] = 3.0
>>> [(s["name"], s["dur_s"], s["parent"]) for s in log.spans()]
[('launch', 2.0, 1), ('flush', 3.0, None)]

A request's spans form one tree: ``log.request(name)`` opens a span
that draws a fresh request id (``req``), which every span opened
beneath it inherits.  A request may outlive the block that opened it
(an asynchronous solve is dispatched in one call and fetched in
another): ``start()`` opens it without making it the thread's parent,
``scope()`` makes it the parent for one block, ``end()`` closes it.

>>> root = log.request("solve").start()
>>> with root.scope():
...     with log.span("prepare"):
...         t[0] = 4.0
>>> root.end()
>>> [(s["name"], s["req"], s["parent"] == root.id) for s in log.spans()[2:]]
[('prepare', 1, True), ('solve', 1, False)]

The log's default clock is ``time.perf_counter``.  While a span is open
it is also a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``
when jax is loaded, so a profile shows the program's spans on the host
plane beside the device ops; without a profiler session that is a no-op,
and without jax nothing is done (this module never imports jax).
"""
from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
from collections import deque
from typing import IO, Dict, Iterator, List, Optional, Union


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` for ``name`` while a
    profiler session runs, else None.  Never imports jax."""
    jax = sys.modules.get("jax")
    prof = getattr(jax, "profiler", None)
    if prof is None or not prof.TraceAnnotation.is_enabled():
        return None
    ann = prof.TraceAnnotation("repro." + name)
    ann.__enter__()
    return ann


class Span:
    """One timed section.  Use as a context manager; attributes passed
    at creation plus any added via ``set(...)`` land in the record."""

    __slots__ = ("log", "name", "attrs", "id", "parent", "req", "t0",
                 "dur_s", "status", "_metric", "_new_req", "_ann")

    def __init__(self, log: "TraceLog", name: str, metric=None,
                 new_req: bool = False, **attrs):
        self.log = log
        self.name = name
        self.attrs: Dict[str, object] = dict(attrs)
        self.id: Optional[int] = None
        self.parent: Optional[int] = None
        self.req: Optional[int] = None
        self.t0 = 0.0
        self.dur_s = 0.0
        self.status = "ok"
        self._metric = metric
        self._new_req = new_req
        self._ann = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def start(self) -> "Span":
        """Open the span without making it this thread's parent."""
        self.log._open(self)
        return self

    def end(self, exc_type=None) -> None:
        """Close the span (``exc_type``: the exception that ended it)."""
        if exc_type is not None:
            self.status = "error"
            self.attrs.setdefault("error", exc_type.__name__)
        self.log._close(self)
        if self._metric is not None:
            self._metric.observe(self.dur_s)

    @contextlib.contextmanager
    def scope(self) -> Iterator["Span"]:
        """Make this open span the parent of spans opened in the block;
        an exception escaping the block ends the span as an error."""
        st = self.log._stack()
        st.append(self)
        try:
            yield self
        except BaseException as e:
            st.pop()
            self.end(type(e))
            raise
        st.pop()

    def __enter__(self) -> "Span":
        self.start()
        self.log._stack().append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        st = self.log._stack()
        if st and st[-1] is self:
            st.pop()
        self.end(exc_type)
        return False


class TraceLog:
    """Bounded collector of closed spans (newest-last ring).

    ``capacity`` bounds memory; ``sink`` (a path or writable file
    object) additionally streams every closed span as one JSON line.
    The per-thread open-span stack lives in ``threading.local`` so
    parentage never crosses threads.
    """

    def __init__(self, capacity: int = 2048, clock=time.perf_counter,
                 sink: Union[None, str, IO[str]] = None):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self._next_id = 1
        self._next_req = 1
        self._tls = threading.local()
        self.clock = clock
        self._sink: Optional[IO[str]] = None
        self._sink_owned = False
        if isinstance(sink, str):
            self._sink = open(sink, "a")
            self._sink_owned = True
        elif sink is not None:
            self._sink = sink

    # ------------------------------------------------------------ spans
    def span(self, name: str, metric=None, **attrs) -> Span:
        return Span(self, name, metric=metric, **attrs)

    def request(self, name: str, **attrs) -> Span:
        """A span that draws a fresh request id (``req``) when it opens;
        every span opened beneath it carries the same ``req``."""
        return Span(self, name, new_req=True, **attrs)

    def event(self, name: str, **attrs) -> None:
        """Record an instantaneous (zero-duration) span — for point
        occurrences like a jit retrace, where the surrounding timing
        belongs to whoever triggered it."""
        with self.span(name, **attrs):
            pass

    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _open(self, span: Span) -> None:
        st = self._stack()
        top = st[-1] if st else None
        with self._lock:
            span.id = self._next_id
            self._next_id += 1
            if span._new_req:
                span.req = self._next_req
                self._next_req += 1
        span.parent = top.id if top is not None else None
        if not span._new_req and top is not None:
            span.req = top.req
        span._ann = _annotation(span.name)
        span.t0 = self.clock()

    def _close(self, span: Span) -> None:
        span.dur_s = self.clock() - span.t0
        if span._ann is not None:
            span._ann.__exit__(None, None, None)
            span._ann = None
        rec = {"id": span.id, "parent": span.parent, "name": span.name,
               "t0": span.t0, "dur_s": span.dur_s, "status": span.status,
               "thread": threading.current_thread().name}
        if span.req is not None:
            rec["req"] = span.req
        if span.attrs:
            rec["attrs"] = dict(span.attrs)
        with self._lock:
            self._ring.append(rec)
            if self._sink is not None:
                self._sink.write(json.dumps(rec, default=str) + "\n")
                self._sink.flush()

    # ------------------------------------------------------------ reads
    def spans(self) -> List[dict]:
        """Closed spans, oldest first (bounded by ``capacity``)."""
        with self._lock:
            return [dict(r) for r in self._ring]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def close(self) -> None:
        with self._lock:
            if self._sink is not None and self._sink_owned:
                self._sink.close()
            self._sink = None


class NullTraceLog(TraceLog):
    """Tracing disabled: spans still time themselves (``dur_s``; the
    solver's ``prepare_s`` is a span's duration) but get no id, feed no
    histogram, annotate no profile and are never recorded.  Engine/solver
    default to the process trace log; pass one of these to switch
    instrumentation off wholesale."""

    def __init__(self):
        super().__init__(capacity=1)

    def span(self, name: str, metric=None, **attrs) -> Span:
        return Span(self, name)

    def request(self, name: str, **attrs) -> Span:
        return Span(self, name)

    def _open(self, span: Span) -> None:
        span.t0 = self.clock()

    def _close(self, span: Span) -> None:
        span.dur_s = self.clock() - span.t0


# Process-default trace log, mirroring metrics.DEFAULT.
DEFAULT = TraceLog()


def default_tracelog() -> TraceLog:
    return DEFAULT
