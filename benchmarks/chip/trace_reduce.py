"""Reduce a JAX profiler trace of a benchmark window to device times.

The window is the host span ``chipbench.window`` that the traffic loop
opens around its solves (a ``jax.profiler.TraceAnnotation``), so the
window and the device ops are on one clock.  For each chip:

* busy: the union of the intervals of its ``XLA Ops`` events inside the
  window;
* by source file: each instant of busy time goes to the innermost op
  running then (a ``while`` op holds its body's ops), and from it to the
  repository file that the op's HLO metadata names (its ``source``
  stat, ``src/repro/...``), or to ``other`` where it names none; so the
  files' seconds add up to busy exactly;
* collectives: ops whose HLO opcode is ``all-to-all``,
  ``collective-permute``, ``all-reduce``, ``all-gather`` or
  ``reduce-scatter`` (or their ``-start``/``-done`` halves).  An op's
  kind is the opcode in its HLO text (``%all_to_all.4 = s32[..]
  all-to-all(...)``), not its instruction name: JAX names instructions
  after the primitive they come from, so a ``reshape`` inside
  ``all_to_all`` is also called ``all_to_all.N``.  An op whose text is
  not in the trace takes its kind from its name;
* loop control: the time inside ``while`` ops in which none of their
  body's ops ran.  A device loop runs on the chip with no host in it
  (its condition and counter are the chip's own work), so that time is
  busy, and goes to the loop's file; the breakdown lists it as one
  entry of its own, ``LOOP_CONTROL``, so that it shows how much of busy
  no body op covers.

Idle gaps are the stretches of the window in which the busiest chip ran
nothing, summed by where they lay relative to the ``chipbench.solve``
spans.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import Counter, defaultdict
from typing import Dict, List, Tuple

from .xspace import read_xspace, stat_value

WINDOW = "chipbench.window"
SOLVE = "chipbench.solve"
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-to-all", "collective-permute", "all-reduce",
               "all-gather", "reduce-scatter")
#: the breakdown's entry for the self time of every ``while`` op
LOOP_CONTROL = "while loops: no body op running"
#: a repository source file in HLO metadata, e.g. ".../src/repro/core/phase1.py:212"
SOURCE_RE = re.compile(r"(repro/[\w/]+\.py)(?::(\d+))?")


@dataclasses.dataclass
class Op:
    name: str            # HLO op kind: "fusion", "while", "all-to-all"
    start: int           # ps
    end: int             # ps
    source: str          # "repro/core/phase1.py" or "other"
    line: str            # source line, "" where unknown


@dataclasses.dataclass
class DeviceTime:
    name: str
    busy_s: float
    by_file: Dict[str, float]
    collective_s: float
    collective_ops: int
    by_op: Dict[str, float]   # "file:line kind" (or LOOP_CONTROL) -> seconds
    loop_control_s: float


@dataclasses.dataclass
class Reduction:
    window_s: float
    n_solves: int
    devices: List[DeviceTime]
    gaps: List[Tuple[str, float]]

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def max_busy_s(self) -> float:
        return max(d.busy_s for d in self.devices)

    @property
    def collective_s(self) -> float:
        return sum(d.collective_s for d in self.devices) / len(self.devices)

    @property
    def loop_control_s(self) -> float:
        """Seconds in ``while`` ops with no body op running, averaged
        over the chips."""
        return sum(d.loop_control_s for d in self.devices) / len(self.devices)

    @property
    def collective_ops(self) -> int:
        return sum(d.collective_ops for d in self.devices)

    def file_seconds(self, files) -> float:
        """Busy seconds in ``files``, averaged over the chips."""
        return sum(d.by_file.get(f, 0.0) for d in self.devices
                   for f in files) / len(self.devices)

    def breakdown(self) -> dict:
        ops: Counter = Counter()
        for d in self.devices:
            for k, v in d.by_op.items():
                ops[k] += v / len(self.devices)
        return {"device_ops": [[k, v] for k, v in ops.most_common(10)],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def _source(stats: dict) -> Tuple[str, str]:
    """(repository file, line) of an op's ``source`` stat, or ("other", "")."""
    m = SOURCE_RE.search(str(stats.get("source", "")))
    return (m.group(1), m.group(2) or "") if m else ("other", "")


def _kind(name: str) -> str:
    """``all-to-all`` of ``%all-to-all.3 = ...`` or ``all-to-all.3``."""
    return name.lstrip("%").split(" ", 1)[0].rsplit(".", 1)[0]


def _opcode(text: str) -> str:
    """The opcode of an HLO instruction's text, ``%name = shape
    opcode(operands), ...``; "" where ``text`` is no such text."""
    if " = " not in text:
        return ""
    rest = text.split(" = ", 1)[1]
    if rest.startswith("("):                  # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.strip().split("(", 1)[0]


def _self_times(ops: List[Op], lo: int, hi: int):
    """Split the union of ``ops`` inside [lo, hi] among them: each instant
    goes to the innermost op that covers it (a ``while`` op holds its
    body's ops).  Returns ([(op, ps)], busy intervals)."""
    shares: Dict[int, int] = defaultdict(int)
    intervals: List[List[int]] = []

    def give(i, a, b):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            shares[i] += b - a
            if intervals and intervals[-1][1] >= a:
                intervals[-1][1] = max(intervals[-1][1], b)
            else:
                intervals.append([a, b])

    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    stack: List[Tuple[int, int]] = []          # (op index, clamped end)
    cursor = None
    for i in order:
        s, e = ops[i].start, ops[i].end
        while stack and stack[-1][1] <= s:
            j, end = stack.pop()
            give(j, cursor, end)
            cursor = end
        if stack:
            give(stack[-1][0], cursor, s)
            e = min(e, stack[-1][1])           # a child ends inside its parent
        cursor = s
        stack.append((i, e))
    while stack:
        j, end = stack.pop()
        give(j, cursor, end)
        cursor = end
    return [(ops[i], ps) for i, ps in shares.items()], intervals


def reduce_space(space) -> Reduction:
    """Reduce an ``XSpace`` (see :mod:`.xspace`); times in picoseconds."""
    windows, solves = [], []
    device_ops: Dict[str, List[Op]] = defaultdict(list)
    for plane in space.planes:
        is_device = plane.name.startswith("/device:TPU")
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        if is_device:
            for k, m in plane.event_metadata.items():
                st = {stat_names.get(x.metadata_id, ""): stat_value(x)
                      for x in m.stats}
                src, lineno = _source(st)
                kind = _opcode(m.name) or _kind(m.display_name or m.name)
                meta[k] = (kind, src, lineno)
        for line in plane.lines:
            if is_device and line.name != OPS_LINE:
                continue
            base = line.timestamp_ns * 1000
            for ev in line.events:
                start = base + ev.offset_ps
                end = start + ev.duration_ps
                if is_device:
                    kind, src, lineno = meta.get(ev.metadata_id,
                                                 ("?", "other", ""))
                    device_ops[plane.name].append(
                        Op(kind, start, end, src, lineno))
                else:
                    name = plane.event_metadata[ev.metadata_id].name
                    if name == WINDOW:
                        windows.append((start, end))
                    elif name == SOLVE:
                        solves.append((start, end))
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(windows)}")
    if not device_ops:
        raise ValueError("the trace holds no device ops")
    lo, hi = windows[0]
    solves = [s for s in solves if lo <= s[0] <= hi]
    devices = []
    busiest = None
    for name in sorted(device_ops):
        shares, intervals = _self_times(device_ops[name], lo, hi)
        by_file: Counter = Counter()
        by_op: Counter = Counter()
        coll_s, coll_n, loop_s = 0.0, 0, 0.0
        for op, ps in shares:
            by_file[op.source] += ps / 1e12
            if op.name == "while":
                by_op[LOOP_CONTROL] += ps / 1e12
                loop_s += ps / 1e12
                continue
            label = f"{op.source}:{op.line}" if op.line else op.source
            by_op[f"{label} {op.name}"] += ps / 1e12
            if op.name.startswith(COLLECTIVES):
                coll_s += ps / 1e12
                coll_n += 1
        busy = sum(b - a for a, b in intervals) / 1e12
        dev = DeviceTime(name, busy, dict(by_file), coll_s, coll_n,
                         dict(by_op), loop_s)
        devices.append(dev)
        if busiest is None or dev.busy_s > busiest[0].busy_s:
            busiest = (dev, intervals)
    gaps = _gaps(busiest[1], lo, hi, solves)
    return Reduction((hi - lo) / 1e12, len(solves), devices, gaps)


def _gaps(intervals, lo: int, hi: int, solves) -> List[Tuple[str, float]]:
    """Idle seconds of the busiest chip, summed by where they lay: in a
    solve before its first device op (host prep, upload, dispatch), after
    its last (fetch, strip), between its ops, or between solves."""
    edges = [lo] + [x for iv in intervals for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    where: Counter = Counter()
    for s, e in idle:
        inside = 0
        for a, b in solves:
            ov = min(e, b) - max(s, a)
            if ov <= 0:
                continue
            inside += ov
            ops = [iv for iv in intervals if iv[1] > a and iv[0] < b]
            if not ops or e <= ops[0][0]:
                where["solve: host before its first device op"] += ov / 1e12
            elif s >= ops[-1][1]:
                where["solve: host after its last device op"] += ov / 1e12
            else:
                where["solve: device idle between its ops"] += ov / 1e12
        if e - s > inside:
            where["between solves"] += (e - s - inside) / 1e12
    return where.most_common()


def find_xplane(directory) -> str:
    found = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, "
                         f"found {len(found)}")
    return found[0]


def reduce_file(path: str) -> Reduction:
    """Reduce the profiler trace ``path`` (an ``.xplane.pb``)."""
    return reduce_space(read_xspace(path))


def reduce_dir(directory) -> Reduction:
    """Reduce the one profiler trace under ``directory``."""
    return reduce_file(find_xplane(directory))
