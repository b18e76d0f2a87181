"""The program's own span tree, for the per-layer metrics that read it.

``repro.obs`` records one tree per solve in its process-default trace
log (the harness builds the solver without ``trace=``): a root ``solve``
span whose children are ``prepare`` (holding ``partition``), ``stage``
(holding ``upload``), ``launch``, ``wait`` and ``strip``, and whose
attributes hold the fused program's loop counters once it is fetched
(``hook_rounds`` and ``splice_rounds``: per-level lists of per-partition
Phase 1 rounds; ``phase3_rounds``).  Its clock is ``time.perf_counter``,
the clock of the loop's records.

A program without these spans (an older tree) gives no root: the readers
then find nothing to read and return None.
"""
from __future__ import annotations

from typing import List, Optional


def _log_spans() -> List[dict]:
    try:
        from repro.obs import default_tracelog
    except ImportError:
        return []
    return default_tracelog().spans()


def window_roots(ctx, spans: Optional[List[dict]] = None
                 ) -> Optional[List[dict]]:
    """The root ``solve`` spans that started inside the window
    ``[records[0].start, records[-1].end]``, oldest first; None unless
    there is exactly one per record."""
    if not ctx.records:
        return None
    spans = _log_spans() if spans is None else spans
    lo, hi = ctx.records[0].start, ctx.records[-1].end
    roots = [s for s in spans
             if s["name"] == "solve" and lo <= s["t0"] <= hi]
    return roots if len(roots) == len(ctx.records) else None


def root_counters(ctx, key: str) -> Optional[list]:
    """Attribute ``key`` of each window root, or None where a root lacks
    it (a solve that failed before its fetch) or the roots are not one
    per record."""
    roots = window_roots(ctx)
    if roots is None or any(key not in s.get("attrs", {}) for s in roots):
        return None
    return [s["attrs"][key] for s in roots]


def children(ctx, names) -> Optional[List[List[dict]]]:
    """For each window root, its child spans named in ``names`` (one
    each); None if any root lacks one."""
    spans = _log_spans()
    roots = window_roots(ctx, spans)
    if roots is None:
        return None
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s.get("parent"), []).append(s)
    out = []
    for r in roots:
        kids = {s["name"]: s for s in by_parent.get(r["id"], [])
                if s["name"] in names}
        if len(kids) != len(names):
            return None
        out.append([kids[n] for n in names])
    return out


def mean(values) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None
