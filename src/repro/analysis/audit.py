"""CLI: audit the fused programs of a representative bucket + lint src.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.analysis.audit \
        --scale 5 --parts 2 --widths 1,4 --json AUDIT.json

Builds an Eulerian R-MAT graph, buckets it through a fresh
:class:`EulerSolver` (same ladder quantization the serving path uses),
traces every requested batch width's fused program and audits each
against the static schedule (:mod:`repro.analysis.jaxpr_audit`), then
runs the repo lint (:mod:`repro.analysis.lint`) over ``src/``.  Writes
the combined report as JSON and exits non-zero on any violation — CI
uploads the report as the ``AUDIT.json`` artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Sequence


def _parse(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.audit", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--scale", type=int, default=5,
                    help="R-MAT scale (2**scale vertices)")
    ap.add_argument("--parts", type=int, default=2,
                    help="partition/device count")
    ap.add_argument("--avg-degree", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--widths", default="1,4",
                    help="comma-separated batch widths to audit")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the full report here (e.g. AUDIT.json)")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the source-tree lint pass")
    ap.add_argument("--no-donation", action="store_true",
                    help="skip the buffer-donation lowering checks")
    ap.add_argument("--replicated-phase3", action="store_true",
                    help="audit the replicated Phase 3 oracle path "
                         "(default: sharded when --parts > 1)")
    ap.add_argument("--no-gather-circuit", action="store_true",
                    help="audit the gather_circuit=False variant "
                         "(sharded rank triple, host-side emission)")
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = _parse(argv)
    import jax

    if len(jax.devices()) < args.parts:
        print(f"audit needs {args.parts} devices, have "
              f"{len(jax.devices())} — set XLA_FLAGS="
              f"--xla_force_host_platform_device_count={args.parts} "
              f"(before importing jax)", file=sys.stderr)
        return 2

    from repro.analysis import audit_graph, lint
    from repro.euler import EulerSolver
    from repro.graphgen.eulerize import eulerian_rmat

    widths = [int(w) for w in args.widths.split(",") if w]
    graph = eulerian_rmat(args.scale, avg_degree=args.avg_degree,
                          seed=args.seed)
    solver = EulerSolver(
        n_parts=args.parts, width_ladder=widths or (1,),
        sharded_phase3=False if args.replicated_phase3 else None,
        gather_circuit=not args.no_gather_circuit)
    report = audit_graph(solver, graph, widths=widths,
                         check_donation=not args.no_donation)

    findings = []
    if not args.no_lint:
        findings = lint.check_paths([lint.default_target()])
        report["lint"] = {
            "findings": [str(f) for f in findings],
            "ok": not findings,
        }
        report["ok"] = report["ok"] and not findings

    for prog in report["programs"]:
        tag = f"e_cap={prog['e_cap']} B={prog['batch'] or 1}"
        state = "ok" if prog["ok"] else "FAIL"
        a2a = prog["census"].get("all_to_all", 0)
        print(f"  [{state}] {tag}: {a2a} all_to_all / "
              f"{prog['census'].get('all_gather', 0)} all_gather / "
              f"{prog['census'].get('ppermute', 0)} ppermute "
              f"(scan length {prog['n_levels']})")
        for viol in prog["violations"]:
            print(f"         - {viol}")
    for f in findings:
        print(f"  [lint] {f}")

    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2, default=str)
        print(f"report -> {args.json}")

    print(f"repro.analysis.audit: "
          f"{'PASS' if report['ok'] else 'FAIL'} "
          f"({len(report['programs'])} program(s), "
          f"{len(findings)} lint finding(s))")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
