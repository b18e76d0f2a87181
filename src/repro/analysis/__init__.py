"""Static analysis for the warm serving path (DESIGN.md §10).

Two passes, both runnable as modules and wired into CI as a hard gate:

  ``repro.analysis.lint``        AST lint over the source tree —
                                 repo-specific rules (trace leaks, tracer
                                 coercion, bare asserts on user paths,
                                 solver lock discipline, thread contracts).
                                 ``python -m repro.analysis.lint``

  ``repro.analysis.jaxpr_audit`` audits the *compiled* fused programs: the
                                 collective census against the engine's
                                 schedule budget, zero host callbacks in
                                 the fused body, donation on the one-shot
                                 path, and the static Phase 3 table and
                                 byte model behind the program cache.
                                 ``python -m repro.analysis.audit --json``

The paper's BSP model only pays off if every superstep stays on-device
and every merge round communicates on the planned schedule; these passes
verify those invariants statically, before a program ever runs.
"""
__all__ = [
    "Finding", "check_paths", "check_source",
    "ProgramAudit", "audit_graph", "census",
    "phase3_cost_model",
]

_HOMES = {
    "Finding": "lint", "check_paths": "lint", "check_source": "lint",
    "ProgramAudit": "jaxpr_audit", "audit_graph": "jaxpr_audit",
    "census": "jaxpr_audit", "phase3_cost_model": "jaxpr_audit",
}


def __getattr__(name):
    # Lazy re-export: keeps `python -m repro.analysis.lint` from
    # double-importing its own module through the package (runpy
    # warning) and keeps the pure-AST lint importable without jax.
    if name in _HOMES:
        import importlib

        return getattr(importlib.import_module(
            f".{_HOMES[name]}", __name__), name)
    raise AttributeError(name)
