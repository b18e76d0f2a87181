"""Share of solves whose Phase 3 ran its CC labels and pivot splice
(replicated or sharded Phase 3): 0 where every solve's ranked orbit
already covered the whole mate.  A count the solver records on each
solve's root span (``phase3_cc_runs``, 0 or 1); a program without it
reads as nothing.  Layer: Phase 3.  Source: program counter."""
from benchmarks.chip.program_spans import mean, root_counters


def read(ctx):
    per_solve = root_counters(ctx, "phase3_cc_runs")
    return None if per_solve is None else mean(per_solve)
