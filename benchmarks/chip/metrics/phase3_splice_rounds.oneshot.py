"""Phase 3 pivot-splice rounds per solve (replicated or sharded Phase 3),
averaged over the window's solves.  A count the fused program returns
(``EulerResult.phase3_rounds``), read off each solve's root span.
Layer: Phase 3.  Source: program counter."""
from benchmarks.chip.program_spans import mean, root_counters


def read(ctx):
    per_solve = root_counters(ctx, "phase3_rounds")
    return None if per_solve is None else mean(per_solve)
