"""Phase 1 splice rounds per solve: for each window solve, the sum over
levels of the most splice rounds any partition ran at that level,
averaged over the window's solves.  A count the fused program returns
(``LevelStats.splice_rounds``), read off each solve's root span.
Layer: level scan.  Source: program counter."""
from benchmarks.chip.program_spans import mean, root_counters


def read(ctx):
    per_solve = root_counters(ctx, "splice_rounds")
    if per_solve is None:
        return None
    return mean(sum(max(level) for level in levels) for levels in per_solve)
