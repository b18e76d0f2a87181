"""Phase 1 splice spanning-forest rounds per solve: for each window
solve, the sum over levels of the most forest rounds any partition ran
at that level, averaged over the window's solves.  A count the fused
program returns (``LevelStats.forest_rounds``), read off each solve's
root span; a program without the forest has no such count and the
reader returns None.  Layer: level scan.  Source: program counter."""
from benchmarks.chip.program_spans import mean, root_counters


def read(ctx):
    per_solve = root_counters(ctx, "forest_rounds")
    if per_solve is None:
        return None
    return mean(sum(max(level) for level in levels) for levels in per_solve)
