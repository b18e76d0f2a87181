"""Phase 1 hook/jump rounds per solve: for each window solve, the sum
over levels of the most rounds any partition ran at that level (the
partitions meet at the next exchange, so the slowest sets the pace),
averaged over the window's solves.  A count the fused program returns
(``LevelStats.hook_rounds``), read off each solve's root span.
Layer: level scan.  Source: program counter."""
from benchmarks.chip.program_spans import mean, root_counters


def read(ctx):
    per_solve = root_counters(ctx, "hook_rounds")
    if per_solve is None:
        return None
    return mean(sum(max(level) for level in levels) for levels in per_solve)
