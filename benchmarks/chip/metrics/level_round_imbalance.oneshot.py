"""How unevenly the partitions' Phase 1 loops run: for each window solve,
the sum over levels of the most hook + splice rounds any partition ran,
over the sum over levels of their mean over partitions; averaged over
the window's solves.  1.0 is balanced; above it, partitions wait at the
next exchange for the one that runs most.  Layer: collectives.
Source: program counter."""
from benchmarks.chip.program_spans import mean, root_counters


def _imbalance(hooks, splices):
    rounds = [[h + s for h, s in zip(hl, sl)]
              for hl, sl in zip(hooks, splices)]
    most = sum(max(level) for level in rounds)
    avg = sum(sum(level) / len(level) for level in rounds)
    return most / avg


def read(ctx):
    hooks = root_counters(ctx, "hook_rounds")
    splices = root_counters(ctx, "splice_rounds")
    if hooks is None or splices is None:
        return None
    return mean(_imbalance(h, s) for h, s in zip(hooks, splices))
