"""Host dispatch per solve: the mean over the window's solves of the
``stage`` (input tables, upload, program lookup) and ``launch`` (the
program call) span durations of each solve's span tree, the host work
between host prep and the first device op.  Layer: host dispatch.
Source: program span."""
from benchmarks.chip.program_spans import children, mean


def read(ctx):
    kids = children(ctx, ("stage", "launch"))
    if kids is None:
        return None
    return mean(sum(s["dur_s"] for s in spans) for spans in kids)
