"""Host prep per solve: the mean of the solver's own ``prepare_s`` timing
(partition, pad into the bucket, merge-tree plan, cap sizing) over the
window's completed solves.  Layer: host prep.  Source: program span."""


def read(ctx):
    spans = [r.prepare_s for r in ctx.records
             if r.error is None and r.prepare_s is not None]
    return sum(spans) / len(spans) if spans else None
