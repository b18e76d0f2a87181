"""Device seconds per solve in collectives between chips (all-to-all,
collective-permute, all-reduce, all-gather ops), averaged over the chips
used.  Nothing to read where no such op ran."""


def read(ctx):
    if ctx.trace is None or not ctx.records or not ctx.trace.collective_ops:
        return None
    return ctx.trace.collective_s / len(ctx.records)
