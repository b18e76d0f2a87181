"""Device seconds per solve in the bounded sorts and scans: ops whose HLO
metadata names ``core/bounded.py`` (bitonic networks, doubling scans),
averaged over the chips used."""

FILES = ("repro/core/bounded.py",)


def read(ctx):
    if ctx.trace is None or not ctx.records:
        return None
    return ctx.trace.file_seconds(FILES) / len(ctx.records)
