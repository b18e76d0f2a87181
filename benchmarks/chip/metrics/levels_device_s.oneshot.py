"""Device seconds per solve in the level scan: ops whose HLO metadata names
``core/phase1.py``, ``core/phase2.py`` or ``core/engine.py`` (Phase 1's
hook/jump and searchsorted, the merge levels, the scan and its routes),
averaged over the chips used."""

FILES = ("repro/core/phase1.py", "repro/core/phase2.py", "repro/core/engine.py")


def read(ctx):
    if ctx.trace is None or not ctx.records:
        return None
    return ctx.trace.file_seconds(FILES) / len(ctx.records)
