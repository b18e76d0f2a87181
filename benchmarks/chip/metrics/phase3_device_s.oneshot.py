"""Device seconds per solve in Phase 3: ops whose HLO metadata names
``core/phase3.py`` or ``kernels/ref.py`` (splice, pointer-doubling
rounds, ranking, emission; replicated or sharded), averaged over the
chips used."""

FILES = ("repro/core/phase3.py", "repro/kernels/ref.py")


def read(ctx):
    if ctx.trace is None or not ctx.records:
        return None
    return ctx.trace.file_seconds(FILES) / len(ctx.records)
