"""Host seconds per step inside OcclGradSync.all_reduce (the span sync)."""


def read(ctx):
    return ctx["spans"].total("sync", since=ctx["win"]["t0"]) / \
        ctx["win"]["units"]
