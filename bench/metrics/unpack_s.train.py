"""Host seconds per step in the span occl.unpack (OcclGradSync.all_reduce:
divide, reshape, upload every leaf)."""
from bench.metrics._occl_spans import per_step


def read(ctx):
    return per_step(ctx, "unpack")
