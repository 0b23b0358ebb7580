"""Host seconds per step in the span occl.pack (OcclGradSync._pack: device
leaves of a bucket to one host buffer)."""
from bench.metrics._occl_spans import per_step


def read(ctx):
    return per_step(ctx, "pack")
