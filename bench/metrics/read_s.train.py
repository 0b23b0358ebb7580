"""Host seconds per step in the span occl.read
(OcclRuntime.read_outputs_bulk: read plan, D2H, un-pad copies)."""
from bench.metrics._occl_spans import per_step


def read(ctx):
    return per_step(ctx, "read")
