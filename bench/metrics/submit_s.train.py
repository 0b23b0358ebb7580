"""Host seconds per step in the span occl.submit (OcclRuntime.submit:
validation, staging snapshot, SQE enqueue)."""
from bench.metrics._occl_spans import per_step


def read(ctx):
    return per_step(ctx, "submit")
