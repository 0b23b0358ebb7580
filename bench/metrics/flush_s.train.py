"""Host seconds per step in the span occl.flush (the launch prologue's
staging flush: concatenate, H2D, write plan)."""
from bench.metrics._occl_spans import per_step


def read(ctx):
    return per_step(ctx, "flush")
