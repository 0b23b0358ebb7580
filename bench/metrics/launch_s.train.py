"""Host seconds per step in the span occl.launch (the rest of launch_once:
SQ pack, daemon, wait, reconcile)."""
from bench.metrics._occl_spans import per_step


def read(ctx):
    return per_step(ctx, "launch")
