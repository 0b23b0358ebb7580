"""Staging plans built per step inside the window (occl.plan_build
events; each new plan compiles on its first call)."""
from bench.metrics._occl_spans import per_step


def read(ctx):
    return per_step(ctx, "plan_builds")
