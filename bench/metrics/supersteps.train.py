"""Daemon supersteps per step (runtime counter)."""


def read(ctx):
    return ctx["counters"]["supersteps_per_unit"]
