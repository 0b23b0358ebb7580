"""Staging layer's share of its HBM roofline, per step."""
from bench.metrics._lib import staging_roofline


def read(ctx):
    return staging_roofline(ctx)
