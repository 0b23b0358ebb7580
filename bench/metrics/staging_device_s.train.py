"""Device seconds per step in the staging engine's jitted plans."""
from bench.metrics._lib import staging_s_per_unit


def read(ctx):
    return staging_s_per_unit(ctx)
