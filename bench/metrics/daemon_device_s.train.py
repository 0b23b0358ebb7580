"""Device seconds per step in the daemon module."""
from bench.metrics._lib import daemon_s_per_unit


def read(ctx):
    return daemon_s_per_unit(ctx)
