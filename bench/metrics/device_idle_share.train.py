"""1 - device busy (union of op intervals) over the traced window, in %."""
from bench.metrics._lib import idle_share


def read(ctx):
    return idle_share(ctx)
