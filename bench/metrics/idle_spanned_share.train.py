"""Share of the window's device-idle time whose gap midpoint lies inside
one of the program's six occl.* phase spans, in %."""
from bench.metrics._occl_spans import value


def read(ctx):
    return value(ctx, "idle_spanned_share")
