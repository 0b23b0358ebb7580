"""Sums completed per second over the window's wall time."""


def read(ctx):
    win = ctx["win"]
    return win["units"] / win["window_s"]
