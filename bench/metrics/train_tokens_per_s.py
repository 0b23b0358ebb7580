"""Tokens trained per second: every rank's tokens of the window's whole
steps over the window's wall time."""


def read(ctx):
    win = ctx["win"]
    return win["tokens"] / win["window_s"] if "tokens" in win else None
