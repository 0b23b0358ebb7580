"""Set-up: process start to the window's start (build, warm-up, compile)."""


def read(ctx):
    return ctx["setup_s"]
