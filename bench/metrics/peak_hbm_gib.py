"""peak_bytes_in_use after the window, on the fullest device."""


def read(ctx):
    return ctx["peak_bytes"] / 2**30
