"""Host milliseconds per sum inside the span psum."""


def read(ctx):
    return 1e3 * ctx["spans"].total("psum", since=ctx["win"]["t0"]) / \
        ctx["win"]["units"]
