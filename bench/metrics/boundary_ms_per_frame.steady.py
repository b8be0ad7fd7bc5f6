"""Emit and tracker path: wall seconds inside ``epoch_boundary`` over
the window, in ms per emitted frame (host clock); cells below the knee."""


def read(ctx):
    if not ctx["emitted"]:
        return None
    return ctx["boundary_s"] * 1e3 / ctx["emitted"]
