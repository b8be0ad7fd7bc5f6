"""Host control plane: wall seconds inside ``ingest`` + ``advance``
over the window, in ms per detected frame (host clock)."""


def read(ctx):
    if not ctx["detected"]:
        return None
    return ctx["ingest_advance_s"] * 1e3 / ctx["detected"]
