"""Seconds from the start of the benchmark process to the window's
opening: weights, frame pool, engine, compiles or cache reads, warm-up
(host clock)."""


def read(ctx):
    return ctx["setup_s"]
