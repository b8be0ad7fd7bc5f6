"""Frames that went through the detector (not interpolated), of those
due in the window, per second from the window's opening to the last
emit of its frames, or the window's length where that is longer (host
clock)."""


def read(ctx):
    return ctx["detected_fps"]
