"""Device: share of the traced window in which no operation ran,
averaged over the chips (device trace), in %."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.busy_s is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
