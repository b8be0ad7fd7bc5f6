"""Detector program, its ``nms`` scope: the least time the chip could
take to suppress the frames detected in the traced window -- their
``nms`` bytes (the family's ``bytes_by_scope``: one read of the score
plane and the boxes, at the window's mean frames per detect call) over
the HBM bandwidth (``bench/peaks.json``) -- over the device seconds of
the ops under the ``nms`` named scope (``Summary.scopes``), in %.
Suppression has no operation count worth a bound, and the bytes are the
same whatever implements it, so a change of kernel cannot move the
yardstick.  A family without an ``nms`` byte count reads nothing."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_detected"]:
        return None
    seconds = tr.scopes.get("nms")
    runs = tr.programs.get("jit_infer")
    if not seconds or runs is None:
        return None
    frames = ctx["trace_detected"]
    nbytes = ctx["family"].bytes_by_scope(ctx["config"],
                                          frames / runs.count).get("nms")
    if nbytes is None:
        return None
    peaks = ctx["peaks"]
    if peaks is None:
        raise KeyError(f"no peaks for {ctx['device_kind']!r} in "
                       "bench/peaks.json")
    t_bytes = (runs.count * nbytes
               / (ctx["chips"] * peaks["hbm_bytes_per_s"]))
    return 100.0 * t_bytes / seconds
