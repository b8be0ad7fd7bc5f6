"""Wall-clock spans of the serving path, on the profiler's clock.

``span(name, **args)`` is ``jax.profiler.TraceAnnotation``: a host span
that a running ``jax.profiler`` trace records on its host plane, on the
same clock as the device's XLA ops, with ``args`` as the event's stats.
With no trace running a span costs about a microsecond, so the serving
path keeps them on always; there is no switch.  A value known only when
the work is done goes in through ``set_metadata`` on the entered span::

    with span("serve.batch", frames=len(chunk)) as sp:
        ...
        sp.set_metadata(dropped=n)

Args are plain ints already at hand (a length, an ``nbytes``).  Summed
over the spans of a window they are the counters: frames, padding,
drops and bytes moved.

These spans are the program's only wall-clock instrumentation.  The
``TraceRecorder`` log (``repro.obs.trace``) is the deterministic
virtual-time record and holds no wall times.  ``SPANS`` lists every
span name with what it covers and what its args mean; see
``docs/OBSERVABILITY.md``, "Wall-clock spans".
"""
from jax.profiler import TraceAnnotation as span

SPANS = {
    "serve.ingest": "ServingRuntime.ingest: frames into the queue",
    "serve.advance": "ServingRuntime.advance: every sealed micro-batch",
    "serve.boundary": "ServingRuntime.epoch_boundary: flush, tracker "
                      "and the window's report",
    "serve.batch": "one micro-batch of the detection core, drop "
                   "decisions to responses; frames: frames in the "
                   "batch, dropped: frames dropped from it",
    "serve.detect": "DetectionEngine._detect_batch, one detect call; "
                    "frames: real frames (crop tiles in the ROI pass), "
                    "padded: pad frames up to the bucket",
    "serve.detect.put": "host-to-device copy of the batch; h2d_bytes: "
                        "bytes copied",
    "serve.detect.run": "detect program dispatch through "
                        "block_until_ready",
    "serve.detect.pull": "device-to-host pull of the detections; "
                         "d2h_bytes: bytes pulled",
    "serve.flush": "advance(inf) at an emit boundary: the batches "
                   "still queued",
    "serve.track": "DetectionEngine._interpolate, the tracker over a "
                   "segment; ticks: lockstep ticks, streams: cameras",
    "serve.track.seed": "the segment's starting track table; "
                        "resident: 1 when it is the last segment's "
                        "device table, 0 when built from rows; "
                        "h2d_bytes: carried rows uploaded",
    "serve.track.tick": "one tracker tick or coast with its det_tid "
                        "pull; h2d_bytes: detection rows uploaded",
    "serve.track.export": "the final table handed on, and any later "
                          "read of its rows; d2h_bytes: bytes pulled, "
                          "0 while it stays on the device",
    "serve.report": "the segment's report after the tracker returns: "
                    "sort, per-stream order, stats, latency",
    "serve.report.latency": "detection_latency_keys: the latency "
                            "histograms of the report",
}

__all__ = ["span", "SPANS"]
