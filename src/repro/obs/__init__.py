"""Deterministic observability for the serving stack: frame-lifecycle
tracing (``trace``), streaming latency histograms (``metrics``),
Perfetto/Chrome timeline export (``export``), and trace-replay
invariant auditing (``audit``).  Wall-clock spans on the profiler's
clock are in ``repro.obs.spans`` (not imported here: it imports JAX).
See ``docs/OBSERVABILITY.md``."""
from repro.obs.audit import AuditResult, audit_events, audit_recorder
from repro.obs.export import (events_from_chrome, to_chrome_trace,
                              write_chrome_trace)
from repro.obs.metrics import (LatencyHistogram, detection_latency_keys,
                               merge_hist_dicts, quantile_of_dict)
from repro.obs.trace import NULL_RECORDER, NullRecorder, TraceRecorder

__all__ = [
    "TraceRecorder", "NullRecorder", "NULL_RECORDER",
    "LatencyHistogram", "detection_latency_keys", "merge_hist_dicts",
    "quantile_of_dict",
    "to_chrome_trace", "events_from_chrome", "write_chrome_trace",
    "AuditResult", "audit_events", "audit_recorder",
]
