"""Wall-clock spans (``repro.obs.spans``): every span the source opens
is in the ``SPANS`` table, every table entry is documented beside the
metric it feeds, and the deterministic recorder holds no wall time."""
import re
from pathlib import Path

from repro.core import proxy_detect_fn_streams
from repro.obs import TraceRecorder
from repro.obs.spans import SPANS
from repro.serving import DetectionEngine, make_nvr_streams

REPO = Path(__file__).resolve().parents[1]


def _source_span_names():
    names = set()
    for path in (REPO / "src").rglob("*.py"):
        names |= set(re.findall(r'span\(\s*"([^"]+)"', path.read_text()))
    return names


def test_every_span_in_the_source_is_in_the_table():
    names = _source_span_names()
    assert names, "no span(...) call found under src/"
    assert names <= set(SPANS), sorted(names - set(SPANS))
    # and the table lists no span that nothing opens
    assert set(SPANS) <= names, sorted(set(SPANS) - names)
    assert all(n.startswith("serve.") for n in SPANS)


def test_every_span_is_documented():
    perf = (REPO / "PERF.md").read_text()
    ops = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    for name in SPANS:
        assert f"`{name}`" in perf, f"{name} missing from PERF.md"
        assert f"`{name}`" in ops, f"{name} missing from OBSERVABILITY.md"


def test_recorder_holds_no_wall_time():
    frames, frame_of, videos, dets = make_nvr_streams(3, 10, 4.0)
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    rec = TraceRecorder()
    DetectionEngine(detect_fn=oracle, n_replicas=2, service_time=0.3,
                    track_and_interpolate=True, recorder=rec).serve(frames)
    kinds = {e["kind"] for e in rec.events}
    assert "emit" in kinds and "stage" not in kinds
    assert not [k for k in rec.series if k.startswith("stage_ms")]
