"""Transprecise multi-model cascade serving: model catalogs, the
virtual-time ``ModelSelector`` state machine, the ROI crop/uncrop
kernel pair (three-tier: Pallas / XLA twin / numpy oracle), the
engine-level cascade + hierarchical second pass, and the bit-identity
bar — a single-entry catalog must leave every gated serving path
(detection, sharded static/rebalance, faults) byte-for-byte identical
to an engine built without one."""
import numpy as np
import pytest

from repro.core import evaluate_streams, proxy_detect_fn_streams
from repro.core.quality import evaluate_map_dets, track_quality
from repro.core.stream import SyntheticVideo, VideoSpec
from repro.kernels import ops
from repro.kernels.ref import crop_resize_ref, uncrop_boxes_ref
from repro.kernels.roi import (crop_resize_pallas, crop_resize_xla,
                               uncrop_boxes_pallas, uncrop_boxes_xla)
from repro.obs import TraceRecorder, audit_recorder
from repro.serving import (DetectionEngine, FaultSchedule, FrameRequest,
                           ModelCatalog, ModelProfile, ModelSelector,
                           ShardedDetectionEngine, Watchdog,
                           make_cascade_detect_fn, make_nvr_streams,
                           make_skewed_streams, paper_catalog)
from repro.serving.cascade import roi_pixels, rois_from_boxes
from repro.serving.models import as_catalog, cascade_report_keys
from test_sharded_serving import assert_reports_identical

SERVICE = 0.4          # the literal shared by both sides of identity

#: per-model bookkeeping keys — present on every report now, and the
#: ONLY keys allowed to differ between a plain engine and a
#: single-entry-catalog engine (the plain side reports them empty)
CASCADE_KEYS = set(cascade_report_keys(
    {}, {}, {}, 0, {"full": 0.0, "roi": 0.0, "passes": 0}, 0))


def assert_identical_modulo_cascade_keys(base, cas):
    assert_reports_identical(
        {k: v for k, v in base.items() if k not in CASCADE_KEYS}, cas)


def single_catalog(service_s=SERVICE):
    return ModelCatalog([ModelProfile("only", 0.8, band="yolov3",
                                      service_s=service_s)])


# ------------------------------------------------------------ catalog
def test_model_profile_derives_mu_and_validates():
    p = ModelProfile("m", 0.5, service_s=0.25)
    assert p.mu == pytest.approx(4.0)
    with pytest.raises(ValueError):
        ModelProfile("m", 0.5)                   # no rate at all
    with pytest.raises(ValueError):
        ModelProfile("m", 0.5, service_s=-1.0)


def test_catalog_ordering_lookup_and_uniqueness():
    cat = paper_catalog(0.4)
    assert [p.name for p in cat.by_quality()] == ["heavy", "medium",
                                                  "fast"]
    assert cat.heaviest.name == "heavy"
    assert cat.lightest.name == "fast"
    assert cat["fast"].mu == pytest.approx(10.0)   # 0.4 / 4
    assert "medium" in cat and "nope" not in cat
    assert set(cat.map_est_by_name()) == {"fast", "medium", "heavy"}
    with pytest.raises(ValueError):
        ModelCatalog([cat["fast"], cat["fast"]])   # duplicate name
    with pytest.raises(ValueError):
        ModelCatalog([])


def test_as_catalog_coercion():
    cat = single_catalog()
    assert as_catalog(None) is None
    assert as_catalog(cat) is cat
    assert as_catalog(list(cat)).names == cat.names


# ----------------------------------------------------- model selector
def caps_for(cat, n_replicas=1):
    return {p.name: n_replicas * p.mu for p in cat}


def test_selector_single_entry_never_switches():
    sel = ModelSelector(single_catalog())
    caps = caps_for(single_catalog())
    for k in range(20):
        name, switched = sel.decide(float(k), 5, 10.0, caps)
        assert name == "only" and not switched
    assert sel.switches == 0


def test_selector_degrades_immediately_under_pressure():
    cat = paper_catalog(0.5)            # caps: heavy 2, medium 4, fast 8
    sel = ModelSelector(cat)
    caps = caps_for(cat)
    sel.decide(0.0, 1, 0.0, caps)       # prime the rate estimator
    # 12 fps instantaneous: even fast (8) is infeasible -> stays lightest
    name, _ = sel.decide(1.0, 12, 0.0, caps)
    assert name == "fast"
    # deep backlog forces the extra degrade step even when feasible
    sel2 = ModelSelector(cat)
    sel2._cur = 0                       # pin at heavy
    name, switched = sel2.decide(0.0, 0, 10.0, caps)   # 20 frames of lag
    assert switched and name == "medium"


def test_selector_upgrade_needs_hold_and_headroom():
    cat = paper_catalog(0.5)
    sel = ModelSelector(cat, hold=3)
    caps = caps_for(cat)                # heavy 2, medium 4, fast 8
    sel.decide(0.0, 1, 0.0, caps)       # prime (counts one slack tick)
    # 1 fps << heavy cap * headroom (1.4): slack, but only after `hold`
    # consecutive slack decisions does the selector step up one tier
    seen = [sel.decide(1.0 + k, 1, 0.0, caps)[0] for k in range(8)]
    assert seen[0] == "fast"            # still holding
    assert "medium" in seen and seen[-1] == "heavy"
    i_med, i_heavy = seen.index("medium"), seen.index("heavy")
    assert i_heavy - i_med >= 3         # one tier per hold, no jumps


def test_selector_hysteresis_band_blocks_upgrade():
    cat = paper_catalog(0.5)
    sel = ModelSelector(cat, hold=2)
    caps = caps_for(cat)
    sel.decide(0.0, 1, 0.0, caps)
    # 3.5 fps: feasible for medium (cap 4) but NOT with 0.7 headroom
    # (2.8), so the selector must sit at fast forever — no flapping
    for k in range(6):
        sel.decide(2.0 * (k + 1), 7, 0.0, caps)   # 7 arrivals / 2 s
    assert sel.current == "fast"
    assert sel.switches == 0


def test_selector_zero_capacity_stays_lightest():
    cat = paper_catalog(0.5)
    sel = ModelSelector(cat)
    dead = {p.name: 0.0 for p in cat}
    sel.decide(0.0, 1, 0.0, dead)
    name, _ = sel.decide(1.0, 4, 0.0, dead)
    assert name == "fast"


# ----------------------------------------------- ROI window selection
def test_rois_from_boxes_topk_pad_clamp():
    boxes = np.array([[10, 10, 30, 30], [100, 100, 200, 200],
                      [0, 0, 5, 5], [600, 440, 700, 520]], np.float32)
    scores = np.array([0.9, 0.5, 0.99, 0.7], np.float32)
    valid = np.array([True, True, False, True])
    rois, n = rois_from_boxes(boxes, scores, valid, bounds=(640, 480),
                              roi_max=2, pad=0.1)
    assert rois.shape == (2, 4) and n == 2
    # top-2 valid by score: box 0 (0.9) then box 3 (0.7); box 2 invalid
    assert rois[0] == pytest.approx([8, 8, 32, 32])    # 10% pad
    assert rois[1][2] == 640.0 and rois[1][3] == 480.0  # clamped
    # degenerate inputs
    r0, n0 = rois_from_boxes(boxes, scores, np.zeros(4, bool),
                             bounds=(640, 480), roi_max=2)
    assert n0 == 0 and r0.shape == (2, 4)


def test_roi_pixels_clamped_to_full_frame():
    rois = np.array([[0, 0, 640, 480], [0, 0, 640, 480]], np.float32)
    assert roi_pixels(rois, 2, (640, 480)) == 640 * 480   # never exceeds
    assert roi_pixels(rois, 0, (640, 480)) == 0.0


# ------------------------------------------- crop/uncrop kernel tiers
def _roi_fixture(b=3, h=24, w=32, r=2, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.random((b, h, w, 3)).astype(np.float32)
    # normalized [x0, y0, x1, y1] windows, well-formed
    lo = rng.uniform(0.0, 0.5, (b, r, 2)).astype(np.float32)
    hi = lo + rng.uniform(0.2, 0.5, (b, r, 2)).astype(np.float32)
    rois = np.concatenate([lo, np.minimum(hi, 1.0)], -1)
    return images, rois


def test_crop_resize_three_tiers_bit_compatible():
    images, rois = _roi_fixture()
    ref = np.asarray(crop_resize_ref(images, rois, out_size=8))
    xla = np.asarray(crop_resize_xla(images, rois, out_size=8))
    pal = np.asarray(crop_resize_pallas(images, rois, out_size=8,
                                        interpret=True))
    # index quantization (floor/clip) absorbs the FMA contraction:
    # all three tiers agree exactly
    assert np.array_equal(ref, xla)
    assert np.array_equal(xla, pal)
    assert pal.shape == (3, 2, 8, 8, 3)


def test_uncrop_boxes_pallas_matches_xla_exactly():
    rng = np.random.default_rng(1)
    boxes = rng.uniform(0, 16, (3, 2, 5, 4)).astype(np.float32)
    _, rois = _roi_fixture()
    kw = dict(bounds=(640, 480), crop_size=16)
    xla = np.asarray(uncrop_boxes_xla(boxes, rois[:, :, None, :], **kw))
    pal = np.asarray(uncrop_boxes_pallas(boxes, rois[:, :, None, :],
                                         interpret=True, **kw))
    ref = uncrop_boxes_ref(boxes, rois[:, :, None, :], **kw)
    # both jitted tiers see the same FMA contraction: exact match;
    # the numpy oracle differs by at most ~1 ULP of the frame scale
    assert np.array_equal(xla, pal)
    np.testing.assert_allclose(pal, ref, atol=1e-3)
    assert pal.shape == boxes.shape


def test_ops_dispatchers_follow_nms_convention():
    images, rois = _roi_fixture(seed=2)
    a = np.asarray(ops.crop_resize(images, rois, out_size=8,
                                   use_pallas=True))
    b = np.asarray(ops.crop_resize(images, rois, out_size=8,
                                   use_pallas=False))
    assert np.array_equal(a, b)
    boxes = np.random.default_rng(3).uniform(
        0, 8, (3, 2, 4, 4)).astype(np.float32)
    ua = np.asarray(ops.uncrop_boxes(boxes, rois[:, :, None, :],
                                     bounds=(64, 48), crop_size=8,
                                     use_pallas=True))
    ub = np.asarray(ops.uncrop_boxes(boxes, rois[:, :, None, :],
                                     bounds=(64, 48), crop_size=8,
                                     use_pallas=False))
    assert np.array_equal(ua, ub)


def test_uncrop_inverts_crop_window_corners():
    # a box spanning the whole crop must map back to the ROI window
    rois = np.array([[[0.25, 0.25, 0.75, 0.75]]], np.float32)
    boxes = np.array([[[[0.0, 0.0, 16.0, 16.0]]]], np.float32)
    out = np.asarray(uncrop_boxes_xla(boxes, rois[:, :, None, :],
                                      bounds=(640, 480), crop_size=16))
    np.testing.assert_allclose(out[0, 0, 0],
                               [160.0, 120.0, 480.0, 360.0], atol=1e-3)


# ----------------------------------------- engine-level cascade + ROI
def fast_videos(n_streams=2, n_frames=64, obj_speed=0.02,
                cam_speed=0.004):
    return {s: SyntheticVideo(VideoSpec("NVR-cascade", 14.0, n_frames,
                                        640, 480, moving_camera=True,
                                        n_objects=8, seed=3 + s,
                                        obj_speed=obj_speed,
                                        cam_speed=cam_speed))
            for s in range(n_streams)}


def trace_for(n, n_streams=2, rate=6.0):
    img = np.zeros((4, 4, 3), np.float32)
    frames, frame_of, seqs = [], {}, [0] * n_streams
    for k in range(n):
        s = k % n_streams
        frames.append(FrameRequest(k, img, k / rate, stream_id=s))
        frame_of[k] = (s, seqs[s])
        seqs[s] += 1
    return frames, frame_of


def test_cascade_report_keys_and_audit_clean():
    videos = fast_videos()
    frames, frame_of = trace_for(48, rate=10.0)
    cat = paper_catalog(0.5)
    rec = TraceRecorder()
    eng = DetectionEngine(detect_fn=make_cascade_detect_fn(
                              videos, frame_of, cat),
                          catalog=cat, n_replicas=2, drop_when_busy=True,
                          track_and_interpolate=True, roi=True,
                          roi_bounds=(640, 480), recorder=rec)
    out = eng.serve(frames)
    for k in ("models", "model_of_frame", "model_map_est",
              "model_switches", "map_estimate", "roi_pixels",
              "roi_pixel_reduction"):
        assert k in out, k
    assert sum(out["models"].values()) == len(out["model_of_frame"])
    assert 0.0 <= out["map_estimate"] <= 1.0
    # overloaded (10 fps vs heavy cap 4): the selector must sit below
    # the heaviest model, so the ROI second pass fires
    assert out["roi_pixels"]["passes"] > 0
    assert 0.0 < out["roi_pixel_reduction"] <= 1.0
    res = audit_recorder(rec)
    assert res.ok, res.violations[:3]
    assert res.stats["roi_pass"] == out["roi_pixels"]["passes"]
    # every served frame is attributed to exactly one model
    for rid, m in out["model_of_frame"].items():
        assert m in cat


def test_model_switch_only_at_batch_boundaries():
    videos = fast_videos()
    # lull -> burst -> lull so the selector actually moves
    img = np.zeros((4, 4, 3), np.float32)
    frames, frame_of, t = [], {}, 0.0
    seqs = [0, 0]
    for k in range(60):
        rate = 12.0 if 20 <= k < 40 else 2.0
        s = k % 2
        frames.append(FrameRequest(k, img, t, stream_id=s))
        frame_of[k] = (s, seqs[s])
        seqs[s] += 1
        t += 1.0 / rate
    cat = paper_catalog(0.5)
    rec = TraceRecorder()
    eng = DetectionEngine(detect_fn=make_cascade_detect_fn(
                              videos, frame_of, cat),
                          catalog=cat, n_replicas=2, drop_when_busy=True,
                          recorder=rec)
    out = eng.serve(frames)
    assert out["model_switches"] > 0
    switches = [e for e in rec.events if e["kind"] == "model_switch"]
    assert len(switches) == out["model_switches"]
    res = audit_recorder(rec)
    assert res.ok, res.violations[:3]
    # corrupting a switch to name an already-started batch must trip
    # the boundary rule
    enq = next(e for e in rec.events if e["kind"] == "enqueue")
    bad = dict(switches[0], batch=enq["batch"])
    bad["i"] = rec.events[-1]["i"] + 1
    res2 = audit_recorder(rec)
    assert res2.ok
    broken = audit_recorder(type("R", (), {"events":
                                           rec.events + [bad]})())
    assert not broken.ok
    assert any(v["rule"] == "model_switch_boundary"
               for v in broken.violations)


def test_roi_detections_contained_and_reduction_counted():
    videos = fast_videos()
    frames, frame_of = trace_for(32, rate=12.0)
    cat = ModelCatalog([paper_catalog(0.5)["fast"],
                        paper_catalog(0.5)["heavy"]])
    rec = TraceRecorder()
    eng = DetectionEngine(detect_fn=make_cascade_detect_fn(
                              videos, frame_of, cat),
                          catalog=cat, n_replicas=2, drop_when_busy=True,
                          roi=True, roi_bounds=(640, 480), recorder=rec)
    out = eng.serve(frames)
    passes = [e for e in rec.events if e["kind"] == "roi_pass"]
    assert passes and out["roi_pixels"]["passes"] == len(passes)
    for e in passes:
        W, H = e["bounds"]
        assert e["px_roi"] <= e["px_full"]
        for r in e["rois"]:
            assert -1e-3 <= r[0] <= r[2] <= W + 1e-3
            assert -1e-3 <= r[1] <= r[3] <= H + 1e-3
    assert audit_recorder(rec).ok
    # pinned at the cheap tier, the heavy second pass reads under half
    # of the full-frame pixels
    assert out["roi_pixel_reduction"] > 0.5
    # second-pass boxes in the report stay inside the frame too
    for r in out["responses"]:
        v = np.asarray(r.valid, bool)
        if v.any():
            bx = np.asarray(r.boxes)[v]
            assert bx[:, [0, 2]].max() <= 640 + 1e-3
            assert bx[:, [1, 3]].max() <= 480 + 1e-3


# --------------------------------------------- single-entry identity
def identity_pair(mode_kw, sharded=False, **extra):
    """(plain, single-entry-catalog) reports over the same trace; both
    sides use the SAME oracle so any divergence is the cascade's."""
    frames, frame_of, videos, dets = make_nvr_streams(3, 16, rate=2.0)
    cat = single_catalog()
    fn = make_cascade_detect_fn(videos, frame_of, cat)
    cls = ShardedDetectionEngine if sharded else DetectionEngine
    base = cls(detect_fn=fn, n_replicas=2, service_time=SERVICE,
               **mode_kw, **extra).serve(frames)
    frames2, _, _, _ = make_nvr_streams(3, 16, rate=2.0)
    cas = cls(detect_fn=fn, n_replicas=2, catalog=cat, roi=True,
              roi_bounds=(videos[0].spec.width, videos[0].spec.height),
              **mode_kw, **extra).serve(frames2)
    return base, cas


@pytest.mark.parametrize("mode_kw", [{"drop_when_busy": True},
                                     {"track_and_interpolate": True}])
def test_single_entry_catalog_bit_identical_detection(mode_kw):
    base, cas = identity_pair(mode_kw)
    assert_identical_modulo_cascade_keys(base, cas)
    assert cas["model_switches"] == 0
    assert cas["roi_pixels"]["passes"] == 0     # heaviest == only model


def test_single_entry_catalog_bit_identical_sharded_static():
    base, cas = identity_pair({"track_and_interpolate": True},
                              sharded=True, n_shards=2)
    assert_identical_modulo_cascade_keys(base, cas)
    assert cas["model_switches"] == 0


def test_single_entry_catalog_bit_identical_rebalance():
    frames, frame_of, videos, dets = make_skewed_streams(4, 12, 3.0,
                                                         n_shards=2)
    cat = single_catalog()
    fn = make_cascade_detect_fn(videos, frame_of, cat)
    kw = dict(n_shards=2, n_replicas=2, track_and_interpolate=True,
              epoch_s=2.0, rebalance=True)
    base = ShardedDetectionEngine(detect_fn=fn, service_time=SERVICE,
                                  **kw).serve(frames)
    cas = ShardedDetectionEngine(detect_fn=fn, catalog=cat,
                                 **kw).serve(frames)
    assert_identical_modulo_cascade_keys(base, cas)


@pytest.mark.chaos
def test_single_entry_catalog_bit_identical_under_faults():
    sched = FaultSchedule.replica_kill(1.0, replica=0, revive_t=3.0)
    base, cas = identity_pair({"track_and_interpolate": True},
                              faults=sched)
    assert_identical_modulo_cascade_keys(base, cas)


# --------------------------------------- empty inputs / empty traces
def test_evaluate_map_dets_empty_inputs():
    video = SyntheticVideo(VideoSpec("t", 10.0, 8, 64, 48, False,
                                     n_objects=2))
    assert evaluate_map_dets(video, []) == 0.0
    assert evaluate_map_dets(video, [None, None]) == 0.0


def test_track_quality_empty_input_schema():
    video = SyntheticVideo(VideoSpec("t", 10.0, 8, 64, 48, False,
                                     n_objects=2))
    tq = track_quality(video, [])
    assert tq == {"id_switches": 0.0, "coverage": 0.0, "fragments": 0.0}


def test_cascade_report_keys_zero_frames_schema():
    empty = cascade_report_keys({}, {}, {}, 0,
                                {"full": 0.0, "roi": 0.0, "passes": 0}, 0)
    populated = cascade_report_keys({"m": 2}, {0: "m", 1: "m"},
                                    {"m": 0.5}, 1,
                                    {"full": 10.0, "roi": 5.0,
                                     "passes": 2}, 2)
    assert set(empty) == set(populated)
    assert empty["map_estimate"] == 0.0
    assert populated["map_estimate"] == pytest.approx(0.5)
    assert populated["roi_pixel_reduction"] == pytest.approx(0.5)


@pytest.mark.parametrize("sharded", [False, True])
def test_empty_trace_report_schema_matches_populated(sharded):
    videos = fast_videos()
    frames, frame_of = trace_for(8)
    cat = paper_catalog(0.5)
    fn = make_cascade_detect_fn(videos, frame_of, cat)
    kw = dict(detect_fn=fn, catalog=cat, n_replicas=2,
              track_and_interpolate=True)
    cls = ShardedDetectionEngine if sharded else DetectionEngine
    if sharded:
        kw["n_shards"] = 2
    populated = cls(**kw).serve(frames)
    empty = cls(**kw).serve([])
    missing = set(populated) - set(empty)
    assert not missing, missing
    assert empty["models"] == {}
    assert empty["map_estimate"] == 0.0
    assert empty["roi_pixel_reduction"] == 0.0


# ----------------------------------------------- faults x catalog
def test_lent_guest_replica_carries_its_catalog():
    """Replica lending moves the executor OBJECT between shard pools:
    its loaded-model catalog must travel with it and come home intact."""
    cat_a = single_catalog(0.3)
    cat_b = paper_catalog(0.5)
    frames, frame_of, videos, dets = make_nvr_streams(2, 4, 4.0)
    fn = proxy_detect_fn_streams(videos, dets, frame_of)
    lender = DetectionEngine(detect_fn=fn, n_replicas=2,
                             service_time=0.3, catalog=cat_a)
    borrower = DetectionEngine(detect_fn=fn, n_replicas=2,
                               service_time=0.3, catalog=cat_b)
    assert all(r.catalog is cat_a for r in lender.replicas)
    wd = Watchdog()
    wd.begin([lender, borrower])
    wd._lend([lender, borrower], 0, 1, epoch=0)
    guest = borrower.replicas[-1]
    assert guest.catalog is cat_a          # home catalog travels along
    assert all(r.catalog is cat_b for r in borrower.replicas[:-1])
    wd._return([lender, borrower], wd._loans[-1], epoch=1)
    assert lender.replicas[-1].catalog is cat_a


@pytest.mark.chaos
def test_probe_health_restore_keeps_selector_hysteresis():
    """A replica revival (``probe_health`` restore) is a scheduler
    event — it must not reset the engine-owned selector's hysteresis
    state (streak, current tier, switch count)."""
    cat = paper_catalog(0.5)
    videos = fast_videos()
    frames, frame_of = trace_for(40, rate=6.0)
    sched = FaultSchedule.replica_kill(1.0, replica=0, revive_t=2.5)
    eng = DetectionEngine(detect_fn=make_cascade_detect_fn(
                              videos, frame_of, cat),
                          catalog=cat, n_replicas=2, drop_when_busy=True,
                          faults=sched)
    sel = eng.cascade
    assert sel is not None
    out = eng.serve(frames)
    assert eng.cascade is sel              # never rebuilt mid-run
    assert sel.switches == out["model_switches"]
    # direct restore probe: selector state is untouched by the scheduler
    sel._streak, sel._cur = 1, 0
    before = (sel._streak, sel._cur, sel.switches)
    eng.scheduler.probe_health(99.0)
    assert (sel._streak, sel._cur, sel.switches) == before


@pytest.mark.chaos
def test_dead_replica_capacity_leaves_cascade_feasibility():
    """A killed replica's catalog capacity drops out of the selector's
    feasible-rate budget: under the same load the degraded pool must
    select a model no heavier than the healthy pool's."""
    cat = paper_catalog(0.5)
    videos = fast_videos()
    frames, frame_of = trace_for(40, rate=7.0)
    fn = make_cascade_detect_fn(videos, frame_of, cat)
    order = [p.name for p in cat.by_quality()]

    def heaviness(report):
        return min(order.index(m) for m in report["models"])

    healthy = DetectionEngine(detect_fn=fn, catalog=cat, n_replicas=2,
                              drop_when_busy=True).serve(frames)
    frames2, _ = trace_for(40, rate=7.0)
    degraded = DetectionEngine(detect_fn=fn, catalog=cat, n_replicas=2,
                               drop_when_busy=True,
                               faults=FaultSchedule.replica_kill(
                                   0.0, replica=0)).serve(frames2)
    assert heaviness(degraded) >= heaviness(healthy)


# ------------------------------------------------- overload behavior
def test_cascade_beats_fixed_models_at_overload():
    """The cascade's quality claim: under a lull/overload cycle its
    tracked mAP beats every fixed-model baseline, and it drops no more
    frames than the fastest fixed model."""
    import math
    # fast motion: coasted (interpolated) boxes decay across bounces,
    # so a baseline that survives overload by dropping + coasting pays
    videos = fast_videos(n_frames=200, obj_speed=0.035, cam_speed=0.006)
    cat = paper_catalog(0.5)
    img = np.zeros((4, 4, 3), np.float32)

    def sinus_trace(n=320, lo=2.0, hi=20.0, period=96):
        frames, frame_of, t = [], {}, 0.0
        seqs = [0, 0]
        for k in range(n):
            rate = lo + (hi - lo) * 0.5 * (
                1 - math.cos(2 * math.pi * k / period))
            s = k % 2
            frames.append(FrameRequest(k, img, t, stream_id=s))
            frame_of[k] = (s, seqs[s])
            seqs[s] += 1
            t += 1.0 / rate
        return frames, frame_of, seqs[0]

    def run(c):
        frames, frame_of, per_stream = sinus_trace()
        eng = DetectionEngine(detect_fn=make_cascade_detect_fn(
                                  videos, frame_of, c),
                              catalog=c, n_replicas=2,
                              drop_when_busy=True,
                              track_and_interpolate=True)
        out = eng.serve(frames)
        q = evaluate_streams(videos, out["streams"], per_stream)
        return out, q["map_mean"]

    out, cas_map = run(cat)
    assert out["model_switches"] > 0
    assert len(out["models"]) >= 2          # actually transprecise
    for name in cat.names:
        fixed, fixed_map = run(ModelCatalog([cat[name]]))
        assert cas_map > fixed_map, (name, cas_map, fixed_map)
        if name == "fast":
            assert len(out["dropped"]) <= len(fixed["dropped"])
