"""Property-based test layer for the serving stack.

Every invariant here is phrased over RANDOMIZED traces — stream
counts, arrival rates, chunk boundaries, fault schedules — rather than
the fixed fixtures the unit tests use:

* frame conservation: every arrival reaches exactly one terminal state
  (the ``obs.audit`` rule), for any trace shape and drop mode;
* per-stream emit monotonicity: sequence numbers strictly increase and
  emit times never decrease, per camera;
* chunked ``ingest``/``advance`` drains byte-for-byte equal to the
  one-shot batch ``serve``, for ANY chunking;
* histogram merge never averages: the merged latency quantile is
  recomputed from summed buckets and must equal the quantile of the
  pooled samples' histogram exactly;
* the fused one-jit tick program produces reports byte-identical to
  the staged tracker chain, for any trace shape;
* track identities survive shard migration: a track id born before a
  ``rebalance_streams`` move re-appears on the destination shard, the
  ``track_import`` reproduces the source's ``track_export``, and the
  audit's continuity rule passes — while ``carry_tracks=False``
  (the old re-seed behaviour) makes the same rule fail;
* randomized (seeded) fault schedules keep all of the above.

``hypothesis`` is an optional dev dependency: the ``@given`` variants
skip without it, and deterministic seed-parametrized fallbacks keep
every property covered either way.
"""
import math

import numpy as np
import pytest

from repro.core import proxy_detect_fn_streams
from repro.obs import TraceRecorder, audit_recorder
from repro.obs.metrics import (LatencyHistogram, merge_hist_dicts,
                               quantile_of_dict)
from repro.serving import (DetectionEngine, FaultSchedule, FrameRequest,
                           ServingRuntime, ShardedDetectionEngine,
                           make_nvr_streams, make_skewed_streams)
from test_sharded_serving import assert_reports_identical

try:
    from hypothesis import example, given, settings, strategies as st
except ModuleNotFoundError:      # optional dep — see requirements-dev.txt
    given = None

SEEDS = list(range(6))           # deterministic fallback space


def random_trace(seed: int):
    """A randomized NVR trace: random camera count, length, pacing and
    per-frame jitter (always sorted by arrival; rids globally unique)."""
    rng = np.random.default_rng(seed)
    n_streams = int(rng.integers(1, 5))
    n_frames = int(rng.integers(2, 12))
    rate = float(rng.uniform(1.0, 8.0))
    frames, frame_of, videos, dets = make_nvr_streams(
        n_streams, n_frames, rate)
    # jitter arrivals so micro-batch composition varies with the seed
    for f in frames:
        f.t_arrival = max(0.0, f.t_arrival +
                          float(rng.uniform(-0.05, 0.05)))
    frames.sort(key=lambda f: (f.t_arrival, f.rid))
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    return frames, oracle, n_streams, n_frames


def engine_for(oracle, seed: int, recorder=None, faults=None):
    rng = np.random.default_rng(1000 + seed)
    mode = ({"drop_when_busy": True} if rng.integers(2)
            else {"track_and_interpolate": True})
    return DetectionEngine(detect_fn=oracle,
                           n_replicas=int(rng.integers(1, 4)),
                           service_time=float(rng.uniform(0.1, 0.6)),
                           recorder=recorder, faults=faults, **mode)


def check_conservation_and_monotonicity(seed: int, faults=None):
    frames, oracle, n_streams, _ = random_trace(seed)
    rec = TraceRecorder()
    out = engine_for(oracle, seed, recorder=rec, faults=faults) \
        .serve(frames)
    res = audit_recorder(rec)
    assert res.ok, res.violations[:3]
    assert res.stats["arrive"] == len(frames)
    if faults is None:
        # terminal accounting closes exactly: emitted + finally-dropped
        # (under faults the audit's conservation rule — part of
        # ``res.ok`` above — is the authority; lost frames included)
        assert (res.stats["emitted"]
                + res.stats["dropped_final"]) == len(frames)
    # direct monotonicity re-check from the report (not just the audit)
    for sid, resp in out["streams"].items():
        seqs = [r.seq for r in resp]
        assert seqs == sorted(set(seqs)), sid


# ---------------------------------------------- frame conservation
@pytest.mark.parametrize("seed", SEEDS)
def test_frame_conservation_randomized_traces(seed):
    check_conservation_and_monotonicity(seed)


if given is not None:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_frame_conservation_property(seed):
        check_conservation_and_monotonicity(seed)


# ------------------------------------------------ chunked == one-shot
def check_chunked_equals_one_shot(seed: int, cuts):
    frames, oracle, _, _ = random_trace(seed)
    base = engine_for(oracle, seed).serve(frames)
    rt = ServingRuntime(engine_for(oracle, seed))
    bounds = sorted({min(c, len(frames)) for c in cuts} | {len(frames)})
    prev = 0
    for b in bounds:
        rt.ingest(frames[prev:b])
        rt.advance()
        prev = b
    out = rt.drain()
    assert_reports_identical(base, out)


@pytest.mark.parametrize("seed,cuts", [
    (0, (1,)), (1, (2, 5)), (2, (3, 4, 9)), (3, (1, 2, 3, 4, 5)),
    (4, (7,)), (5, (2, 2, 6)),
])
def test_chunked_ingest_matches_one_shot_randomized(seed, cuts):
    check_chunked_equals_one_shot(seed, cuts)


if given is not None:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10_000),
           cuts=st.lists(st.integers(1, 40), max_size=6))
    def test_chunked_ingest_matches_one_shot_property(seed, cuts):
        check_chunked_equals_one_shot(seed, cuts)


# ----------------------------------------- merge never averages
def check_merge_never_average(latencies, n_shards: int):
    pooled = LatencyHistogram()
    shards = [LatencyHistogram() for _ in range(n_shards)]
    for i, x in enumerate(latencies):
        pooled.add(x)
        shards[i % n_shards].add(x)
    merged = merge_hist_dicts([h.to_dict() for h in shards])
    for q in (0.5, 0.9, 0.95, 0.99):
        # bucket-sum + recompute == pooled quantile, exactly
        assert quantile_of_dict(merged, q) == pooled.quantile(q), q
        # and the recomputed quantile is NOT the per-shard average
        # (a strictly weaker statement, but the one that catches the
        # classic mean-of-p99s bug on skewed shards).  A rank quantile
        # of pooled parts lies between the parts' rank quantiles, so
        # it sits in a bucket between theirs: at or above the lowest
        # capped per-shard answer, and at or below the highest bucket
        # edge.  Not below the highest *capped* answer: each shard caps
        # at its own max, which may lie below the pooled answer
        # (lat=[3, 2, 1] on 2 shards: the shard holding only 2 answers
        # p50 = 2, while the pooled p50 is the upper edge of 2's
        # bucket, 2.097152, capped only at 3).  With no max to cap at,
        # a shard's quantile is its bucket's upper edge.
        live = [h.to_dict() for h in shards if h.n]
        if live:
            lo = min(quantile_of_dict(d, q) for d in live)
            hi = max(quantile_of_dict({**d, "max": math.inf}, q)
                     for d in live)
            assert lo <= quantile_of_dict(merged, q) <= hi, q


@pytest.mark.parametrize("seed", SEEDS)
def test_latency_merge_never_averages_randomized(seed):
    rng = np.random.default_rng(seed)
    lat = rng.lognormal(-2.0, 1.0, size=int(rng.integers(1, 200)))
    check_merge_never_average([float(x) for x in lat],
                              n_shards=int(rng.integers(1, 5)))


if given is not None:
    @settings(max_examples=30, deadline=None)
    @given(lat=st.lists(st.floats(1e-4, 10.0), min_size=1, max_size=80),
           n_shards=st.integers(1, 5))
    @example(lat=[3.0, 2.0, 1.0], n_shards=2)
    def test_latency_merge_never_averages_property(lat, n_shards):
        check_merge_never_average(lat, n_shards)


# -------------------------------------------- fused tick == staged
def check_fused_matches_staged(seed: int):
    """The one-jit donated-buffer tick program must be report-identical
    to the staged ``trk.step``/``trk.coast`` chain on any trace."""
    frames, oracle, _, _ = random_trace(seed)
    rng = np.random.default_rng(2000 + seed)
    kw = dict(n_replicas=int(rng.integers(1, 4)),
              service_time=float(rng.uniform(0.1, 0.6)),
              track_and_interpolate=True,
              drop_when_busy=bool(rng.integers(2)))
    staged = DetectionEngine(detect_fn=oracle, **kw).serve(frames)
    frames2, oracle2, _, _ = random_trace(seed)
    fused = DetectionEngine(detect_fn=oracle2, fused_tick=True,
                            **kw).serve(frames2)
    assert_reports_identical(staged, fused)


@pytest.mark.parametrize("seed", SEEDS)
def test_fused_tick_matches_staged_randomized(seed):
    check_fused_matches_staged(seed)


if given is not None:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_fused_tick_matches_staged_property(seed):
        check_fused_matches_staged(seed)


# --------------------------------- track identity across migration
def migration_run(carry_tracks: bool):
    """A skewed 2-shard rebalancing trace with tracker interpolation:
    guaranteed to migrate stream(s) off the hot shard at epoch 0."""
    frames, frame_of, videos, dets = make_skewed_streams(
        6, n_shards=2, n_frames=12, rate=1.0)
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    rec = TraceRecorder()
    rep = ShardedDetectionEngine(
        detect_fn=oracle, n_shards=2, n_replicas=2, service_time=0.36,
        track_and_interpolate=True, carry_tracks=carry_tracks,
        rebalance=True, epoch_s=4.0, recorder=rec).serve(frames)
    return rep, rec


def test_track_identity_survives_migration():
    rep, rec = migration_run(carry_tracks=True)
    moves = rep["migrations"]
    assert moves, "trace must actually migrate"
    res = audit_recorder(rec)
    assert res.ok, res.violations[:3]
    assert res.stats["track_export"] > 0
    assert res.stats["track_import"] > 0
    evs = rec.events
    for m in (e for e in evs if e["kind"] == "migrate"):
        sid = m["stream"]
        exps = [e for e in evs if e["kind"] == "track_export"
                and e["stream"] == sid and e["i"] < m["i"]]
        imps = [e for e in evs if e["kind"] == "track_import"
                and e["stream"] == sid and e["i"] > m["i"]]
        assert exps and imps, sid
        exp, imp = exps[-1], imps[0]
        # the destination shard imports the source's exact table ...
        assert imp["next_id"] == exp["next_id"]
        assert imp["tids"] == exp["tids"]
        assert imp["shard"] == m["dst"] != exp["shard"] == m["src"]
        # ... and a track id born BEFORE the boundary shows up again in
        # responses the DESTINATION shard emitted after the move
        surviving = set(exp["tids"])
        assert surviving
        post_rids = {e["rid"] for e in evs
                     if e["kind"] in ("emit", "interp_emit")
                     and e["stream"] == sid and e["i"] > m["i"]}
        emitted_after = set()
        for r in rep["streams"][sid]:
            if r.rid in post_rids and r.track_ids is not None:
                emitted_after |= {int(t) for t in np.asarray(r.track_ids)
                                  if t >= 0}
        assert surviving & emitted_after, (sid, surviving, emitted_after)


def test_reseed_without_carry_fails_continuity_audit():
    """The pre-refactor behaviour (re-seed at epoch boundaries),
    reproduced via ``carry_tracks=False``, must TRIP the new audit
    rule — the invariant genuinely distinguishes the two."""
    rep, rec = migration_run(carry_tracks=False)
    assert rep["migrations"]
    res = audit_recorder(rec)
    assert any(v["rule"] == "track_continuity" for v in res.violations), \
        res.violations


# ------------------------------------------- randomized fault chaos
@pytest.mark.chaos
@pytest.mark.parametrize("seed", SEEDS)
def test_conservation_under_randomized_faults(seed):
    sched = FaultSchedule.random(seed, 6.0, n_replicas=3,
                                 n_replica_events=2)
    check_conservation_and_monotonicity(seed, faults=sched)


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 3, 7])
def test_sharded_chaos_replay_is_deterministic(seed):
    """Same (trace, FaultSchedule) seed => byte-identical reports —
    randomized chaos stays assertable."""
    sched = FaultSchedule.random(seed, 8.0, n_shards=2, n_replicas=2,
                                 n_replica_events=2)

    def run():
        frames, frame_of, videos, dets = make_nvr_streams(3, 8, 3.0)
        oracle = proxy_detect_fn_streams(videos, dets, frame_of)
        return ShardedDetectionEngine(
            detect_fn=oracle, n_shards=2, n_replicas=2,
            service_time=0.3, track_and_interpolate=True,
            faults=sched).serve(frames)

    assert_reports_identical(run(), run())


if given is not None:
    @pytest.mark.chaos
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_conservation_under_faults_property(seed):
        sched = FaultSchedule.random(seed, 6.0, n_replicas=3,
                                     n_replica_events=2)
        check_conservation_and_monotonicity(seed, faults=sched)
