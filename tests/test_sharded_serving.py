"""Sharded multi-host NVR serving: the camera partition is
deterministic and balanced, a single-shard engine is bit-identical to
``DetectionEngine`` on the same trace, multi-shard reports merge back
to the global accounting, the SPMD mesh detect program matches the
plain jitted path bit-for-bit, and a forced-multi-device mesh run
(subprocess, ``xla_force_host_platform_device_count``) keeps full
per-stream coverage."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import proxy_detect_fn_streams
from repro.serving import (DetectionEngine, FrameRequest,
                           ShardedDetectionEngine, make_nvr_streams,
                           make_spmd_detect, merge_shard_reports)
from repro.sharding import shard_streams, streams_of_shard

REPO = Path(__file__).resolve().parents[1]


def sharded_for(frames, frame_of, videos, dets, **kw):
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    return ShardedDetectionEngine(detect_fn=oracle, **kw)


# --------------------------------------------------- camera partition
def test_shard_streams_deterministic_and_balanced():
    sids = [9, 3, 5, 0, 7, 1, 4]
    for n in (1, 2, 3, 7, 12):
        part = shard_streams(sids, n)
        assert part == shard_streams(reversed(sids), n)   # order-free
        assert set(part) == set(sids)
        loads = [len(streams_of_shard(part, h)) for h in range(n)]
        assert max(loads) - min(loads) <= 1               # balanced
        assert sum(loads) == len(sids)
    with pytest.raises(ValueError):
        shard_streams(sids, 0)


# ------------------------------------------- single-shard regression
def assert_reports_identical(base, sharded):
    """Every DetectionEngine report key must match bit-for-bit; the
    sharded layer may only ADD keys."""
    assert set(base).issubset(set(sharded))
    for k, bv in base.items():
        sv = sharded[k]
        if k == "responses":
            assert len(bv) == len(sv)
            for ra, rb in zip(bv, sv):
                for f in ("rid", "replica", "t_start", "t_done",
                          "service_s", "interpolated", "stream_id",
                          "seq"):
                    assert getattr(ra, f) == getattr(rb, f), (ra.rid, f)
                for f in ("boxes", "scores", "classes", "valid"):
                    assert np.array_equal(getattr(ra, f),
                                          getattr(rb, f)), (ra.rid, f)
                ta, tb = ra.track_ids, rb.track_ids
                assert (ta is None) == (tb is None)
                if ta is not None:
                    assert np.array_equal(np.asarray(ta), np.asarray(tb))
        elif k == "streams":
            assert bv.keys() == sv.keys()
            for sid in bv:
                assert [r.rid for r in bv[sid]] == [r.rid
                                                    for r in sv[sid]]
        else:
            assert bv == sv, k


@pytest.mark.parametrize("mode", ["drop", "track"])
def test_single_shard_bit_identical_to_detection_engine(mode):
    """The PR acceptance bar: shards=1 on the oracle path produces a
    bit-identical report to ``DetectionEngine`` on the same request
    trace, in both drop and track-and-interpolate modes."""
    frames, frame_of, videos, dets = make_nvr_streams(3, 16, rate=2.0)
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    kw = dict(n_replicas=2, service_time=0.4,
              **({"drop_when_busy": True} if mode == "drop"
                 else {"track_and_interpolate": True}))
    base = DetectionEngine(detect_fn=oracle, **kw).serve(frames)
    sh = ShardedDetectionEngine(n_shards=1, detect_fn=oracle,
                                **kw).serve(frames)
    assert_reports_identical(base, sh)
    assert sh["n_shards"] == 1
    assert sh["shard_of_stream"] == {0: 0, 1: 0, 2: 0}


# ------------------------------------------------- multi-shard merge
def test_multi_shard_partition_covers_every_frame_and_stream():
    """3 shards x 5 cameras: every camera lands on exactly one shard,
    per-stream accounting survives the merge, the tracked run keeps
    coverage 1.0, and replica ids are renumbered globally."""
    n_streams, n_frames, n_shards = 5, 12, 3
    frames, frame_of, videos, dets = make_nvr_streams(n_streams,
                                                      n_frames, rate=4.0)
    eng = sharded_for(frames, frame_of, videos, dets, n_shards=n_shards,
                      n_replicas=2, service_time=0.4,
                      track_and_interpolate=True)
    out = eng.serve(frames)
    assert out["n_shards"] == n_shards
    assert out["n_streams"] == n_streams
    # partition: disjoint, complete, matches the report's own map
    seen = [s for shard in out["per_shard"] for s in shard["streams"]]
    assert sorted(seen) == list(range(n_streams))
    for sid, h in out["shard_of_stream"].items():
        assert sid in out["per_shard"][h]["streams"]
    # every frame answered, in rid order, with per-stream seq intact
    assert out["coverage"] == 1.0
    assert [r.rid for r in out["responses"]] == sorted(
        r.rid for r in out["responses"])
    assert len(out["responses"]) == len(frames)
    for sid in range(n_streams):
        assert [r.seq for r in out["streams"][sid]] == list(range(n_frames))
        assert out["per_stream"][sid]["coverage"] == 1.0
        emits = out["emit_t"][sid]
        assert emits == sorted(emits)
    # per-shard totals sum to the global ones
    assert sum(s["frames"] for s in out["per_shard"]) == len(frames)
    assert sum(s["responses"] for s in out["per_shard"]) == len(frames)
    assert sum(s["tracker_launches"] for s in out["per_shard"]) \
        == out["tracker_launches"]
    # replica ids renumbered per shard pool: 3 shards x 2 replicas,
    # on the per_replica map AND on every response (so grouping
    # responses by replica stays consistent with the map)
    assert set(out["per_replica"]) == set(range(6))
    for r in out["responses"]:
        if r.interpolated:
            assert r.replica == -1
        else:
            h = out["shard_of_stream"][r.stream_id]
            assert 2 * h <= r.replica < 2 * (h + 1), (r.rid, r.replica)


def test_multi_shard_drop_accounting_merges_in_arrival_order():
    """Overloaded drop-mode run: merged ``dropped`` rids come back in
    global arrival order and per-stream drops sum to the global list."""
    frames, frame_of, videos, dets = make_nvr_streams(4, 20, rate=5.0)
    eng = sharded_for(frames, frame_of, videos, dets, n_shards=2,
                      n_replicas=1, service_time=0.4,
                      drop_when_busy=True)
    out = eng.serve(frames)
    assert len(out["dropped"]) > 0                   # 4x overload drops
    pos = {f.rid: i for i, f in
           enumerate(sorted(frames, key=lambda f: f.t_arrival))}
    order = [pos[r] for r in out["dropped"]]
    assert order == sorted(order)
    assert sum(v["dropped"] for v in out["per_stream"].values()) \
        == len(out["dropped"])
    assert out["coverage"] == len(out["responses"]) / len(frames)


def test_sharded_engine_empty_trace():
    """serve([]) mirrors DetectionEngine's empty report across shards."""
    frames, frame_of, videos, dets = make_nvr_streams(1, 1, rate=1.0)
    eng = sharded_for(frames, frame_of, videos, dets, n_shards=2,
                      n_replicas=1, service_time=0.1)
    out = eng.serve([])
    assert out["responses"] == [] and out["dropped"] == []
    assert out["coverage"] == 0.0 and out["n_streams"] == 0
    assert set(out["per_replica"]) == {0, 1}


def test_mesh_and_detect_fn_are_mutually_exclusive():
    with pytest.raises(ValueError):
        ShardedDetectionEngine(mesh=object(), detect_fn=lambda i, r: None)


def test_launcher_spmd_refuses_more_shards_than_devices():
    """``serve --payload frames --spmd`` must fail, not quietly serve
    on the meshless partition, when a shard would have no device."""
    import argparse

    import jax

    from repro.launch.serve import serve_frames
    args = argparse.Namespace(
        trace=None, cameras=2, frames=2, rate=2.0, spmd=True,
        shards=len(jax.devices()) + 1, n_replicas=1, scheduler="fcfs",
        models=None, roi=False)
    with pytest.raises(SystemExit, match="one device per shard"):
        serve_frames(args)


# ------------------------------------------------------ SPMD detect
def test_spmd_detect_bit_identical_to_plain_jit_path():
    """``make_spmd_detect`` on a host mesh must return bit-identical
    detections to ``DetectionEngine``'s own jitted mini-SSD program —
    the sharding constraints change placement, never values."""
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(1)
    rng = np.random.default_rng(3)
    frames = [FrameRequest(i, rng.random((64, 64, 3)).astype(np.float32),
                           i / 20.0, stream_id=i % 2) for i in range(8)]
    kw = dict(n_replicas=2, service_time=0.05, seed=0)
    sh = ShardedDetectionEngine(n_shards=1, mesh=mesh, **kw).serve(frames)
    base = DetectionEngine(**kw).serve(frames)
    assert_reports_identical(base, sh)


def test_multi_device_mesh_subprocess():
    """End-to-end on a REAL 4-device mesh (forced host devices in a
    subprocess — the parent jax is already initialized single-device):
    4 shards serve 4 cameras through one SPMD detect+NMS program with
    full coverage, and fresh-frame outputs match the meshless engine."""
    code = """
import numpy as np, jax
assert len(jax.devices()) == 4, jax.devices()
from repro.launch.mesh import make_serving_mesh
from repro.serving import DetectionEngine, FrameRequest, \
    ShardedDetectionEngine
rng = np.random.default_rng(0)
frames = [FrameRequest(i, rng.random((64, 64, 3)).astype(np.float32),
                       i / 40.0, stream_id=i % 4) for i in range(24)]
mesh = make_serving_mesh(4)
out = ShardedDetectionEngine(n_shards=4, mesh=mesh, n_replicas=1,
                             service_time=0.05, seed=0,
                             track_and_interpolate=True).serve(frames)
assert out["n_shards"] == 4
assert out["coverage"] == 1.0
assert [s["streams"] for s in out["per_shard"]] == [[0], [1], [2], [3]]
base = DetectionEngine(n_replicas=1, service_time=0.05, seed=0,
                       track_and_interpolate=True).serve(frames)
for ra, rb in zip(out["responses"], base["responses"]):
    if not (ra.interpolated or rb.interpolated):
        assert np.array_equal(ra.boxes, rb.boxes), ra.rid
print("MESH-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MESH-OK" in r.stdout


# --------------------------------------------------- merge invariants
def test_merge_shard_reports_recomputes_global_scalars():
    """The merged scalars must follow DetectionEngine's own formulas
    over the union of responses, not an average of shard scalars."""
    frames, frame_of, videos, dets = make_nvr_streams(4, 10, rate=3.0)
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    part = shard_streams(range(4), 2)
    subs = [[f for f in frames if part[f.stream_id] == h]
            for h in range(2)]
    engines = [DetectionEngine(detect_fn=oracle, n_replicas=1,
                               service_time=0.2, drop_when_busy=True)
               for _ in range(2)]
    reports = [e.serve(s) for e, s in zip(engines, subs)]
    merged = merge_shard_reports(frames, reports, [1, 1])
    assert merged["coverage"] == len(merged["responses"]) / len(frames)
    makespan = max(r.t_done for r in merged["responses"])
    assert merged["throughput_fps"] == \
        len(merged["responses"]) / max(makespan, 1e-9)
    assert merged["interpolated"] == sum(r["interpolated"]
                                         for r in reports)
    assert set(merged["per_replica"]) == {0, 1}
    # merging must not mutate the caller's shard reports (replica ids
    # are renumbered on copies), so merging twice is identical
    assert all(r.replica in (-1, 0) for rep in reports
               for r in rep["responses"])
    again = merge_shard_reports(frames, reports, [1, 1])
    assert [r.replica for r in again["responses"]] == \
        [r.replica for r in merged["responses"]]
    # the merged streams hold the SAME objects as merged responses
    # (the DetectionEngine contract), not the originals
    by_rid = {r.rid: r for r in merged["responses"]}
    for sid, rs in merged["streams"].items():
        assert all(r is by_rid[r.rid] for r in rs)
